"""Share of the traced part's steps that were dispatched before their batch
had arrived: `late` on the `train.dispatch` spans that closed there (1 where
a leaf of the device batch was not ready when the span opened, the step's
share of the program's counter `infeed.late_at_dispatch`), over those
spans. An earlier line says the counters of the whole run, warm-up and
all. None on a program that does not count it."""

import program_spans


def read(run):
    view = program_spans.view(run)
    dispatches = view["closed"].get("train.dispatch") if view else None
    if not dispatches or any("late" not in s["counts"] for s in dispatches):
        return None
    counters = program_spans.recorded(run)["counters"]
    run.reporter.say(
        f"dispatched before the batch had arrived, whole run: "
        f"{counters.get('infeed.late_at_dispatch', 0)} of "
        f"{counters.get('infeed.dispatched', 0)} steps"
    )
    return 100.0 * sum(s["counts"]["late"] for s in dispatches) / len(dispatches)
