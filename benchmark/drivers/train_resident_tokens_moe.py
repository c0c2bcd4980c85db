"""Driver `train_resident_tokens_moe`: `train_resident_tokens`, whole and
unchanged, for a model with routed experts: its loop, traffic, readings,
faults and layer-by-layer reference are that driver's own objects. What
this one adds: the program's counters `moe.routed_rows` and
`moe.max_expert_rows` over the window's steps, and the routed rows of the
traced steps, join `run.counters`.

`train_resident_tokens.run` hands the last step's counts times the
window's steps to `run.counters`. That is exact for tokens of a resident
batch and not for routing, which changes from step to step as the router
trains. So the window here keeps every step's metrics as the step returned
them (a handful of device scalars a step, not read while the window is
open), and once it has closed they go through the program's
`add_token_counts`, a step at a time as `train_eval_model` adds them, and
through `token_log_record` once: the growth of the `moe.*` counters over
that call is the window's, step by step. `moe.routed_rows.traced` and
`moe.traced_steps` are the same counts over the steps the trace holds, so
that a roofline's rows and its device time come from the same steps. A
program without routed experts in its metrics (the parent) leaves all four
out and the readers return nothing.
"""

from __future__ import annotations

import manifest

_tokens = manifest.driver("train_resident_tokens")

packed_documents = _tokens.packed_documents
HostStepReadings = _tokens.HostStepReadings
BATCH_FAULTS = _tokens.BATCH_FAULTS
constructor_arguments = _tokens.constructor_arguments
install_streaming_reference = _tokens.install_streaming_reference

MOE_COUNTERS = ("moe.routed_rows", "moe.max_expert_rows")
Window = _tokens.Window


class StepNotingWindow(Window):
    """The window, keeping (traced?, metrics) of each of its steps. A step
    is traced when the trace was on as it was dispatched: the trace starts
    and stops on a drained device, so those are the steps it holds."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        self.noted = []

    def after_step(self, state, metrics):
        if self.opened_at is not None:
            self.noted.append((self.trace_state == "on", metrics))
        super().after_step(state, metrics)


def run(run):
    import jax

    from tensor2robot_tpu.train import train_eval
    from tensor2robot_tpu.utils import tracing

    _tokens.Window = StepNotingWindow
    try:
        _tokens.run(run)
    finally:
        _tokens.Window = Window
    noted, run.window.noted = run.window.noted, []

    sums = None
    for _, metrics in noted:
        sums = train_eval.add_token_counts(sums, metrics)
    before = tracing.counters()
    record = train_eval.token_log_record(
        jax.device_get(sums), run.window.results()["window_s"]
    )
    after = tracing.counters()
    grown = {
        name: after[name] - before.get(name, 0)
        for name in MOE_COUNTERS if name in after
    }
    rows = [(on, int(m["moe_routed_rows"])) for on, m in noted if "moe_routed_rows" in m]
    traced = [count for on, count in rows if on]
    if traced:
        grown["moe.routed_rows.traced"] = sum(traced)
        grown["moe.traced_steps"] = len(traced)
    run.counters.update(grown)
    run.reporter.say(
        f"routed rows over the window's {len(noted)} steps: {grown}; program's "
        f"record {record}; a step: {[count for _, count in rows]}"
    )
