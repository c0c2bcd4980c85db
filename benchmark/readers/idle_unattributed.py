"""Share of the device's idle time in the traced part that lies under no
span of the train thread. The loop body is spans end to end, so this stays
near 0 while the program's clock and the profiler's agree and no code runs
between the spans."""

import program_spans


def read(run):
    view = program_spans.view(run)
    if not view or not view["steps"] or not view["idle_ns"]:
        return None
    return 100.0 * view["idle_unattributed_ns"] / view["idle_ns"]
