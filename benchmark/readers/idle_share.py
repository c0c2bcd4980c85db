"""Share of the traced window in which no operation ran on the device."""


def read(run):
    summary = run.trace_summary
    if not summary:
        return None
    return 100.0 * summary["idle_share"]
