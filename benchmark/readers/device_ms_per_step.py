"""Device-busy milliseconds a step: the union of the device-op intervals of
the traced window over its steps."""


def read(run):
    summary = run.trace_summary
    if not summary or not summary["steps"]:
        return None
    return 1e3 * summary["busy_s"] / summary["steps"]
