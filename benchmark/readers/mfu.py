"""Model FLOPs utilization of the traced steps: model FLOPs a step (from
the plain reference's equations, flops.py) x steps of the traced window
over window seconds x chips x the chip's bf16 peak. Whole-window time, so
idle time counts against it."""


def read(run):
    summary = run.trace_summary
    if not summary or not summary["steps"]:
        return None
    peak = run.peaks["bf16_flops_per_s"] * summary["devices"]
    achieved = run.flops["step_flops"] * summary["steps"] / summary["window_s"]
    return 100.0 * achieved / peak
