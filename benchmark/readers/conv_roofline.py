"""Roofline share of the convolution and matrix kernels: the least time the
chip could take for one step's convolutions and dots (the larger of their
model FLOPs over peak FLOP/s and their operand-and-result bytes over peak
HBM bytes/s, both from the plain reference's equations) over the device
time the trace bills to convolution-category ops a step. XLA fuses
BatchNorm statistics, ReLU and the optimizer update into those ops, so
their time holds more than the convolution: the share reads low, never
high. Says on an earlier line which bound it was."""


def read(run):
    summary = run.trace_summary
    if not summary or not summary["steps"] or not summary["conv_s"]:
        return None
    # The counts are of the whole step's batch, the measured time a
    # device's: each of the step's devices has its share of the rows.
    chips = summary["devices"]
    by_flops = run.flops["step_flops"] / chips / run.peaks["bf16_flops_per_s"]
    by_bytes = run.flops["step_bytes"] / chips / run.peaks["hbm_bytes_per_s"]
    least = max(by_flops, by_bytes)
    measured = summary["conv_s"] / summary["steps"]
    run.reporter.say(
        f"conv roofline: least {least * 1e3:.3f} ms a step, bound by "
        f"{'FLOPs' if by_flops >= by_bytes else 'bytes'} (FLOPs "
        f"{by_flops * 1e3:.3f} ms, bytes {by_bytes * 1e3:.3f} ms); measured "
        f"{measured * 1e3:.3f} ms of convolution-category ops a step"
    )
    return 100.0 * least / measured
