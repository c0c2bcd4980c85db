"""Milliseconds a step the train thread spends inside `infeed.wait`, the
program's own span around `next(it)` in `infeed.device_prefetch`, over the
traced part: the inside twin of `host_input.wait_ms_per_step`, whose
bracket sits in the benchmark's iterator wrapper and covers the window."""

import program_spans


def read(run):
    return program_spans.ms_per_step(run, "infeed.wait")
