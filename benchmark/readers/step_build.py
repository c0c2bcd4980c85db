"""Seconds of the first call of the cell's step program: the program's
`train.build` span labelled `train_step` (`train_scan` where the trainer
scans) that closed before the window opened. The earlier line splits it:
trace, lowering, compile or the cache's load, hits and misses, programs,
and the remainder under no jax event. None on a program without the span."""

import setup_spans


def read(run):
    build = setup_spans.step_build(run)
    if build is None:
        return None
    return (build["end_ns"] - build["start_ns"]) / 1e9
