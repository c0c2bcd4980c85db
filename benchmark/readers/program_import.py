"""Seconds the import of the training stack took this process: the
program's `program.import` span, from the package's first line to the last
line of `train/train_eval.py` (jax, flax, optax, orbax and the package's
own modules, and whatever the caller imported between the two). The earlier
line gives the time from the process's start to that first line beside it.
None on a program without the span."""

import setup_spans


def read(run):
    span = setup_spans.program_import(run)
    if span is None:
        return None
    return (span["end_ns"] - span["start_ns"]) / 1e9
