"""Milliseconds a step the train thread spends inside `train.dispatch`, the
program's span around `compiled.train_step`, over the traced part. The
benchmark's `bench.dispatch` bracket holds it and the hooks' edges too."""

import program_spans


def read(run):
    return program_spans.ms_per_step(run, "train.dispatch")
