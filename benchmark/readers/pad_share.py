"""Padding over the positions that are a token or padding, both from the
program's counters `train.pad_tokens` and `train.tokens`
(utils/tracing.py), as the train log's `pad_share` divides them. In a
resident cell it restates the packing the driver made from the seed: it
says how much of the step's work was spent on padding, and no change of
the program moves it."""


def read(run):
    pad = run.counters.get("train.pad_tokens")
    tokens = run.counters.get("train.tokens")
    if pad is None or tokens is None:
        return None
    return 100.0 * pad / (pad + tokens)
