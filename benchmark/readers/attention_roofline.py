"""Roofline share of attention's score-and-value products (not the
projections): least time from the plain reference's blocked attention at
the cell's shapes, which skips the blocks above the diagonal, over the
device time under scope `attention` a step."""

import scope_time


def read(run):
    return scope_time.roofline(run, "attention", ("attention",))
