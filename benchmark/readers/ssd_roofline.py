"""Roofline share of the chunked scans: the least time the chip could take
for one step's Mamba-2 scans, forward and backward (the larger of their
FLOPs over peak FLOP/s and their operand-and-result bytes over peak HBM
bytes/s, both counted from the plain reference's scan at the cell's
shapes), over the device time under scope `mamba2/ssd` a step. The scope
also holds the decay matrices' elementwise work, which the count leaves
out: the share reads low, never high."""

import scope_time


def read(run):
    return scope_time.roofline(run, "ssd", ("mamba2/ssd",))
