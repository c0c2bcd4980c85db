"""Device milliseconds a step under scope `mamba2/in_proj` (the fused input
projection of every Mamba-2 layer: forward, whatever of it the backward
recomputes, backward)."""

import scope_time


def read(run):
    value = scope_time.per_step(run, ("mamba2/in_proj",))
    return None if value is None else 1e3 * value
