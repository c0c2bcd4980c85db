"""Device milliseconds a step under scope `mamba2/ssd` (the chunked scans
of all Mamba-2 layers, forward, recomputation and backward)."""

import scope_time


def read(run):
    value = scope_time.per_step(run, ("mamba2/ssd",))
    return None if value is None else 1e3 * value
