"""Device milliseconds a step under scope `mlp` (the SwiGLU feed-forward of
every layer: forward, whatever of it the backward recomputes, backward)."""

import scope_time


def read(run):
    value = scope_time.per_step(run, ("mlp",))
    return None if value is None else 1e3 * value
