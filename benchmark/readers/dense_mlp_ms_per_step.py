"""Device milliseconds a step under scope `mlp` of a model whose later
layers route: the leading dense layers' SwiGLU alone (forward, whatever of
it the backward recomputes, backward); the shared experts are `moe/shared`."""

import kimi_scopes


def read(run):
    value = kimi_scopes.per_step(run, ("mlp",))
    return None if value is None else 1e3 * value
