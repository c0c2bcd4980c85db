"""Device milliseconds a step under the scopes `moe/*`: router, dispatch
(sort and gather), the grouped products of the experts held, the weighted
scatter-add and the shared expert, of every routed layer."""

import kimi_scopes


def read(run):
    value = kimi_scopes.per_step(run, kimi_scopes.MOE)
    return None if value is None else 1e3 * value
