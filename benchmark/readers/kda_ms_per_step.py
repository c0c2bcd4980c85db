"""Device milliseconds a step under scope `kda/delta_rule`: the chunked
gated delta rule of every KDA layer from the normalised q, k, v, g and beta
to o (forward, recomputation and backward), not its projections."""

import kimi_scopes


def read(run):
    value = kimi_scopes.per_step(run, ("kda/delta_rule",))
    return None if value is None else 1e3 * value
