"""Device milliseconds a step under scope `kda/carry`: the walk over the
chunks of every KDA layer (the carry between chunks and the products that
read a chunk's entering state; forward, recomputation and backward), inside
`kda/delta_rule`, which `train_step.kda_ms_per_step` goes on billing whole:
the accepted readers take an op's innermost scope of `kimi_scopes.ALL`, which
does not hold this name. `train_step.kda_ms_per_step` less this metric and
`train_step.kda_pair_scores_ms_per_step` is W and U, the substitution and the
masks. None where the program names no such scope."""

import kimi_scopes
import scope_sums

SCOPE = "kda/carry"


def read(run):
    value = scope_sums.per_step(
        run, (SCOPE,), kimi_scopes.ALL + (SCOPE,), kimi_scopes.UNLABELLED
    )
    return None if value is None else 1e3 * value
