"""Device milliseconds a step under scope `kda/pair_scores`: the pair scores
of every chunk of every KDA layer (forward, recomputation and backward),
inside `kda/delta_rule`, which `train_step.kda_ms_per_step` goes on billing
whole: the accepted readers take an op's innermost scope of
`kimi_scopes.ALL`, which does not hold this name. What it leaves of that
metric is the carry, the substitution and the rule's products. None where
the program names no such scope."""

import kimi_scopes
import scope_sums

SCOPE = "kda/pair_scores"


def read(run):
    value = scope_sums.per_step(
        run, (SCOPE,), kimi_scopes.ALL + (SCOPE,), kimi_scopes.UNLABELLED
    )
    return None if value is None else 1e3 * value
