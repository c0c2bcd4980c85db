"""Device milliseconds a step under scope `mla/rope`: the rotary positions
of latent attention (each position's offset within its document, float32
angles, cos and sin, the turn of the queries' and the shared key's rope
channels; forward, recomputation and backward). Read by a reduction of its
own with the name added to `kimi_scopes.ALL`, so that the accepted readers
bill nothing differently. None where the program names no such scope, or
where the compiler fused every op of it into a neighbour's fusion."""

import kimi_scopes
import scope_sums

SCOPE = "mla/rope"


def read(run):
    value = scope_sums.per_step(
        run, (SCOPE,), kimi_scopes.ALL + (SCOPE,), kimi_scopes.UNLABELLED
    )
    return None if value is None else 1e3 * value
