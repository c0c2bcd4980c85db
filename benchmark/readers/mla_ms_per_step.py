"""Device milliseconds a step under scope `attention` of the latent
attention layers (scores, softmax and values: the kernels; the projections
are `mla/*` and `attention_proj`)."""

import kimi_scopes


def read(run):
    value = kimi_scopes.per_step(run, ("attention",))
    return None if value is None else 1e3 * value
