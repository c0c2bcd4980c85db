"""Device milliseconds a step under scope `attention` (scores, softmax and
values of the attention layers; their projections are `attention_proj`)."""

import scope_time


def read(run):
    value = scope_time.per_step(run, ("attention",))
    return None if value is None else 1e3 * value
