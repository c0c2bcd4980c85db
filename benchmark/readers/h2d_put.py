"""Milliseconds a step the train thread spends inside `infeed.h2d`, the
program's span around `shard_fn(batch)` in `infeed.device_prefetch`: the
`device_put` of the uint8 batch and whatever of its host-side relayout the
call waits for. Traced part."""

import program_spans


def read(run):
    return program_spans.ms_per_step(run, "infeed.h2d")
