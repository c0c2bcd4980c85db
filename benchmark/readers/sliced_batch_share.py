"""Share of the batches the dataset delivered that its parse pool
assembled from slices (`data.parse_batches_sliced` over
`data.parse_batches`, the program's cumulative counters, read after the
window: warm-up included). 100 where every batch was cut over the pool's
workers, 0 where each was one worker's job. Nothing to read where the
program does not count its batches (a commit before PR 27)."""

import program_spans


def read(run):
    snap = program_spans.recorded(run)
    batches = snap["counters"].get("data.parse_batches") if snap else None
    if not batches:
        return None
    return 100.0 * snap["counters"].get("data.parse_batches_sliced", 0) / batches
