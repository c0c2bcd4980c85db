"""Share of the consumer's gets that found the dataset's prefetch queue
empty (`data.prefetch_empty` over `data.prefetch_gets`, the program's
cumulative counters, read after the window: the dozen gets of the warm-up
are in it, of some three hundred)."""

import program_spans


def read(run):
    snap = program_spans.recorded(run)
    gets = snap["counters"].get("data.prefetch_gets") if snap else None
    if not gets:
        return None
    return 100.0 * snap["counters"].get("data.prefetch_empty", 0) / gets
