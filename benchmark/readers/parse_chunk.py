"""Milliseconds of parse workers' time per 256 records: the durations of
the `data.parse_chunk` spans closed in the traced part, summed over every
worker, over the `records` they count, times 256 (protobuf scan, jpeg
decode into the batch, scalars). A span is a slice of a batch or a whole
one; per record both read alike, and 256 records are the one-chip fed
cell's batch, so the number is a batch's there and a quarter of a step's
rows on four chips. Nothing to read where the spans count no records."""

import program_spans

RECORDS = 256


def read(run):
    view = program_spans.view(run)
    closed = view["closed"].get("data.parse_chunk") if view else None
    records = sum(s["counts"].get("records", 0) for s in closed or ())
    if not records:
        return None
    spent_ns = sum(s["end_ns"] - s["start_ns"] for s in closed)
    return RECORDS * spent_ns / 1e6 / records
