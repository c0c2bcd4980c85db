"""Share of the parse workers' time that is image decode: the `decode_ns`
the decoder added to the `data.parse_chunk` spans closed in the traced part
(two monotonic clock reads an image, cache lookup and copy included) over
those spans' durations. Nothing to read where the fast parser is off (the
oracle's decode is not timed)."""

import program_spans


def read(run):
    view = program_spans.view(run)
    closed = view["closed"].get("data.parse_chunk") if view else None
    if not closed or not any("decode_ns" in s["counts"] for s in closed):
        return None
    decode = sum(s["counts"].get("decode_ns", 0) for s in closed)
    return 100.0 * decode / sum(s["end_ns"] - s["start_ns"] for s in closed)
