"""Milliseconds of image decode an image: the `decode_ns` over the `images`
that the decoder added to the `data.parse_chunk` spans closed in the traced
part (wall time of the decoding thread, two monotonic clock reads an image,
cache lookup and copy included), whether a span is a whole batch's or a
slice's. It grows where the pool's threads share cores with each other and
with the program's other threads, so it says whether a wider pool pays for
itself. Nothing to read where the decoder was not timed."""

import program_spans


def read(run):
    view = program_spans.view(run)
    closed = view["closed"].get("data.parse_chunk") if view else None
    images = sum(s["counts"].get("images", 0) for s in closed or ())
    if not images:
        return None
    return sum(s["counts"].get("decode_ns", 0) for s in closed) / 1e6 / images
