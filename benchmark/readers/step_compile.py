"""Seconds of the backend's compile inside the step program's first call:
`compile_ns` of the span `setup.step_build_s` reads. On a warm machine the
compile cache's lookup and the executable's load, on a machine's first run
the compiler itself; the earlier line of `setup.step_build_s` says which
(hits, misses, retrieval). None on a program without the span."""

import setup_spans


def read(run):
    build = setup_spans.step_build(run)
    if build is None:
        return None
    return build["counts"]["compile_ns"] / 1e9
