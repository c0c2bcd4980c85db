"""Seconds of tracing and lowering inside the step program's first call:
`trace_ns + lower_ns` of the span `setup.step_build_s` reads. Python's and
MLIR's work, the same on a warm machine and a cold one; what a kernel that
is traced once a layer raises. None on a program without the span."""

import setup_spans


def read(run):
    build = setup_spans.step_build(run)
    if build is None:
        return None
    return (build["counts"]["trace_ns"] + build["counts"]["lower_ns"]) / 1e9
