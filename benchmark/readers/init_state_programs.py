"""Executables `CompiledModel.init_state` built or loaded: `programs` on
the program's `train.init_state` span (every backend compile of the
calling thread inside it). The earlier line gives the seconds they took by
kind. None on a program that does not count them."""

import setup_spans


def read(run):
    span = setup_spans.init_state(run)
    if span is None or "programs" not in span["counts"]:
        return None
    counts = span["counts"]
    run.reporter.say(
        f"init_state builds: programs {counts['programs']}, trace "
        f"{counts['trace_ns'] / 1e9:.3f} s, lower {counts['lower_ns'] / 1e9:.3f} s, "
        f"compile or load {counts['compile_ns'] / 1e9:.3f} s, cache hits "
        f"{counts['cache_hits']} misses {counts['cache_misses']}, of "
        f"{(span['end_ns'] - span['start_ns']) / 1e9:.3f} s"
    )
    return counts["programs"]
