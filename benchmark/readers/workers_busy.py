"""Share of the parse pool's capacity in use over the traced part: time
inside `data.parse_chunk` spans, clipped to it, over the number of parse
workers (threads that ever held such a span) times its length. Near 100
the host is short of parse throughput; well under it the pool is starved by
the serial read or the in-flight cap."""

import program_spans


def read(run):
    view = program_spans.view(run)
    if not view or not view["parse_workers"]:
        return None
    busy = view["inside_ns"].get("data.parse_chunk", 0)
    return 100.0 * busy / (view["parse_workers"] * view["window_ns"])
