"""Mean length, in milliseconds, of the `infeed.transfer` spans that closed
in the traced part: from where `infeed.h2d` opens (the enqueue of a batch's
`device_put`, on the train thread) to the arrival of the last shard of its
last leaf, stamped by the program's watcher thread. Beside
`host_input.h2d_put_ms_per_step`, which is the enqueue alone, it says how
long a batch is in flight. None on a program without the span."""

import program_spans


def read(run):
    view = program_spans.view(run)
    closed = view["closed"].get("infeed.transfer") if view else None
    if not closed:
        return None
    last = [s["counts"]["last_device"] for s in closed if "last_device" in s["counts"]]
    lengths = sorted((s["end_ns"] - s["start_ns"]) / 1e6 for s in closed)
    run.reporter.say(
        f"infeed.transfer: {len(closed)} closed in the traced part over "
        f"{closed[0]['counts'].get('devices')} devices, ms min "
        f"{lengths[0]:.3f} median {lengths[len(lengths) // 2]:.3f} max "
        f"{lengths[-1]:.3f}; the device waited for last, by id: "
        + (", ".join(f"{d} x {last.count(d)}" for d in sorted(set(last))) or "none")
    )
    return sum(lengths) / len(lengths)
