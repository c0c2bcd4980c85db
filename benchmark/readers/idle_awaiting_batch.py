"""Of the first device's idle time in the traced part, the share in which
the device stood before a step whose batch had not arrived: the idle time
between the end of step n-1's module and the first op of step n's that lies
before `infeed.transfer` of step n's ordinal closed. The rest of the idle
time is some other wait (the dispatch came late though the batch was there,
a hook's read-back, the log).

A further reduction of `program_spans`' view, on its clock: the k-th step
module of the traced part belongs to its k-th `train.dispatch` span (the
device was drained when the part opened), and that span's ordinal names the
transfer. A transfer that left the recorder's ring, or closed before the
part opened, had arrived. The watcher thread stamps an arrival when it sees
it, so the share is an upper bound by that thread's lag. None on a program
without the span."""

import bisect
import collections

import program_spans


def awaiting(spans, window, busy, modules):
    """(idle ns awaiting a batch, idle ns, [(ordinal, ns awaited)] of the
    steps that waited), or None where no `infeed.transfer` span is among
    `spans`. Arguments as `program_spans.reduce` takes them."""
    lo, hi = window
    arrived = {
        s["ordinal"]: s["end_ns"] for s in spans if s["name"] == "infeed.transfer"
    }
    if not arrived:
        return None
    dispatches = sorted(
        (s for s in spans
         if s["name"] == "train.dispatch" and lo <= s["start_ns"] < hi),
        key=lambda s: s["start_ns"],
    )
    inside = [m for m in modules if m[1] >= lo and m[2] <= hi]
    names = collections.Counter(name for name, _, _ in inside)
    step_module = names.most_common(1)[0][0] if names else None
    steps = [(start, end) for name, start, end in inside if name == step_module]
    idle = program_spans.Cover(program_spans.complement(busy, lo, hi))
    busy_starts = [start for start, _ in busy]
    busy_ends = [end for _, end in busy]
    total, waited, cursor = 0, [], lo
    for dispatch, (start, end) in zip(dispatches, steps):
        # The step's first op: the module's start where the device is busy
        # there, else the next op's.
        i = bisect.bisect_right(busy_starts, start)
        if not (i and busy_ends[i - 1] > start) and i < len(busy_starts):
            start = min(busy_starts[i], end)
        there = arrived.get(dispatch["ordinal"], lo)
        if there > cursor:
            ns = idle.inside([(cursor, min(start, there))])
            if ns:
                total += ns
                waited.append((dispatch["ordinal"], ns))
        cursor = end
    return total, idle.before[-1], waited


def read(run):
    view = program_spans.view(run)
    snap = program_spans.recorded(run)
    if not view or not view["steps"] or not view["idle_ns"]:
        return None
    if not any(s["name"] == "infeed.transfer" for s in snap["spans"]):
        return None
    start_ns, busy, modules = program_spans.device_timeline(run.trace_dir)
    window = next(s for s in run.window.spans if s[0] == program_spans.WINDOW_SPAN)
    shifted = [
        dict(s, start_ns=s["start_ns"] - start_ns, end_ns=s["end_ns"] - start_ns)
        for s in snap["spans"]
        if s["name"] in ("infeed.transfer", "train.dispatch")
    ]
    total, idle_ns, waited = awaiting(
        shifted, (window[1] - start_ns, window[2] - start_ns), busy, modules
    )
    steps = view["steps"]
    longest = sorted(waited, key=lambda w: -w[1])[:8]
    run.reporter.say(
        f"device idle awaiting its batch: {total / 1e6 / steps:.3f} ms a step "
        f"of {idle_ns / 1e6 / steps:.3f} ms idle, in {len(waited)} of {steps} "
        f"steps; the longest, ms by ordinal: "
        + (", ".join(f"{o}: {ns / 1e6:.3f}" for o, ns in longest) or "none")
    )
    return 100.0 * total / idle_ns
