"""The program's own spans and counters (tensor2robot_tpu/utils/tracing.py),
clipped to the traced part and laid over the device's timeline.

The only benchmark module that knows the program's recorder. The recorder
stamps its spans with the host's epoch clock, as window.py stamps the
benchmark's own and as the profiler dates its session
(`profile_start_time`), so program spans, benchmark spans and device
operations share one axis with no alignment of their own. Everything here
is in nanoseconds from the start of the profiler's session.

`recorded(run)` is the recorder's snapshot, `view(run)` its reduction over
the traced part (the `bench.trace_window` span of `run.window.spans`); both
are made once a run and kept on `run`. Both are None where the program has
no recorder (a commit before PR 26), and every reader then returns None.
`view` also says on earlier lines: the device's idle milliseconds a step
under each span of the train thread, how many parse workers were busy while
the device was idle, the inside spans beside the benchmark's outside
brackets, and whether any step ran on the device before its dispatch opened.
"""

from __future__ import annotations

import bisect
import collections

import xplane

WINDOW_SPAN = "bench.trace_window"
#: A step's device module may not start more than this before the
#: `train.dispatch` span of its step opens, if the two clocks agree.
CLOCK_SLACK_NS = 1e6


def snapshot():
    """{"spans": [...], "counters": {...}} of the program's recorder, or
    None where the program has none."""
    try:
        from tensor2robot_tpu.utils import tracing
    except ImportError:
        return None
    return tracing.snapshot()


def recorded(run):
    if not hasattr(run, "_program_recorded"):
        run._program_recorded = snapshot()
    return run._program_recorded


def view(run):
    if not hasattr(run, "_program_view"):
        run._program_view = _view(run)
    return run._program_view


# -- interval arithmetic ---------------------------------------------------------


class Cover:
    """A sorted disjoint union of intervals that answers how much of it lies
    inside [start, end) by two bisections: the device leaves tens of
    thousands of idle gaps in a traced part."""

    def __init__(self, merged):
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.before = [0]
        for start, end in merged:
            self.before.append(self.before[-1] + end - start)

    def _upto(self, t):
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0
        return self.before[i - 1] + min(t, self.ends[i - 1]) - self.starts[i - 1]

    def inside(self, intervals):
        """Summed over `intervals` (any order; where they overlap each
        other, each counts)."""
        return sum(self._upto(e) - self._upto(s) for s, e in intervals)


def complement(merged, lo, hi):
    """The parts of [lo, hi) that `merged` (sorted, disjoint) leaves free."""
    gaps, cursor = [], lo
    for start, end in merged:
        if start > cursor:
            gaps.append((cursor, min(start, hi)))
        cursor = max(cursor, end)
        if cursor >= hi:
            break
    if cursor < hi:
        gaps.append((cursor, hi))
    return gaps


# -- the device's side -------------------------------------------------------------


def device_timeline(trace_dir):
    """(profile_start_time in epoch ns, busy intervals of the first device as
    a sorted disjoint union, module executions [(name, start, end)]), times
    in ns from the session's start."""
    planes = xplane.load(xplane.find(trace_dir))
    start_ns = next(
        (p.stats["profile_start_time"] for p in planes
         if "profile_start_time" in p.stats), None,
    )
    device = next(p for p in planes if p.name.startswith("/device:TPU:"))
    ops, modules = [], []
    for name, events in device.lines:
        if name == xplane.SYNC_LINE:
            ops += [(s / 1e3, (s + d) / 1e3) for _, s, d in events]
        elif name == "XLA Modules":
            modules += [
                (device.event_names.get(i, "?"), s / 1e3, (s + d) / 1e3)
                for i, s, d in events
            ]
    return start_ns, [tuple(i) for i in xplane.union(ops)], sorted(
        modules, key=lambda m: m[1]
    )


# -- the reduction -------------------------------------------------------------------


def reduce(spans, window, busy, modules):
    """The traced part [lo, hi) = `window`, `busy` and `modules` as
    `device_timeline` gives them, `spans` the recorder's dicts with their
    times already from the session's start. Returns the numbers the readers
    and the earlier lines want; times in ns."""
    lo, hi = window
    inside = [s for s in spans if s["end_ns"] > lo and s["start_ns"] < hi]
    by_name = collections.defaultdict(list)
    for span in inside:
        by_name[span["name"]].append(span)

    def clipped(group):
        return [(max(s["start_ns"], lo), min(s["end_ns"], hi)) for s in group]

    dispatches = sorted(
        (s for s in by_name["train.dispatch"] if s["start_ns"] >= lo),
        key=lambda s: s["start_ns"],
    )
    out = {
        "window_ns": hi - lo,
        "steps": len(dispatches),
        # Time of each span name inside the traced part, every thread.
        "inside_ns": {
            name: sum(e - s for s, e in clipped(group))
            for name, group in by_name.items()
        },
        # Whole durations and counts of the spans that closed in it.
        "closed": {
            name: [s for s in group if lo <= s["end_ns"] < hi]
            for name, group in by_name.items()
        },
        "parse_workers": len({
            s["thread"] for s in spans if s["name"] == "data.parse_chunk"
        }),
    }

    # The device's idle time, and which span of the train thread (the
    # thread that dispatches; its outermost spans only) it lies under.
    idle = Cover(complement(busy, lo, hi))
    out["idle_ns"] = idle.before[-1]
    train_thread = dispatches[0]["thread"] if dispatches else None
    under = collections.defaultdict(int)
    for span in inside:
        if span["thread"] == train_thread and span["parent"] is None:
            under[span["name"]] += idle.inside(clipped([span]))
    out["idle_under_ns"] = dict(under)
    out["idle_unattributed_ns"] = max(out["idle_ns"] - sum(under.values()), 0)
    out["parse_busy_in_idle_ns"] = idle.inside(
        clipped(by_name["data.parse_chunk"])
    )

    # The clocks: the k-th step module of the traced part (the device was
    # drained when it opened) belongs to its k-th dispatch.
    names = collections.Counter(
        name for name, start, end in modules if start >= lo and end <= hi
    )
    step_module = names.most_common(1)[0][0] if names else None
    starts = [
        start for name, start, end in modules
        if name == step_module and start >= lo and end <= hi
    ]
    out["step_modules"] = len(starts)
    out["clock_violations"] = sum(
        1 for span, start in zip(dispatches, starts)
        if start < span["start_ns"] - CLOCK_SLACK_NS
    )
    return out


def _view(run):
    snap = recorded(run)
    spans = getattr(run.window, "spans", None) or ()
    window = next((s for s in spans if s[0] == WINDOW_SPAN), None)
    if snap is None or window is None or not run.trace_dir:
        return None
    start_ns, busy, modules = device_timeline(run.trace_dir)
    if start_ns is None:
        return None
    shifted = [
        dict(s, start_ns=s["start_ns"] - start_ns, end_ns=s["end_ns"] - start_ns)
        for s in snap["spans"]
    ]
    out = reduce(
        shifted, (window[1] - start_ns, window[2] - start_ns), busy, modules
    )
    _say(run, out)
    return out


def _say(run, v):
    steps = max(v["steps"], 1)
    say = run.reporter.say
    say(
        f"program spans: {v['steps']} train.dispatch spans and "
        f"{v['step_modules']} step modules in the traced part of "
        f"{v['window_ns'] / 1e9:.3f} s (the window counted "
        f"{run.window.trace_steps} steps); device idle "
        f"{v['idle_ns'] / 1e6 / steps:.3f} ms a step, under the train "
        f"thread's spans: " + ", ".join(
            f"{name} {ns / 1e6 / steps:.3f}"
            for name, ns in sorted(v["idle_under_ns"].items(), key=lambda kv: -kv[1])
        ) + f", under none {v['idle_unattributed_ns'] / 1e6 / steps:.3f}"
    )
    if v["idle_ns"]:
        say(
            f"program spans: parse workers busy while the device was idle "
            f"{v['parse_busy_in_idle_ns'] / v['idle_ns']:.2f} of "
            f"{v['parse_workers']} (over the whole traced part "
            f"{v['inside_ns'].get('data.parse_chunk', 0) / v['window_ns']:.2f})"
        )
    outside = (run.trace_summary or {}).get("spans", {})
    for inner, outer in (
        ("infeed.wait", "bench.host_input.next"),
        ("train.dispatch", "bench.dispatch"),
    ):
        if inner in v["inside_ns"] and outer in outside:
            say(
                f"inside and outside: {inner} "
                f"{v['inside_ns'][inner] / 1e9:.6f} s, {outer} "
                f"{outside[outer]:.6f} s in the traced part"
            )
    say(
        f"clocks: {v['clock_violations']} of "
        f"{min(v['steps'], v['step_modules'])} step modules started more "
        f"than {CLOCK_SLACK_NS / 1e6:.0f} ms before their train.dispatch opened"
    )


# -- what the readers ask for --------------------------------------------------------


def ms_per_step(run, name):
    """Milliseconds a step of the traced part inside spans of this name."""
    v = view(run)
    if not v or not v["steps"] or name not in v["inside_ns"]:
        return None
    return v["inside_ns"][name] / 1e6 / v["steps"]


def mean_ms(run, name):
    """Mean duration, in milliseconds, of the spans of this name that closed
    in the traced part."""
    v = view(run)
    closed = v["closed"].get(name) if v else None
    if not closed:
        return None
    return sum(s["end_ns"] - s["start_ns"] for s in closed) / 1e6 / len(closed)
