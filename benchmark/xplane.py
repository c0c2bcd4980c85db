"""From a profiler trace (`*.xplane.pb`) to busy time, idle gaps and
per-category device time.

The reader is a small protobuf wire parser of tsl's xplane.proto, kept to
the fields the reduction needs (the installed jax's `ProfileData` hides the
per-op metadata stats, and `hlo_category` is one of them). Copied in idea
from tools/read_trace.py (its parser and its care to bill the synchronous
`XLA Ops` line only); added here: the busy-interval union, the idle share,
host spans on the same clock, and gap labelling.

  XSpace.planes=1
  XPlane.name=2 .lines=3 .event_metadata=4 (map) .stat_metadata=5 (map)
  XLine.name=2 .display_name=11 .timestamp_ns=3 .events=4
  XEvent.metadata_id=1 .offset_ps=2 .duration_ps=3
  XEventMetadata.id=1 .name=2 .display_name=4 .stats=5
  XPlane.stats=6
  XStat.metadata_id=1 .uint64_value=3 .int64_value=4 .str_value=5 .ref_value=7
  XStatMetadata.id=1 .name=2

Times are picoseconds from the start of the profiler's session. Host spans
come either from the trace itself (`jax.profiler.TraceAnnotation` events
named `bench.*`, where the host tracer was on) or from the caller, stamped
with the host's epoch clock: the session's `profile_start_time` (a stat of
the `Task Environment` plane, epoch nanoseconds) puts those on the same
axis.
"""

from __future__ import annotations

import collections
import glob
import os

SYNC_LINE = "XLA Ops"
#: HLO categories of the operations that move data between chips; the
#: halves of an asynchronous one carry the name with `-start` / `-done`.
COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all",
)
WINDOW_SPAN = "bench.trace_window"
HOST_SPANS = ("bench.host_input.next", "bench.dispatch", "bench.readback")


# -- wire format ---------------------------------------------------------------


def _varint(buf, pos):
    result = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        result |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return result, pos
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message; fixed-width fields
    are skipped, length-delimited ones yield a memoryview."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
            yield field, wire, value
        elif wire == 2:
            size, pos = _varint(buf, pos)
            yield field, wire, buf[pos:pos + size]
            pos += size
        elif wire == 1:
            pos += 8
        elif wire == 5:
            pos += 4
        else:
            raise ValueError(f"unsupported wire type {wire}")


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _map_value(entry):
    for field, wire, value in _fields(entry):
        if field == 2 and wire == 2:
            return value
    return b""


class Plane:
    def __init__(self, buf):
        self.name = ""
        self.lines = []            # [(name, [(metadata id, start ps, duration ps)])]
        self.event_names = {}      # metadata id -> short name
        self.event_stats = {}      # metadata id -> {stat name: str}
        self.stats = {}            # plane-level integer stats by name
        stat_names = {}
        raw_meta, raw_lines, raw_stats = [], [], []
        for field, wire, value in _fields(buf):
            if wire != 2:
                continue
            if field == 2:
                self.name = _text(value)
            elif field == 3:
                raw_lines.append(value)
            elif field == 4:
                raw_meta.append(_map_value(value))
            elif field == 6:
                raw_stats.append(value)
            elif field == 5:
                ident, name = 0, ""
                for f, w, v in _fields(_map_value(value)):
                    if f == 1 and w == 0:
                        ident = v
                    elif f == 2 and w == 2:
                        name = _text(v)
                stat_names[ident] = name
        for stat in raw_stats:
            key = number = None
            for f, w, v in _fields(stat):
                if f == 1 and w == 0:
                    key = v
                elif f in (3, 4) and w == 0:
                    number = v
            if number is not None:
                self.stats[stat_names.get(key, str(key))] = number
        for meta in raw_meta:
            ident, name, display, stats = 0, "", "", {}
            for f, w, v in _fields(meta):
                if f == 1 and w == 0:
                    ident = v
                elif f == 2 and w == 2:
                    name = _text(v)
                elif f == 4 and w == 2:
                    display = _text(v)
                elif f == 5 and w == 2:
                    key = text = ref = None
                    for sf, sw, sv in _fields(v):
                        if sf == 1 and sw == 0:
                            key = sv
                        elif sf == 5 and sw == 2:
                            text = _text(sv)
                        elif sf == 7 and sw == 0:
                            ref = sv
                    if text is None and ref is not None:
                        text = stat_names.get(ref)
                    if text is not None:
                        stats[stat_names.get(key, str(key))] = text
            self.event_names[ident] = display or name
            self.event_stats[ident] = stats
        for line in raw_lines:
            name = display = ""
            timestamp_ns = 0
            events = []
            for f, w, v in _fields(line):
                if f == 2 and w == 2:
                    name = _text(v)
                elif f == 11 and w == 2:
                    display = _text(v)
                elif f == 3 and w == 0:
                    timestamp_ns = v
                elif f == 4 and w == 2:
                    ident = offset = duration = 0
                    for ef, ew, ev in _fields(v):
                        if ew != 0:
                            continue
                        if ef == 1:
                            ident = ev
                        elif ef == 2:
                            offset = ev
                        elif ef == 3:
                            duration = ev
                    events.append((ident, offset, duration))
            base = timestamp_ns * 1000
            self.lines.append((
                display or name,
                [(i, base + o, d) for i, o, d in events],
            ))


def load(path):
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [
        Plane(value) for field, wire, value in _fields(buf)
        if field == 1 and wire == 2
    ]


def find(trace_dir):
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    ))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


# -- reduction -----------------------------------------------------------------


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _clip(intervals, lo, hi):
    return [
        (max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi
    ]


def _is_device(plane):
    return plane.name.startswith("/device:TPU:")


def is_collective(category):
    return category.startswith(COLLECTIVES)


def _op_label(stats, name):
    """`<hlo category>:<layer path>` of a device op: the categoriser's name."""
    category = stats.get("hlo_category", "uncategorized")
    tf_op = stats.get("tf_op", "").rstrip(":")
    parts = [p for p in tf_op.split("/") if p and not p.startswith("jit(")]
    backward = any(p.startswith("transpose(") for p in parts)
    parts = [p for p in parts if "(" not in p]
    where = "/".join(parts[-4:-1] or parts) if parts else name
    return f"{category}:{'bwd:' if backward else ''}{where}"


def summarize(planes, steps=None, epoch_spans=()):
    """The trace reduced to what the readers want.

    `epoch_spans`: [(name, start, end)] in epoch nanoseconds, laid over the
    trace by the session's `profile_start_time`.

    The window is the `bench.trace_window` host span where the trace has
    one, else the extent of the device ops. Returns a dict with times in
    seconds: window_s, busy_s (union of the synchronous device ops, mean
    over device planes), idle_share, steps (module executions inside the
    window unless given), category_s {hlo category: s}, conv_s, device_ops
    [[label, s]..], idle_gaps [[label, s]..], spans {name: s}.
    """
    host_spans = collections.defaultdict(list)
    for plane in planes:
        if _is_device(plane):
            continue
        for _, events in plane.lines:
            for ident, start, duration in events:
                name = plane.event_names.get(ident, "")
                if name.startswith("bench."):
                    host_spans[name].append((start, start + duration))

    start_ns = next(
        (p.stats["profile_start_time"] for p in planes
         if "profile_start_time" in p.stats), None,
    )
    host_clock = "the trace's own spans"
    if epoch_spans and start_ns is not None:
        host_clock = "profile_start_time"
        for name, begin, end in epoch_spans:
            host_spans[name].append(
                ((begin - start_ns) * 1000, (end - start_ns) * 1000)
            )

    devices = [p for p in planes if _is_device(p)]
    if not devices:
        raise ValueError("the trace has no /device:TPU plane")
    per_device_ops = []
    for plane in devices:
        ops = [ev for name, evs in plane.lines if name == SYNC_LINE for ev in evs]
        per_device_ops.append(ops)
    if not any(per_device_ops):
        raise ValueError("no operation ran on the device in the trace")

    if host_spans.get(WINDOW_SPAN):
        lo, hi = host_spans[WINDOW_SPAN][0]
    else:
        lo = min(s for ops in per_device_ops for _, s, _ in ops)
        hi = max(s + d for ops in per_device_ops for _, s, d in ops)
    window_ps = hi - lo

    busy_ps, category_ps, label_ps = [], collections.Counter(), collections.Counter()
    collective_ps, collective_n = collections.Counter(), collections.Counter()
    modules = 0
    for plane, ops in zip(devices, per_device_ops):
        intervals = _clip([(s, s + d) for _, s, d in ops], lo, hi)
        merged = union(intervals)
        busy_ps.append(sum(e - s for s, e in merged))
        for ident, start, duration in ops:
            clipped = min(start + duration, hi) - max(start, lo)
            if clipped <= 0:
                continue
            stats = plane.event_stats.get(ident, {})
            category = stats.get("hlo_category", "uncategorized")
            category_ps[category] += clipped
            label_ps[_op_label(stats, plane.event_names.get(ident, "?"))] += clipped
            if is_collective(category):
                op = (plane.event_names.get(ident, "?"), category)
                collective_ps[op] += clipped
                collective_n[op] += 1
        for name, evs in plane.lines:
            if name == "XLA Modules":
                modules += sum(1 for _, s, d in evs if s >= lo and s + d <= hi)
    n_dev = len(devices)
    busy_s = sum(busy_ps) / n_dev / 1e12

    # Idle gaps of the first device, labelled by the host span that covers
    # most of each.
    merged = union(_clip([(s, s + d) for _, s, d in per_device_ops[0]], lo, hi))
    gaps, cursor = [], lo
    for start, end in merged + [[hi, hi]]:
        if start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor, end)
    def label_of(g0, g1):
        best, best_cover = "host.other", 0
        for name in HOST_SPANS:
            cover = sum(
                max(0, min(e, g1) - max(s, g0)) for s, e in host_spans.get(name, ())
            )
            if cover > best_cover:
                best, best_cover = name, cover
        return best

    labelled = [(label_of(g0, g1), g1 - g0) for g0, g1 in gaps]
    gap_by_label = collections.Counter()
    for label, length in labelled:
        gap_by_label[label] += length
    longest = sorted(labelled, key=lambda g: -g[1])[:10]

    conv_ps = sum(v for k, v in category_ps.items() if "convolution" in k)
    return {
        "window_s": window_ps / 1e12,
        "host_clock": host_clock,
        # Where the clocks agree the device, drained at both ends, starts
        # within a dispatch of the window's opening and ends at its close.
        "lead_s": (min(
            s for ops in per_device_ops for _, s, _ in ops if s >= lo
        ) - lo) / 1e12,
        "tail_s": (hi - max(s + d for ops in per_device_ops for _, s, d in ops)) / 1e12,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (window_ps / 1e12),
        "devices": n_dev,
        "steps": steps if steps is not None else modules // max(n_dev, 1),
        "modules_in_window": modules,
        "category_s": {k: v / n_dev / 1e12 for k, v in category_ps.most_common()},
        "conv_s": conv_ps / n_dev / 1e12,
        "collectives": {
            name: [category, ps / n_dev / 1e12, collective_n[name, category] / n_dev]
            for (name, category), ps in collective_ps.most_common()
        },
        "device_ops": [
            [k, v / n_dev / 1e12] for k, v in label_ps.most_common(10)
        ],
        "idle_gaps": [[label, length / 1e12] for label, length in longest],
        "idle_by_span_s": {k: v / 1e12 for k, v in gap_by_label.most_common()},
        "spans": {
            name: sum(
                max(0, min(e, hi) - max(s, lo)) for s, e in spans
            ) / 1e12
            for name, spans in host_spans.items() if name != WINDOW_SPAN
        },
    }
