"""Device time of the traced window under any list of `jax.named_scope`s.

`scope_time.py` bills a traced run's device time to the scopes of the
hybrid state-space model, a list fixed in that file; a configuration whose
program names other scopes brings its list here and gets the same sums
(the same trace, the same window, an op billed once, to the innermost of
the listed scopes on its name stack):

    per_step(run, scopes, among, unlabelled) -> seconds a traced step under `scopes`

`among` is every scope the configuration's program names: the innermost
of *those* takes an op, so that `moe/shared` is not billed to a scope that
merely encloses it. `unlabelled` is the configuration's too: {start of a
label: scope} for the ops the chip's compiler emits with no name stack
(the grouped products of `lax.ragged_dot` come out as a custom call
labelled `ragged-dot-<n>`), billed to the scope that calls them. None
where there is no trace or where the program names none of `scopes` (an
older program).
"""

from __future__ import annotations

import collections
import re

import xplane

#: Control flow on the device's op line: its events span the events of the
#: ops inside it, which are on the line too, so its own time is not billed.
WRAPPERS = ("while", "conditional", "call")
_TRANSFORMS = re.compile(r"[A-Za-z_]+\(|\)")


def innermost(label, among, unlabelled=()):
    """The scope of `among` that ends last on an op's name stack, or None.
    A transformation wraps the frame it meets first (`transpose(jvp(moe/
    experts))` inside a hand-written backward): the wrappers are dropped."""
    for start, scope in unlabelled:
        if label.startswith(start):
            return scope
    path = "/" + _TRANSFORMS.sub("", label).strip("/") + "/"
    best, best_at = None, -1
    for scope in among:
        at = path.rfind("/" + scope + "/")
        if at >= 0 and at + len(scope) > best_at:
            best, best_at = scope, at + len(scope)
    return best


def reduce(planes, lo, hi, among, unlabelled=()):
    """({scope: picoseconds}, {(scope, hlo category, where): picoseconds}) of
    the synchronous device ops clipped to [lo, hi), mean over device planes;
    under None the ops outside `among`. `where` is the end of an op's name
    stack: the second dict says which ops a scope's time is."""
    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    total, ops = collections.Counter(), collections.Counter()
    for plane in devices:
        for name, events in plane.lines:
            if name != xplane.SYNC_LINE:
                continue
            for ident, start, duration in events:
                clipped = min(start + duration, hi) - max(start, lo)
                stats = plane.event_stats.get(ident, {})
                category = stats.get("hlo_category", "uncategorized")
                if clipped <= 0 or category in WRAPPERS:
                    continue
                label = stats.get("tf_op", "")
                scope = innermost(label, among, unlabelled)
                total[scope] += clipped
                where = "/".join(_TRANSFORMS.sub("", label).strip("/:").split("/")[-3:])
                ops[(scope, category, where)] += clipped
    share = max(len(devices), 1)
    return ({k: v / share for k, v in total.items()},
            {k: v / share for k, v in ops.items()})


def seconds(run, among, unlabelled=()):
    """{scope: device seconds inside the traced window}, once a run."""
    cache = run.__dict__.setdefault("_scope_sums", {})
    if (among, unlabelled) in cache:
        return cache[among, unlabelled]
    out = {}
    window = [s for s in (run.window.spans if run.window else ())
              if s[0] == xplane.WINDOW_SPAN]
    if run.trace_dir and window:
        planes = xplane.load(xplane.find(run.trace_dir))
        start_ns = next(
            (p.stats["profile_start_time"] for p in planes
             if "profile_start_time" in p.stats), None,
        )
        if start_ns is not None:
            _, begin, end = window[0]
            found, ops = reduce(
                planes, (begin - start_ns) * 1000, (end - start_ns) * 1000,
                among, unlabelled,
            )
            steps = max(run.trace_summary["steps"], 1)
            run.reporter.say("device time by scope, ms a step: " + ", ".join(
                f"{k or 'outside every scope'} {v / 1e9 / steps:.2f}"
                for k, v in sorted(found.items(), key=lambda kv: -kv[1])
            ))
            run.reporter.say("longest ops, ms a step: " + "; ".join(
                f"{scope or 'outside'} {category} {where} {v / 1e9 / steps:.2f}"
                for (scope, category, where), v in sorted(
                    ops.items(), key=lambda kv: -kv[1])[:40]
            ))
            out = {k: v / 1e12 for k, v in found.items() if k is not None}
    cache[among, unlabelled] = out
    return out


def per_step(run, scopes, among, unlabelled=()):
    found = seconds(run, tuple(among), tuple(unlabelled))
    if not run.trace_summary or not run.trace_summary["steps"]:
        return None
    if not any(s in found for s in scopes):
        return None
    return sum(found.get(s, 0.0) for s in scopes) / run.trace_summary["steps"]


def roofline(run, name, cost, measured, scopes):
    """100 x least time of `cost` ({"step_flops", "step_bytes"}) over
    `measured` seconds a step; says which bound it was."""
    by_flops = cost["step_flops"] / run.peaks["bf16_flops_per_s"]
    by_bytes = cost["step_bytes"] / run.peaks["hbm_bytes_per_s"]
    least = max(by_flops, by_bytes)
    run.reporter.say(
        f"{name} roofline: least {least * 1e3:.3f} ms a step, bound by "
        f"{'FLOPs' if by_flops >= by_bytes else 'bytes'} (FLOPs "
        f"{by_flops * 1e3:.3f} ms, bytes {by_bytes * 1e3:.3f} ms); measured "
        f"{measured * 1e3:.3f} ms a step under {', '.join(scopes)}"
    )
    return 100.0 * least / measured
