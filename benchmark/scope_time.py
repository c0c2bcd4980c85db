"""Device time of the traced window by `jax.named_scope`, for the readers
whose kernels are named by scope and not by HLO category.

`xplane.summarize` keeps the ten longest operations; a scope's time is the
sum over every operation whose label (`tf_op`, the op's name stack, forward
and `transpose(jvp(..))` alike) passes through that scope. A fusion carries
one label, its root's: time is billed to one scope, never to two.

    seconds(run) -> {scope: device seconds inside the traced window}

for the scopes in `SCOPES`, mean over device planes, computed once a run
and kept on it. {} where there is no trace, or where the program names no
such scope (an older program): the readers then return None.
"""

from __future__ import annotations

import collections

import xplane

SCOPES = (
    "mamba2/in_proj", "mamba2/conv", "mamba2/ssd", "mamba2/gate_norm",
    "mamba2/out_proj", "attention", "attention_proj", "mlp", "lm_head",
)
MIXER_SCOPES = tuple(
    s for s in SCOPES if s.startswith(("mamba2/", "attention"))
)


def scope_of(label):
    """The innermost of SCOPES on an op's name stack, or None."""
    path = "/" + label.strip("/") + "/"
    best, best_at = None, -1
    for scope in SCOPES:
        at = path.rfind("/" + scope + "/")
        if at > best_at:
            best, best_at = scope, at
    return best


def reduce(planes, lo, hi):
    """{scope: picoseconds} of the synchronous device ops clipped to
    [lo, hi), mean over device planes; under `None` the time of the ops
    outside every scope."""
    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    total = collections.Counter()
    for plane in devices:
        for name, events in plane.lines:
            if name != xplane.SYNC_LINE:
                continue
            for ident, start, duration in events:
                clipped = min(start + duration, hi) - max(start, lo)
                if clipped <= 0:
                    continue
                scope = scope_of(plane.event_stats.get(ident, {}).get("tf_op", ""))
                total[scope] += clipped
    return {k: v / max(len(devices), 1) for k, v in total.items()}


def seconds(run):
    cached = getattr(run, "_scope_seconds", None)
    if cached is not None:
        return cached
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
            found = reduce(
                planes, (begin - start_ns) * 1000, (end - start_ns) * 1000
            )
            steps = max(run.trace_summary["steps"], 1)
            run.reporter.say("device time by scope, ms a step: " + ", ".join(
                f"{k or 'outside every scope'} {v / 1e9 / steps:.2f}"
                for k, v in sorted(found.items(), key=lambda kv: -kv[1])
            ))
            out = {k: v / 1e12 for k, v in found.items() if k is not None}
    run._scope_seconds = out
    return out


def per_step(run, scopes):
    """Seconds a traced step under `scopes`, or None."""
    found = seconds(run)
    if not run.trace_summary or not run.trace_summary["steps"]:
        return None
    if not any(s in found for s in scopes):
        return None
    return sum(found.get(s, 0.0) for s in scopes) / run.trace_summary["steps"]


def roofline(run, kernel, scopes):
    """100 x least time of `kernel` (the reference's `kernel_costs`) over
    the device time under `scopes`, or None; says which bound it was."""
    import numpy as np

    measured = per_step(run, scopes)
    if not measured or not hasattr(run.reference, "kernel_costs"):
        return None
    cost = run.reference.kernel_costs(
        run.config, run.cell["batch"] * len(run.devices),
        run.config["arguments"]["sequence_length"],
        np.dtype(run.config["compute_dtype"]).itemsize,
    )[kernel]
    by_flops = cost["step_flops"] / run.peaks["bf16_flops_per_s"]
    by_bytes = cost["step_bytes"] / run.peaks["hbm_bytes_per_s"]
    least = max(by_flops, by_bytes)
    run.reporter.say(
        f"{kernel} roofline: least {least * 1e3:.3f} ms a step, bound by "
        f"{'FLOPs' if by_flops >= by_bytes else 'bytes'} (FLOPs "
        f"{by_flops * 1e3:.3f} ms, bytes {by_bytes * 1e3:.3f} ms); measured "
        f"{measured * 1e3:.3f} ms a step under {', '.join(scopes)}"
    )
    return 100.0 * least / measured
