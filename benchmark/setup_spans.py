"""What the program's own spans say about set-up: the first build of the
cell's step program (`train.build`), the programs `init_state` built, and
the import of the training stack (`program.import`). Read from the
recorder's snapshot (program_spans.recorded), whatever `--trace` says:
set-up is over before the profiler starts.

Only what closed before the window opened counts. The plain reference
compiles its own programs after the window, in this process, and jax tells
the program's listeners of those too. `window.py` stamps the opening with
`perf_counter`; `opened_ns` moves it onto the spans' epoch clock by the
offset of the two clocks now (they drift microseconds a minute).

Every function returns None on a program without the span (a commit before
PR 38), and the reader then returns None.
"""

from __future__ import annotations

import time

import program_spans

#: The step programs of `CompiledModel`, by the labels their builds carry.
STEP_LABELS = ("train_step", "train_scan", "quant_train_step", "quant_train_scan")
_KINDS = {"jit.trace": "trace_ns", "jit.lower": "lower_ns", "jit.compile": "compile_ns"}


def opened_ns(run):
    """The window's opening in epoch nanoseconds."""
    return int(
        run.window.opened_at * 1e9 + time.time_ns() - time.perf_counter() * 1e9
    )


def before_window(run):
    """The recorded spans that closed before the window opened, oldest
    first; empty where the program has no recorder."""
    if not hasattr(run, "_setup_spans"):
        snap = program_spans.recorded(run)
        opened = opened_ns(run)
        run._setup_spans = [
            s for s in (snap["spans"] if snap else ()) if s["end_ns"] <= opened
        ]
    return run._setup_spans


def _seconds(ns):
    return ns / 1e9


def step_build(run):
    """The first `train.build` of a step program, or None. Says once a run
    what it is made of, and whether jax's own spans of that build lie inside
    it on its thread."""
    if hasattr(run, "_setup_step_build"):
        return run._setup_step_build
    spans = before_window(run)
    build = next(
        (s for s in spans
         if s["name"] == "train.build" and s.get("label") in STEP_LABELS),
        None,
    )
    run._setup_step_build = build
    if build is None:
        return None
    counts = build["counts"]
    length = build["end_ns"] - build["start_ns"]
    covered = counts["trace_ns"] + counts["lower_ns"] + counts["compile_ns"]
    own = [
        s for s in spans
        if s["name"] in _KINDS and build["label"] in (s.get("label") or "")
    ]
    outside = [
        s for s in own
        if s["thread"] != build["thread"] or s["start_ns"] < build["start_ns"]
        or s["end_ns"] > build["end_ns"]
    ]
    run.reporter.say(
        f"step build: train.build {build['label']} {_seconds(length):.3f} s = "
        f"trace {_seconds(counts['trace_ns']):.3f} + lower "
        f"{_seconds(counts['lower_ns']):.3f} + compile or load "
        f"{_seconds(counts['compile_ns']):.3f} (programs {counts['programs']}, "
        f"cache hits {counts['cache_hits']} misses {counts['cache_misses']}, "
        f"retrieval {_seconds(counts['retrieval_ns']):.3f}) + under no jax "
        f"event {_seconds(length - covered):.3f}; jit spans of "
        f"{build['label']} before the window: {len(own)}, outside their "
        f"train.build or off its thread: {len(outside)}; spans the ring holds: "
        f"{len(program_spans.recorded(run)['spans'])}"
    )
    return build


def init_state(run):
    """The program's first `train.init_state` span, or None."""
    return next(
        (s for s in before_window(run) if s["name"] == "train.init_state"), None
    )


def program_import(run):
    """The `program.import` span, or None. Says what lay before it."""
    span = next(
        (s for s in before_window(run) if s["name"] == "program.import"), None
    )
    if span is None:
        return None
    started = program_spans.recorded(run)["counters"].get("process.start_ns")
    run.reporter.say(
        "program import: "
        + ("process start not known" if started is None else
           f"process start to the package's first line "
           f"{_seconds(span['start_ns'] - started):.3f} s")
        + f", the training stack's import "
        f"{_seconds(span['end_ns'] - span['start_ns']):.3f} s, of it "
        f"train_eval.py's own first line to its last "
        f"{_seconds(span['counts'].get('own_ns', 0)):.3f} s"
    )
    return span
