"""Profiling hook: a jax.profiler trace of a window of steps, with the
program's own spans of that window beside it.

The reference had NO tracing subsystem (SURVEY §5: absent), but the rebuild
targets an MFU north star, so observability of where step time goes is
first-class here. ProfilerHookBuilder captures the device's timeline
(XPlane, viewable in TensorBoard or xprof) for the steps
[start_step, start_step + num_steps) and, when the window closes, writes
the host path's spans of that window (utils/tracing.py) as `spans.jsonl`
in the directory of the `.xplane.pb`: one JSON object a line with `name`,
`id`, `parent`, `thread`, `ordinal`, `start_ns`, `end_ns` (epoch
nanoseconds) and `counts`. The xplane's `profile_start_time` is on the
same clock, so the spans lie on the device's axis once it is subtracted.

The profiler's host and python tracers stay off: the host-side relayout of
a fed uint8 batch alone writes millions of events a few seconds, hundreds
of megabytes, and the tracing itself then stalls the transfer (PERF.md,
PR 24). What the host did is in `spans.jsonl`.
"""

from __future__ import annotations

import glob
import json
import os
import time
from typing import List, Optional

import jax

from tensor2robot_tpu.config import configurable
from tensor2robot_tpu.hooks.hook_builder import Hook, HookBuilder, HookContext
from tensor2robot_tpu.utils import tracing


class ProfilerHook(Hook):
    def __init__(self, log_dir: str, start_step: int, num_steps: int):
        self._log_dir = log_dir
        self._start = start_step
        self._stop = start_step + num_steps
        self._active = False
        self._done = False
        self._trace_dir: Optional[str] = None
        self._started_ns = 0

    def before_step(self, ctx: HookContext) -> None:
        # >= (not a range check): in the multi-step regime ctx.step advances
        # by iterations_per_loop and may never land inside the window.
        if not self._active and not self._done and ctx.step >= self._start:
            log_dir = self._log_dir
            if not os.path.isabs(log_dir) and ctx.model_dir:
                log_dir = os.path.join(ctx.model_dir, log_dir)
            os.makedirs(log_dir, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.host_tracer_level = 0
            options.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=options)
            self._trace_dir = log_dir
            self._started_ns = time.time_ns()
            self._active = True

    def after_step(self, ctx: HookContext) -> None:
        if self._active and ctx.step >= self._stop:
            self._finish(ctx)

    def _finish(self, ctx: HookContext) -> None:
        # Drain in-flight device work so the trace holds whole steps.
        if ctx.device_metrics is not None:
            jax.block_until_ready(ctx.device_metrics)
        jax.profiler.stop_trace()
        self._active = False
        self._done = True
        # Beside the newest xplane (jax writes plugins/profile/<time>/), or
        # in the trace directory itself where the profiler left none.
        traces = sorted(glob.glob(
            os.path.join(self._trace_dir, "**", "*.xplane.pb"), recursive=True
        ))
        out_dir = os.path.dirname(traces[-1]) if traces else self._trace_dir
        spans = tracing.snapshot(since_ns=self._started_ns)["spans"]
        with open(os.path.join(out_dir, "spans.jsonl"), "w") as f:
            for span in spans:
                f.write(json.dumps(span) + "\n")

    def on_train_end(self, ctx: HookContext) -> None:
        if self._active:
            self._finish(ctx)


@configurable("ProfilerHookBuilder")
class ProfilerHookBuilder(HookBuilder):
    """Trace steps [start_step, start_step + num_steps) into
    model_dir/profiling/ (or an explicit log_dir)."""

    def __init__(
        self,
        start_step: int = 10,
        num_steps: int = 5,
        log_dir: Optional[str] = None,
    ):
        self._start_step = start_step
        self._num_steps = num_steps
        self._log_dir = log_dir

    def create_hooks(self, t2r_model, trainer=None) -> List[Hook]:
        del t2r_model, trainer
        log_dir = self._log_dir or "profiling"
        return [ProfilerHook(log_dir, self._start_step, self._num_steps)]
