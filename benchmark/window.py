"""The measured window: warm-up, step stamps, the end-to-end numbers, and
the short traced part of a `--trace 1` run.

Both training drivers put the same three calls around the program's step:

    window.before_step()                    # just before the dispatch
    window.after_step(state, metrics)       # just after it
    window.expired()                        # feed no more steps

The first `warmup_steps` steps are set-up (the first one compiles or loads
from the cache; the first three are also what `correct` reads). When the
last of them has completed (`block_until_ready` on its state) the window
opens. From then on every step's completion is stamped by the host clock
after a readback of that step's loss, two steps behind the dispatch, so
the device always has a step queued. When `seconds` have passed the driver
stops feeding, `close()` drains the steps in flight, and the window ends
at the last completion:

  train.examples_per_s  batch x steps completed in the window over the
                        whole window. Never a median of chunks.
  train.step_ms.p90     90th percentile of the time between one step's
                        completion and the next's, over every step.

With `trace_seconds` set, a part of the window that starts `trace_after`
seconds in is run under `jax.profiler`: the device is drained, a
`bench.trace_window` span opens, steps run, the device is drained again
and the span closes. Spans `bench.dispatch` and `bench.readback` (and the
fed driver's `bench.host_input.next`) say what the host was doing.

The spans are the window's own, stamped with the host's epoch clock
(`time.time_ns`), which is the clock the profiler dates its session by
(`profile_start_time`), so `xplane.summarize` lays them over the device's
timeline. The profiler's host tracer is off: with it on, the runtime's
host-side relayout of a fed uint8 batch writes some 17 million `Transpose`
events in nine seconds, 600 MB of trace, and the tracing itself stalls the
transfer for 8.5 s (my chip runs, PR 24). Python's tracer is off as well.
"""

from __future__ import annotations

import collections
import contextlib
import math
import os
import shutil
import time

import jax

LAG = 2


def percentile(values, q):
    """The q-quantile by linear interpolation between order statistics."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("no samples")
    pos = (len(ordered) - 1) * q
    low = math.floor(pos)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (pos - low)


class Window:
    def __init__(self, *, batch, seconds, warmup_steps, reporter,
                 readings=None, trace_dir=None, trace_seconds=3.0,
                 trace_after=2.0):
        self.batch = batch
        self.seconds = seconds
        self.warmup_steps = warmup_steps
        self.reporter = reporter
        self.readings = readings
        self.trace_dir = trace_dir
        self.trace_seconds = trace_seconds
        self.trace_after = trace_after

        self.steps_done = 0
        self.opened_at = None          # host clock, window start
        self.stamps = []               # completion time of each window step
        self.losses = []               # loss of each window step
        self._pending = collections.deque()
        self._dispatch_t0 = None
        self.spans = []                # (name, start, end), epoch ns, traced part
        self._compiles_at_open = None
        self._compiles_at_last_stamp = None
        self.compiles_in_window = None
        # traced part
        self.trace_state = "off" if trace_dir is None else "waiting"
        self.trace_steps = 0
        self.trace_window_s = None
        self._trace_t0 = None
        self._trace_t0_ns = None
        self._last_state = None

    # -- the three calls -------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name):
        """A host span of the traced part; free outside it."""
        if self.trace_state != "on":
            yield
            return
        started = time.time_ns()
        try:
            yield
        finally:
            self.spans.append((name, started, time.time_ns()))

    def before_step(self):
        if self.trace_state == "on":
            self._dispatch_t0 = time.time_ns()

    def after_step(self, state, metrics):
        if self._dispatch_t0 is not None:
            self.spans.append(("bench.dispatch", self._dispatch_t0, time.time_ns()))
            self._dispatch_t0 = None
        self.steps_done += 1
        self._last_state = state
        if self.opened_at is None:
            if self.readings is not None:
                self.readings.after_step(self.steps_done, state, metrics)
            if self.steps_done == self.warmup_steps:
                jax.block_until_ready(state)
                self._compiles_at_open = self.reporter.backend_compiles
                self._compiles_at_last_stamp = self._compiles_at_open
                self.opened_at = time.perf_counter()
            return
        self._pending.append(metrics["loss"])
        while len(self._pending) > LAG:
            self._stamp_oldest()
        self._drive_trace(state)

    def expired(self):
        return (
            self.opened_at is not None
            and time.perf_counter() - self.opened_at >= self.seconds
        )

    def drain(self):
        """Stamps every step in flight as it completes."""
        while self._pending:
            self._stamp_oldest()

    def close(self):
        """Drains the steps in flight; the window ends at the last stamp."""
        if self.trace_state == "on":
            self._stop_trace(self._last_state)
        self.drain()
        # The window ends at its last stamp. What compiles after it (the
        # trainer's closing checkpoint slices every array of a state that
        # lies on several devices, a small program a shape and device) is
        # not inside it.
        self.compiles_in_window = (
            self._compiles_at_last_stamp - self._compiles_at_open
        )
        self._last_state = None

    # -- internals -------------------------------------------------------------

    def _stamp_oldest(self):
        loss = self._pending.popleft()
        with self.span("bench.readback"):
            value = float(loss)
        self.stamps.append(time.perf_counter())
        self.losses.append(value)
        self._compiles_at_last_stamp = self.reporter.backend_compiles

    def _drive_trace(self, state):
        now = time.perf_counter()
        if (
            self.trace_state == "waiting"
            and now - self.opened_at >= self.trace_after
        ):
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            os.makedirs(self.trace_dir, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.host_tracer_level = 0
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            jax.block_until_ready(state)
            self._trace_t0 = time.perf_counter()
            self._trace_t0_ns = time.time_ns()
            self.trace_state = "on"
            self.trace_steps = 0
        elif self.trace_state == "on":
            self.trace_steps += 1
            if now - self._trace_t0 >= self.trace_seconds:
                self._stop_trace(state)

    def _stop_trace(self, state):
        jax.block_until_ready(state)
        self.trace_window_s = time.perf_counter() - self._trace_t0
        self.spans.append(("bench.trace_window", self._trace_t0_ns, time.time_ns()))
        jax.profiler.stop_trace()
        self.trace_state = "done"

    # -- results ---------------------------------------------------------------

    def results(self):
        steps = len(self.stamps)
        if steps < 2:
            raise RuntimeError(f"the window completed {steps} steps")
        window_s = self.stamps[-1] - self.opened_at
        gaps = [
            (b - a) * 1e3
            for a, b in zip([self.opened_at] + self.stamps[:-1], self.stamps)
        ]
        failed = sum(1 for x in self.losses if not math.isfinite(x))
        return {
            "steps": steps,
            "failed": failed,
            "window_s": window_s,
            # The end-to-end metrics this window stands for, by their names
            # in BENCHMARK.json (`setup_s` is the harness's own).
            "metrics": {
                "train.examples_per_s": self.batch * steps / window_s,
                "train.step_ms.p90": percentile(gaps, 0.90),
            },
            "step_ms_p50": percentile(gaps, 0.50),
            "step_ms_max": max(gaps),
        }
