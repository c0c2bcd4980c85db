"""What building a jitted program costs, seen from inside the process.

jax reports every build through `jax.monitoring`: tracing a function to a
jaxpr, lowering the jaxpr to a module, and the backend's compile, which is
the persistent cache's lookup and, on a hit, the executable's load. Each
arrives with its own `time.time()` stamps, the clock of utils/tracing.py's
recorder, and with the name of the function. `install()` makes them spans
of that recorder, on the thread that built:

  jit.trace, jit.lower, jit.compile   `label` is jax's `fun_name`. A
      `jit.compile` also counts `cache_hit` (0 or 1), `retrieval_ns` (the
      cache's read on a hit) and `saved_ns` (the compile the hit spared).
  jit.cache_hits, jit.cache_misses    cumulative counters.

Builds nest: a step's trace traces every jitted function it calls,
thousands of them; a lowering rule traces; a trace may run a small program
eagerly. Only the outermost event of a thread becomes a span and is summed.
What it encloses is part of it, so the three kinds never overlap on one
thread and their sum is at most the wall time around them.

`totals()` is the calling thread's running sum of all that, an immutable
tuple that is replaced on every event: two reads around a call that are
the same object say that the call built nothing, at the price of two
thread-local reads; `after.since(before)` is the difference as counts of a
span, and `span(name)` is a recorder span that carries the difference over
itself. The sums do not depend on what the recorder's ring still holds.

There is no switch. `install()` is idempotent and costs a listener call of
a microsecond or so an event; `CompiledModel` and `train_eval_model` call
it, so a process that trains has it.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Dict, Iterator, NamedTuple, Optional

from tensor2robot_tpu.utils import tracing

__all__ = ["Totals", "install", "process_start_ns", "span", "totals"]

_SPAN_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}
_CACHE_COUNTER_OF = {
    "/jax/compilation_cache/cache_hits": "jit.cache_hits",
    "/jax/compilation_cache/cache_misses": "jit.cache_misses",
}
_CACHE_DURATION_OF = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_ns",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_ns",
}


class Totals(NamedTuple):
    """One thread's builds so far."""

    programs: int = 0       # backend compiles: executables built or loaded
    trace_ns: int = 0
    lower_ns: int = 0
    compile_ns: int = 0     # compile, or the cache's lookup and load
    cache_hits: int = 0
    cache_misses: int = 0
    retrieval_ns: int = 0   # of compile_ns, the cache's reads that hit

    def since(self, before: "Totals") -> Dict[str, int]:
        return {
            field: mine - theirs
            for field, mine, theirs in zip(self._fields, self, before)
        }


class _Thread(threading.local):
    """The building thread's state between two listener calls."""

    def __init__(self):
        self.totals = Totals()
        self.depth = 0      # build events open on this thread
        self.cache = {}     # cache events since the last compile closed


_thread = _Thread()
_install_lock = threading.Lock()
_installed = False


def totals() -> Totals:
    """The calling thread's running sums."""
    return _thread.totals


@contextlib.contextmanager
def span(name: str) -> Iterator[tracing.Span]:
    """A span of the recorder that also counts what its thread built
    inside it (the fields of `Totals`)."""
    before = totals()
    with tracing.span(name) as opened:
        yield opened
        opened.add(**totals().since(before))


def _on_enter(event, value, **_):
    # jax sends an event's start time as a scalar when the event opens.
    if event in _SPAN_OF:
        _thread.depth += 1


def _on_span(event, start_time, end_time, fun_name=None, **_):
    name = _SPAN_OF.get(event)
    if name is None:
        return
    state = _thread
    # An event that was open when the listeners were registered closes
    # without having been counted in.
    state.depth = max(state.depth - 1, 0)
    cache = {}
    if name == "jit.compile":
        cache, state.cache = state.cache, cache
    if state.depth:
        return
    start_ns, end_ns = int(start_time * 1e9), int(end_time * 1e9)
    spent = end_ns - start_ns
    before = state.totals
    counts = {}
    if name == "jit.trace":
        state.totals = before._replace(trace_ns=before.trace_ns + spent)
    elif name == "jit.lower":
        state.totals = before._replace(lower_ns=before.lower_ns + spent)
    else:
        counts = {
            "cache_hit": cache.get("jit.cache_hits", 0),
            "retrieval_ns": cache.get("retrieval_ns", 0),
            "saved_ns": cache.get("saved_ns", 0),
        }
        state.totals = before._replace(
            programs=before.programs + 1,
            compile_ns=before.compile_ns + spent,
            cache_hits=before.cache_hits + counts["cache_hit"],
            cache_misses=before.cache_misses + cache.get("jit.cache_misses", 0),
            retrieval_ns=before.retrieval_ns + counts["retrieval_ns"],
        )
    tracing.between(
        name, start_ns, end_ns, label=None if fun_name is None else str(fun_name),
        **counts,
    )


def _on_event(event, **_):
    # The cache's events fire inside the backend compile they belong to.
    counter = _CACHE_COUNTER_OF.get(event)
    if counter is not None:
        tracing.count(counter)
        _thread.cache[counter] = 1


def _on_duration(event, duration_secs, **_):
    key = _CACHE_DURATION_OF.get(event)
    if key is not None:
        _thread.cache[key] = int(duration_secs * 1e9)


def install() -> None:
    """Registers the listeners with jax, once a process."""
    global _installed
    with _install_lock:
        if _installed:
            return
        from jax import monitoring

        monitoring.register_scalar_listener(_on_enter)
        monitoring.register_event_time_span_listener(_on_span)
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True


def process_start_ns() -> Optional[int]:
    """Epoch nanoseconds at which the kernel started this process: the boot
    time by the two clocks plus `/proc/self/stat`'s start in ticks since
    boot. None where there is no such file or clock (off Linux)."""
    try:
        with open("/proc/self/stat") as f:
            # The command's name, field 2, may hold spaces and brackets.
            fields = f.read().rsplit(")", 1)[1].split()
        ticks = int(fields[19])  # field 22, `starttime`
        boot_ns = time.time_ns() - time.clock_gettime_ns(time.CLOCK_BOOTTIME)
        return boot_ns + ticks * 10**9 // os.sysconf("SC_CLK_TCK")
    except (OSError, AttributeError, IndexError, ValueError):
        return None
