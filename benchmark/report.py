"""Earlier-line reporting and jax's own compile and cache events.

Copied in substance from chip_smoke.py's Reporter (PR 21), which is sound:
jax.monitoring's duration events give compile seconds, its plain events
give persistent-cache hits and misses. The yardstick keeps its own copy so
that it does not move when the program's smoke does.
"""

from __future__ import annotations

import sys


class Reporter:
    _COMPILE_EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self, tag: str):
        from jax import monitoring

        self.tag = tag
        self.compile_s = 0.0
        self.backend_compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_):
        if name in self._COMPILE_EVENTS:
            self.compile_s += secs
        if name == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def say(self, text: str) -> None:
        """An earlier line: standard error, so that standard output ends in
        the one result line."""
        print(f"[bench {self.tag}] {text}", file=sys.stderr, flush=True)
