"""JAX persistent compilation cache: one resolver for every entry point.

The flagship train step and every serving bucket take seconds to
minutes to compile and never change between runs of the same code on
the same topology — exactly what jax's persistent compilation cache
deduplicates. Where that cache lives is decided HERE and nowhere else:

  * `JAX_COMPILATION_CACHE_DIR` set: jax already holds the directory
    (it reads the variable at import); this module sets none.
  * unset: a fixed directory inside the checkout (`CHECKOUT_CACHE_DIR`,
    git-ignored), resolved from this file's location — the path is part
    of the cache key, so it must never move between runs.

Entry points (`bin/run_t2r_trainer`, `bin/run_continuous_eval`,
`bench.py`, `chip_smoke.py`) call `enable_compile_cache()` before their
first compile. Library code never places a cache: the predictor's
restore path and the policy server call `engage_compile_cache()`, which
only makes an already-placed directory take effect for a compile tier
that is about to run.

With serialized AOT executables in the artifact (export/aot.py) this
cache is the SECOND tier of the restore ladder: AOT executable ->
persistent compile cache -> fresh trace
(`serving/compile_cache.enable_compile_cache_for` decides per restored
version whether that tier is live).
"""

from __future__ import annotations

import contextlib
import os
import sys
from typing import Iterator, Optional

__all__ = [
    "CHECKOUT_CACHE_DIR",
    "compile_cache_bypass",
    "enable_compile_cache",
    "engage_compile_cache",
    "placed_cache_dir",
]

#: The cache directory when `JAX_COMPILATION_CACHE_DIR` is unset.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def _unlatch() -> None:
    # jax memoizes the cache's used/unused state at the FIRST compile: a
    # process that compiled anything before the config changed would
    # silently keep the old answer. reset_cache() drops the memo so the
    # next compile re-reads the config.
    from jax._src import compilation_cache

    compilation_cache.reset_cache()


def enable_compile_cache() -> str:
    """Entry-point switch: places (or adopts) the persistent cache and
    returns the directory in effect. Every compile is cacheable (min
    compile time 0): trainers and replica fleets re-run the same
    programs, so even sub-second entries pay for themselves by the
    second process."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _unlatch()
    return jax.config.jax_compilation_cache_dir


def placed_cache_dir() -> Optional[str]:
    """The directory jax's persistent cache uses in this process, or
    None. A process that never imported jax (mock replicas) cannot
    compile and therefore has no cache — jax is not imported to ask."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    return jax.config.jax_compilation_cache_dir or None


def engage_compile_cache() -> Optional[str]:
    """Library-side engagement before a compile tier runs: when a cache
    directory is already in effect (from the environment or an entry
    point), drop jax's latched state so this process's NEXT compile uses
    it even if it compiled something before the directory was set.
    Returns the directory, or None (nothing touched) when no cache was
    placed."""
    cache_dir = placed_cache_dir()
    if cache_dir is not None:
        _unlatch()
    return cache_dir


@contextlib.contextmanager
def compile_cache_bypass() -> Iterator[None]:
    """Keeps the persistent cache out of the compiles inside the block
    (reads AND writes), then restores it.

    Two callers need a genuinely fresh compile: the AOT export build (an
    executable served from the cache serializes WITHOUT its object code,
    so the shipped blob fails every later deserialize) and the planner's
    measured probe (a cache hit reports near-zero compile time and no
    compile count). Only `jax_enable_compilation_cache` is flipped — the
    directory, wherever it came from, is never touched — and the latch
    is reset on both edges so the flip takes effect immediately. The
    config is process-GLOBAL: an unrelated compile in another thread
    during the window skips the cache too (a performance miss, never a
    correctness one)."""
    import jax

    prev_enabled = bool(jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_enable_compilation_cache", False)
    _unlatch()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_enabled)
        _unlatch()
