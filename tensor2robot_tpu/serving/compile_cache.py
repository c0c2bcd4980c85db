"""Restore-time engagement of the persistent compile cache
(utils/compile_cache.py) for one loaded export version: the second tier
of the restore ladder AOT executable -> persistent cache -> fresh trace.
"""

from __future__ import annotations

from typing import Optional

from tensor2robot_tpu.utils.compile_cache import engage_compile_cache

__all__ = ["enable_compile_cache_for"]


def enable_compile_cache_for(loaded) -> Optional[str]:
    """Restore-time cache engagement for one loaded export version.

    When the version will serve EVERY bucket of its resolved ladder
    (T2R_SERVE_BUCKETS override included, `serving/buckets.py`
    resolution) from deserialized AOT executables, no compile will
    happen for it — skip the engagement entirely (returns None).
    Otherwise behaves exactly like `engage_compile_cache()`: a compile
    tier is live for this version and the cache must engage BEFORE its
    first compile (the prewarm that follows restore). A server
    constructed with an explicit `batch_buckets` ladder is invisible
    from here; `PolicyServer` re-engages at start() for any bucket
    outside the AOT table.
    """
    if loaded is not None and getattr(loaded, "aot_covered", False):
        from tensor2robot_tpu.serving import buckets as buckets_lib

        table = getattr(loaded, "aot_executables", None) or {}
        try:
            ladder = buckets_lib.resolve_buckets(
                None, getattr(loaded, "metadata", None) or {}
            )
        except ValueError:
            ladder = ()
        if ladder and all(bucket in table for bucket in ladder):
            return None
    return engage_compile_cache()
