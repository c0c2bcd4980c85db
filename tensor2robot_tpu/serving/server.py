"""PolicyServer: dynamic micro-batching over an AbstractPredictor.

The fleet-serving layer: many concurrent clients (robots, CEM planners,
web frontends) share one predictor whose exported StableHLO artifact is
batch-polymorphic but — like every XLA program — pays a full compile per
CONCRETE batch size. This server turns per-client batch-1 traffic into
bucket-sized batches the export already pre-warmed:

  * bounded request queue with per-request deadlines and admission
    control — when the queue is full the overload policy either sheds
    the OLDEST queued request (freshest-first service, the right default
    for control loops where a stale action is worthless) or rejects the
    incoming one (`T2R_SERVE_OVERLOAD`);
  * a dispatcher thread that coalesces queued requests up to a
    max-wait/max-batch window (`T2R_SERVE_MAX_WAIT_MS`), pads the batch
    to the smallest fitting bucket (serving/buckets.py; ladder =
    exporter's `warmup_batch_sizes`), and runs ONE predict per batch.
    Every served shape is a warmup bucket, so no request ever waits on a
    fresh XLA compile;
  * zero-downtime hot-swap: `hot_swap()` rides
    `ExportedSavedModelPredictor.restore(is_async=True)` — the in-flight
    batch drains on the old version (the predictor swaps its serving fn
    atomically under its own lock), subsequent batches land on the new
    one, and every response reports the model version that computed it;
  * per-request spans + counters (serving/metrics.py) exported as one
    structured `snapshot()`.

Discipline rule (enforced by the `serve-blocking-predict` lint,
analysis/lints.py): inside this package the predictor's blocking
`predict`/`traced_predict` surface is called ONLY from the dispatcher's
`_execute_batch` (and `_prewarm` at startup) — a predict call on the
submit path would serialize clients behind the model and defeat the
whole subsystem.
"""

from __future__ import annotations

import itertools
import logging
import threading

from tensor2robot_tpu.testing import locksmith
import time
from collections import deque
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from tensor2robot_tpu import flags as t2r_flags
from tensor2robot_tpu.serving import buckets as buckets_lib
from tensor2robot_tpu.serving.metrics import RequestSpan, ServerMetrics
from tensor2robot_tpu.specs import (
    ExtendedTensorSpec,
    flatten_spec_structure,
    make_random_numpy,
)

__all__ = [
    "PolicyServer",
    "ServeFuture",
    "ServeResponse",
    "ServeError",
    "RequestRejected",
    "RequestShed",
    "DeadlineExceeded",
    "ServerClosed",
    "PredictFailed",
    "PredictTimeout",
]


class ServeError(RuntimeError):
    """Base class for request-level serving failures."""


class RequestRejected(ServeError):
    """Admission control refused the request (reject overload policy)."""


class RequestShed(ServeError):
    """The request was shed from a full queue (shed_oldest policy)."""


class DeadlineExceeded(ServeError):
    """The request's deadline passed before compute dispatched it."""


class ServerClosed(ServeError):
    """The server stopped before the request could be served."""


class PredictFailed(ServeError):
    """The predictor raised mid-batch; this batch failed, the loop lives.

    `failure_class` carries the original exception's type name (it is
    also the key in the metrics failed_by_class breakdown)."""

    def __init__(self, message: str, failure_class: str = "PredictFailed"):
        super().__init__(message)
        self.failure_class = failure_class


class PredictTimeout(ServeError):
    """The predictor exceeded the compute watchdog; the batch's futures
    failed typed and the dispatcher moved on (the stuck call is
    abandoned on a daemon thread — a hung accelerator call cannot be
    cancelled from the host, only routed around)."""


class ServeResponse:
    """One request's outputs + the model version that computed them."""

    __slots__ = ("outputs", "model_version", "spans")

    def __init__(self, outputs: Dict[str, np.ndarray], model_version: int,
                 spans: Dict[str, float]):
        self.outputs = outputs
        self.model_version = model_version
        self.spans = spans


class ServeFuture:
    """Completion handle returned by submit(); result() blocks."""

    def __init__(self, request_id: int):
        self.request_id = request_id
        self._event = threading.Event()
        self._response: Optional[ServeResponse] = None
        self._error: Optional[BaseException] = None
        self._callbacks: List = []
        self._cb_lock = locksmith.make_lock("ServeFuture._cb_lock")

    def done(self) -> bool:
        return self._event.is_set()

    def error(self) -> Optional[BaseException]:
        """The failure, if the future completed with one (None while
        pending or on success) — lets completion callbacks branch
        without re-raising."""
        return self._error if self._event.is_set() else None

    def result(self, timeout: Optional[float] = None) -> ServeResponse:
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} still pending after {timeout}s"
            )
        if self._error is not None:
            raise self._error
        return self._response

    def add_done_callback(self, fn) -> None:
        """Calls `fn(future)` when the future completes (immediately if it
        already has). Callbacks run on the completing thread (the
        dispatcher) and must be cheap and non-blocking — replica loops
        use this to post replies without a waiter thread per request."""
        with self._cb_lock:
            if not self._event.is_set():
                self._callbacks.append(fn)
                return
        fn(self)

    def _complete(self) -> None:
        with self._cb_lock:
            self._event.set()
            callbacks, self._callbacks = self._callbacks, []
        for fn in callbacks:
            fn(self)

    def _set_response(self, response: ServeResponse) -> None:
        self._response = response
        self._complete()

    def _set_error(self, error: BaseException) -> None:
        self._error = error
        self._complete()


class _Request:
    __slots__ = ("id", "features", "deadline", "span", "future")

    def __init__(self, request_id: int, features: Dict[str, np.ndarray],
                 deadline: float, span: RequestSpan):
        self.id = request_id
        self.features = features
        self.deadline = deadline
        self.span = span
        self.future = ServeFuture(request_id)


class PolicyServer:
    """Micro-batching policy server over a restored AbstractPredictor.

    Constructor arguments override the `T2R_SERVE_*` flag defaults;
    `batch_buckets` overrides the exporter-published ladder entirely
    (tests, bring-up). The predictor must be restored (or restorable)
    before start().
    """

    def __init__(
        self,
        predictor,
        batch_buckets: Optional[Sequence[int]] = None,
        max_queue: Optional[int] = None,
        max_wait_ms: Optional[int] = None,
        overload: Optional[str] = None,
        default_deadline_ms: Optional[int] = None,
        predict_timeout_ms: Optional[int] = None,
    ):
        self._predictor = predictor
        self._explicit_buckets = batch_buckets
        self._max_queue = (
            max_queue if max_queue is not None
            else t2r_flags.get_int("T2R_SERVE_MAX_QUEUE")
        )
        self._max_wait_s = (
            max_wait_ms if max_wait_ms is not None
            else t2r_flags.get_int("T2R_SERVE_MAX_WAIT_MS")
        ) / 1e3
        self._overload = (
            overload if overload is not None
            else t2r_flags.get_enum("T2R_SERVE_OVERLOAD")
        )
        if self._overload not in ("shed_oldest", "reject"):
            raise ValueError(
                f"overload must be shed_oldest|reject, got {self._overload!r}"
            )
        self._default_deadline_s = (
            default_deadline_ms if default_deadline_ms is not None
            else t2r_flags.get_int("T2R_SERVE_DEADLINE_MS")
        ) / 1e3
        self._predict_timeout_s = (
            predict_timeout_ms if predict_timeout_ms is not None
            else t2r_flags.get_int("T2R_SERVE_PREDICT_TIMEOUT_MS")
        ) / 1e3  # 0 = watchdog off (predict on the dispatcher thread)
        self._buckets: Tuple[int, ...] = ()
        self._flat_spec: Dict[str, ExtendedTensorSpec] = {}
        # Per-bucket restore tier of the SERVING version ("aot" |
        # "cache" | "compile"; mock-ish predictors report "compile"):
        # updated at start() and on every swap prewarm, surfaced in
        # snapshot() so router health probes carry it fleet-wide.
        self._prewarm_source: Dict[int, str] = {}
        self._metrics = ServerMetrics()
        self._queue: deque = deque()
        self._cond = locksmith.make_condition("PolicyServer._cond")
        self._ids = itertools.count(1)
        self._dispatcher: Optional[threading.Thread] = None
        self._started = False
        self._closed = False

    # -- lifecycle ------------------------------------------------------------

    def start(self, prewarm: bool = True) -> "PolicyServer":
        """Resolves the bucket ladder from the loaded export, optionally
        pre-warms every bucket (compiles each served shape BEFORE traffic
        arrives), and starts the dispatcher."""
        if self._started:
            raise RuntimeError("PolicyServer.start() called twice")
        if self._predictor.model_version < 0:
            if not self._predictor.restore():
                raise RuntimeError(
                    "predictor restore failed; cannot start the server"
                )
        loaded = getattr(self._predictor, "loaded_model", None)
        metadata = getattr(loaded, "metadata", None) or {}
        self._buckets = buckets_lib.resolve_buckets(
            self._explicit_buckets, metadata
        )
        spec = self._predictor.get_feature_specification()
        self._flat_spec = {
            key: leaf
            for key, leaf in flatten_spec_structure(spec).items()
            if isinstance(leaf, ExtendedTensorSpec) and not leaf.is_optional
        }
        # Precompiled validation table: submit() runs per request on the
        # client thread, so the spec walk must not (fully-static shapes
        # compare as one tuple; dynamic dims fall back to a rank check;
        # dtypes are coerced to the spec's so one float64 request cannot
        # poison a coalesced batch with a novel-dtype recompile).
        self._spec_checks = []
        for key, leaf in self._flat_spec.items():
            dims = tuple(leaf.shape)
            static = tuple(int(d) for d in dims) if all(
                d is not None for d in dims
            ) else None
            try:
                want_dtype = np.dtype(leaf.dtype)
            except TypeError:
                want_dtype = None
            self._spec_checks.append(
                (key, dims, static, len(dims), want_dtype)
            )
        self._bucket_batches = self._build_bucket_batches(loaded, spec)
        self._ensure_compile_tier(loaded)
        self._record_prewarm_sources(loaded)
        if prewarm:
            self._prewarm()
        # Hot-swap continuity: compile every bucket on an INCOMING version
        # before the predictor flips to it (predictors without the hook
        # simply swap cold).
        installer = getattr(self._predictor, "set_restore_prewarm", None)
        if installer is not None:
            installer(self._prewarm_restored)
        self._started = True
        # t2r: unguarded-ok(start() runs before the dispatcher thread exists)
        self._closed = False
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, name="t2r-serve-dispatch", daemon=True
        )
        self._dispatcher.start()
        return self

    def _build_bucket_batches(self, loaded, spec):
        """One spec-conforming batch per bucket: the exporter's warmup
        payloads when the artifact carries them, synthesized random
        batches otherwise. Shared by start()-time prewarm and the
        restore-time prewarm of incoming versions (contents are
        irrelevant for compilation; shapes are the contract)."""
        warmed = {}
        export_dir = getattr(loaded, "export_dir", None)
        if export_dir:
            try:
                warmed = buckets_lib.load_warmup_batches(
                    export_dir, spec, getattr(loaded, "metadata", {})
                )
            except Exception as err:  # noqa: BLE001 — warmup payloads are an
                # optimization; synthesized batches warm the same shapes.
                logging.warning("warmup tfrecord unusable (%s); synthesizing", err)
        batches = {}
        for bucket in self._buckets:
            batch = warmed.get(bucket)
            if batch is None:
                batch = dict(
                    flatten_spec_structure(
                        make_random_numpy(spec, batch_size=bucket, seed=0)
                    ).items()
                )
            batches[bucket] = batch
        return batches

    def _prewarm(self) -> None:
        """One predict per bucket before traffic; after this, serving
        never compiles (on an AOT-hit version it never compiled at
        all — each predict deserialized its bucket's executable)."""
        for bucket in self._buckets:
            self._predictor.predict(self._bucket_batches[bucket])

    def _prewarm_restored(self, loaded, serve_fn) -> None:
        """Runs ON THE RESTORE THREAD before a new version swaps in:
        every bucket readies on the incoming serving fn while the old
        version keeps draining batches — the hot-swap blip stays queue
        drain, never an XLA compile. With AOT executables covering the
        ladder this loop is deserialize-time, not compile-time."""
        # Shapes are fixed by the start()-time ladder/spec; `loaded` is
        # the INCOMING version.
        self._ensure_compile_tier(loaded)
        for bucket in self._buckets:
            serve_fn(self._bucket_batches[bucket])
        # Record the incoming version's restore tiers only once every
        # bucket readied: a failed prewarm ABORTS the swap (the old
        # version keeps serving), and its record must not be
        # overwritten by a version that never served.
        self._record_prewarm_sources(loaded)

    def _ensure_compile_tier(self, loaded) -> None:
        """Engages the persistent compile cache whenever THIS server's
        resolved ladder has a bucket the loaded version cannot serve
        from an AOT executable. The restore-time engagement
        (enable_compile_cache_for) only sees the artifact's own ladder;
        an explicit `batch_buckets` constructor ladder can be wider, and
        its extra buckets must not compile uncached just because the
        warmup ladder happened to be AOT-covered. No-op when no cache
        directory has been placed (utils/compile_cache.py)."""
        table = getattr(loaded, "aot_executables", None) or {}
        if any(bucket not in table for bucket in self._buckets):
            from tensor2robot_tpu.utils.compile_cache import (
                engage_compile_cache,
            )

            engage_compile_cache()

    def _record_prewarm_sources(self, loaded) -> None:
        """Per-bucket restore tier of `loaded` + the aot_hits/aot_misses
        counters. A miss is counted ONLY when AOT was requested (the
        loaded model resolved T2R_SERVE_AOT=1) and the bucket still fell
        back — the loud, counted fallback contract."""
        table = getattr(loaded, "aot_executables", None) or {}
        aot_requested = bool(getattr(loaded, "aot_enabled", False))
        from tensor2robot_tpu.utils.compile_cache import placed_cache_dir

        cache_on = placed_cache_dir() is not None
        sources: Dict[int, str] = {}
        hits = misses = 0
        for bucket in self._buckets:
            if bucket in table:
                sources[bucket] = "aot"
                hits += 1
            else:
                sources[bucket] = "cache" if cache_on else "compile"
                if aot_requested:
                    misses += 1
        self._prewarm_source = sources
        if hits:
            self._metrics.count("aot_hits", hits)
        if misses:
            self._metrics.count("aot_misses", misses)

    def stop(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Stops the dispatcher. drain=True serves everything already
        queued first; drain=False fails queued requests with
        ServerClosed."""
        with self._cond:
            if not self._started:
                return
            self._closed = True
            if not drain:
                while self._queue:
                    request = self._queue.popleft()
                    request.future._set_error(
                        ServerClosed(f"server stopped, request {request.id} dropped")
                    )
                    self._metrics.count_failure("ServerClosed")
            self._cond.notify_all()
        if self._dispatcher is not None:
            self._dispatcher.join(timeout=timeout)
        # The predictor may outlive this server; detach the prewarm hook.
        installer = getattr(self._predictor, "set_restore_prewarm", None)
        if installer is not None:
            installer(None)
        self._started = False

    def __enter__(self) -> "PolicyServer":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- client surface -------------------------------------------------------

    def submit(
        self,
        features: Mapping[str, Any],
        deadline_ms: Optional[float] = None,
    ) -> ServeFuture:
        """Enqueues ONE example (leaf shapes = the spec's, no batch dim);
        returns a future. Never blocks on the model."""
        if not self._started:
            raise RuntimeError("PolicyServer is not started")
        flat = self._validate(features)
        now = time.monotonic()
        deadline = now + (
            deadline_ms / 1e3 if deadline_ms is not None
            else self._default_deadline_s
        )
        request = _Request(next(self._ids), flat, deadline, RequestSpan(now))
        with self._cond:
            if self._closed:
                raise ServerClosed("server is stopping; request refused")
            if len(self._queue) >= self._max_queue:
                if self._overload == "reject":
                    self._metrics.count("rejected")
                    raise RequestRejected(
                        f"queue full ({self._max_queue}); request rejected"
                    )
                victim = self._queue.popleft()
                victim.future._set_error(
                    RequestShed(
                        f"request {victim.id} shed by newer arrival under load"
                    )
                )
                self._metrics.count("shed")
            self._queue.append(request)
            self._metrics.count("admitted")
            self._cond.notify()
        return request.future

    def call(
        self,
        features: Mapping[str, Any],
        deadline_ms: Optional[float] = None,
        timeout: Optional[float] = None,
    ) -> ServeResponse:
        """Blocking convenience: submit + wait (one client thread's view).
        The default wait outlives THIS request's deadline, not the server
        default — a long-deadline call must not time out while live."""
        future = self.submit(features, deadline_ms=deadline_ms)
        if timeout is None:
            timeout = (
                deadline_ms / 1e3 if deadline_ms is not None
                else self._default_deadline_s
            ) + 30.0
        return future.result(timeout)

    def _validate(self, features: Mapping[str, Any]) -> Dict[str, np.ndarray]:
        # Fast path: clients usually pass the flat dict already; fall back
        # to the full spec-structure flatten only for nested inputs.
        flat_in = features
        out: Dict[str, np.ndarray] = {}
        for key, dims, static, rank, want_dtype in self._spec_checks:
            value = flat_in.get(key)
            if value is None:
                if flat_in is features:
                    flat_in = dict(flatten_spec_structure(features).items())
                    value = flat_in.get(key)
                if value is None:
                    raise ValueError(
                        f"request is missing required feature {key!r}"
                    )
            if not isinstance(value, np.ndarray):
                value = np.asarray(value)
            shape = value.shape
            ok = shape == static if static is not None else (
                len(shape) == rank
                and all(d is None or d == g for d, g in zip(dims, shape))
            )
            if not ok:
                raise ValueError(
                    f"feature {key!r}: expected one example of shape "
                    f"{dims}, got {shape} (batching is the server's job — "
                    "submit single examples)"
                )
            if want_dtype is not None and value.dtype != want_dtype:
                value = value.astype(want_dtype)
            out[key] = value
        return out

    # -- introspection --------------------------------------------------------

    @property
    def buckets(self) -> Tuple[int, ...]:
        return self._buckets

    def snapshot(self) -> Dict:
        with self._cond:
            depth = len(self._queue)
        snap = self._metrics.snapshot(queue_depth=depth)
        snap["buckets"] = list(self._buckets)
        snap["overload_policy"] = self._overload
        snap["max_queue"] = self._max_queue
        snap["max_wait_ms"] = self._max_wait_s * 1e3
        snap["model_version"] = self._predictor.model_version
        # Low-precision serving regime of the loaded artifact: router
        # health probes carry this snapshot, so a fleet can verify a
        # mixed rollout (some replicas int8, some fp32) version by
        # version instead of discovering a silent precision mismatch in
        # production Q-values.
        regime = getattr(self._predictor, "quant_regime", None)
        if regime is not None:
            snap["serve_quant"] = regime
            if regime != "none":
                # Which layers the loaded regime contracts NATIVELY in
                # its storage dtype (empty = pure dequant path, e.g.
                # fp16 or a parity-demoted map) — compute attribution
                # per replica, next to the regime it belongs to.
                snap["serve_quant_native_layers"] = list(
                    getattr(self._predictor, "native_dot_layers", ()) or ()
                )
                attention = getattr(
                    self._predictor, "native_attention", ()
                ) or ()
                if attention:
                    snap["serve_quant_native_attention"] = list(attention)
                # Activation-calibration mode + the export-recorded
                # reduce audit of the serving program: a fleet verifies
                # per replica that statically-calibrated versions really
                # dispatch zero activation-quant reduces
                # (activation_quant_reduces == 0), version by version.
                calib = getattr(self._predictor, "calib_mode", None)
                if calib is not None:
                    snap["serve_quant_calib"] = calib
                reduce_audit = getattr(
                    self._predictor, "quant_reduce_audit", None
                )
                if reduce_audit is not None:
                    snap["serve_quant_reduce_audit"] = dict(reduce_audit)
        # Per-bucket restore tier ("aot" = deserialized executable,
        # "cache"/"compile" = the fallback tiers): the boot-attribution
        # surface the router/autoscaler snapshots and the bench's
        # zero-fresh-compile audit read.
        snap["prewarm_source"] = {
            str(bucket): source
            for bucket, source in sorted(self._prewarm_source.items())
        }
        loaded = getattr(self._predictor, "loaded_model", None)
        fallbacks = getattr(loaded, "aot_fallbacks", None)
        if fallbacks:
            # WHY each declared bucket fell off the AOT tier (topology/
            # fingerprint mismatch, corrupt file, ...) — the loud half
            # of the loud-fallback contract, per bucket.
            snap["aot_fallbacks"] = {
                str(bucket): reason
                for bucket, reason in sorted(fallbacks.items())
            }
        # The artifact's recorded AOT fingerprint for the active regime
        # (the PR-11 sha256 over program + weight-payload bytes): the
        # gateway folds it into the coalescing key so requests against
        # different artifacts can never share a dispatch, and the
        # artifact store keys siblings on the same construction.
        meta = getattr(loaded, "metadata", None)
        if isinstance(meta, Mapping):
            fp_table = (meta.get("aot") or {}).get("fingerprint") or {}
            regime_key = getattr(loaded, "quant_regime", None) or "none"
            fingerprint = fp_table.get(regime_key)
            if fingerprint:
                snap["model_fingerprint"] = str(fingerprint)
        # Fleet-visible leak surface: a predictor whose close() abandoned
        # a restore thread reports it here, so router health probes (which
        # ride this snapshot) can see the wounded replica.
        leaked = getattr(self._predictor, "restore_thread_leaked", None)
        if leaked is not None:
            snap["restore_thread_leaked"] = bool(leaked)
        return snap

    # -- hot swap -------------------------------------------------------------

    def hot_swap(self, wait: bool = False) -> bool:
        """Begins serving the newest export version with zero downtime:
        the predictor reloads (async by default) while batches keep
        draining on the current version; the swap lands atomically
        between batches. Responses report model_version per batch."""
        self._metrics.count("hot_swaps")
        return self._predictor.restore(is_async=not wait)

    # -- dispatcher -----------------------------------------------------------

    def _dispatch_loop(self) -> None:
        max_bucket = self._buckets[-1]
        while True:
            with self._cond:
                while not self._queue and not self._closed:
                    self._cond.wait()
                if not self._queue and self._closed:
                    return
                # Coalesce: from the first request's enqueue, wait up to
                # max_wait for the batch to fill (skip the wait entirely
                # when it's already full or the server is draining).
                window_end = self._queue[0].span.t_enqueue + self._max_wait_s
                while (
                    len(self._queue) < max_bucket
                    and not self._closed
                ):
                    remaining = window_end - time.monotonic()
                    if remaining <= 0:
                        break
                    self._cond.wait(timeout=remaining)
                    if not self._queue:
                        break  # everything shed while we slept
                # Micro-batch formation: a request whose deadline passed
                # while queued must not occupy a batch slot — it would
                # both burn compute (the router's backstop already
                # resolved its client future) and displace a LIVE
                # batchmate into the next dispatch cycle. Dropped typed
                # and counted (deadline_dropped) right here.
                batch: List[_Request] = []
                expired: List[_Request] = []
                now = time.monotonic()
                while self._queue and len(batch) < max_bucket:
                    request = self._queue.popleft()
                    if request.deadline < now:
                        expired.append(request)
                    else:
                        batch.append(request)
            for request in expired:
                # deadline_missed stays the aggregate expiry counter
                # (either enforcement point); deadline_dropped attributes
                # the formation-time drops specifically.
                self._metrics.count("deadline_missed")
                self._metrics.count("deadline_dropped")
                request.future._set_error(
                    DeadlineExceeded(
                        f"request {request.id} dropped at batch formation "
                        f"{(now - request.deadline) * 1e3:.1f}ms past its "
                        "deadline"
                    )
                )
            if not batch:
                continue
            try:
                self._execute_batch(batch)
            except Exception as err:  # noqa: BLE001 — a structural failure
                # (bad output shape, bucket assertion) must fail THIS
                # batch's futures, never kill the dispatcher: a dead
                # dispatcher with a live submit() surface is a silent
                # permanent outage.
                logging.exception(
                    "dispatcher: batch of %d failed structurally", len(batch)
                )
                pending = [r for r in batch if not r.future.done()]
                self._metrics.count_failure("DispatchError", len(pending))
                for request in pending:
                    request.future._set_error(
                        ServeError(
                            f"dispatch failed: {type(err).__name__}: {err}"
                        )
                    )

    def _execute_batch(self, batch: List[_Request]) -> None:
        now = time.monotonic()
        live: List[_Request] = []
        for request in batch:
            if request.deadline < now:
                self._metrics.count("deadline_missed")
                request.future._set_error(
                    DeadlineExceeded(
                        f"request {request.id} missed its deadline by "
                        f"{(now - request.deadline) * 1e3:.1f}ms before dispatch"
                    )
                )
            else:
                request.span.t_dispatch = now
                live.append(request)
        if not live:
            return
        bucket = buckets_lib.pick_bucket(self._buckets, len(live))
        features = buckets_lib.pad_feature_batch(
            [r.features for r in live], bucket
        )
        # Belt and braces for the no-novel-shapes guarantee: the batch
        # leading dim must be a warmup bucket.
        lead = {int(v.shape[0]) for v in features.values()}
        if lead != {bucket}:
            raise AssertionError(
                f"padded batch has leading dims {lead}, bucket {bucket}"
            )
        def run_predict():
            # predict_versioned reads (serving fn, version) as one atomic
            # pair so a hot-swap landing mid-call cannot mislabel the
            # responses; predictors without it fall back to the (benignly
            # racy) split read.
            predict_versioned = getattr(
                self._predictor, "predict_versioned", None
            )
            if predict_versioned is not None:
                return predict_versioned(features)
            version = self._predictor.model_version
            return self._predictor.predict(features), version

        def run_predict_watchdogged():
            # Compute watchdog: predict runs on a daemon thread and the
            # dispatcher waits at most the configured budget. A predictor
            # wedged inside an accelerator call cannot be interrupted
            # from here — the thread is abandoned (daemon) and THIS
            # batch fails typed, which is what lets a fleet router route
            # around a stuck replica instead of hanging its clients.
            box: Dict[str, Any] = {}
            done = threading.Event()

            def work():
                try:
                    box["value"] = run_predict()
                except BaseException as err:  # noqa: BLE001 — crosses threads
                    box["error"] = err
                finally:
                    done.set()

            worker = threading.Thread(
                target=work, name="t2r-serve-predict", daemon=True
            )
            worker.start()
            if not done.wait(self._predict_timeout_s):
                raise PredictTimeout(
                    f"predict exceeded the {self._predict_timeout_s * 1e3:.0f}"
                    "ms compute watchdog; batch failed, call abandoned"
                )
            if "error" in box:
                raise box["error"]
            return box["value"]

        try:
            if self._predict_timeout_s > 0:
                outputs, version = run_predict_watchdogged()
            else:
                outputs, version = run_predict()
        except Exception as err:  # noqa: BLE001 — one bad batch must not
            # kill the dispatcher; each request learns the real, TYPED
            # error and the metrics record which failure class it was.
            if isinstance(err, PredictTimeout):
                failure_class = "PredictTimeout"
                typed: ServeError = err
            else:
                failure_class = type(err).__name__
                typed = PredictFailed(
                    f"predict failed: {failure_class}: {err}",
                    failure_class=failure_class,
                )
            self._metrics.count_failure(failure_class, len(live))
            self._metrics.observe_batch(bucket, len(live))
            for request in live:
                request.future._set_error(typed)
            return
        done = time.monotonic()
        self._metrics.observe_batch(bucket, len(live))
        arrays = {k: np.asarray(v) for k, v in outputs.items()}
        spans = []
        for i, request in enumerate(live):
            request.span.t_compute_done = done
            request.span.t_reply = done
            row = {k: v[i] for k, v in arrays.items()}
            millis = request.span.as_millis()
            request.future._set_response(ServeResponse(row, version, millis))
            spans.append(millis)
        self._metrics.observe_replies(spans)


# -- multi-policy loader -------------------------------------------------------


def exported_policy_loader(
    store_root: str,
    policy_ids=None,
    work_dir: Optional[str] = None,
    batch_buckets=None,
    max_wait_ms: Optional[int] = None,
    predict_timeout_ms: Optional[int] = None,
    restore_timeout_s: int = 120,
):
    """(loader, catalog) for a MultiPolicyServer over the artifact store.

    Each load MATERIALIZES the policy's export dir from the
    content-addressed store (export/artifact_store.py — program/AOT
    blobs shared with its base, delta payload decoded and
    hash-verified), then boots a PolicyServer over it with the SHARED
    bucket ladder (`batch_buckets`, defaulting to each artifact's own
    warmup ladder — siblings share a program, hence a ladder) and
    prewarms every bucket before the policy serves. The started
    server's `mem_bytes` is the policy's dense weight footprint from
    the manifest, which is what the resident-set budget meters.
    """
    import tempfile

    from tensor2robot_tpu.export.artifact_store import ArtifactStore
    from tensor2robot_tpu.predictors.exported_savedmodel_predictor import (
        ExportedSavedModelPredictor,
    )

    store = ArtifactStore(store_root)
    catalog = list(policy_ids) if policy_ids is not None else store.policies()
    if not catalog:
        raise ValueError(f"artifact store {store_root} holds no policies")
    if work_dir is None:
        work_dir = tempfile.mkdtemp(prefix="t2r-policies-")

    def loader(policy_id: str):
        import os

        dest = os.path.join(work_dir, policy_id)
        if not os.path.exists(dest):
            store.materialize(policy_id, dest)
        predictor = ExportedSavedModelPredictor(
            export_dir=dest, timeout=restore_timeout_s
        )
        if not predictor.restore():
            raise RuntimeError(
                f"policy {policy_id!r} predictor restore timed out "
                f"under {dest}"
            )
        server = PolicyServer(
            predictor,
            batch_buckets=batch_buckets,
            max_wait_ms=max_wait_ms,
            predict_timeout_ms=predict_timeout_ms,
        )
        server.start(prewarm=True)
        server.mem_bytes = int(
            store.manifest(policy_id)["payload"].get("weights_nbytes", 0)
        )
        return server

    return loader, catalog
