"""FleetRouter: deadline-aware routing over a pool of replica processes.

PR 4's PolicyServer made one process serve many clients; this layer
makes many *processes* serve them — the horizontal step the "millions
of users" north star actually needs, built so that every failure mode a
fleet exhibits is a first-class, tested behavior rather than an outage:

  * **Least-loaded, deadline-aware dispatch.** Each request goes to the
    healthy replica with the fewest in-flight requests (ties broken
    round-robin from a seeded RNG); a request whose deadline has already
    passed is failed typed, never shipped. The wall-clock deadline rides
    to the replica, whose own PolicyServer enforces it pre-dispatch.
  * **Retry with jittered exponential backoff.** A replica failure
    (death, corrupt reply, typed serve error) re-dispatches the request
    to a different replica after `backoff * 2^attempt * (1 + U[0,1))`
    ms, up to `T2R_FLEET_RETRIES` extra attempts, always bounded by the
    request deadline.
  * **Hedging.** A request still pending `T2R_FLEET_HEDGE_MS` after
    dispatch is duplicated to a second replica; first reply wins, the
    loser is discarded on arrival. This is the classic tail-latency
    amputation for straggler replicas (stuck GC, throttled core).
  * **Health probing + eviction + circuit breaking + respawn.** The
    monitor polls each replica's `snapshot()`; a silent replica is
    SUSPECT (unrouted) and eventually hard-killed and respawned; a
    replica failing `circuit_threshold` consecutive requests is BROKEN
    (circuit open) for a cooloff, then readmitted on its next health
    reply. A dead process's in-flight requests fail over immediately.
  * **Graceful degradation — shed, never hang.** With every healthy
    replica at its in-flight cap the router fails new requests with
    `FleetSaturated` immediately; with no live replica,
    `ReplicaUnavailable`. Every submitted request also carries a
    router-side deadline timer, so even a wedged replica + a missed
    monitor tick cannot strand a future: *every* future resolves.
  * **Rolling deploys.** `rolling_swap()` hot-swaps one replica at a
    time (each keeps serving its old version until the new one is
    prewarmed — PR 4's per-replica zero-downtime swap), so a fleet-wide
    deploy never reduces capacity by more than the replica mid-swap.

Transport is `serving/transport.py`: checksummed inline pickles with a
shared-memory slab ring (the `data/dataset.py` ring discipline) for
large request payloads. See docs/RESILIENCE.md for the policy table and
the chaos plans that pin each behavior.
"""

from __future__ import annotations

import heapq
import itertools
import logging
import threading

from tensor2robot_tpu.testing import locksmith
import time
from collections import deque
from typing import Any, Dict, List, Mapping, Optional, Sequence, Set, Tuple

import numpy as np

from tensor2robot_tpu import flags as t2r_flags
from tensor2robot_tpu.net import codec as wire_codec
from tensor2robot_tpu.serving import transport
from tensor2robot_tpu.serving.metrics import percentile
from tensor2robot_tpu.serving.replica import (
    ReplicaSpec,
    check_one_process_per_chip,
    replica_main,
)
from tensor2robot_tpu.utils.backoff import Backoff, poll_loop
from tensor2robot_tpu.utils.errors import best_effort

_log = logging.getLogger(__name__)

__all__ = [
    "FleetRouter",
    "FleetResponse",
    "RouterFuture",
    "FleetError",
    "FleetSaturated",
    "ReplicaUnavailable",
    "RequestAbandoned",
    "RouterClosed",
]


class FleetError(RuntimeError):
    """Base class for router-level request failures.

    Deliberately NOT a ServeError subclass: importing server.py would
    drag jax into mock-backend parents, and the two layers' errors never
    mix in one except clause (the router converts replica-side serve
    errors into its own types)."""


class FleetSaturated(FleetError):
    """Every healthy replica is at its in-flight cap; request shed."""


class ReplicaUnavailable(FleetError):
    """No live replica to dispatch to (pool down or still starting)."""


class RequestAbandoned(FleetError):
    """The request ran out of deadline or retry budget. `reason` is
    'deadline' or 'retries'; `detail` carries the last failure."""

    def __init__(self, message: str, reason: str, detail: str = ""):
        super().__init__(message)
        self.reason = reason
        self.detail = detail


class RouterClosed(FleetError):
    """The router stopped before the request completed."""


# Replica lifecycle states. `draining` is the scale-down limbo: unrouted
# (only `up` replicas take traffic) but alive until its in-flight
# requests finish — the state the autoscaler parks a replica in so
# retiring capacity never kills a request.
_STARTING, _UP, _SUSPECT, _BROKEN, _DEAD, _DRAINING = (
    "starting", "up", "suspect", "broken", "dead", "draining",
)


class FleetResponse:
    """One request's outputs plus fleet-level provenance."""

    __slots__ = (
        "outputs", "model_version", "spans", "replica", "attempts", "hedged",
    )

    def __init__(self, outputs, model_version, spans, replica, attempts,
                 hedged):
        self.outputs = outputs
        self.model_version = model_version
        self.spans = spans
        self.replica = replica
        self.attempts = attempts
        self.hedged = hedged


class RouterFuture:
    """Completion handle for one fleet request; resolves exactly once,
    always (success, typed failure, or RouterClosed at stop)."""

    def __init__(self, request_id: int):
        self.request_id = request_id
        self._event = threading.Event()
        self._response: Optional[FleetResponse] = None
        self._error: Optional[BaseException] = None
        self._callbacks: List = []
        self._cb_lock = locksmith.make_lock("RouterFuture._cb_lock")

    def done(self) -> bool:
        return self._event.is_set()

    def error(self) -> Optional[BaseException]:
        return self._error if self._event.is_set() else None

    def result(self, timeout: Optional[float] = None) -> FleetResponse:
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"fleet request {self.request_id} still pending after "
                f"{timeout}s"
            )
        if self._error is not None:
            raise self._error
        return self._response

    def add_done_callback(self, fn) -> None:
        """Runs `fn(self)` when the future resolves — on the resolving
        thread for pending futures, immediately for completed ones.
        Fires exactly once per registration (open-loop load generators
        and relays hang off this instead of blocking in result())."""
        with self._cb_lock:
            if not self._event.is_set():
                self._callbacks.append(fn)
                return
        fn(self)

    def _set(self, response, error) -> None:
        self._response, self._error = response, error
        with self._cb_lock:
            self._event.set()
            callbacks, self._callbacks = self._callbacks, []
        for fn in callbacks:
            fn(self)


class _FleetRequest:
    __slots__ = (
        "id", "features", "deadline", "future", "t_submit", "dispatches",
        "hedged", "hedge_attempts", "live", "last_failure", "policy_id",
    )

    def __init__(self, request_id, features, deadline, policy_id=None):
        self.id = request_id
        self.features = features
        self.deadline = deadline  # monotonic, router-local
        self.future = RouterFuture(request_id)
        self.t_submit = time.monotonic()
        self.dispatches = 0  # non-hedge dispatch count
        self.hedged = False
        self.hedge_attempts: Set[int] = set()  # attempt numbers placed as hedges
        self.live: Set[Tuple[int, int]] = set()  # (attempt, replica)
        self.last_failure = ""
        self.policy_id: Optional[str] = policy_id


class _Replica:
    __slots__ = (
        "index", "spec", "proc", "request_q", "state", "inflight",
        "consecutive_failures", "broken_until", "version", "last_health",
        "last_health_time", "respawns", "started_at", "retired", "boot_ms",
    )

    def __init__(self, index: int, spec: ReplicaSpec):
        self.index = index
        self.spec = spec
        self.proc = None
        self.request_q = None
        self.state = _STARTING
        self.inflight: Set[Tuple[int, int]] = set()  # (req_id, attempt)
        self.consecutive_failures = 0
        self.broken_until = 0.0
        self.version = -1
        self.last_health: Dict = {}
        self.last_health_time = 0.0
        self.respawns = 0
        self.started_at = 0.0
        self.retired = False  # scale-down: exits are expected, no respawn
        # spawn -> "started" wall time of the LAST boot (None before the
        # first): with prewarm_source this attributes a slow scale-up to
        # its restore tier (deserialize vs compile).
        self.boot_ms: Optional[float] = None


class _RouterMetrics:
    """Counters + bounded latency window; all O(1) mutators."""

    def __init__(self, span_window: int = 4096):
        self._lock = locksmith.make_lock("_RouterMetrics._lock")
        self._counters: Dict[str, int] = {}
        self._latencies: deque = deque(maxlen=span_window)

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def observe_latency(self, ms: float) -> None:
        with self._lock:
            self._latencies.append(ms)

    def snapshot(self) -> Dict:
        with self._lock:
            counters = dict(self._counters)
            latencies = sorted(self._latencies)
        return {
            "counters": counters,
            "latency_ms": {
                "p50": round(percentile(latencies, 0.50), 3),
                "p99": round(percentile(latencies, 0.99), 3),
                "p999": round(percentile(latencies, 0.999), 3),
                "window": len(latencies),
            },
        }


class FleetRouter:
    """Routes requests over `num_replicas` spawned replica processes.

    Args mirror the `T2R_FLEET_*` flags (constructor overrides flag
    overrides default, the PolicyServer convention). `replica_spec` may
    be one ReplicaSpec (replicated) or a sequence of per-replica specs
    (how chaos plans target a single replica). `seed` drives backoff
    jitter and dispatch tie-breaks — router behavior under a fixed fault
    plan is reproducible.
    """

    def __init__(
        self,
        replica_spec,
        num_replicas: Optional[int] = None,
        *,
        max_inflight: Optional[int] = None,
        hedge_ms: Optional[int] = None,
        retries: Optional[int] = None,
        backoff_ms: float = 25.0,
        default_deadline_ms: Optional[int] = None,
        probe_interval_ms: float = 200.0,
        probe_miss_limit: int = 3,
        circuit_threshold: int = 3,
        circuit_cooloff_ms: float = 1000.0,
        respawn: bool = True,
        max_respawns: int = 3,
        boot_timeout_s: float = 120.0,
        inline_max_bytes: int = transport.DEFAULT_INLINE_MAX_BYTES,
        shm_slots: int = 8,
        seed: int = 0,
        transport_mode: Optional[str] = None,
        fabric_root: Optional[str] = None,
        zone: Optional[str] = None,
    ):
        if isinstance(replica_spec, ReplicaSpec):
            if num_replicas is None:
                raise ValueError(
                    "num_replicas is required with a single ReplicaSpec"
                )
            specs = [replica_spec] * num_replicas
        else:
            specs = list(replica_spec)
            if num_replicas is not None and num_replicas != len(specs):
                raise ValueError(
                    f"num_replicas={num_replicas} but {len(specs)} specs given"
                )
        if not specs:
            raise ValueError("a fleet needs at least one replica")
        self._specs = specs
        self._max_inflight = (
            max_inflight if max_inflight is not None
            else t2r_flags.get_int("T2R_FLEET_MAX_INFLIGHT")
        )
        self._hedge_s = (
            hedge_ms if hedge_ms is not None
            else t2r_flags.get_int("T2R_FLEET_HEDGE_MS")
        ) / 1e3
        self._retries = (
            retries if retries is not None
            else t2r_flags.get_int("T2R_FLEET_RETRIES")
        )
        self._backoff_s = backoff_ms / 1e3
        # Retry pacing through the shared schedule (utils/backoff.py):
        # uncapped per-delay (the request deadline is the real bound),
        # seeded so a fixed fault plan replays the same pacing.
        self._retry_backoff = Backoff(
            base_ms=backoff_ms, cap_ms=None, seed=seed
        )
        self._default_deadline_s = (
            default_deadline_ms if default_deadline_ms is not None
            else t2r_flags.get_int("T2R_SERVE_DEADLINE_MS")
        ) / 1e3
        self._probe_interval_s = probe_interval_ms / 1e3
        self._probe_miss_limit = probe_miss_limit
        self._circuit_threshold = circuit_threshold
        self._circuit_cooloff_s = circuit_cooloff_ms / 1e3
        self._respawn = respawn
        self._max_respawns = max_respawns
        self._boot_timeout_s = boot_timeout_s
        self._inline_max = inline_max_bytes
        self._shm_slots = shm_slots
        # Which fabric carries replica traffic: "local" (mp queues +
        # shared-memory slots, one process group — byte-compatible
        # tier-1 default) or "socket" (independent process groups on the
        # shared CRC-framed wire, published-address discovery — the
        # cross-host fabric). Everything above _spawn/start is
        # transport-blind: handles and links duck-type the mp surface.
        self._transport_mode = (
            transport_mode if transport_mode is not None
            else t2r_flags.get_enum("T2R_FLEET_TRANSPORT")
        )
        if self._transport_mode not in ("local", "socket"):
            raise ValueError(
                f"unknown transport_mode {self._transport_mode!r} "
                "(expected 'local' or 'socket')"
            )
        self._fabric_root = fabric_root
        self._zone = zone
        self._pool = None  # RemoteReplicaPool, socket mode only

        self._lock = locksmith.make_rlock("FleetRouter._lock")
        self._metrics = _RouterMetrics()
        self._replicas: List[_Replica] = [
            _Replica(i, spec) for i, spec in enumerate(specs)
        ]
        self._requests: Dict[int, _FleetRequest] = {}
        self._ids = itertools.count(1)
        self._probe_ids = itertools.count(1)
        self._swap_ids = itertools.count(1)
        self._swaps: Dict[int, List] = {}  # id -> [Event, ok, version]
        self._rr = 0  # dispatch tie-break cursor
        self._started = False
        self._closed = False

        # Timer wheel: (when, seq, fn) heap drained by one thread.
        self._timer_heap: List = []
        self._timer_seq = itertools.count()
        self._timer_cond = locksmith.make_condition("FleetRouter._timer_cond")

        self._ctx = None
        self._response_q = None
        self._free_q = None
        self._codec: Optional[transport.RequestCodec] = None
        self._threads: List[threading.Thread] = []

    # -- lifecycle ------------------------------------------------------------

    def start(self, timeout_s: float = 120.0) -> "FleetRouter":
        """Spawns every replica and waits until at least one reports
        started (raises on a fully-failed bring-up). Late starters keep
        warming in the background and join the pool when ready."""
        if self._started:
            raise RuntimeError("FleetRouter.start() called twice")
        check_one_process_per_chip(self._specs)
        if self._transport_mode == "socket":
            # Cross-host fabric: replicas are independent process groups
            # on the CRC-framed wire. No mp context, no shared-memory
            # ring (the codec degrades to inline pickled arrays — the
            # only shape that crosses hosts); replies arrive through the
            # per-replica links into a plain thread queue.
            from tensor2robot_tpu.serving.pool import (
                RemoteReplicaPool, ResponseQueue,
            )

            if self._fabric_root is None:
                import tempfile

                self._fabric_root = tempfile.mkdtemp(prefix="t2r-fabric-")
            self._response_q = ResponseQueue()
            self._free_q = None
            self._codec = transport.RequestCodec(
                None, inline_max_bytes=self._inline_max
            )
            self._pool = RemoteReplicaPool(
                self._fabric_root,
                self._response_q.put,
                zone=self._zone,
                connect_timeout_s=t2r_flags.get_int(
                    "T2R_FABRIC_CONNECT_TIMEOUT_MS"
                ) / 1e3,
            )
        else:
            import multiprocessing

            self._ctx = multiprocessing.get_context("spawn")
            self._response_q = self._ctx.Queue()
            self._free_q = self._ctx.Queue()
            self._codec = transport.RequestCodec(
                self._free_q,
                inline_max_bytes=self._inline_max,
                num_slots=self._shm_slots,
            )
        # t2r: unguarded-ok(start() runs before any fleet thread exists)
        for replica in self._replicas:
            self._spawn(replica)
        self._started = True
        for name, target in (
            ("t2r-fleet-collect", self._collector_loop),
            ("t2r-fleet-timer", self._timer_loop),
            ("t2r-fleet-monitor", self._monitor_loop),
        ):
            thread = threading.Thread(target=target, name=name, daemon=True)
            thread.start()
            self._threads.append(thread)
        def bring_up_settled() -> bool:
            with self._lock:
                return any(r.state == _UP for r in self._replicas) or all(
                    r.state == _DEAD and r.respawns >= self._max_respawns
                    for r in self._replicas
                )

        Backoff(base_ms=20.0, cap_ms=60.0, factor=1.0, seed=0).poll(
            bring_up_settled, total_s=timeout_s
        )
        with self._lock:
            if any(r.state == _UP for r in self._replicas):
                return self
        self.stop()
        raise RuntimeError(
            f"no replica became healthy within {timeout_s}s"
        )

    def _spawn(self, replica: _Replica) -> None:
        replica.state = _STARTING
        replica.started_at = time.monotonic()
        replica.inflight = set()
        replica.consecutive_failures = 0
        if self._pool is not None:
            # Socket fabric: the pool bumps the incarnation, launches
            # the detached process, and hands back a (handle, link)
            # pair that duck-types (proc, request_q). The link refuses
            # the predecessor's stale published address; the monitor's
            # health-probe puts double as the re-resolution loop, and
            # the fresh connection's ("hello",) handshake elicits the
            # ("started", ...) that readmits the replica to routing.
            replica.proc, replica.request_q = self._pool.spawn(
                replica.index, replica.spec
            )
            return
        replica.request_q = self._ctx.Queue()
        replica.proc = self._ctx.Process(
            target=replica_main,
            args=(
                replica.index, replica.spec, replica.request_q,
                self._response_q, self._free_q,
            ),
            name=f"t2r-replica-{replica.index}",
            daemon=True,
        )
        replica.proc.start()

    def stop(self, timeout_s: float = 10.0) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            pending = list(self._requests.values())
            self._requests.clear()
        for request in pending:
            if not request.future.done():
                request.future._set(
                    None, RouterClosed("router stopped with request pending")
                )
        with self._timer_cond:
            self._timer_cond.notify_all()
        # t2r: unguarded-ok(stop() flipped _closed under the lock above; _replicas is append-only and fenced)
        for replica in self._replicas:
            if replica.request_q is not None:
                best_effort(replica.request_q.put, ("stop",))
        deadline = time.monotonic() + timeout_s
        # t2r: unguarded-ok(stop() flipped _closed under the lock above; _replicas is append-only and fenced)
        for replica in self._replicas:
            proc = replica.proc
            if proc is None:
                continue
            proc.join(timeout=max(0.1, deadline - time.monotonic()))
            if proc.is_alive():
                proc.terminate()
                proc.join(timeout=1.0)
            if proc.is_alive():
                proc.kill()
                proc.join(timeout=1.0)
        if self._codec is not None:
            self._codec.close()
        for q in [self._response_q, self._free_q] + [
            # t2r: unguarded-ok(stop() flipped _closed under the lock above; _replicas is append-only and fenced)
            r.request_q for r in self._replicas
        ]:
            if q is None:
                continue
            best_effort(q.cancel_join_thread)
            best_effort(q.close)
        if self._pool is not None:
            # Socket links already closed through the loop above (they
            # duck-type the queue teardown); this sweeps any link the
            # pool still tracks for a replica mid-respawn.
            best_effort(self._pool.close)

    def __enter__(self) -> "FleetRouter":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- client surface -------------------------------------------------------

    def submit(
        self,
        features: Mapping[str, Any],
        deadline_ms: Optional[float] = None,
        policy_id: Optional[str] = None,
    ) -> RouterFuture:
        """Routes one example; never blocks on replicas. Raises typed
        admission errors (FleetSaturated / ReplicaUnavailable /
        RouterClosed) synchronously; everything after admission resolves
        through the returned future. `policy_id` names the policy on a
        multi-policy fleet (placement-aware: replicas already holding it
        resident are preferred; a miss is a counted cold dispatch)."""
        # t2r: unguarded-ok(racy fast-fail only; admission re-checks _closed under the lock below)
        if not self._started or self._closed:
            raise RouterClosed("router is not running")
        now = time.monotonic()
        deadline = now + (
            deadline_ms / 1e3 if deadline_ms is not None
            else self._default_deadline_s
        )
        arrays = {k: np.asarray(v) for k, v in features.items()}
        request = _FleetRequest(next(self._ids), arrays, deadline, policy_id)
        with self._lock:
            # Re-check under the lock: stop() flips _closed and drains
            # _requests while holding it, so a request admitted past the
            # unlocked fast-path check but registered AFTER the drain
            # would never be failed by stop() — and the deadline backstop
            # timer has already exited — leaving its future unresolved
            # forever.
            if self._closed:
                raise RouterClosed("router is not running")
            replica = self._pick_replica(exclude=(), policy_id=policy_id)
            self._requests[request.id] = request
            self._metrics.count("submitted")
            try:
                self._dispatch(request, replica, hedge=False)
            except Exception:
                self._requests.pop(request.id, None)
                self._metrics.count("submitted", -1)
                raise
        # Router-side deadline backstop: EVERY future resolves, even if
        # the replica wedges and the monitor misses it.
        self._schedule(
            deadline - now + 0.005, lambda: self._on_deadline(request)
        )
        return request.future

    def call(
        self,
        features: Mapping[str, Any],
        deadline_ms: Optional[float] = None,
        timeout: Optional[float] = None,
        policy_id: Optional[str] = None,
    ) -> FleetResponse:
        future = self.submit(
            features, deadline_ms=deadline_ms, policy_id=policy_id
        )
        if timeout is None:
            timeout = (
                deadline_ms / 1e3 if deadline_ms is not None
                else self._default_deadline_s
            ) + 30.0
        return future.result(timeout)

    # -- dispatch core (all called under self._lock) --------------------------

    def _pick_replica(
        self,
        exclude: Sequence[int],
        count: bool = True,
        policy_id: Optional[str] = None,
    ) -> _Replica:
        """Least-loaded healthy replica, deadline-aware admission.

        Raises FleetSaturated when healthy replicas exist but all are at
        the in-flight cap; ReplicaUnavailable when none are healthy.
        `count=False` suppresses the shed counters (hedge probes are
        best-effort and must not read as admission failures).

        With `policy_id` on a multi-policy fleet, replicas whose last
        health snapshot lists the policy RESIDENT are preferred among
        the admissible candidates — dispatching to one avoids a
        replica-side cold load. When the fleet reports residency but no
        admissible replica holds this policy, the dispatch is counted
        (`policy_cold_dispatches`) and falls back to least-loaded: a
        cold load there is still cheaper than shedding."""
        up = [r for r in self._replicas if r.state == _UP]
        if not up:
            if count:
                self._metrics.count("no_replica")
            raise ReplicaUnavailable(
                "no healthy replica (pool starting, broken, or dead)"
            )
        candidates = [
            r for r in up
            if r.index not in exclude and len(r.inflight) < self._max_inflight
        ]
        if not candidates:
            candidates = [
                r for r in up if len(r.inflight) < self._max_inflight
            ]
        if not candidates:
            if count:
                self._metrics.count("shed_saturated")
            raise FleetSaturated(
                f"all {len(up)} healthy replicas at the in-flight cap "
                f"({self._max_inflight}); request shed"
            )
        if policy_id is not None:
            aware = [
                r for r in candidates
                if r.last_health.get("resident_policies") is not None
            ]
            if aware:
                resident = [
                    r for r in aware
                    if policy_id in r.last_health["resident_policies"]
                ]
                if resident:
                    candidates = resident
                    if count:
                        self._metrics.count("policy_resident_dispatches")
                elif count:
                    # No admissible replica holds this policy resident:
                    # the dispatch will cold-load on arrival. Counted so
                    # placement regressions show up as a ratio, not as
                    # silent tail latency.
                    self._metrics.count("policy_cold_dispatches")
        load = min(len(r.inflight) for r in candidates)
        tied = [r for r in candidates if len(r.inflight) == load]
        self._rr += 1
        return tied[self._rr % len(tied)]

    def _dispatch(
        self, request: _FleetRequest, replica: _Replica, hedge: bool
    ) -> None:
        remaining = request.deadline - time.monotonic()
        if remaining <= 0:
            raise RequestAbandoned(
                f"request {request.id} deadline passed before dispatch",
                reason="deadline",
                detail=request.last_failure,
            )
        if not hedge:
            request.dispatches += 1
        attempt = request.dispatches + (1 if hedge or request.hedged else 0)
        if self._pool is not None and wire_codec.wire_mode() == "spec":
            # Socket fabric on the spec wire: ship the features dict
            # itself and let the frame codec segment the arrays —
            # pickling them into an inline blob here would re-bury the
            # payload the zero-copy wire exists to expose.
            payload = (
                "raw",
                {k: np.asarray(v) for k, v in request.features.items()},
            )
        else:
            payload = self._codec.encode(request.features)
        key = (request.id, attempt)
        replica.inflight.add(key)
        request.live.add((attempt, replica.index))
        message = ("req", request.id, attempt, time.time() + remaining, payload)
        if request.policy_id is not None:
            # Optional trailing element keeps the 5-tuple wire shape for
            # single-policy traffic byte-for-byte unchanged.
            message = message + (request.policy_id,)
        try:
            replica.request_q.put(message)
        except Exception as err:
            replica.inflight.discard(key)
            request.live.discard((attempt, replica.index))
            # The slot name never crossed the process boundary, so the
            # replica-side decode that normally releases it will never
            # run — reclaim it here or the ring shrinks by one slot per
            # failed dispatch.
            self._codec.release(payload)
            raise ReplicaUnavailable(
                f"replica {replica.index} transport failed: {err}"
            ) from err
        self._metrics.count("dispatched")
        if hedge:
            request.hedge_attempts.add(attempt)
            self._metrics.count("hedged")
        elif self._hedge_s > 0 and not request.hedged:
            self._schedule(
                self._hedge_s, lambda: self._maybe_hedge(request)
            )

    def _maybe_hedge(self, request: _FleetRequest) -> None:
        with self._lock:
            if (
                self._closed
                or request.future.done()
                or request.hedged
                or request.id not in self._requests
            ):
                return
            carrying = {replica for _, replica in request.live}
            try:
                replica = self._pick_replica(
                    exclude=tuple(carrying),
                    count=False,
                    policy_id=request.policy_id,
                )
            except FleetError:
                return  # no spare capacity: hedging is best-effort
            if replica.index in carrying:
                return  # only the original is free; a hedge there is noise
            request.hedged = True
            try:
                self._dispatch(request, replica, hedge=True)
            except FleetError:
                request.hedged = False  # failed to place; original stands

    def _retry(self, request: _FleetRequest, exclude: Tuple[int, ...]) -> None:
        with self._lock:
            if (
                self._closed
                or request.future.done()
                or request.id not in self._requests
            ):
                return
            self._metrics.count("retries")
            try:
                replica = self._pick_replica(
                    exclude=exclude, policy_id=request.policy_id
                )
                self._dispatch(request, replica, hedge=False)
                return
            except FleetError as err:
                failure = f"{type(err).__name__}: {err}"
        self._fail_request(
            request,
            RequestAbandoned(
                f"request {request.id} could not be re-dispatched: {failure}",
                reason="retries",
                detail=request.last_failure,
            ),
        )

    # -- completion paths -----------------------------------------------------

    def _finish(self, request: _FleetRequest, response, error) -> None:
        """Resolves a request exactly once and drops its bookkeeping.
        Caller must NOT hold the lock for the future._set (client
        callbacks run there)."""
        with self._lock:
            if self._requests.pop(request.id, None) is None:
                return  # already resolved
            for attempt, replica_index in request.live:
                self._replicas[replica_index].inflight.discard(
                    (request.id, attempt)
                )
            request.live.clear()
        if error is None:
            self._metrics.count("completed")
            self._metrics.observe_latency(
                (time.monotonic() - request.t_submit) * 1e3
            )
        else:
            self._metrics.count("failed")
        request.future._set(response, error)

    def _fail_request(self, request: _FleetRequest, error) -> None:
        self._finish(request, None, error)

    def _on_deadline(self, request: _FleetRequest) -> None:
        with self._lock:
            if request.future.done() or request.id not in self._requests:
                return
        self._metrics.count("abandoned_deadline")
        self._fail_request(
            request,
            RequestAbandoned(
                f"request {request.id} hit its deadline after "
                f"{request.dispatches} dispatch(es)"
                + (f"; last failure: {request.last_failure}"
                   if request.last_failure else ""),
                reason="deadline",
                detail=request.last_failure,
            ),
        )

    def _on_attempt_failure(
        self,
        request: _FleetRequest,
        replica_index: int,
        failure: str,
        fatal: bool = False,
    ) -> None:
        """One attempt failed: retry elsewhere with jittered backoff, or
        fail typed when budget/deadline is gone."""
        with self._lock:
            if request.future.done() or request.id not in self._requests:
                return
            request.last_failure = failure
            if fatal:
                fail_now: Optional[FleetError] = RequestAbandoned(
                    f"request {request.id} failed fatally on replica "
                    f"{replica_index}: {failure}",
                    reason="deadline" if "Deadline" in failure else "fatal",
                    detail=failure,
                )
            elif request.dispatches > self._retries:
                self._metrics.count("abandoned_retries")
                fail_now = RequestAbandoned(
                    f"request {request.id} exhausted its retry budget "
                    f"({self._retries} retries): {failure}",
                    reason="retries",
                    detail=failure,
                )
            else:
                fail_now = None
                backoff = self._retry_backoff.delay_s(
                    max(1, request.dispatches)
                )
                exclude = (replica_index,)
        if fail_now is not None:
            self._fail_request(request, fail_now)
            return
        self._schedule(backoff, lambda: self._retry(request, exclude))

    # -- replica state machine ------------------------------------------------

    def _note_replica_failure(self, replica: _Replica) -> None:
        replica.consecutive_failures += 1
        if (
            replica.consecutive_failures >= self._circuit_threshold
            and replica.state == _UP
        ):
            replica.state = _BROKEN
            replica.broken_until = time.monotonic() + self._circuit_cooloff_s
            self._metrics.count("circuit_breaks")
            _log.warning(
                "replica %d circuit-broken after %d consecutive failures",
                replica.index, replica.consecutive_failures,
            )

    def _on_replica_death(self, replica: _Replica) -> None:
        """Process gone: fail its in-flight attempts over to siblings,
        then respawn (bounded). A RETIRED replica's exit is the expected
        end of a drain — counted separately, never respawned."""
        with self._lock:
            if replica.state == _DEAD:
                return
            replica.state = _DEAD
            if replica.retired:
                self._metrics.count("retired_exits")
            else:
                self._metrics.count("replica_deaths")
            orphans = list(replica.inflight)
            replica.inflight = set()
            requests = []
            for req_id, attempt in orphans:
                request = self._requests.get(req_id)
                if request is None:
                    continue
                request.live.discard((attempt, replica.index))
                requests.append(request)
        if orphans or not replica.retired:
            _log.warning(
                "replica %d died with %d in-flight request(s); failing over",
                replica.index, len(orphans),
            )
        for request in requests:
            self._on_attempt_failure(
                request, replica.index, "replica process died"
            )
        with self._lock:
            can_respawn = (
                self._respawn
                and not self._closed
                and not replica.retired
                and replica.respawns < self._max_respawns
            )
            if can_respawn:
                replica.respawns += 1
                self._metrics.count("respawns")
                self._spawn(replica)

    # -- background threads ---------------------------------------------------

    def _collector_loop(self) -> None:
        import queue as queue_lib

        # t2r: unguarded-ok(loop-exit staleness is one 0.1s tick; stop() also closes the queue under us)
        while not self._closed:
            try:
                message = self._response_q.get(timeout=0.1)
            except queue_lib.Empty:
                continue
            except (OSError, ValueError):
                return  # queue closed under us during stop()
            try:
                self._handle_message(message)
            except Exception:
                _log.exception("collector: failed handling %r", message[:2])

    def _handle_message(self, message) -> None:
        kind = message[0]
        if kind == "rsp":
            self._on_reply(*message[1:])
        elif kind == "health":
            _, index, _probe_id, snap, _t = message
            with self._lock:
                replica = self._replicas[index]
                replica.last_health = snap
                replica.last_health_time = time.monotonic()
                replica.version = snap.get("model_version", replica.version)
                if replica.state == _SUSPECT:
                    replica.state = _UP
                    replica.consecutive_failures = 0
                elif (
                    replica.state == _BROKEN
                    and time.monotonic() >= replica.broken_until
                ):
                    replica.state = _UP
                    replica.consecutive_failures = 0
                    self._metrics.count("circuit_recoveries")
                elif replica.state == _STARTING and not replica.retired:
                    # Socket fabric: the ("hello",)->("started",...)
                    # handshake can be lost on the wire (drop/partition
                    # at net_send). The replica then answers probes
                    # while the router still holds it in `starting` —
                    # and every answer refreshes last_health_time, so
                    # the boot-timeout branch never fires either: the
                    # replica would be wedged out of routing forever.
                    # A health reply carries the same evidence
                    # "started" does (an address is only published
                    # after the factory succeeded), so it admits too.
                    replica.state = _UP
                    replica.consecutive_failures = 0
                    if replica.started_at:
                        replica.boot_ms = round(
                            (time.monotonic() - replica.started_at)
                            * 1e3,
                            3,
                        )
        elif kind == "started":
            _, index, version, _pid = message
            with self._lock:
                replica = self._replicas[index]
                if replica.retired or replica.state == _DRAINING:
                    # Socket fabric: a link reconnect re-elicits the
                    # ("hello",)->("started",...) handshake; a draining
                    # replica must not be readmitted to routing by it.
                    return
                replica.state = _UP
                replica.version = version
                replica.last_health_time = time.monotonic()
                replica.consecutive_failures = 0
                if replica.started_at:
                    replica.boot_ms = round(
                        (time.monotonic() - replica.started_at) * 1e3, 3
                    )
        elif kind == "swapped":
            _, index, swap_id, ok, version = message
            with self._lock:
                self._replicas[index].version = version
                entry = self._swaps.get(swap_id)
                if entry is not None:
                    entry[1], entry[2] = ok, version
                    entry[0].set()
        elif kind == "stopped":
            pass
        else:
            _log.warning("collector: unknown message kind %r", kind)

    def _on_reply(self, index, req_id, attempt, crc, blob) -> None:
        with self._lock:
            replica = self._replicas[index]
            replica.inflight.discard((req_id, attempt))
            request = self._requests.get(req_id)
            if request is not None:
                was_live = (attempt, index) in request.live
                request.live.discard((attempt, index))
            else:
                was_live = False
        try:
            body = transport.unpack(crc, blob)
        except transport.IntegrityError as err:
            self._metrics.count("corrupt_replies")
            with self._lock:
                self._note_replica_failure(replica)
            if request is not None and was_live:
                self._on_attempt_failure(
                    request, index, f"corrupt reply: {err}"
                )
            return
        if request is None or request.future.done():
            self._metrics.count("late_replies")
            return
        if body[0] == "ok":
            _, outputs, version, spans = body
            with self._lock:
                replica.consecutive_failures = 0
            spans = dict(spans)
            spans["total_ms"] = (
                time.monotonic() - request.t_submit
            ) * 1e3
            # Only an attempt actually PLACED as a hedge counts as a
            # hedge win — a retry winning on a hedged request must not
            # inflate the metric operators tune T2R_FLEET_HEDGE_MS by.
            if attempt in request.hedge_attempts:
                self._metrics.count("hedge_wins")
            self._finish(
                request,
                FleetResponse(
                    outputs, version, spans, index,
                    attempts=max(attempt, request.dispatches),
                    hedged=request.hedged,
                ),
                None,
            )
            return
        # Typed replica-side failure.
        _, failure_class, detail = body
        failure = f"{failure_class}: {detail}"
        self._metrics.count(f"replica_error_{failure_class}")
        with self._lock:
            # A deadline miss inside the replica is congestion, not a
            # replica fault; do not tip the circuit breaker for it.
            if failure_class != "DeadlineExceeded":
                self._note_replica_failure(replica)
        if not was_live:
            self._metrics.count("late_replies")
            return
        self._on_attempt_failure(
            request, index, failure,
            fatal=failure_class == "DeadlineExceeded",
        )

    def _timer_loop(self) -> None:
        # t2r: unguarded-ok(loop-exit staleness is one timer tick; stop() notifies the cond to wake us)
        while not self._closed:
            due: List = []
            with self._timer_cond:
                now = time.monotonic()
                while self._timer_heap and self._timer_heap[0][0] <= now:
                    due.append(heapq.heappop(self._timer_heap)[2])
                if not due:
                    wait = (
                        self._timer_heap[0][0] - now
                        if self._timer_heap else 0.05
                    )
                    self._timer_cond.wait(timeout=max(0.001, min(wait, 0.05)))
            # Actions run with NO lock held: they take self._lock
            # themselves, and holding the timer condition across them
            # would invert against _schedule() callers under self._lock.
            for fn in due:
                try:
                    fn()
                except Exception:
                    _log.exception("timer action failed")

    def _schedule(self, delay_s: float, fn) -> None:
        with self._timer_cond:
            heapq.heappush(
                self._timer_heap,
                (time.monotonic() + max(0.0, delay_s), next(self._timer_seq), fn),
            )
            self._timer_cond.notify()

    @poll_loop
    def _monitor_loop(self) -> None:
        # t2r: unguarded-ok(monitor cadence read; one stale probe tick is harmless)
        while not self._closed:
            time.sleep(self._probe_interval_s)
            # t2r: unguarded-ok(re-check after the sleep; worst case is one extra probe)
            if self._closed:
                return
            now = time.monotonic()
            # Copy: the autoscaler may append replicas mid-iteration.
            # t2r: unguarded-ok(snapshot copy; list append is atomic under the GIL and state is re-checked)
            for replica in list(self._replicas):
                proc = replica.proc
                if proc is not None and not proc.is_alive():
                    self._on_replica_death(replica)
                    continue
                if replica.state == _DEAD:
                    continue
                # Probe (replies flow back through the collector).
                try:
                    replica.request_q.put(("health", next(self._probe_ids)))
                except Exception:
                    continue
                silent_for = now - max(
                    replica.last_health_time, replica.started_at
                )
                if replica.state == _UP and silent_for > (
                    self._probe_miss_limit * self._probe_interval_s
                ):
                    with self._lock:
                        if replica.state == _UP:
                            replica.state = _SUSPECT
                            self._metrics.count("evictions")
                            _log.warning(
                                "replica %d silent for %.0fms; evicted from "
                                "routing", replica.index, silent_for * 1e3,
                            )
                elif replica.state in (_SUSPECT, _BROKEN) and silent_for > (
                    2 * self._probe_miss_limit * self._probe_interval_s
                ):
                    # Unresponsive past the hard limit: kill it and let
                    # the death path respawn a fresh one.
                    if self._respawn and proc is not None:
                        _log.warning(
                            "replica %d unresponsive %.0fms; hard-killing",
                            replica.index, silent_for * 1e3,
                        )
                        self._metrics.count("hard_kills")
                        proc.kill()
                elif (
                    replica.state == _STARTING
                    and silent_for > self._boot_timeout_s
                ):
                    # A boot can be slow (restore + bucket prewarm), but
                    # a process WEDGED in its factory would otherwise sit
                    # in `starting` forever — unrouted, unprobed by the
                    # eviction branches, permanently lost capacity. Kill
                    # it; the death path respawns it against the same
                    # max_respawns budget, so a boot-crash-loop still
                    # terminates in _DEAD rather than cycling forever.
                    if self._respawn and proc is not None:
                        _log.warning(
                            "replica %d stuck starting for %.0fs; "
                            "hard-killing", replica.index, silent_for,
                        )
                        self._metrics.count("hard_kills")
                        proc.kill()

    # -- fleet operations ------------------------------------------------------

    def add_replica(self, spec: Optional[ReplicaSpec] = None) -> int:
        """Grows the pool by one replica (the autoscaler's scale-up
        primitive): appends a fresh _Replica on the next index and
        spawns it — it joins routing when it reports started. `spec`
        defaults to the first construction spec (the homogeneous-pool
        case). Returns the new replica's index."""
        if not self._started:
            raise RuntimeError("add_replica() before start()")
        with self._lock:
            if self._closed:
                raise RouterClosed("router is not running")
            replica = _Replica(
                len(self._replicas), spec if spec is not None else self._specs[0]
            )
            check_one_process_per_chip(
                [r.spec for r in self._replicas] + [replica.spec]
            )
            self._replicas.append(replica)
            self._metrics.count("scale_ups")
            self._spawn(replica)
            return replica.index

    def retire_replica(
        self, index: int, drain_timeout_s: float = 30.0
    ) -> bool:
        """Shrinks the pool by draining replica `index` (the autoscaler's
        scale-down primitive): the replica leaves the routing set
        immediately (state `draining`), keeps serving its in-flight
        requests to completion, and only then is told to stop — the
        rolling-swap discipline applied to capacity, so retiring never
        kills a request. Returns False (and restores the replica to
        routing) if the drain does not empty within the timeout."""
        with self._lock:
            replica = self._replicas[index]
            if replica.state not in (_UP, _SUSPECT, _BROKEN):
                return False
            prior_state = replica.state
            replica.state = _DRAINING
            replica.retired = True
            self._metrics.count("retirements")

        def drained() -> bool:
            with self._lock:
                return not replica.inflight or self._closed

        Backoff(base_ms=10.0, cap_ms=50.0, factor=1.0, seed=index).poll(
            drained, total_s=drain_timeout_s
        )
        with self._lock:
            if replica.inflight and not self._closed:
                # Drain stalled: put the replica back rather than kill
                # its in-flight work. The caller may retry later.
                replica.state = prior_state
                replica.retired = False
                self._metrics.count("retirement_aborts")
                return False
        best_effort(replica.request_q.put, ("stop",))
        return True

    def load(self) -> Dict:
        """The autoscaler's signal: live capacity and how full it is.
        `utilization` is in-flight work over routable capacity
        (up-replicas x max_inflight); `shed_saturated` is cumulative —
        scalers diff it across ticks to see overload the in-flight
        gauge already shed."""
        with self._lock:
            up = [r for r in self._replicas if r.state == _UP]
            pending = [
                r for r in self._replicas
                if r.state in (_STARTING, _SUSPECT, _BROKEN)
                and not r.retired
            ]
            draining = [r for r in self._replicas if r.state == _DRAINING]
            inflight = sum(len(r.inflight) for r in up)
        counters = self._metrics.snapshot()["counters"]
        capacity = len(up) * self._max_inflight
        return {
            "replicas_up": len(up),
            "replicas_pending": len(pending),
            "replicas_draining": len(draining),
            "inflight": inflight,
            "capacity": capacity,
            "utilization": (inflight / capacity) if capacity else 1.0,
            "shed_saturated": counters.get("shed_saturated", 0),
        }

    def rolling_swap(
        self,
        swap_timeout_s: float = 60.0,
        policy_id: Optional[str] = None,
    ) -> Dict:
        """Hot-swaps every live replica to the newest export, one at a
        time. Each replica keeps serving its OLD version until the new
        one is prewarmed (PolicyServer's restore-prewarm hook), so fleet
        capacity never drops by more than zero servers and drops by one
        only if a swap fails outright. Returns per-replica results; a
        failed swap aborts the roll (the remaining replicas keep the old
        version — a bad artifact must not take the fleet down).

        `policy_id` scopes the roll to ONE policy on a multi-policy
        fleet: only that policy's server swaps per replica, so sibling
        policies keep serving their current versions without a blip."""
        results: Dict[str, Any] = {"swapped": [], "failed": None}
        self._metrics.count("rolling_swaps")
        # t2r: unguarded-ok(iterates a snapshot copy; per-replica work re-validates state under the lock)
        for replica in list(self._replicas):
            with self._lock:
                if replica.state not in (_UP, _SUSPECT, _BROKEN):
                    continue
                swap_id = next(self._swap_ids)
                entry = [threading.Event(), False, replica.version]
                self._swaps[swap_id] = entry
                message = ("swap", swap_id, time.time() + swap_timeout_s)
                if policy_id is not None:
                    message = message + (policy_id,)
                try:
                    # t2r: blocking-ok(unbounded mp.Queue put never blocks on capacity)
                    replica.request_q.put(message)
                except Exception:
                    results["failed"] = replica.index
                    self._swaps.pop(swap_id, None)
                    break
            if not entry[0].wait(swap_timeout_s + 5.0):
                results["failed"] = replica.index
                with self._lock:
                    self._swaps.pop(swap_id, None)
                break
            with self._lock:
                self._swaps.pop(swap_id, None)
            if not entry[1]:
                results["failed"] = replica.index
                break
            results["swapped"].append(
                {"replica": replica.index, "version": entry[2]}
            )
        return results

    # -- introspection --------------------------------------------------------

    @property
    def num_replicas(self) -> int:
        # t2r: unguarded-ok(len() of an append-only list is an atomic snapshot)
        return len(self._replicas)

    def replica_states(self) -> List[str]:
        with self._lock:
            return [r.state for r in self._replicas]

    def replica_pids(self) -> List[Optional[int]]:
        """Replica process pids by index (None before spawn). The ops
        surface for external fault injection — bench.py's chaos leg
        SIGKILLs a pid from here mid-sweep."""
        with self._lock:
            return [
                r.proc.pid if r.proc is not None else None
                for r in self._replicas
            ]

    def snapshot(self) -> Dict:
        snap = self._metrics.snapshot()
        with self._lock:
            snap["pending_requests"] = len(self._requests)
            snap["replicas"] = [
                {
                    "index": r.index,
                    "state": r.state,
                    "inflight": len(r.inflight),
                    "version": r.version,
                    "consecutive_failures": r.consecutive_failures,
                    "respawns": r.respawns,
                    # Per-replica low-precision regime off the last health
                    # snapshot: a mixed rollout (some replicas int8, some
                    # fp32) is verified HERE, version by version, instead
                    # of by observing precision drift in production.
                    "serve_quant": r.last_health.get("serve_quant"),
                    # ...and its calibration mode: a mixed static/dynamic
                    # rollout changes per-dispatch cost (quant reduces),
                    # so the fleet surface carries it next to the regime.
                    "serve_quant_calib": r.last_health.get(
                        "serve_quant_calib"
                    ),
                    # Boot attribution: how long the last spawn took to
                    # report started, and which restore tier each warmup
                    # bucket came from (off the health snapshot) — the
                    # pair that tells an operator whether a scale-up paid
                    # deserialize-time or compile-time.
                    "boot_ms": r.boot_ms,
                    "prewarm_source": r.last_health.get("prewarm_source"),
                    # Recorded AOT fingerprint of the loaded artifact
                    # (None on backends without one): the gateway folds
                    # this into its coalescing key so two pools serving
                    # different artifacts can never share a dispatch.
                    "model_fingerprint": r.last_health.get(
                        "model_fingerprint"
                    ),
                    # Multi-policy placement surface (None on
                    # single-policy backends): which policies this
                    # replica holds resident right now, and its
                    # replica-side eviction/cold-load counters — all off
                    # the health snapshot, backend-independent.
                    "resident_policies": r.last_health.get(
                        "resident_policies"
                    ),
                    "policy_evictions": r.last_health.get(
                        "policy_evictions"
                    ),
                    "policy_cold_loads": r.last_health.get(
                        "policy_cold_loads"
                    ),
                    # Host identity + per-host AOT key off the health
                    # snapshot (hostname/pid/topology): on the socket
                    # fabric this is the per-host table — which
                    # platform/topology each replica resolved the
                    # artifact's aot/ executables against.
                    "host": r.last_health.get("host"),
                }
                for r in self._replicas
            ]
        snap["transport"] = self._transport_mode
        snap["zone"] = self._zone
        # Router-process wire accounting (codec/stage timings, segment
        # byte classes, receive-pool audit). Meaningful on the socket
        # fabric; ~empty counters on the mp transport.
        snap["wire"] = wire_codec.wire_snapshot()
        snap["wire"]["codec"] = wire_codec.wire_mode()
        snap["wire"]["quant"] = wire_codec.quant_mode()
        snap["policy"] = {
            "max_inflight": self._max_inflight,
            "hedge_ms": self._hedge_s * 1e3,
            "retries": self._retries,
            "backoff_ms": self._backoff_s * 1e3,
            "probe_interval_ms": self._probe_interval_s * 1e3,
            "circuit_threshold": self._circuit_threshold,
            "circuit_cooloff_ms": self._circuit_cooloff_s * 1e3,
            "respawn": self._respawn,
        }
        return snap
