"""Replica process entry: one policy-server worker in the router's pool.

`replica_main` is the spawn target. It stays deliberately light at
import time — the heavy stack (specs -> jax -> XLA) loads only inside
`policy_server_factory`, so a mock-backend replica (tests, bench
plumbing smoke) boots in fractions of a second while a real one pays
the jax import exactly once.

A replica owns: its request queue (router -> replica), the shared
response queue (replica -> router), and the shared free-slot queue of
the request shm ring (names go back as soon as a payload is copied
out). The protocol is at the bottom of this docstring; the router is
the only peer.

Chaos scope: each replica declares `r<index>` (testing/chaos.py), so a
plan can target one replica of a fleet ("r0/predict:3:kill") while its
siblings stay healthy — which is exactly the partial-failure regime the
router's retry/hedge/eviction logic exists for.

Wire protocol (all tuples, pickled by multiprocessing):

  router -> replica (request queue):
    ("req", req_id, attempt, deadline_wall_s, payload[, policy_id])
                                                         payload: transport.py
    ("health", probe_id)
    ("swap", swap_id, deadline_wall_s[, policy_id])
    ("stop",)

The optional trailing policy_id targets one policy of a MULTI-POLICY
backend (serving/policies.py, `multi_policy = True`); absent or None
means the backend's default. A single-policy backend receiving a
policy-addressed request replies with a typed PolicyUnknown error —
never silently serving the wrong weights.

  replica -> router (shared response queue):
    ("started", index, version, pid)
    ("rsp", index, req_id, attempt, crc, blob)     blob: ("ok", outputs,
                                                   version, spans) |
                                                   ("error", class, message)
    ("health", index, probe_id, snapshot, t_wall)
    ("swapped", index, swap_id, ok, version)
    ("stopped", index)
"""

from __future__ import annotations

import dataclasses
import logging
import os
import queue
import socket
import sys
import time
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import numpy as np

from tensor2robot_tpu import flags as t2r_flags
from tensor2robot_tpu.serving import transport
from tensor2robot_tpu.testing import chaos
from tensor2robot_tpu.utils.errors import best_effort

_log = logging.getLogger(__name__)

__all__ = [
    "ReplicaCore",
    "ReplicaSpec",
    "replica_main",
    "policy_server_factory",
    "multi_policy_store_factory",
    "mock_server_factory",
    "multi_policy_mock_factory",
    "check_one_process_per_chip",
]


@dataclasses.dataclass
class ReplicaSpec:
    """How a replica process builds its server.

    `factory` must be a module-level (picklable-by-name) callable
    returning a started server-like object: `submit(features,
    deadline_ms) -> future` (future: `add_done_callback`, `error()`,
    `result()`), `snapshot()`, `hot_swap(wait)`, `stop()`. `env` entries
    are applied in the child before the factory runs — `T2R_*` keys go
    through the flags registry (validated), everything else through the
    raw environment; this is the route chaos plans take into a replica.
    """

    factory: Callable
    factory_args: Tuple = ()
    factory_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    env: Dict[str, str] = dataclasses.field(default_factory=dict)
    scope: Optional[str] = None  # chaos scope override (default r<index>)


def check_one_process_per_chip(specs) -> None:
    """Refuses a fleet whose replica processes would contend for the
    accelerator, instead of letting them fail or hang at boot.

    A chip belongs to one process at a time, and nothing in `serving/`
    binds a replica to a device: a jax-backed replica opens EVERY local
    chip. So more than one such replica cannot share a one-chip host,
    on a four-chip host each would claim all four, and a single one
    still collides with a parent that has already opened the device.
    A replica whose environment asks for the CPU explicitly
    (`JAX_PLATFORMS=cpu`, inherited or in `spec.env`) needs no chip;
    mock backends never import jax. Per-replica chip binding is
    ROADMAP A3/B5 — until then these fleets are "not brought up" on
    the chip."""
    needing = [
        spec
        for spec in specs
        if spec.factory in (policy_server_factory, multi_policy_store_factory)
        and spec.env.get("JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS"))
        != "cpu"
    ]
    if not needing:
        return
    reason = None
    if len(needing) > 1:
        reason = (
            f"{len(needing)} jax-backed replica processes would each open "
            "every local accelerator"
        )
    else:
        jax = sys.modules.get("jax")
        if jax is not None:
            from jax._src import xla_bridge

            if (
                xla_bridge.backends_are_initialized()
                and jax.default_backend() != "cpu"
            ):
                reason = (
                    "this process already holds the accelerator "
                    f"({jax.default_backend()!r}) its replica process needs"
                )
    if reason is not None:
        raise RuntimeError(
            f"refusing to start the fleet: {reason}. A chip belongs to one "
            "process at a time and replicas have no per-process chip "
            "binding yet (ROADMAP A3/B5). For the CPU proxy ask for it "
            "explicitly with JAX_PLATFORMS=cpu; on the chip, serve "
            "in-process with PolicyServer."
        )


def _apply_env(env: Mapping[str, str]) -> None:
    for key, value in env.items():
        if key.startswith("T2R_"):
            t2r_flags.write_env(key, value)
        else:
            os.environ[key] = value


def _server_version(server) -> int:
    version = getattr(server, "model_version", None)
    if version is not None:
        return int(version)
    try:
        return int(server.snapshot().get("model_version", -1))
    except Exception:
        return -1


class ReplicaCore:
    """Transport-agnostic replica message core.

    One instance owns a started server and answers the router protocol
    (module docstring) — `handle(message)` for each inbound tuple,
    `tick(now)` between messages so an async hot-swap still resolves,
    `close()` on the way out. Replies leave through the injected `post`
    callable, which is the ONLY transport-specific piece: the local
    fabric passes `response_q.put` (mp queue), the socket fabric
    (serving/fabric.py) passes the duplex frame-writer. Everything the
    router depends on — typed error replies, CRC'd response bodies,
    swap one-in-flight discipline, deadline-at-dequeue shedding — lives
    here exactly once, so the two fabrics cannot diverge in behavior
    any more than they can in wire bytes.

    `post` may be called from the server's compute thread (the reply
    callback) concurrently with the message loop's thread; it must be
    thread-safe. Both existing posts are: mp.Queue.put and the
    send-lock-guarded frame writer.
    """

    def __init__(self, index: int, server, post: Callable[[tuple], None],
                 free_q=None):
        self._index = index
        self._server = server
        self._post = post
        self._free_q = free_q
        self._cache = transport.ReplicaSlotCache()
        # id, old_version, deadline, policy_id (None = whole-backend swap)
        self._pending_swap: Optional[
            Tuple[int, int, float, Optional[str]]
        ] = None

    def started_message(self) -> tuple:
        return (
            "started", self._index, _server_version(self._server), os.getpid()
        )

    def _host_identity(self) -> dict:
        """This replica's host/AOT key, folded into every health
        snapshot: on a cross-host fleet the router's per-replica rows
        then SHOW which platform/topology each host resolved the
        artifact's `aot/` executables against — a transplanted topology
        is visible at the fleet surface, not just in the replica's
        logs."""
        identity = {
            "hostname": socket.gethostname(),
            "pid": os.getpid(),
        }
        # Topology only when this process ALREADY runs jax (any real
        # policy backend does): importing it here would block the first
        # health reply for seconds on a lightweight backend — long
        # enough for the router to evict the replica as silent.
        import sys

        def topology():
            from tensor2robot_tpu.export import aot as aot_lib

            return aot_lib.device_topology()

        identity["topology"] = (
            best_effort(topology) if "jax" in sys.modules else None
        )
        return identity

    def _version_of(self, policy_id: Optional[str]) -> int:
        server = self._server
        if policy_id is not None and getattr(server, "multi_policy", False):
            try:
                return int(server.policy_version(policy_id))
            except Exception:
                return -1
        return _server_version(server)

    def _post_reply(self, req_id: int, attempt: int, body) -> None:
        crc, blob = transport.pack(body)
        fault = chaos.maybe_fire("reply")
        if fault is not None and fault.action == "corrupt" and blob:
            # Flip one byte AFTER the checksum: the router must detect
            # the mismatch and treat this replica reply as a failure.
            blob = blob[:-1] + bytes([blob[-1] ^ 0xFF])
        # Router gone -> best effort; our process is about to be reaped.
        best_effort(
            self._post, ("rsp", self._index, req_id, attempt, crc, blob)
        )

    def _on_request(self, req_id: int, attempt: int, deadline_wall: float,
                    payload, policy_id: Optional[str] = None) -> None:
        chaos.maybe_fire("recv")
        server = self._server
        try:
            features = transport.decode_request(
                payload, self._free_q, self._cache
            )
        except transport.IntegrityError as err:
            self._post_reply(
                req_id, attempt, ("error", "RequestCorrupt", str(err))
            )
            return
        remaining_ms = (deadline_wall - time.time()) * 1e3
        if remaining_ms <= 0:
            self._post_reply(
                req_id, attempt,
                ("error", "DeadlineExceeded",
                 "deadline passed before the replica dequeued the request"),
            )
            return
        if policy_id is not None and not getattr(server, "multi_policy", False):
            self._post_reply(
                req_id, attempt,
                ("error", "PolicyUnknown",
                 f"request names policy {policy_id!r} but this replica "
                 "runs a single-policy backend"),
            )
            return
        try:
            if policy_id is None:
                future = server.submit(features, deadline_ms=remaining_ms)
            else:
                future = server.submit(
                    features, deadline_ms=remaining_ms, policy_id=policy_id
                )
        except Exception as err:  # typed submit failures (queue full,
            # closed, PolicyUnknown/PolicyEvicted residency refusals)
            self._post_reply(
                req_id, attempt, ("error", type(err).__name__, str(err))
            )
            return

        def on_done(f, req_id=req_id, attempt=attempt):
            err = f.error()
            if err is not None:
                self._post_reply(
                    req_id, attempt, ("error", type(err).__name__, str(err))
                )
                return
            response = f.result(0)
            outputs = {
                k: np.asarray(v) for k, v in response.outputs.items()
            }
            self._post_reply(
                req_id, attempt,
                ("ok", outputs, response.model_version,
                 dict(response.spans)),
            )

        future.add_done_callback(on_done)

    def tick(self, now_wall: float) -> None:
        """Resolve a pending async hot-swap (success on version flip,
        failure on deadline). Called between messages and on idle."""
        if self._pending_swap is None:
            return
        swap_id, old_version, deadline, swap_policy = self._pending_swap
        version = self._version_of(swap_policy)
        if version != old_version:
            self._pending_swap = None
            self._post(("swapped", self._index, swap_id, True, version))
        elif now_wall > deadline:
            self._pending_swap = None
            self._post(("swapped", self._index, swap_id, False, version))

    def _on_swap(self, message: tuple) -> None:
        chaos.maybe_fire("swap")
        server = self._server
        swap_policy = message[3] if len(message) > 3 else None
        is_multi = getattr(server, "multi_policy", False)
        if swap_policy is not None and not is_multi:
            self._post(
                ("swapped", self._index, message[1], False,
                 _server_version(server))
            )
            return
        if (
            swap_policy is not None
            and is_multi
            and not server.is_resident(swap_policy)
        ):
            # Nothing resident to swap: trivially done — the next cold
            # load materializes whatever the store now publishes for
            # this policy.
            self._post(
                ("swapped", self._index, message[1], True,
                 self._version_of(swap_policy))
            )
            return
        old_version = self._version_of(swap_policy)
        if self._pending_swap is not None:
            # A second swap while one is in flight (two concurrent
            # rolling_swap calls) must not overwrite pending_swap: the
            # first swap_id would then never be answered and its
            # router-side waiter would burn the full timeout. Fail the
            # NEW one fast instead; the in-flight swap keeps its reply.
            self._post(
                ("swapped", self._index, message[1], False, old_version)
            )
        else:
            try:
                if swap_policy is None:
                    server.hot_swap(wait=False)
                else:
                    server.hot_swap(wait=False, policy_id=swap_policy)
                self._pending_swap = (
                    message[1], old_version, message[2], swap_policy
                )
            except Exception:
                _log.exception(
                    "replica %d: hot_swap failed", self._index
                )
                self._post(
                    ("swapped", self._index, message[1], False, old_version)
                )

    def handle(self, message: tuple) -> bool:
        """Dispatch one router message. Returns False on ("stop",) —
        the caller must then exit its loop and close()."""
        kind = message[0]
        if kind == "req":
            self._on_request(
                message[1], message[2], message[3], message[4],
                message[5] if len(message) > 5 else None,
            )
        elif kind == "health":
            chaos.maybe_fire("health")
            try:
                snap = self._server.snapshot()
            except Exception as err:  # a server that cannot even
                # snapshot is unhealthy; say so rather than vanish.
                snap = {"error": f"{type(err).__name__}: {err}"}
            if isinstance(snap, dict):
                snap.setdefault("host", self._host_identity())
            self._post(
                ("health", self._index, message[1], snap, time.time())
            )
        elif kind == "swap":
            self._on_swap(message)
            self.tick(time.time())
        elif kind == "hello":
            # Socket-fabric connect handshake: the router (or a fresh
            # router incarnation re-resolving us) asks who we are; the
            # local fabric never sends it, mp queues carry identity by
            # construction.
            self._post(self.started_message())
        elif kind == "stop":
            return False
        else:
            _log.warning(
                "replica %d: unknown message %r", self._index, kind
            )
        self.tick(time.time())
        return True

    def close(self) -> None:
        try:
            self._server.stop()
        except Exception:
            _log.exception("replica %d: server stop failed", self._index)
        self._cache.close()
        best_effort(self._post, ("stopped", self._index))


def build_server(index: int, spec: ReplicaSpec):
    """Apply the spec's env + chaos scope, then run its factory. Shared
    by both fabric entries so a socket replica boots exactly like a
    local one (same env routing, same scope defaulting, same typed
    factory-failure signal: the raised exception -> nonzero exit)."""
    _apply_env(spec.env)
    chaos.set_scope(spec.scope if spec.scope is not None else f"r{index}")
    try:
        return spec.factory(*spec.factory_args, **spec.factory_kwargs)
    except Exception:
        _log.exception("replica %d: server factory failed", index)
        # Exiting nonzero IS the failure signal; the router's monitor
        # handles a replica that dies before serving.
        raise


def replica_main(index: int, spec: ReplicaSpec, request_q, response_q,
                 free_q) -> None:
    """Process entry (local fabric). Never raises: a replica that cannot
    build its server exits nonzero — the router sees the exit and
    applies its death handling; a replica that cannot *reach* the
    router any more (queue torn down) just exits."""
    server = build_server(index, spec)
    core = ReplicaCore(index, server, response_q.put, free_q)
    chaos.maybe_fire("boot")
    response_q.put(core.started_message())
    try:
        while True:
            try:
                message = request_q.get(timeout=0.05)
            except queue.Empty:
                core.tick(time.time())
                continue
            except (OSError, ValueError):
                return  # request queue torn down: router is gone
            if not core.handle(message):
                return
    finally:
        core.close()


# -- backends ------------------------------------------------------------------


def policy_server_factory(
    export_root: str,
    batch_buckets=None,
    max_wait_ms: Optional[int] = None,
    predict_timeout_ms: Optional[int] = None,
    restore_timeout_s: int = 120,
):
    """The production backend: a PolicyServer over the newest export
    under `export_root`, predictor wrapped for chaos `predict`-site
    injection, every bucket prewarmed before the replica reports
    started. Heavy imports happen here, in the child, on purpose."""
    from tensor2robot_tpu.predictors.exported_savedmodel_predictor import (
        ExportedSavedModelPredictor,
    )
    from tensor2robot_tpu.serving.server import PolicyServer

    # Persistent compilation cache (utils/compile_cache.py): engaged by
    # the predictor's restore path per incoming version, BEFORE that
    # version's first compile (enable_compile_cache_for) — and skipped
    # there when the artifact's AOT executables cover every warmup
    # bucket, in which case this boot never compiles at all.
    chaos.maybe_fire("restore")
    predictor = ExportedSavedModelPredictor(
        export_dir=export_root, timeout=restore_timeout_s
    )
    if not predictor.restore():
        raise RuntimeError(
            f"replica predictor restore timed out under {export_root}"
        )
    server = PolicyServer(
        chaos.ChaosPredictor(predictor),
        batch_buckets=batch_buckets,
        max_wait_ms=max_wait_ms,
        predict_timeout_ms=predict_timeout_ms,
    )
    server.start(prewarm=True)
    return server


class _LocalFuture:
    """Minimal ServeFuture-alike for the mock backend (no jax import)."""

    def __init__(self):
        import threading
        from tensor2robot_tpu.testing import locksmith

        self._event = threading.Event()
        self._response = None
        self._error: Optional[BaseException] = None
        self._callbacks = []
        self._lock = locksmith.make_lock("_LocalFuture._lock")

    def done(self) -> bool:
        return self._event.is_set()

    def error(self) -> Optional[BaseException]:
        return self._error if self._event.is_set() else None

    def result(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise TimeoutError("mock request still pending")
        if self._error is not None:
            raise self._error
        return self._response

    def add_done_callback(self, fn) -> None:
        with self._lock:
            if not self._event.is_set():
                self._callbacks.append(fn)
                return
        fn(self)

    def _complete(self, response, error) -> None:
        self._response, self._error = response, error
        with self._lock:
            self._event.set()
            callbacks, self._callbacks = self._callbacks, []
        for fn in callbacks:
            fn(self)


class _MockResponse:
    __slots__ = ("outputs", "model_version", "spans")

    def __init__(self, outputs, model_version, spans):
        self.outputs = outputs
        self.model_version = model_version
        self.spans = spans


class _MockServer:
    """Deterministic server-surface stand-in: serial compute thread,
    fixed per-request service time, chaos `predict`/`restore` hooks.
    Outputs echo a checksum of the inputs so end-to-end tests can verify
    the reply really came from the submitted features."""

    def __init__(
        self,
        service_ms: float = 1.0,
        version: int = 1,
        scale: float = 1.0,
        bias: float = 0.0,
        mem_bytes: int = 0,
        fingerprint: Optional[str] = None,
    ):
        import threading
        from tensor2robot_tpu.testing import locksmith

        self._service_s = service_ms / 1e3
        self.model_version = version
        # Per-policy affine fingerprint: y = scale * sum(features) + bias
        # computed in float64 then cast once — bitwise-reproducible, so a
        # multi-policy fleet's responses can be audited against a
        # single-policy twin serving the same (scale, bias).
        self._scale = float(scale)
        self._bias = float(bias)
        self.mem_bytes = int(mem_bytes)
        # Optional artifact identity (PolicyServer snapshot parity):
        # pools of identical mocks can DECLARE interchangeability, so
        # gateway cross-pool failover has a fingerprint to match on.
        self._fingerprint = fingerprint
        self._queue: "queue.Queue" = queue.Queue()
        self._closed = False
        self._completed = 0
        self._lock = locksmith.make_lock("_MockServer._lock")
        self._worker = threading.Thread(
            target=self._compute_loop, name="t2r-mock-compute", daemon=True
        )
        self._worker.start()

    def _compute_loop(self) -> None:
        while True:
            item = self._queue.get()
            if item is None:
                return
            future, features, deadline = item
            try:
                chaos.maybe_fire("predict")
                if self._service_s > 0:
                    time.sleep(self._service_s)
                if time.monotonic() > deadline:
                    raise TimeoutError("mock deadline passed in compute")
                total = 0.0
                for key in sorted(features):
                    total += float(np.sum(features[key].astype(np.float64)))
                outputs = {
                    "y": np.float32(total * self._scale + self._bias),
                    "nbytes": np.int64(
                        sum(v.nbytes for v in features.values())
                    ),
                }
                with self._lock:
                    self._completed += 1
                future._complete(
                    _MockResponse(
                        outputs, self.model_version, {"compute_ms": 0.0}
                    ),
                    None,
                )
            except BaseException as err:  # noqa: BLE001 — the future is the
                # error channel; the compute loop must survive any fault.
                future._complete(None, err)

    def submit(self, features, deadline_ms: float = 1000.0) -> _LocalFuture:
        if self._closed:
            raise RuntimeError("mock server is stopped")
        future = _LocalFuture()
        self._queue.put(
            (future, features, time.monotonic() + deadline_ms / 1e3)
        )
        return future

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            completed = self._completed
        snap = {
            "counters": {"completed": completed},
            "queue_depth": self._queue.qsize(),
            "model_version": self.model_version,
            # Health-snapshot parity with PolicyServer: the fleet's
            # boot-attribution surface (router/autoscaler snapshots)
            # reads prewarm_source off every backend; the mock has one
            # degenerate bucket and nothing to compile.
            "prewarm_source": {"1": "mock"},
        }
        if self._fingerprint is not None:
            snap["model_fingerprint"] = str(self._fingerprint)
        return snap

    def hot_swap(self, wait: bool = False) -> bool:
        """Version bump on a background thread after the chaos `restore`
        site — mirrors the async-restore shape so slow-restore plans
        exercise the router's swap timeout without stalling serving."""
        import threading

        def flip():
            chaos.maybe_fire("restore")
            self.model_version += 1

        if wait:
            flip()
            return True
        threading.Thread(target=flip, daemon=True).start()
        return True

    def stop(self) -> None:
        self._closed = True
        self._queue.put(None)
        self._worker.join(timeout=5)


def mock_server_factory(service_ms: float = 1.0, version: int = 1,
                        fingerprint: Optional[str] = None):
    """Jax-free replica backend for router tests and plumbing smokes.
    `fingerprint` optionally declares an artifact identity (surfaced as
    `model_fingerprint` in health snapshots), which is what gateway
    cross-pool failover matches on before moving a request."""
    return _MockServer(
        service_ms=service_ms, version=version, fingerprint=fingerprint
    )


def multi_policy_mock_factory(
    catalog: Mapping[str, Mapping[str, Any]],
    service_ms: float = 1.0,
    load_ms: float = 0.0,
    default_policy: Optional[str] = None,
    preload=(),
    mem_budget_mb: Optional[int] = None,
    max_resident: Optional[int] = None,
    cold_load: Optional[bool] = None,
):
    """Jax-free MULTI-policy backend: one `_MockServer` per resident
    policy, each with its own (scale, bias, version, mem_bytes) from the
    catalog — so every policy's replies are distinguishable and
    bitwise-auditable against a single-policy twin. `load_ms` models the
    cold-load (materialize + prewarm) cost."""
    from tensor2robot_tpu.serving.policies import MultiPolicyServer

    catalog = {str(k): dict(v) for k, v in catalog.items()}

    def loader(policy_id: str):
        chaos.maybe_fire("load")
        entry = catalog[policy_id]
        if load_ms > 0:
            time.sleep(load_ms / 1e3)
        return _MockServer(
            service_ms=service_ms,
            version=int(entry.get("version", 1)),
            scale=float(entry.get("scale", 1.0)),
            bias=float(entry.get("bias", 0.0)),
            mem_bytes=int(entry.get("mem_bytes", 0)),
        )

    return MultiPolicyServer(
        loader,
        list(catalog),
        default_policy=default_policy,
        mem_budget_mb=mem_budget_mb,
        max_resident=max_resident,
        cold_load=cold_load,
        preload=preload,
    )


def multi_policy_store_factory(
    store_root: str,
    policy_ids=None,
    work_dir: Optional[str] = None,
    batch_buckets=None,
    max_wait_ms: Optional[int] = None,
    predict_timeout_ms: Optional[int] = None,
    restore_timeout_s: int = 120,
    default_policy: Optional[str] = None,
    preload=(),
    mem_budget_mb: Optional[int] = None,
    max_resident: Optional[int] = None,
    cold_load: Optional[bool] = None,
):
    """The production multi-policy backend: every policy materializes
    from the content-addressed store (export/artifact_store.py — base
    payload shared, deltas decoded on load) into a PolicyServer
    prewarmed off the SHARED bucket ladder. Heavy imports happen here,
    in the child, on purpose."""
    from tensor2robot_tpu.serving.policies import MultiPolicyServer
    from tensor2robot_tpu.serving.server import exported_policy_loader

    loader, catalog = exported_policy_loader(
        store_root,
        policy_ids=policy_ids,
        work_dir=work_dir,
        batch_buckets=batch_buckets,
        max_wait_ms=max_wait_ms,
        predict_timeout_ms=predict_timeout_ms,
        restore_timeout_s=restore_timeout_s,
    )
    return MultiPolicyServer(
        loader,
        catalog,
        default_policy=default_policy,
        mem_budget_mb=mem_budget_mb,
        max_resident=max_resident,
        cold_load=cold_load,
        preload=preload,
    )
