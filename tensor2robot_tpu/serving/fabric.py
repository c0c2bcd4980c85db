"""Cross-host serving fabric: socket replicas, zones, cross-host stores.

Four pieces turn the one-host fleet into a multi-host serving fabric,
all riding the shared CRC-framed wire (`net/frames.py`):

  * **The fabric replica entry** (`fabric_replica_main`, and the
    `python -m tensor2robot_tpu.serving.fabric` CLI the pool launches).
    One replica process = one `ReplicaCore` (serving/replica.py — the
    SAME message core the mp fabric runs) driven by a duplex
    `FrameServer` instead of an mp queue, publishing its
    incarnation-stamped address only after its server factory has
    succeeded, so "address published" ≈ "ready to serve".
  * **ZoneRouter** — zone-aware least-loaded dispatch over per-zone
    `FleetRouter`s with CROSS-ZONE hedging and retry: a hedge always
    goes to a different zone than every attempt already in flight, a
    failed attempt retries onto an untried zone first, and every future
    still resolves through the per-zone routers' deadline backstops.
    The surface duck-types FleetRouter (submit/call/load/snapshot/
    rolling_swap/stop), so the gateway can span ZoneRouters as pools.
  * **Cross-host artifact store** — `StoreServer` exports an
    `ArtifactStore` over the wire by content address; `mirror_policy`
    pulls a policy (manifest + every referenced blob + its transitive
    delta bases) into a local mirror, hash-verifying every blob on
    receipt, manifests landing last, bases before dependents; and
    `remote_store_factory` is the replica factory that cold-loads its
    policies from such a mirror — so a fresh host materializes exactly
    the bytes the publisher's store holds, by sha256, or refuses typed.
  * **Per-host AOT resolution** (`host_aot_report`) — each host checks
    the artifact's `aot/` executables against ITS OWN platform/topology
    key (header-only: integrity then key, the payload is never
    unpickled here). A matching host restores from the executables; a
    mismatched one gets a typed per-file reason (`topology`,
    `jax_version`, `corrupt`) and falls down the restore ladder — the
    per-host table a heterogeneous fleet needs so a transplanted
    topology is never silently served.

Chaos peers: fabric replicas scope as `z<zone>.r<i>` (serving/pool.py
`replica_scope`), so `net_send`/`net_recv` plans cut specific links and
`partition:z1.r0+z1.r1` cuts a whole zone, exactly as replay shard
plans cut `s<k>`.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import itertools
import json
import logging
import os
import pickle
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

from tensor2robot_tpu import flags as t2r_flags
from tensor2robot_tpu.net import frames
from tensor2robot_tpu.serving import replica as replica_lib
from tensor2robot_tpu.serving.router import (
    FleetError,
    RequestAbandoned,
    RouterClosed,
    RouterFuture,
    _RouterMetrics,
)
from tensor2robot_tpu.testing import chaos, locksmith
from tensor2robot_tpu.utils.errors import best_effort

_log = logging.getLogger(__name__)

__all__ = [
    "ZoneRouter",
    "StoreServer",
    "fabric_replica_main",
    "host_aot_report",
    "mirror_policy",
    "remote_store_factory",
]


# -- fabric replica entry ------------------------------------------------------


class _PostBox:
    """Holds the CURRENT router connection's duplex send callable.

    The core's `post` is fixed at construction but the router reconnects
    (respawn re-resolution, torn frames, partitions heal); the postbox
    rebinds on every inbound message, so an async reply completing after
    a reconnect rides the NEW connection instead of dying with the old
    one. With no router connected, posts drop — the same best-effort
    contract as an mp replica whose response queue is gone."""

    def __init__(self):
        self._lock = locksmith.make_lock("_PostBox._lock")
        self._send: Optional[Callable[[Any], bool]] = None

    def bind(self, send: Callable[[Any], bool]) -> None:
        with self._lock:
            self._send = send

    def __call__(self, message: tuple) -> None:
        with self._lock:
            send = self._send
        if send is None:
            return
        send(message)


def fabric_replica_main(
    index: int,
    spec: "replica_lib.ReplicaSpec",
    root: str,
    incarnation: int,
    zone: Optional[str] = None,
) -> None:
    """Process entry for a socket-fabric replica.

    Boot order is the discovery contract: build the server (factory may
    be slow — restore, prewarm), THEN start the frame server, THEN
    publish the incarnation-stamped address. A router that can connect
    is talking to a replica whose factory already succeeded; a factory
    crash exits nonzero with nothing published, and the supervisor's
    boot timeout handles the silence."""
    from tensor2robot_tpu.serving.pool import replica_scope

    if spec.scope is None:
        spec = dataclasses.replace(
            spec, scope=replica_scope(index, spec, zone)
        )
    server = replica_lib.build_server(index, spec)
    postbox = _PostBox()
    core = replica_lib.ReplicaCore(index, server, postbox, free_q=None)
    stop_event = threading.Event()
    # One core, many possible connections (a reconnecting router, a
    # probing sibling): core.handle is not reentrant, so every
    # connection thread serializes through this lock. Idle ticks take
    # it non-blocking — a tick skipped under traffic costs nothing,
    # the next message's own tick covers it.
    core_lock = locksmith.make_lock("fabric_replica.core_lock")

    def handler(message: tuple, send: Callable[[Any], bool]) -> None:
        postbox.bind(send)
        with core_lock:
            if not core.handle(message):
                stop_event.set()

    def idle_tick() -> None:
        if core_lock.acquire(blocking=False):
            try:
                core.tick(time.time())
            finally:
                core_lock.release()

    frame_server = frames.FrameServer(
        handler, duplex=True, idle_tick=idle_tick
    ).start()
    chaos.maybe_fire("boot")
    frames.publish_address(
        root, frame_server.port, incarnation=incarnation
    )
    try:
        stop_event.wait()
    finally:
        frame_server.stop()
        core.close()


def _cli_main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tensor2robot_tpu.serving.fabric",
        description="Fabric replica process entry (launched by "
        "serving/pool.py RemoteReplicaPool; not a user-facing tool).",
    )
    parser.add_argument("--replica", action="store_true", required=True)
    parser.add_argument("--index", type=int, required=True)
    parser.add_argument("--root", required=True)
    parser.add_argument("--incarnation", type=int, required=True)
    parser.add_argument("--spec", required=True)
    parser.add_argument("--zone", default=None)
    args = parser.parse_args(argv)
    with open(args.spec, "rb") as f:
        spec = pickle.load(f)
    fabric_replica_main(
        args.index, spec, args.root, args.incarnation, zone=args.zone
    )
    return 0


# -- zone-aware dispatch -------------------------------------------------------


class _ZoneRequest:
    __slots__ = (
        "future", "features", "deadline", "policy_id", "tried",
        "outstanding", "retries_left", "hedged", "last_error", "resolved",
        "t_submit",
    )

    def __init__(self, future, features, deadline, policy_id, retries):
        self.future = future
        self.features = features
        self.deadline = deadline  # monotonic
        self.policy_id = policy_id
        self.tried: List[str] = []  # zone names, placement order
        self.outstanding = 0
        self.retries_left = retries
        self.hedged = False
        self.last_error: Optional[BaseException] = None
        self.resolved = False
        self.t_submit = time.monotonic()


class ZoneRouter:
    """Least-loaded dispatch across availability zones, hedged ACROSS
    zones — the cross-host tail-amputation the one-pool hedge cannot
    give (a straggling zone hedges into a healthy one, and a partitioned
    zone's requests win from its sibling).

    `zones` maps zone name -> a started FleetRouter (typically one
    socket-transport router per host/zone). Dispatch picks the
    admissible zone with the lowest utilization (ties broken
    round-robin); a request still pending `T2R_FABRIC_HEDGE_MS` after
    placement is duplicated into a DIFFERENT zone (first reply wins); a
    failed attempt retries onto an untried zone while deadline and
    `zone_retries` budget remain. Every returned future resolves: inner
    futures carry their routers' deadline backstops, and a placement
    that fails synchronously resolves the wrapper typed.

    Duck-types the FleetRouter client surface (submit/call/load/
    snapshot/rolling_swap/stop), so a ZoneRouter can stand where a
    router stands — including as a Gateway pool."""

    def __init__(
        self,
        zones: Mapping[str, Any],
        hedge_ms: Optional[int] = None,
        zone_retries: int = 1,
        default_deadline_ms: Optional[int] = None,
    ):
        if not zones:
            raise ValueError("ZoneRouter needs at least one zone")
        self._zones: Dict[str, Any] = dict(zones)
        self._hedge_s = (
            hedge_ms if hedge_ms is not None
            else t2r_flags.get_int("T2R_FABRIC_HEDGE_MS")
        ) / 1e3
        self._zone_retries = int(zone_retries)
        self._default_deadline_s = (
            default_deadline_ms if default_deadline_ms is not None
            else t2r_flags.get_int("T2R_SERVE_DEADLINE_MS")
        ) / 1e3
        # Reentrant: an inner future that is ALREADY resolved when
        # _place registers its callback fires _on_inner_done
        # synchronously on the placing thread, which holds this lock.
        self._lock = locksmith.make_rlock("ZoneRouter._lock")
        self._metrics = _RouterMetrics()
        self._ids = itertools.count(1)
        self._rr = 0
        self._closed = False

    @property
    def zones(self) -> List[str]:
        return sorted(self._zones)

    # -- placement ------------------------------------------------------------

    def _pick_zone(self, exclude: Tuple[str, ...]) -> str:
        """Least-utilized zone with routable capacity, preferring zones
        not in `exclude` (the cross-zone discipline: a hedge/retry only
        falls back onto a tried zone when no other has capacity)."""
        loads = {}
        for name, router in self._zones.items():
            try:
                loads[name] = router.load()
            except Exception:  # a stopping/broken zone is unroutable
                continue
        candidates = [
            n for n, l in loads.items()
            if n not in exclude and l["replicas_up"] > 0
        ]
        if not candidates:
            candidates = [
                n for n, l in loads.items() if l["replicas_up"] > 0
            ]
        if not candidates:
            raise FleetError(
                "no zone has a healthy replica "
                f"({len(self._zones)} zones, all down or starting)"
            )
        best = min(loads[n]["utilization"] for n in candidates)
        tied = sorted(
            n for n in candidates if loads[n]["utilization"] == best
        )
        self._rr += 1
        return tied[self._rr % len(tied)]

    def _place(self, req: _ZoneRequest, exclude: Tuple[str, ...],
               is_hedge: bool) -> None:
        """Called under self._lock. Walks admissible zones least-loaded
        first: a zone whose submit refuses synchronously (closed,
        saturated, no healthy replica) is counted as a failed attempt
        and the NEXT zone is tried — so one dead zone costs a counter,
        not the request. Raises FleetError only when every zone has
        refused (caller decides whether that is fatal)."""
        remaining_s = req.deadline - time.monotonic()
        if remaining_s <= 0:
            raise RequestAbandoned(
                "request deadline passed before zone placement",
                reason="deadline",
            )
        tried_now = set(exclude)
        last_error: Optional[BaseException] = None
        while True:
            try:
                zone = self._pick_zone(tuple(tried_now))
            except FleetError as err:
                raise last_error if isinstance(
                    last_error, FleetError
                ) else err
            if zone in tried_now:
                # _pick_zone's capacity fallback reused an excluded
                # zone: no fresh zone remains for this attempt.
                raise last_error if isinstance(
                    last_error, FleetError
                ) else FleetError(
                    "every zone refused the attempt "
                    f"(last: {last_error})"
                )
            router = self._zones[zone]
            try:
                inner = router.submit(
                    req.features,
                    deadline_ms=remaining_s * 1e3,
                    policy_id=req.policy_id,
                )
            except Exception as err:
                last_error = err
                req.last_error = err
                tried_now.add(zone)
                self._metrics.count(f"zone_attempt_failed_{zone}")
                continue
            req.tried.append(zone)
            req.outstanding += 1
            self._metrics.count(f"zone_dispatch_{zone}")
            if is_hedge:
                self._metrics.count("zone_hedges")
            inner.add_done_callback(
                lambda f, zone=zone, hedge=is_hedge:
                self._on_inner_done(req, f, zone, hedge)
            )
            return

    def _on_inner_done(self, req: _ZoneRequest, inner, zone: str,
                       was_hedge: bool) -> None:
        fire = None
        with self._lock:
            req.outstanding -= 1
            if req.resolved:
                return
            err = inner.error()
            if err is None:
                req.resolved = True
                if was_hedge:
                    self._metrics.count("zone_hedge_wins")
                self._metrics.count(f"zone_win_{zone}")
                self._metrics.count("completed")
                fire = (inner.result(0), None)
            else:
                req.last_error = err
                self._metrics.count(f"zone_attempt_failed_{zone}")
                remaining = req.deadline - time.monotonic()
                placed = False
                if (
                    not self._closed
                    and remaining > 0
                    and req.retries_left > 0
                ):
                    req.retries_left -= 1
                    self._metrics.count("zone_retries")
                    try:
                        self._place(
                            req, exclude=tuple(req.tried), is_hedge=False
                        )
                        placed = True
                    except FleetError as place_err:
                        req.last_error = place_err
                if not placed and req.outstanding == 0:
                    req.resolved = True
                    self._metrics.count("failed")
                    fire = (None, req.last_error)
        if fire is not None:
            response, error = fire
            if error is None:
                self._metrics.observe_latency(
                    (time.monotonic() - req.t_submit) * 1e3
                )
            # The future fires OUTSIDE self._lock: user callbacks may
            # re-enter submit().
            req.future._set(response, error)

    def _maybe_hedge(self, req: _ZoneRequest) -> None:
        with self._lock:
            if (
                self._closed
                or req.resolved
                or req.hedged
                or len(self._zones) < 2
            ):
                return
            req.hedged = True
            try:
                # exclude=tried → the hedge lands in a DIFFERENT zone
                # than every attempt in flight; with no untried zone
                # left, _pick_zone's fallback would reuse one, so check.
                untried = [
                    z for z in self._zones if z not in req.tried
                ]
                if not untried:
                    req.hedged = False
                    return
                self._place(req, exclude=tuple(req.tried), is_hedge=True)
            except FleetError:
                req.hedged = False  # best-effort; original stands

    # -- client surface -------------------------------------------------------

    def submit(
        self,
        features: Mapping[str, Any],
        deadline_ms: Optional[float] = None,
        policy_id: Optional[str] = None,
    ) -> RouterFuture:
        with self._lock:
            if self._closed:
                raise RouterClosed("zone router is not running")
            deadline = time.monotonic() + (
                deadline_ms / 1e3 if deadline_ms is not None
                else self._default_deadline_s
            )
            req = _ZoneRequest(
                RouterFuture(next(self._ids)), features, deadline,
                policy_id, self._zone_retries,
            )
            self._metrics.count("submitted")
            self._place(req, exclude=(), is_hedge=False)
        if self._hedge_s > 0 and len(self._zones) > 1:
            timer = threading.Timer(
                self._hedge_s, self._maybe_hedge, args=(req,)
            )
            timer.daemon = True
            timer.start()
        return req.future

    def call(
        self,
        features: Mapping[str, Any],
        deadline_ms: Optional[float] = None,
        timeout: Optional[float] = None,
        policy_id: Optional[str] = None,
    ):
        future = self.submit(
            features, deadline_ms=deadline_ms, policy_id=policy_id
        )
        if timeout is None:
            timeout = (
                deadline_ms / 1e3 if deadline_ms is not None
                else self._default_deadline_s
            ) + 30.0
        return future.result(timeout)

    # -- fleet surface --------------------------------------------------------

    def load(self) -> Dict:
        """Aggregate capacity across zones, per-zone detail included —
        the shape autoscalers and the gateway's shed accounting read."""
        per_zone = {}
        for name, router in self._zones.items():
            try:
                per_zone[name] = router.load()
            except Exception:
                per_zone[name] = {
                    "replicas_up": 0, "inflight": 0, "capacity": 0,
                    "utilization": 1.0, "shed_saturated": 0,
                    "replicas_pending": 0, "replicas_draining": 0,
                }
        inflight = sum(l["inflight"] for l in per_zone.values())
        capacity = sum(l["capacity"] for l in per_zone.values())
        return {
            "replicas_up": sum(
                l["replicas_up"] for l in per_zone.values()
            ),
            "replicas_pending": sum(
                l.get("replicas_pending", 0) for l in per_zone.values()
            ),
            "replicas_draining": sum(
                l.get("replicas_draining", 0) for l in per_zone.values()
            ),
            "inflight": inflight,
            "capacity": capacity,
            "utilization": (inflight / capacity) if capacity else 1.0,
            "shed_saturated": sum(
                l.get("shed_saturated", 0) for l in per_zone.values()
            ),
            "zones": per_zone,
        }

    def snapshot(self) -> Dict:
        snap = self._metrics.snapshot()
        snap["zones"] = {
            name: router.snapshot()
            for name, router in self._zones.items()
        }
        # Flattened replica list with zone labels: the shape the gateway
        # reads model fingerprints and residency off, unchanged.
        replicas = []
        for name in sorted(self._zones):
            for rep in snap["zones"][name].get("replicas", ()):
                entry = dict(rep)
                entry["zone"] = name
                replicas.append(entry)
        snap["replicas"] = replicas
        # This process's wire accounting (every zone router here shares
        # one codec, pool, and stats surface): stage timings,
        # per-segment-class bytes, receive-pool allocation audit.
        snap["wire"] = frames.wire_snapshot()
        snap["policy"] = {
            "hedge_ms": self._hedge_s * 1e3,
            "zone_retries": self._zone_retries,
            "zones": self.zones,
        }
        return snap

    def rolling_swap(self, swap_timeout_s: float = 60.0,
                     policy_id: Optional[str] = None) -> Dict:
        """Zone by zone, replica by replica — one replica mid-swap
        fleet-wide, the rolling discipline applied across zones. A
        failed swap aborts the roll (remaining zones keep serving the
        old version)."""
        results: Dict[str, Any] = {"zones": {}, "failed": None}
        for name in sorted(self._zones):
            zone_result = self._zones[name].rolling_swap(
                swap_timeout_s=swap_timeout_s, policy_id=policy_id
            )
            results["zones"][name] = zone_result
            if zone_result.get("failed") is not None:
                results["failed"] = f"{name}:{zone_result['failed']}"
                break
        return results

    def stop(self, timeout_s: float = 10.0) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
        for router in self._zones.values():
            best_effort(router.stop, timeout_s)

    def __enter__(self) -> "ZoneRouter":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()


# -- cross-host artifact store -------------------------------------------------


class StoreServer:
    """Serves an ArtifactStore over the frame wire, content-addressed.

    Protocol (request/reply shape; replies lead with the request's
    req_id, the SocketChannel correlation contract):

        ("manifest", req_id, policy_id) -> (req_id, "ok", manifest)
        ("blob", req_id, sha)           -> (req_id, "ok", bytes)
        ("list", req_id)                -> (req_id, "ok", [policy_id])
        any failure                     -> (req_id, "error", class, msg)

    Blob replies are raw stored bytes; the CLIENT re-hashes them against
    the sha it asked for (mirror_policy), so a corrupt wire or store
    surfaces as a typed refusal on the receiving host, never as a
    silently-wrong artifact. Publishes its address under
    `<store root>/serve/transport.json`."""

    def __init__(self, store, root: Optional[str] = None,
                 incarnation: int = 0):
        self._store = store
        self.root = root if root is not None else os.path.join(
            store.root, "serve"
        )
        os.makedirs(self.root, exist_ok=True)
        self._server = frames.FrameServer(self._handle)
        self._incarnation = int(incarnation)

    def start(self) -> "StoreServer":
        self._server.start()
        frames.publish_address(
            self.root, self._server.port, incarnation=self._incarnation
        )
        return self

    @property
    def port(self) -> int:
        return self._server.port

    def _handle(self, request: tuple):
        if not isinstance(request, tuple) or len(request) < 2:
            return None  # unfluent peer; no req_id to answer to
        kind, req_id = request[0], request[1]
        try:
            if kind == "manifest":
                return (req_id, "ok", self._store.manifest(request[2]))
            if kind == "blob":
                sha = request[2]
                return (
                    req_id, "ok",
                    self._store._read_blob(sha, f"remote fetch {sha[:12]}"),
                )
            if kind == "list":
                return (req_id, "ok", self._store.policies())
            return (req_id, "error", "BadRequest", f"unknown op {kind!r}")
        except Exception as err:
            return (req_id, "error", type(err).__name__, str(err))

    def stop(self) -> None:
        self._server.stop()


# Blob fetches kept in flight per mirror connection. Bounded so a
# mirror of a many-blob policy cannot hold an unbounded reply backlog
# in memory on either end.
MIRROR_WINDOW = 8


class _StoreClient:
    """Typed call helper over a PipelinedChannel to a StoreServer.

    `submit`/`result` expose the pipelining: several blob fetches ride
    one connection concurrently, correlated by req_id — `mirror_policy`
    keeps a window of them in flight instead of paying a full lockstep
    round trip per blob."""

    def __init__(self, service_root: str, timeout_s: float = 30.0):
        self._channel = frames.PipelinedChannel(service_root)
        self._timeout_s = timeout_s
        self._ids = itertools.count(1)

    def submit(self, op: str, *args):
        req_id = f"{op}-{next(self._ids)}"
        return self._channel.submit((op, req_id) + args, req_id)

    def result(self, pending):
        from tensor2robot_tpu.export import artifact_store as store_lib

        reply = self._channel.result(pending, timeout_s=self._timeout_s)
        if reply[1] == "ok":
            return reply[2]
        # Rehydrate the store's own error classes: a server-side
        # ArtifactCorrupt / PolicyNotFound stays THAT type on this
        # host, so mirror callers branch on it exactly as local ones.
        error_cls = getattr(store_lib, reply[2], None)
        if not (
            isinstance(error_cls, type)
            and issubclass(error_cls, store_lib.ArtifactStoreError)
        ):
            error_cls = store_lib.ArtifactStoreError
        raise error_cls(
            f"remote store failed: {reply[2]}: {reply[3]}"
        )

    def call(self, op: str, *args):
        return self.result(self.submit(op, *args))

    def close(self) -> None:
        self._channel.close()


def mirror_policy(
    service_root: str,
    policy_id: str,
    dest_store,
    timeout_s: float = 30.0,
) -> Dict[str, Any]:
    """Pull one policy (and its transitive delta bases) from a remote
    StoreServer into `dest_store`, by content address.

    Every blob is fetched by sha256 and RE-HASHED on receipt (a wire or
    remote-disk corruption is a typed ArtifactCorrupt here, before any
    byte lands); already-present blobs are skipped (content-addressed
    dedup across mirrors). Manifests land LAST, bases before
    dependents, each atomically — so a partially-mirrored policy does
    not exist, and a concurrent reader sees either nothing or a policy
    whose every referenced blob is already on disk. Returns
    {policies, blobs_fetched, blobs_reused, bytes_fetched}."""
    from tensor2robot_tpu.export.artifact_store import ArtifactCorrupt

    client = _StoreClient(service_root, timeout_s=timeout_s)
    try:
        # Walk the delta-base chain: manifests base-first.
        chain: List[Tuple[str, Dict[str, Any]]] = []
        seen = set()
        cursor: Optional[str] = policy_id
        while cursor is not None:
            if cursor in seen:
                raise ArtifactCorrupt(
                    f"policy {policy_id!r}: delta base chain cycles "
                    f"at {cursor!r}"
                )
            seen.add(cursor)
            manifest = client.call("manifest", cursor)
            chain.append((cursor, manifest))
            cursor = manifest["payload"].get("base")
        chain.reverse()  # bases first

        fetched = reused = nbytes = 0
        # Want-list across the whole chain (dedup preserving order: a
        # base and its dependent may share blobs).
        want: List[Tuple[str, str]] = []
        want_seen = set()
        for pid, manifest in chain:
            shas = [
                entry["blob"] for entry in manifest["files"].values()
            ]
            payload_blob = manifest["payload"].get("blob")
            if payload_blob:
                shas.append(payload_blob)
            for sha in shas:
                if sha in want_seen:
                    continue
                want_seen.add(sha)
                if os.path.exists(dest_store._blob_path(sha)):
                    reused += 1
                else:
                    want.append((pid, sha))
        # Windowed pipeline: keep up to MIRROR_WINDOW blob requests in
        # flight on the one connection (the channel multiplexes them by
        # req_id), landing each oldest-first — a WAN round trip is paid
        # once per window, not once per blob. Each blob is still
        # sha256-re-hashed before it touches disk.
        window: List[Tuple[str, str, Any]] = []
        idx = 0
        while idx < len(want) or window:
            while idx < len(want) and len(window) < MIRROR_WINDOW:
                pid, sha = want[idx]
                window.append((pid, sha, client.submit("blob", sha)))
                idx += 1
            pid, sha, pending = window.pop(0)
            data = client.result(pending)
            if hashlib.sha256(data).hexdigest() != sha:
                raise ArtifactCorrupt(
                    f"mirror of {pid!r}: blob sha256-{sha[:12]}… "
                    "failed its content hash on receipt — refusing "
                    "the transfer"
                )
            dest_store._write_blob(data)
            fetched += 1
            nbytes += len(data)
        # Blobs are all down; NOW the manifests, bases first.
        for pid, manifest in chain:
            if dest_store.has(pid):
                continue
            path = dest_store._manifest_path(pid)
            data = json.dumps(manifest, sort_keys=True, indent=1).encode()
            fd, tmp = tempfile.mkstemp(
                dir=os.path.dirname(path), prefix=".tmp-"
            )
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(data)
                os.replace(tmp, path)
            except BaseException:
                if os.path.exists(tmp):
                    os.unlink(tmp)
                raise
        return {
            "policies": [pid for pid, _ in chain],
            "blobs_fetched": fetched,
            "blobs_reused": reused,
            "bytes_fetched": nbytes,
        }
    finally:
        client.close()


def remote_store_factory(
    service_root: str,
    mirror_root: str,
    policy_ids=None,
    **kwargs,
):
    """Replica factory for a host that does NOT hold the artifact store:
    list (or take) the policy ids, mirror each — content-addressed,
    hash-verified, transitive bases included — into a LOCAL store under
    `mirror_root`, then serve from the mirror through the standard
    multi-policy store factory. Heavy work happens in the replica child,
    on purpose; a second replica on the same host reuses the mirror's
    blobs by content address."""
    from tensor2robot_tpu.export.artifact_store import ArtifactStore

    mirror = ArtifactStore(mirror_root)
    if policy_ids is None:
        client = _StoreClient(service_root)
        try:
            policy_ids = client.call("list")
        finally:
            client.close()
    for policy_id in policy_ids:
        mirror_policy(service_root, policy_id, mirror)
    return replica_lib.multi_policy_store_factory(
        mirror_root, policy_ids=list(policy_ids), **kwargs
    )


# -- per-host AOT resolution ---------------------------------------------------


def host_aot_report(
    export_root: str,
    topology: Optional[Mapping[str, Any]] = None,
) -> Dict[str, Any]:
    """How THIS host resolves the artifact's `aot/` executables.

    Header-only: each envelope is integrity-checked (magic/length/CRC)
    and its key compared against this host's platform/topology triple
    and jax version — the payload is NEVER unpickled here, so a
    transplanted or corrupt executable costs a typed row, not a crash.
    Per file: `status` is `aot` (this host restores from it),
    `topology` / `jax_version` / `key` (intact but keyed elsewhere —
    the restore ladder falls back, loudly), or `corrupt`. The summary
    is the per-host AOT key table docs/SERVING.md documents and the
    heterogeneity bench leg asserts."""
    from tensor2robot_tpu.export import aot as aot_lib

    if topology is None:
        topology = aot_lib.device_topology()
    topology = dict(topology)
    aot_dir = os.path.join(export_root, aot_lib.AOT_DIR)
    files: Dict[str, Dict[str, Any]] = {}
    counts = {"aot": 0, "topology": 0, "jax_version": 0, "key": 0,
              "corrupt": 0}
    names = []
    if os.path.isdir(aot_dir):
        names = sorted(
            n for n in os.listdir(aot_dir) if n.endswith(".bin")
        )
    for name in names:
        path = os.path.join(aot_dir, name)
        with open(path, "rb") as f:
            blob = f.read()
        entry: Dict[str, Any] = {}
        try:
            header, _payload = aot_lib._unpack(blob)
        except aot_lib.AOTCorrupt as err:
            entry = {"status": "corrupt", "detail": str(err)}
            files[name] = entry
            counts["corrupt"] += 1
            continue
        entry["header_topology"] = header.get("topology")
        import jax

        # Same check order as aot._check_key, so this report names the
        # SAME first reason the restore ladder's typed fallback will.
        if header.get("format_version") != aot_lib.AOT_FORMAT_VERSION:
            entry["status"] = "key"
            entry["detail"] = (
                f"format_version {header.get('format_version')} != "
                f"{aot_lib.AOT_FORMAT_VERSION}"
            )
        elif header.get("jax") != jax.__version__:
            entry["status"] = "jax_version"
            entry["detail"] = (
                f"serialized under jax {header.get('jax')}, host runs "
                f"{jax.__version__}"
            )
        elif dict(header.get("topology") or {}) != topology:
            entry["status"] = "topology"
            entry["detail"] = (
                f"lowered for {header.get('topology')}, this host is "
                f"{topology}"
            )
        else:
            entry["status"] = "aot"
        files[name] = entry
        counts[entry["status"]] += 1
    return {
        "host_topology": topology,
        "files": files,
        "counts": counts,
        # The one-line verdict placement logic keys on: does THIS host
        # restore every bucket from the executables, or none, or a mix
        # (a mix means a partially-regenerated aot/ dir — worth eyes).
        "all_aot": bool(names) and counts["aot"] == len(names),
    }


if __name__ == "__main__":  # pragma: no cover — subprocess entry
    raise SystemExit(_cli_main())
