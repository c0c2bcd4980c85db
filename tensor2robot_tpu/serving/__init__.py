"""Fleet serving: micro-batching policy server + multi-replica router
(docs/SERVING.md, docs/RESILIENCE.md).

The host-side traffic layer over AbstractPredictor: bounded queue with
deadlines and backpressure, bucket-padded micro-batches (ladder = the
exporter's warmup_batch_sizes, so every served shape is pre-compiled),
zero-downtime hot-swap, structured observability snapshots — one level
up, a FleetRouter dispatching over a pool of policy-server replica
*processes* with deadline-aware least-loaded routing, retries, hedging,
health eviction, and rolling deploys — and, at the top, the
multi-tenant Gateway (per-tenant quotas, priority tiers, coalescing,
per-tenant circuit breaking) with a load-driven Autoscaler spawning and
draining replicas off the router's own load counters.

Exports resolve lazily (PEP 562): replica worker processes import this
package on spawn, and the replica entry path must not drag the full
server/specs/jax stack into a child that may only ever run the
lightweight mock backend. `from tensor2robot_tpu.serving import X`
works exactly as before; `import tensor2robot_tpu.serving` alone now
costs microseconds.
"""

from typing import TYPE_CHECKING

_EXPORTS = {
    # server.py — the single-process micro-batching policy server.
    "PolicyServer": "server",
    "ServeFuture": "server",
    "ServeResponse": "server",
    "ServeError": "server",
    "RequestRejected": "server",
    "RequestShed": "server",
    "DeadlineExceeded": "server",
    "ServerClosed": "server",
    "PredictFailed": "server",
    "PredictTimeout": "server",
    # metrics.py
    "RequestSpan": "metrics",
    "ServerMetrics": "metrics",
    # buckets.py
    "resolve_buckets": "buckets",
    "buckets_from_metadata": "buckets",
    "pick_bucket": "buckets",
    # router.py — the multi-replica fleet layer.
    "FleetRouter": "router",
    "FleetResponse": "router",
    "RouterFuture": "router",
    "FleetError": "router",
    "FleetSaturated": "router",
    "ReplicaUnavailable": "router",
    "RequestAbandoned": "router",
    "RouterClosed": "router",
    # replica.py — process entry + backends.
    "ReplicaSpec": "replica",
    "policy_server_factory": "replica",
    "mock_server_factory": "replica",
    "multi_policy_mock_factory": "replica",
    "multi_policy_store_factory": "replica",
    # policies.py — the multi-policy resident set behind one replica.
    "MultiPolicyServer": "policies",
    "PolicyError": "policies",
    "PolicyUnknown": "policies",
    "PolicyEvicted": "policies",
    "PolicyLoadFailed": "policies",
    # compile_cache.py — restore-time compile-cache engagement.
    "enable_compile_cache_for": "compile_cache",
    # gateway.py — the multi-tenant front door over router pools.
    "Gateway": "gateway",
    "TenantBinding": "gateway",
    "GateFuture": "gateway",
    "GateResponse": "gateway",
    "GateError": "gateway",
    "UnknownTenant": "gateway",
    "TenantThrottled": "gateway",
    "TenantSuspended": "gateway",
    "TierShed": "gateway",
    "GateDeadline": "gateway",
    "GatewayClosed": "gateway",
    "TIERS": "gateway",
    "observation_digest": "gateway",
    # autoscaler.py — load-driven replica count over a router pool.
    "Autoscaler": "autoscaler",
    # pool.py — socket-fabric replica processes (cross-host transport).
    "RemoteReplicaPool": "pool",
    "ReplicaLink": "pool",
    # fabric.py — zone-aware dispatch + cross-host stores + host AOT.
    "ZoneRouter": "fabric",
    "StoreServer": "fabric",
    "mirror_policy": "fabric",
    "remote_store_factory": "fabric",
    "host_aot_report": "fabric",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    module_name = _EXPORTS.get(name)
    if module_name is None:
        raise AttributeError(
            f"module 'tensor2robot_tpu.serving' has no attribute {name!r}"
        )
    import importlib

    module = importlib.import_module(f"{__name__}.{module_name}")
    value = getattr(module, name)
    globals()[name] = value  # cache: next access skips __getattr__
    return value


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))


if TYPE_CHECKING:  # pragma: no cover — static analyzers only
    from tensor2robot_tpu.serving.autoscaler import Autoscaler  # noqa: F401
    from tensor2robot_tpu.serving.fabric import (  # noqa: F401
        StoreServer,
        ZoneRouter,
        host_aot_report,
        mirror_policy,
        remote_store_factory,
    )
    from tensor2robot_tpu.serving.pool import (  # noqa: F401
        RemoteReplicaPool,
        ReplicaLink,
    )
    from tensor2robot_tpu.serving.compile_cache import (  # noqa: F401
        enable_compile_cache_for,
    )
    from tensor2robot_tpu.serving.gateway import (  # noqa: F401
        TIERS,
        GateDeadline,
        GateError,
        GateFuture,
        GateResponse,
        Gateway,
        GatewayClosed,
        TenantBinding,
        TenantSuspended,
        TenantThrottled,
        TierShed,
        UnknownTenant,
        observation_digest,
    )
    from tensor2robot_tpu.serving.buckets import (  # noqa: F401
        buckets_from_metadata,
        pick_bucket,
        resolve_buckets,
    )
    from tensor2robot_tpu.serving.metrics import (  # noqa: F401
        RequestSpan,
        ServerMetrics,
    )
    from tensor2robot_tpu.serving.policies import (  # noqa: F401
        MultiPolicyServer,
        PolicyError,
        PolicyEvicted,
        PolicyLoadFailed,
        PolicyUnknown,
    )
    from tensor2robot_tpu.serving.replica import (  # noqa: F401
        ReplicaSpec,
        mock_server_factory,
        multi_policy_mock_factory,
        multi_policy_store_factory,
        policy_server_factory,
    )
    from tensor2robot_tpu.serving.router import (  # noqa: F401
        FleetError,
        FleetResponse,
        FleetRouter,
        FleetSaturated,
        ReplicaUnavailable,
        RequestAbandoned,
        RouterClosed,
        RouterFuture,
    )
    from tensor2robot_tpu.serving.server import (  # noqa: F401
        DeadlineExceeded,
        PolicyServer,
        PredictFailed,
        PredictTimeout,
        RequestRejected,
        RequestShed,
        ServeError,
        ServeFuture,
        ServeResponse,
        ServerClosed,
    )
