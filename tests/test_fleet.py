"""serving/router.py + serving/transport.py: the replica-fleet layer.

Every fleet failure mode the router claims to survive is pinned here
with a seeded chaos plan injected into a targeted replica process
(testing/chaos.py rides the T2R_CHAOS env flag through ReplicaSpec.env):
replica crash mid-predict, corrupt reply, straggler hedging, saturation
shed, deadline backstop, health eviction + recovery, slow-restore swap
abort. Replicas run the jax-free mock backend, so each test costs
process spawns, not XLA compiles. No assertion depends on wall-clock
rates — only on typed outcomes, counters, and generous ordering bounds
(an injected 2.5 s stall vs a 0.3 s deadline).
"""

import queue as queue_lib
import time

import numpy as np
import pytest

from tensor2robot_tpu.serving import (
    FleetRouter,
    FleetSaturated,
    ReplicaSpec,
    ReplicaUnavailable,
    RequestAbandoned,
    RouterClosed,
    mock_server_factory,
)
from tensor2robot_tpu.serving import transport


@pytest.fixture(autouse=True)
def _lock_sanitizer_armed(locksmith_sanitizer):
    """Every run of this chaos suite doubles as a deadlock hunt: the
    lock sanitizer (testing/locksmith.py) is armed for each test and
    teardown fails on any observed lock-order cycle or hold-budget
    violation (fixture: tests/conftest.py)."""
    yield


def _spec(service_ms=1.0, chaos=None, version=1):
    env = {"T2R_CHAOS": chaos} if chaos else {}
    return ReplicaSpec(
        factory=mock_server_factory,
        factory_kwargs={"service_ms": service_ms, "version": version},
        env=env,
    )


def _start(specs, num=None, timeout_s=90.0, **kwargs):
    kwargs.setdefault("probe_interval_ms", 50.0)
    kwargs.setdefault("backoff_ms", 5.0)
    router = FleetRouter(specs, num, **kwargs)
    return router.start(timeout_s=timeout_s)


def _wait(predicate, timeout=30.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


def _wait_all_up(router):
    assert _wait(
        lambda: all(s == "up" for s in router.replica_states())
    ), f"fleet never fully up: {router.replica_states()}"


def _features(n=4, value=1.0):
    return {"x": np.full((n,), value, np.float32)}


def _broken_factory():
    raise RuntimeError("this replica can never build its server")


class TestRouting:
    def test_end_to_end_with_provenance(self):
        with _start(_spec(), 2) as router:
            _wait_all_up(router)
            for value in (1.0, 2.0, 3.0):
                response = router.call(
                    _features(value=value), deadline_ms=20000
                )
                assert response.outputs["y"] == pytest.approx(4 * value)
                assert response.model_version == 1
                assert response.attempts == 1 and not response.hedged
                assert response.replica in (0, 1)
                assert response.spans["total_ms"] > 0
            snap = router.snapshot()
            assert snap["counters"]["completed"] == 3
            assert snap["counters"].get("failed", 0) == 0
            assert snap["latency_ms"]["window"] == 3
            assert snap["pending_requests"] == 0

    def test_load_spreads_over_replicas(self):
        with _start(_spec(service_ms=30.0), 2, max_inflight=4) as router:
            _wait_all_up(router)
            futures = [
                router.submit(_features(), deadline_ms=30000)
                for _ in range(8)
            ]
            for future in futures:
                future.result(30)
            served = set()
            for future in futures:
                served.add(future.result(0).replica)
            assert served == {0, 1}

    def test_constructor_validation(self):
        with pytest.raises(ValueError, match="num_replicas is required"):
            FleetRouter(_spec())
        with pytest.raises(ValueError, match="at least one replica"):
            FleetRouter([])
        with pytest.raises(ValueError, match="2 specs"):
            FleetRouter([_spec(), _spec()], 3)

    def test_failed_bringup_raises_after_respawn_budget(self):
        router = FleetRouter(
            [ReplicaSpec(factory=_broken_factory)],
            probe_interval_ms=50.0,
            max_respawns=1,
        )
        with pytest.raises(RuntimeError, match="no replica became healthy"):
            router.start(timeout_s=60.0)


class TestFailureHandling:
    def test_replica_kill_mid_predict_is_retried(self):
        """One replica SIGKILLs itself on its first predict; every request
        must still complete (failover), the death must be counted, and
        the killed replica must come back via respawn."""
        specs = [_spec(chaos="predict:1:kill"), _spec()]
        with _start(specs, max_respawns=2) as router:
            _wait_all_up(router)
            futures = [
                router.submit(_features(value=v), deadline_ms=30000)
                for v in (1.0, 2.0, 3.0, 4.0)
            ]
            for value, future in zip((1.0, 2.0, 3.0, 4.0), futures):
                response = future.result(60)
                assert response.outputs["y"] == pytest.approx(4 * value)
            snap = router.snapshot()
            assert snap["counters"]["replica_deaths"] >= 1
            assert snap["counters"]["retries"] >= 1
            assert snap["counters"]["respawns"] >= 1
            assert snap["counters"]["completed"] == 4
            # The respawned replica (fresh process, fresh chaos counters,
            # plan re-armed but predict:1 already consumed by... a NEW
            # process would re-fire; requests may route to its sibling).
            # What matters: the fleet returns to full strength.
            assert _wait(
                lambda: router.replica_states().count("up") == 2
            ), router.replica_states()

    def test_corrupt_reply_detected_and_retried(self):
        """A byte-flipped (checksummed) reply must be treated as a replica
        failure and the request re-dispatched — never decoded into a
        silently-wrong response."""
        specs = [_spec(chaos="reply:1:corrupt"), _spec()]
        with _start(specs) as router:
            _wait_all_up(router)
            for value in (1.0, 2.0, 3.0, 4.0):
                response = router.call(
                    _features(value=value), deadline_ms=30000
                )
                assert response.outputs["y"] == pytest.approx(4 * value)
            snap = router.snapshot()
            assert snap["counters"]["corrupt_replies"] == 1
            assert snap["counters"]["retries"] >= 1
            assert snap["counters"]["completed"] == 4

    def test_hedge_beats_straggler(self):
        """First request lands on the replica whose first predict stalls
        2.5 s; the hedge (after 100 ms) runs on the fast sibling and its
        reply wins long before the straggler wakes."""
        specs = [_spec(), _spec(chaos="predict:1:delay:2500")]
        with _start(specs, hedge_ms=100, default_deadline_ms=20000) as router:
            _wait_all_up(router)
            # Deterministic: the round-robin cursor sends request 1 to
            # replica index 1 (the straggler) when both are idle.
            response = router.call(_features(), deadline_ms=20000)
            assert response.hedged
            assert response.replica == 0
            snap = router.snapshot()
            assert snap["counters"]["hedged"] == 1
            assert snap["counters"]["hedge_wins"] == 1
            assert snap["counters"]["completed"] == 1

    def test_saturated_fleet_sheds_typed_and_recovers(self):
        with _start(
            _spec(service_ms=400.0), 1, max_inflight=1
        ) as router:
            _wait_all_up(router)
            first = router.submit(_features(), deadline_ms=30000)
            with pytest.raises(FleetSaturated, match="in-flight cap"):
                router.submit(_features(), deadline_ms=30000)
            assert first.result(30).outputs["y"] == pytest.approx(4.0)
            snap = router.snapshot()
            assert snap["counters"]["shed_saturated"] == 1
            # Capacity freed: admission works again.
            assert router.call(
                _features(), deadline_ms=30000
            ).outputs["y"] == pytest.approx(4.0)

    def test_deadline_backstop_always_resolves(self):
        """A request whose only replica is wedged (2.5 s stall) and whose
        deadline is 300 ms must fail typed at the deadline — the future
        resolves while the replica is still stuck, because the router
        itself arms a per-request timer."""
        with _start(_spec(chaos="predict:1:delay:2500"), 1) as router:
            _wait_all_up(router)
            future = router.submit(_features(), deadline_ms=300)
            with pytest.raises(RequestAbandoned) as excinfo:
                future.result(2.0)  # well inside the injected 2.5s stall
            assert excinfo.value.reason == "deadline"
            assert router.snapshot()["pending_requests"] == 0

    def test_single_replica_death_abandons_typed_then_unavailable(self):
        """With the whole pool dead (respawn off), in-flight requests fail
        typed through the retry budget and NEW submissions are rejected
        synchronously with ReplicaUnavailable."""
        with _start(
            _spec(chaos="predict:1:kill"), 1, respawn=False, retries=1
        ) as router:
            _wait_all_up(router)
            future = router.submit(_features(), deadline_ms=30000)
            with pytest.raises(RequestAbandoned) as excinfo:
                future.result(60)
            assert excinfo.value.reason == "retries"
            assert "died" in excinfo.value.detail
            assert _wait(
                lambda: router.replica_states() == ["dead"]
            ), router.replica_states()
            with pytest.raises(ReplicaUnavailable):
                router.submit(_features(), deadline_ms=30000)

    def test_silent_replica_evicted_then_readmitted(self):
        """A replica that stops answering health probes (1.5 s stall in
        its loop) must leave the routing set (SUSPECT) and rejoin when it
        answers again. respawn=False pins the eviction path alone — no
        hard-kill racing the recovery."""
        with _start(
            [_spec(chaos="health:2:hang:1500"), _spec()],
            respawn=False,
            probe_interval_ms=50.0,
            probe_miss_limit=3,
        ) as router:
            _wait_all_up(router)
            assert _wait(
                lambda: router.replica_states()[0] == "suspect", timeout=10
            ), router.replica_states()
            # While suspect, traffic still flows via the healthy sibling.
            assert router.call(
                _features(), deadline_ms=20000
            ).replica == 1
            assert _wait(
                lambda: router.replica_states()[0] == "up", timeout=10
            ), router.replica_states()
            assert router.snapshot()["counters"]["evictions"] >= 1

    def test_stop_resolves_pending_with_router_closed(self):
        router = _start(_spec(chaos="predict:1:delay:2000"), 1)
        _wait_all_up(router)
        future = router.submit(_features(), deadline_ms=30000)
        router.stop()
        with pytest.raises(RouterClosed):
            future.result(5)
        with pytest.raises(RouterClosed):
            router.submit(_features())


class TestRollingSwap:
    def test_rolling_swap_entire_fleet(self):
        with _start(_spec(), 3) as router:
            _wait_all_up(router)
            assert router.call(_features(), deadline_ms=20000).model_version == 1
            result = router.rolling_swap(swap_timeout_s=30.0)
            assert result["failed"] is None
            assert sorted(s["replica"] for s in result["swapped"]) == [0, 1, 2]
            assert all(s["version"] == 2 for s in result["swapped"])
            assert router.call(
                _features(), deadline_ms=20000
            ).model_version == 2

    def test_slow_restore_aborts_roll_and_keeps_serving(self):
        """Replica 1's restore stalls past the swap deadline: the roll
        must abort there (bad artifact must not take the fleet down), the
        remaining replica keeps the old version, and traffic still
        completes throughout."""
        specs = [_spec(), _spec(chaos="restore:1:hang:4000"), _spec()]
        with _start(specs) as router:
            _wait_all_up(router)
            result = router.rolling_swap(swap_timeout_s=0.6)
            assert result["failed"] == 1
            assert [s["replica"] for s in result["swapped"]] == [0]
            # Replica 2 was never asked: still the old version.
            versions = {
                r["index"]: r["version"]
                for r in router.snapshot()["replicas"]
            }
            assert versions[0] == 2 and versions[2] == 1
            response = router.call(_features(), deadline_ms=20000)
            assert response.outputs["y"] == pytest.approx(4.0)


class TestTransport:
    def test_pack_unpack_integrity(self):
        crc, blob = transport.pack({"a": 1})
        assert transport.unpack(crc, blob) == {"a": 1}
        bad = blob[:-1] + bytes([blob[-1] ^ 0xFF])
        with pytest.raises(transport.IntegrityError, match="CRC32"):
            transport.unpack(crc, bad)
        # Checksums-but-not-unpickles is the same wire failure.
        garbage = b"\x80\x04nonsense"
        with pytest.raises(transport.IntegrityError, match="decode"):
            transport.unpack(__import__("zlib").crc32(garbage), garbage)

    def test_codec_small_payloads_ride_inline(self):
        codec = transport.RequestCodec(
            queue_lib.Queue(), inline_max_bytes=1 << 20
        )
        payload = codec.encode({"x": np.ones((8,), np.float32)})
        assert payload[0] == "inline"
        decoded = transport.decode_request(
            payload, None, transport.ReplicaSlotCache()
        )
        np.testing.assert_array_equal(decoded["x"], np.ones((8,), np.float32))
        codec.close()

    def test_codec_large_payload_uses_ring_and_recycles_slot(self):
        free = queue_lib.Queue()
        codec = transport.RequestCodec(free, inline_max_bytes=1024, num_slots=2)
        cache = transport.ReplicaSlotCache()
        big = np.arange(64 * 1024, dtype=np.uint8).reshape(256, 256)
        try:
            payload = codec.encode({"big": big, "small": np.int64(7)})
            if payload[0] == "inline":
                pytest.skip("no /dev/shm in this environment")
            assert payload[0] == "shm"
            decoded = transport.decode_request(payload, free, cache)
            np.testing.assert_array_equal(decoded["big"], big)
            assert decoded["small"] == 7
            # decode_request returned the slot: the same name cycles.
            name = payload[1]
            seen = set()
            for _ in range(2 * 2 + 1):
                again = codec.encode({"big": big})
                assert again[0] == "shm"
                seen.add(again[1])
                transport.decode_request(again, free, cache)
            assert name in seen
        finally:
            cache.close()
            codec.close()

    def test_codec_exhausted_ring_degrades_to_inline(self):
        free = queue_lib.Queue()
        codec = transport.RequestCodec(free, inline_max_bytes=1024, num_slots=1)
        big = np.zeros((4096,), np.float64)
        try:
            first = codec.encode({"big": big})
            if first[0] == "inline":
                pytest.skip("no /dev/shm in this environment")
            # Slot never released: the next large payload must go inline
            # rather than block (shed-to-slower, never stuck).
            second = codec.encode({"big": big})
            assert second[0] == "inline"
            decoded = transport.decode_request(
                second, free, transport.ReplicaSlotCache()
            )
            np.testing.assert_array_equal(decoded["big"], big)
        finally:
            codec.close()

    def test_router_ships_large_payloads_intact(self):
        """End-to-end shm transport: a payload far over the inline cap
        round-trips through a replica process bit-exactly (the mock
        echoes a checksum + byte count)."""
        frame = (np.arange(96 * 1024, dtype=np.int64) % 251).astype(np.uint8)
        with _start(
            _spec(), 1, inline_max_bytes=4096, shm_slots=4
        ) as router:
            _wait_all_up(router)
            response = router.call(
                {"frame": frame, "scalar": np.float32(2.5)},
                deadline_ms=30000,
            )
            assert response.outputs["nbytes"] == frame.nbytes + 4
            assert response.outputs["y"] == pytest.approx(
                float(frame.astype(np.float64).sum()) + 2.5
            )


class TestOneProcessPerChip:
    """A chip belongs to one process at a time and replicas have no chip
    binding: fleets that would contend for the accelerator are refused
    up front with the reason, instead of failing or hanging at boot."""

    @staticmethod
    def _specs(n, factory, env=None):
        from tensor2robot_tpu.serving import ReplicaSpec

        return [
            ReplicaSpec(
                factory=factory, factory_kwargs={"export_root": "/nowhere"},
                env=dict(env or {}),
            )
            for _ in range(n)
        ]

    def test_jax_fleet_without_the_cpu_request_is_refused(self, monkeypatch):
        from tensor2robot_tpu.serving import FleetRouter, policy_server_factory
        from tensor2robot_tpu.serving.replica import (
            check_one_process_per_chip,
        )

        monkeypatch.delenv("JAX_PLATFORMS")
        specs = self._specs(2, policy_server_factory)
        with pytest.raises(RuntimeError, match="each open every local acc"):
            check_one_process_per_chip(specs)
        with pytest.raises(RuntimeError, match="one process at a time"):
            FleetRouter(specs).start()
        # One jax replica under a parent whose backend is the CPU is fine.
        check_one_process_per_chip(specs[:1])

    def test_explicit_cpu_and_mock_fleets_pass(self, monkeypatch):
        from tensor2robot_tpu.serving import (
            mock_server_factory,
            policy_server_factory,
        )
        from tensor2robot_tpu.serving.replica import (
            check_one_process_per_chip,
        )

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        check_one_process_per_chip(self._specs(4, policy_server_factory))
        monkeypatch.delenv("JAX_PLATFORMS")
        check_one_process_per_chip(
            self._specs(4, policy_server_factory, {"JAX_PLATFORMS": "cpu"})
        )
        check_one_process_per_chip(self._specs(4, mock_server_factory))
