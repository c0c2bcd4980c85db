"""Policy-server tests: micro-batching, bucket discipline, deadlines,
backpressure, hot-swap, and the warmup-request round-trip contract.

The load-bearing assertion for the serving subsystem is bucket
discipline: NO batch shape the server hands the predictor may fall
outside the exporter's warmup ladder — a novel shape means a fresh XLA
compile in the serve path, a multi-second latency cliff invisible in
unit-scale functional tests. _RecordingPredictor wraps the real
predictor and records every served leading dim so the tests assert it
directly.
"""

import threading
import time

import jax
import numpy as np
import pytest

from tensor2robot_tpu import flags as t2r_flags
from tensor2robot_tpu.export import DefaultExportGenerator
from tensor2robot_tpu.export.exporters import LatestExporter
from tensor2robot_tpu.predictors import ExportedSavedModelPredictor
from tensor2robot_tpu.serving import (
    DeadlineExceeded,
    PolicyServer,
    RequestRejected,
    RequestShed,
    ServerClosed,
    buckets_from_metadata,
    pick_bucket,
    resolve_buckets,
)
from tensor2robot_tpu.serving import buckets as buckets_lib
from tensor2robot_tpu.train.train_eval import CompiledModel
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

BUCKETS = (1, 2, 4)


@pytest.fixture(scope="module")
def trained():
    model = MockT2RModel(device_type="cpu")
    generator = MockInputGenerator(batch_size=8)
    generator.set_specification_from_model(model, "train")
    batches = iter(generator.create_dataset("train"))
    compiled = CompiledModel(model, donate_state=False)
    state = compiled.init_state(jax.random.PRNGKey(0), next(batches))
    return compiled, state


@pytest.fixture(scope="module")
def export_root(trained, tmp_path_factory):
    compiled, state = trained
    model_dir = str(tmp_path_factory.mktemp("serve_export"))
    exporter = LatestExporter(name="latest", warmup_batch_sizes=BUCKETS)
    exporter.maybe_export(
        step=1, state=state, eval_metrics={"loss": 1.0},
        compiled=compiled, model_dir=model_dir,
    )
    return exporter.export_root(model_dir)


class _RecordingPredictor:
    """Delegating wrapper that records every served batch size (both
    predict surfaces — the server prefers predict_versioned)."""

    def __init__(self, inner):
        self._inner = inner
        self.batch_sizes = []

    def _record(self, features):
        sizes = {int(np.asarray(v).shape[0]) for v in features.values()}
        assert len(sizes) == 1, f"ragged batch: {sizes}"
        self.batch_sizes.append(sizes.pop())

    def predict(self, features):
        self._record(features)
        return self._inner.predict(features)

    def predict_versioned(self, features):
        self._record(features)
        return self._inner.predict_versioned(features)

    def __getattr__(self, name):
        return getattr(self._inner, name)


@pytest.fixture()
def predictor(export_root):
    inner = ExportedSavedModelPredictor(export_dir=export_root)
    assert inner.restore()
    return _RecordingPredictor(inner)


def _example(seed=0):
    return {
        "x": np.random.RandomState(seed).uniform(-1, 1, (3,)).astype(np.float32)
    }


class TestPolicyServer:
    def test_single_request_roundtrip(self, predictor):
        with PolicyServer(predictor, max_wait_ms=1).start() as server:
            assert server.buckets == BUCKETS  # from export metadata
            response = server.call(_example(), timeout=30)
            assert response.outputs["a_predicted"].shape == (1,)
            assert response.model_version == predictor.model_version
            assert response.spans["total_ms"] >= 0

    def test_concurrent_requests_coalesce_and_match_direct(self, predictor):
        rows = [_example(seed) for seed in range(3)]
        with PolicyServer(predictor, max_wait_ms=60).start() as server:
            predictor.batch_sizes.clear()  # drop the prewarm calls
            futures = [
                server.submit(row, deadline_ms=30000) for row in rows
            ]
            responses = [f.result(30) for f in futures]
        # 3 requests within one 60ms window -> ONE padded bucket-4 batch.
        assert predictor.batch_sizes == [4]
        direct = predictor.predict(
            {"x": np.stack([row["x"] for row in rows])}
        )
        for i, response in enumerate(responses):
            np.testing.assert_allclose(
                response.outputs["a_predicted"],
                direct["a_predicted"][i],
                rtol=1e-5,
            )

    def test_every_served_shape_is_a_warmup_bucket(self, predictor):
        """The no-novel-shapes acceptance guarantee, under a ragged
        multi-threaded load that exercises every coalesce path."""
        with PolicyServer(predictor, max_wait_ms=3).start() as server:
            errors = []

            def client(seed):
                rng = np.random.RandomState(seed)
                for _ in range(10):
                    try:
                        server.call(_example(seed), timeout=30)
                    except Exception as err:  # noqa: BLE001
                        errors.append(err)
                    time.sleep(float(rng.uniform(0, 0.004)))

            threads = [
                threading.Thread(target=client, args=(seed,))
                for seed in range(5)
            ]
            for thread in threads:
                thread.start()
            for thread in threads:
                thread.join()
            assert not errors
            assert predictor.batch_sizes, "no batches served"
            assert set(predictor.batch_sizes) <= set(BUCKETS)
            snap = server.snapshot()
            assert snap["counters"]["completed"] == 50
            assert 0 < snap["batch_fill_ratio"] <= 1.0

    def test_deadline_missed_before_dispatch(self, predictor):
        with PolicyServer(predictor, max_wait_ms=50).start() as server:
            future = server.submit(_example(), deadline_ms=0.0)
            with pytest.raises(DeadlineExceeded):
                future.result(30)
            assert server.snapshot()["counters"]["deadline_missed"] == 1

    def test_submit_coerces_dtype_to_spec(self, predictor):
        """A float64 request (e.g. a plain Python list) must be cast to
        the spec dtype at admission — one off-dtype client must not hand
        the whole coalesced batch a novel-dtype recompile (or poison its
        batchmates with a ServeError)."""
        with PolicyServer(predictor, max_wait_ms=1).start() as server:
            response = server.call({"x": [0.1, 0.2, 0.3]}, timeout=30)
            assert response.outputs["a_predicted"].shape == (1,)

    def test_submit_rejects_batched_input(self, predictor):
        with PolicyServer(predictor, max_wait_ms=1).start() as server:
            with pytest.raises(ValueError, match="single example"):
                server.submit({"x": np.zeros((2, 3), np.float32)})

    def test_submit_rejects_missing_feature(self, predictor):
        with PolicyServer(predictor, max_wait_ms=1).start() as server:
            with pytest.raises(ValueError, match="missing required"):
                server.submit({"y": np.zeros((3,), np.float32)})

    def test_submit_after_stop_raises(self, predictor):
        server = PolicyServer(predictor, max_wait_ms=1).start()
        server.stop()
        with pytest.raises((ServerClosed, RuntimeError)):
            server.submit(_example())

    def test_dispatcher_survives_structurally_bad_outputs(self, predictor):
        """A reply-path failure (outputs that cannot be split per
        request) must fail THAT batch's futures and leave the dispatcher
        alive — a dead dispatcher behind a live submit() is a silent
        permanent outage."""
        from tensor2robot_tpu.serving import ServeError

        class _BrokenOnce:
            def __init__(self, inner):
                self._inner = inner
                self.break_next = True

            def predict_versioned(self, features):
                outputs, version = self._inner.predict_versioned(features)
                if self.break_next:
                    self.break_next = False
                    # 0-d output: the per-request row split must blow up.
                    outputs = {"a_predicted": np.float32(0.0)}
                return outputs, version

            def __getattr__(self, name):
                return getattr(self._inner, name)

        broken = _BrokenOnce(predictor)
        with PolicyServer(broken, max_wait_ms=1).start(
            prewarm=False
        ) as server:
            bad = server.submit(_example(), deadline_ms=30000)
            with pytest.raises(ServeError, match="dispatch failed"):
                bad.result(30)
            # The dispatcher is still serving.
            good = server.call(_example(), timeout=30)
            assert good.outputs["a_predicted"].shape == (1,)
            assert server.snapshot()["counters"]["failed"] == 1

    def test_dispatcher_survives_predictor_exception_with_typed_error(
        self, predictor
    ):
        """A predictor RAISING mid-_execute_batch must fail that batch's
        futures with the typed PredictFailed (carrying the original
        exception class), record the failure class in the metrics, and
        keep the dispatch loop alive."""
        from tensor2robot_tpu.serving import PredictFailed

        class _RaisesOnce:
            def __init__(self, inner):
                self._inner = inner
                self.raise_next = True

            def predict_versioned(self, features):
                if self.raise_next:
                    self.raise_next = False
                    raise ConnectionResetError("backend fell over")
                return self._inner.predict_versioned(features)

            def __getattr__(self, name):
                return getattr(self._inner, name)

        flaky = _RaisesOnce(predictor)
        with PolicyServer(flaky, max_wait_ms=1).start(
            prewarm=False
        ) as server:
            bad = server.submit(_example(), deadline_ms=30000)
            with pytest.raises(PredictFailed, match="ConnectionResetError"):
                bad.result(30)
            assert bad.error().failure_class == "ConnectionResetError"
            # The loop survived; the next request serves normally.
            good = server.call(_example(), timeout=30)
            assert good.outputs["a_predicted"].shape == (1,)
            snap = server.snapshot()
            assert snap["counters"]["failed"] == 1
            assert snap["failed_by_class"] == {"ConnectionResetError": 1}

    def test_dispatcher_survives_predictor_timeout_with_typed_error(
        self, predictor
    ):
        """A predictor HANGING mid-_execute_batch must trip the compute
        watchdog: the batch fails with PredictTimeout, the failure class
        lands in the counters, and the dispatcher routes the next batch
        normally (the stuck call is abandoned on its daemon thread)."""
        from tensor2robot_tpu.serving import PredictTimeout

        class _HangsOnce:
            def __init__(self, inner):
                self._inner = inner
                self.hang_next = False
                self.unhang = threading.Event()

            def predict_versioned(self, features):
                if self.hang_next:
                    self.hang_next = False
                    assert self.unhang.wait(30), "test never released the hang"
                return self._inner.predict_versioned(features)

            def __getattr__(self, name):
                return getattr(self._inner, name)

        stuck = _HangsOnce(predictor)
        with PolicyServer(
            stuck, max_wait_ms=1, predict_timeout_ms=150
        ).start() as server:
            # start() prewarmed every bucket outside the watchdog (hang
            # still unarmed), so the 150ms budget below is measuring the
            # hang, not first-call compile on a loaded host.
            stuck.hang_next = True
            bad = server.submit(_example(), deadline_ms=30000)
            with pytest.raises(PredictTimeout, match="watchdog"):
                bad.result(30)
            # Release the abandoned thread so it doesn't outlive the test.
            stuck.unhang.set()
            good = server.call(_example(), timeout=30)
            assert good.outputs["a_predicted"].shape == (1,)
            snap = server.snapshot()
            assert snap["failed_by_class"] == {"PredictTimeout": 1}

    def test_snapshot_surfaces_restore_thread_leak(self, predictor):
        """The fleet health probe rides snapshot(): a predictor that
        leaked its restore thread at close() must be visible there, so
        the router can see the wounded replica."""
        with PolicyServer(predictor, max_wait_ms=1).start(
            prewarm=False
        ) as server:
            assert server.snapshot()["restore_thread_leaked"] is False
            predictor._inner._restore_thread_leaked = True
            assert server.snapshot()["restore_thread_leaked"] is True

    def test_future_done_callbacks_fire_on_both_paths(self, predictor):
        """add_done_callback must fire exactly once per future — on the
        completing thread for pending futures, immediately for already-
        completed ones (the replica loop's reply path depends on it)."""
        with PolicyServer(predictor, max_wait_ms=1).start(
            prewarm=False
        ) as server:
            seen = []
            future = server.submit(_example(), deadline_ms=30000)
            future.add_done_callback(lambda f: seen.append(f.request_id))
            future.result(30)
            # Already-done: callback runs synchronously at registration.
            future.add_done_callback(lambda f: seen.append(-f.request_id))
            assert seen == [future.request_id, -future.request_id]
            assert future.error() is None

    def test_stop_drains_queued_requests(self, predictor):
        server = PolicyServer(predictor, max_wait_ms=200).start()
        futures = [
            server.submit(_example(seed), deadline_ms=30000)
            for seed in range(3)
        ]
        server.stop(drain=True)
        for future in futures:
            assert future.result(1).outputs["a_predicted"].shape == (1,)


class _GatedPredictor(_RecordingPredictor):
    """Blocks inside the predict call until released — pins the
    dispatcher so backpressure tests can fill the queue
    deterministically."""

    def __init__(self, inner):
        super().__init__(inner)
        self.entered = threading.Event()
        self.release = threading.Event()

    def _gate(self):
        self.entered.set()
        assert self.release.wait(30), "gate never released"

    def predict(self, features):
        self._gate()
        return super().predict(features)

    def predict_versioned(self, features):
        self._gate()
        return super().predict_versioned(features)


class TestBackpressure:
    def _gated_server(self, export_root, overload):
        inner = ExportedSavedModelPredictor(export_dir=export_root)
        assert inner.restore()
        gated = _GatedPredictor(inner)
        server = PolicyServer(
            gated, batch_buckets=(1,), max_queue=2, max_wait_ms=0,
            overload=overload,
        )
        server.start(prewarm=False)
        # Pin the dispatcher inside compute, then fill the queue.
        first = server.submit(_example(), deadline_ms=30000)
        assert gated.entered.wait(10)
        queued = [
            server.submit(_example(seed), deadline_ms=30000)
            for seed in (1, 2)
        ]
        return server, gated, first, queued

    def test_reject_policy_refuses_newest(self, export_root):
        server, gated, first, queued = self._gated_server(
            export_root, "reject"
        )
        with pytest.raises(RequestRejected):
            server.submit(_example(9))
        assert server.snapshot()["counters"]["rejected"] == 1
        gated.release.set()
        for future in (first, *queued):
            assert future.result(30)
        server.stop()

    def test_expired_in_queue_dropped_at_formation_without_compute(
        self, export_root
    ):
        """Induced queue delay: requests whose deadlines pass while
        queued behind a pinned batch must be dropped typed at
        micro-batch formation (deadline_dropped) WITHOUT reaching the
        predictor or occupying batch slots — an expired entry would
        both burn compute and displace a live batchmate."""
        inner = ExportedSavedModelPredictor(export_dir=export_root)
        assert inner.restore()
        gated = _GatedPredictor(inner)
        server = PolicyServer(
            gated, batch_buckets=(1, 2, 4), max_queue=16, max_wait_ms=0
        )
        server.start(prewarm=False)
        first = server.submit(_example(), deadline_ms=30000)
        assert gated.entered.wait(10)
        # Two short-deadline requests expire while queued; a long-
        # deadline sibling queued BEHIND them must still be served in
        # the next batch (the corpses must not consume its slots).
        doomed = [
            server.submit(_example(seed), deadline_ms=80) for seed in (1, 2)
        ]
        live = server.submit(_example(3), deadline_ms=30000)
        time.sleep(0.25)
        gated.release.set()
        for future in doomed:
            with pytest.raises(DeadlineExceeded, match="batch formation"):
                future.result(30)
        assert first.result(30).outputs
        assert live.result(30).outputs
        snap = server.snapshot()
        assert snap["counters"]["deadline_dropped"] == 2
        assert snap["counters"]["completed"] == 2
        # The predictor served exactly two batches of one live request
        # each — the expired pair never reached compute.
        assert gated.batch_sizes == [1, 1]
        server.stop()

    def test_shed_oldest_policy_fails_oldest(self, export_root):
        server, gated, first, queued = self._gated_server(
            export_root, "shed_oldest"
        )
        newest = server.submit(_example(9), deadline_ms=30000)
        with pytest.raises(RequestShed):
            queued[0].result(5)  # oldest QUEUED request was shed
        assert server.snapshot()["counters"]["shed"] == 1
        gated.release.set()
        for future in (first, queued[1], newest):
            assert future.result(30)
        server.stop()


class TestHotSwap:
    def test_swap_under_load_no_failures(self, trained, export_root):
        compiled, state = trained
        inner = ExportedSavedModelPredictor(export_dir=export_root)
        assert inner.restore()
        predictor = _RecordingPredictor(inner)
        with PolicyServer(predictor, max_wait_ms=2).start() as server:
            v1 = predictor.model_version
            results = []
            errors = []
            stop = threading.Event()

            def client():
                while not stop.is_set():
                    try:
                        results.append(
                            server.call(_example(), timeout=30).model_version
                        )
                    except Exception as err:  # noqa: BLE001
                        errors.append(err)

            threads = [threading.Thread(target=client) for _ in range(3)]
            for thread in threads:
                thread.start()
            time.sleep(0.2)
            exporter = LatestExporter(
                name="latest", warmup_batch_sizes=BUCKETS
            )
            model_dir = export_root[: export_root.index("/export/")]
            exporter.maybe_export(
                step=2, state=state, eval_metrics={"loss": 0.5},
                compiled=compiled, model_dir=model_dir,
            )
            assert server.hot_swap(wait=True)
            v2 = predictor.model_version
            time.sleep(0.3)
            stop.set()
            for thread in threads:
                thread.join()
            assert not errors  # zero failed requests across the swap
            assert v2 > v1
            assert v2 in results  # new version actually served
            # The server installed its bucket prewarm on the predictor,
            # so the incoming version compiled BEFORE the swap landed.
            assert inner._restore_prewarm is not None
            # Bucket discipline holds across versions too.
            assert set(predictor.batch_sizes) <= set(BUCKETS)
            assert server.snapshot()["counters"]["hot_swaps"] == 1


class TestBuckets:
    def test_resolution_order(self, monkeypatch):
        assert resolve_buckets((4, 2, 2), {"warmup_batch_sizes": [8]}) == (2, 4)
        assert resolve_buckets(None, {"warmup_batch_sizes": [8, 1]}) == (1, 8)
        assert resolve_buckets(None, {}) == (1,)
        assert resolve_buckets(None, None) == (1,)
        monkeypatch.setenv("T2R_SERVE_BUCKETS", "16,2")
        assert resolve_buckets(None, {"warmup_batch_sizes": [8]}) == (2, 16)

    def test_metadata_parsing(self):
        assert buckets_from_metadata({}) is None
        assert buckets_from_metadata({"warmup_batch_sizes": []}) is None
        assert buckets_from_metadata({"warmup_batch_sizes": [4, 2]}) == (2, 4)
        with pytest.raises(ValueError, match="positive"):
            buckets_from_metadata({"warmup_batch_sizes": [0, 2]})

    def test_pick_bucket(self):
        assert pick_bucket((1, 2, 4), 1) == 1
        assert pick_bucket((1, 2, 4), 3) == 4
        with pytest.raises(ValueError, match="max bucket"):
            pick_bucket((1, 2, 4), 5)

    def test_pad_feature_batch(self):
        rows = [{"x": np.full((3,), float(i), np.float32)} for i in range(2)]
        padded = buckets_lib.pad_feature_batch(rows, 4)
        assert padded["x"].shape == (4, 3)
        np.testing.assert_array_equal(padded["x"][2], padded["x"][1])

    def test_serve_flags_declared(self):
        for name in (
            "T2R_SERVE_BUCKETS",
            "T2R_SERVE_DEADLINE_MS",
            "T2R_SERVE_MAX_QUEUE",
            "T2R_SERVE_MAX_WAIT_MS",
            "T2R_SERVE_OVERLOAD",
            "T2R_SERVE_QUANT",
        ):
            assert t2r_flags.get_flag(name).name == name


class TestWarmupRoundTrip:
    """The satellite contract: warmup_requests.tfrecord — the exact wire
    payloads server requests arrive as — must parse byte-identically
    through the SpecParser oracle and the fast wire parser, and validate
    against the artifact's packed spec."""

    def test_warmup_parses_identically_and_validates(self, trained, tmp_path):
        from tensor2robot_tpu.data.parser import SpecParser
        from tensor2robot_tpu.data.tfrecord import read_tfrecords
        from tensor2robot_tpu.data.wire import FastSpecParser
        from tensor2robot_tpu.specs import (
            flatten_spec_structure,
            validate_and_pack,
        )

        compiled, _ = trained
        generator = DefaultExportGenerator()
        generator.set_specification_from_model(compiled.model)
        path = generator.create_warmup_requests_numpy(
            batch_sizes=BUCKETS, export_dir=str(tmp_path)
        )
        records = list(read_tfrecords(path))
        assert len(records) == sum(BUCKETS)
        spec = generator.serving_input_spec()

        oracle = SpecParser(spec).parse_batch(records)
        fast_parser = FastSpecParser(spec)
        assert fast_parser.supported, fast_parser.unsupported_reason
        fast = fast_parser.parse_batch(records)

        oracle_flat = dict(flatten_spec_structure(oracle).items())
        fast_flat = dict(flatten_spec_structure(fast).items())
        assert set(oracle_flat) == set(fast_flat)
        for key in oracle_flat:
            assert oracle_flat[key].dtype == fast_flat[key].dtype
            np.testing.assert_array_equal(
                oracle_flat[key], fast_flat[key], err_msg=key
            )
            # Byte-identical, not merely value-equal.
            assert (
                oracle_flat[key].tobytes() == fast_flat[key].tobytes()
            ), key

        packed = validate_and_pack(spec, oracle, ignore_batch=True)
        assert "x" in packed

    def test_warmup_loads_by_bucket_from_export(self, export_root):
        """load_warmup_batches re-chunks the record stream by the
        published ladder — the server's prewarm path."""
        import json
        import os

        from tensor2robot_tpu.export.saved_model import latest_export_dir

        version_dir = latest_export_dir(export_root)
        with open(os.path.join(version_dir, "t2r_metadata.json")) as f:
            metadata = json.load(f)
        assert metadata["warmup_batch_sizes"] == list(BUCKETS)
        predictor = ExportedSavedModelPredictor(export_dir=export_root)
        assert predictor.restore()
        spec = predictor.get_feature_specification()
        batches = buckets_lib.load_warmup_batches(
            version_dir, spec, metadata
        )
        assert set(batches) == set(BUCKETS)
        for size, batch in batches.items():
            assert batch["x"].shape == (size, 3)


class TestServingLint:
    """The serve-blocking-predict rule: predict outside the dispatcher in
    serving/ is a build error; the shipped package is clean."""

    def test_shipped_serving_package_is_clean(self):
        from tensor2robot_tpu.analysis.lints import lint_paths

        diagnostics = lint_paths(
            ["tensor2robot_tpu/serving"],
            root=__import__("os").path.dirname(
                __import__("os").path.dirname(__file__)
            ),
        )
        assert diagnostics == []

    def test_blocking_predict_outside_dispatcher_is_flagged(self):
        from tensor2robot_tpu.analysis.lints import lint_source

        bad = (
            "def submit(self, features):\n"
            "    return self._predictor.predict(features)\n"
        )
        findings = lint_source(
            bad, path="tensor2robot_tpu/serving/server.py"
        )
        assert [f.rule for f in findings] == ["serve-blocking-predict"]

    def test_dispatcher_predict_is_allowed(self):
        from tensor2robot_tpu.analysis.lints import lint_source

        good = (
            "def _execute_batch(self, batch):\n"
            "    return self._predictor.predict(batch)\n"
            "def _prewarm(self, loaded, spec):\n"
            "    self._predictor.predict({})\n"
        )
        assert (
            lint_source(good, path="tensor2robot_tpu/serving/server.py")
            == []
        )

    def test_rule_scoped_to_serving_package(self):
        from tensor2robot_tpu.analysis.lints import lint_source

        outside = "def f(p):\n    return p.predict({})\n"
        assert (
            lint_source(outside, path="tensor2robot_tpu/policies.py") == []
        )
