"""Low-precision serving tests: blockwise quant payloads, export-time
calibration + parity gate, the T2R_SERVE_QUANT load path, and the
persistent serving compile cache.

The load-bearing contracts:

  * the quantized payload reuses the GRADIENT collectives' wire format
    (parallel/collectives.py BlockScaledCollective) — encode here must
    decode there and vice versa;
  * an export that fails its declared parity gate must not exist at all;
  * `T2R_SERVE_QUANT=none` is bit-exact to an export that never heard of
    quantization — same bytes on disk, same output bits;
  * the policy server serves quantized artifacts through the SAME bucket
    ladder with no fresh compiles and no client-visible changes.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu import flags as t2r_flags
from tensor2robot_tpu.export import serve_quant as sq
from tensor2robot_tpu.export.exporters import LatestExporter
from tensor2robot_tpu.export.saved_model import (
    ExportedModel,
    quant_payload_relpath,
)
from tensor2robot_tpu.parallel.collectives import get_collective
from tensor2robot_tpu.predictors import ExportedSavedModelPredictor
from tensor2robot_tpu.serving import PolicyServer
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


def _export(trained, model_dir, **kwargs):
    compiled, state = trained
    exporter = LatestExporter(
        name="latest", warmup_batch_sizes=BUCKETS, **kwargs
    )
    path = exporter.maybe_export(
        step=1, state=state, eval_metrics={"loss": 1.0},
        compiled=compiled, model_dir=str(model_dir),
    )
    return path, exporter.export_root(str(model_dir))


@pytest.fixture(scope="module")
def quant_export(trained, tmp_path_factory):
    """One export carrying fp16 + int8 regimes alongside the default."""
    return _export(
        trained,
        tmp_path_factory.mktemp("quant_export"),
        serve_quant=("fp16", "int8"),
    )


@pytest.fixture(scope="module")
def plain_export(trained, tmp_path_factory):
    return _export(trained, tmp_path_factory.mktemp("plain_export"))


# -- the payload codec ---------------------------------------------------------


class TestQuantizeTree:
    def test_roundtrip_error_bounded_by_block_step(self):
        rng = np.random.RandomState(0)
        kernel = (rng.randn(64, 96) * 0.3).astype(np.float32)
        tree = {"params": {"k": kernel}}
        for regime, levels in (("int8", 127.0), ("fp16", None)):
            payload, layout = sq.quantize_tree(tree, regime, block=128)
            deq = np.asarray(
                sq.dequantize_tree(payload, layout, regime)["params"]["k"]
            )
            if levels:
                # Blockwise max-abs scale: error <= scale/2 per block.
                flat = kernel.reshape(-1)
                blocks = flat.reshape(-1, 128)
                step = np.abs(blocks).max(axis=1) / levels
                err = np.abs(deq.reshape(-1).reshape(-1, 128) - blocks)
                assert np.all(err <= step[:, None] / 2 + 1e-7)
            else:
                np.testing.assert_allclose(deq, kernel, rtol=2e-3, atol=2e-3)

    def test_wire_format_is_the_gradient_collectives(self):
        """The payload decodes through BlockScaledCollective.decode
        directly — one codec, shared with the ZeRO-2 gradient exchange."""
        rng = np.random.RandomState(1)
        leaf = (rng.randn(4, 128) * 0.5).astype(np.float32)
        payload, layout = sq.quantize_tree({"k": leaf}, "int8", block=64)
        node = payload["k"]
        collective = get_collective("int8", 64)
        via_collective = np.asarray(
            collective.decode(
                {"q": jnp.asarray(node[sq.Q_KEY]),
                 "s": jnp.asarray(node[sq.S_KEY])}
            )
        )
        via_module = np.asarray(
            sq.dequantize_tree(payload, layout, "int8")["k"]
        ).reshape(-1)
        np.testing.assert_array_equal(via_collective, via_module)
        assert node[sq.Q_KEY].dtype == np.int8

    def test_small_leaves_get_leaf_sized_blocks_not_padding_bloat(self):
        bias = np.linspace(-1, 1, 100).astype(np.float32)
        payload, layout = sq.quantize_tree({"b": bias}, "int8", block=512)
        assert layout["b"]["block"] == 100  # not padded out to 512
        assert payload["b"][sq.Q_KEY].nbytes == 100

    def test_min_size_and_non_float_passthrough(self):
        tree = {"tiny": np.ones((4,), np.float32), "ids": np.arange(64)}
        payload, layout = sq.quantize_tree(tree, "int8", min_size=16)
        assert layout == {}
        np.testing.assert_array_equal(payload["tiny"], tree["tiny"])
        np.testing.assert_array_equal(payload["ids"], tree["ids"])

    def test_dequantize_traces_into_jit(self):
        kernel = np.random.RandomState(2).randn(32, 32).astype(np.float32)
        payload, layout = sq.quantize_tree({"k": kernel}, "fp16")

        @jax.jit
        def forward(p, x):
            return x @ sq.dequantize_tree(p, layout, "fp16")["k"]

        out = forward(payload, np.ones((1, 32), np.float32))
        assert np.all(np.isfinite(np.asarray(out)))

    def test_unknown_regime_rejected(self):
        with pytest.raises(ValueError, match="regime"):
            sq.quantize_tree({"k": np.ones((64,), np.float32)}, "fp8")

    def test_int8_payload_bytes_under_quarter_of_fp32(self):
        kernel = np.random.RandomState(3).randn(128, 128).astype(np.float32)
        payload, _ = sq.quantize_tree({"k": kernel}, "int8")
        counts = sq.payload_nbytes(payload)
        quant_bytes = counts["values"] + counts["scales"]
        assert kernel.nbytes / quant_bytes >= 3.5


class TestCalibration:
    def test_percentile_clip_ignores_outliers(self):
        x = np.zeros((10000,), np.float32)
        x[0] = 1000.0  # one rogue sample must not stretch the int8 step
        x[1:] = np.random.RandomState(0).uniform(-2, 2, 9999)
        calibration = sq.calibrate_activations([{"x": x}])
        assert calibration["x"] < 10.0

    def test_non_float_features_skipped(self):
        calibration = sq.calibrate_activations(
            [{"ids": np.arange(8), "x": np.ones((8,), np.float32)}]
        )
        assert set(calibration) == {"x"}

    def test_zero_feature_gets_usable_step(self):
        calibration = sq.calibrate_activations(
            [{"x": np.zeros((8,), np.float32)}]
        )
        assert calibration["x"] == 1.0

    def test_fake_quant_int8_quantizes_and_fp16_casts(self):
        calibration = {"x": 1.0}
        x = np.asarray([0.1234567, 0.9, -2.0], np.float32)
        q8 = np.asarray(
            sq.fake_quant_activations({"x": x}, calibration, "int8")["x"]
        )
        # Values land on the 1/127 grid, clipped to the calibration range.
        np.testing.assert_allclose(
            q8, np.round(np.clip(x, -1, 1) * 127) / 127, atol=1e-6
        )
        q16 = np.asarray(
            sq.fake_quant_activations({"x": x}, calibration, "fp16")["x"]
        )
        np.testing.assert_array_equal(q16, x.astype(np.float16).astype(np.float32))

    def test_empty_corpus_rejected(self):
        with pytest.raises(ValueError, match="warmup"):
            sq.calibrate_activations([])


# -- the export-time parity gate -----------------------------------------------


class TestParityGate:
    def test_check_parity_raises_with_offending_keys(self):
        with pytest.raises(sq.QuantParityError, match="q_predicted=0.5"):
            sq.check_parity("int8", {"q_predicted": 0.5, "ok": 0.0}, 0.1)

    def test_failing_gate_aborts_export_writing_nothing(
        self, trained, tmp_path
    ):
        compiled, state = trained
        exporter = LatestExporter(
            name="latest",
            warmup_batch_sizes=BUCKETS,
            serve_quant=("int8",),
            quant_parity_tol={"int8": 1e-12},  # unmeetably tight
        )
        with pytest.raises(sq.QuantParityError, match="parity gate FAILED"):
            exporter.maybe_export(
                step=1, state=state, eval_metrics={"loss": 1.0},
                compiled=compiled, model_dir=str(tmp_path),
            )
        root = exporter.export_root(str(tmp_path))
        # Loud failure means NO artifact — not even a temp dir.
        assert not os.path.isdir(root) or not os.listdir(root)

    def test_measured_parity_recorded_in_metadata(self, quant_export):
        path, _ = quant_export
        with open(os.path.join(path, "t2r_metadata.json")) as f:
            meta = json.load(f)
        quant = meta["serve_quant"]
        assert quant["regimes"] == ["fp16", "int8"]
        for regime in ("fp16", "int8"):
            parity = quant["parity"][regime]
            assert parity["max_divergence"]["a_predicted"] <= parity["tolerance"]
            assert quant["block"][regime] >= 1
            assert "x" in quant["calibration"][regime]
            assert quant["payload_bytes"][regime]["values"] > 0
            assert quant["stablehlo"][regime] is True

    def test_config_time_validation(self):
        with pytest.raises(ValueError, match="warmup"):
            LatestExporter(name="q", serve_quant=("int8",))
        with pytest.raises(ValueError, match="regimes"):
            LatestExporter(
                name="q", warmup_batch_sizes=(1,), serve_quant=("int4",)
            )
        with pytest.raises(ValueError, match="fp32 forward"):
            LatestExporter(
                name="q", warmup_batch_sizes=(1,), serve_quant=("int8",),
                quantize_weights=True,
            )
        # Quant payloads without serving programs could never be served:
        # the incompatibility must fail at config time, not fleet-wide
        # at the first T2R_SERVE_QUANT restore.
        with pytest.raises(ValueError, match="serialize_stablehlo"):
            LatestExporter(
                name="q", warmup_batch_sizes=(1,), serve_quant=("int8",),
                serialize_stablehlo=False,
            )

    def test_nan_divergence_fails_the_gate(self):
        """A quantized forward that emits NaN must never pass: max(0.0,
        nan) is 0.0 in Python, so an unguarded reduce would record
        PERFECT parity for a NaN-serving artifact."""
        divergence = sq.measure_parity(
            [{"q": np.zeros((2,), np.float32)}],
            [{"q": np.asarray([np.nan, 0.0], np.float32)}],
        )
        assert divergence["q"] == float("inf")
        with pytest.raises(sq.QuantParityError):
            sq.check_parity("int8", divergence, 1e9)


# -- artifact sizes ------------------------------------------------------------


class TestArtifactBytes:
    def test_int8_payload_at_least_3_5x_under_fp32_on_disk(
        self, quant_export
    ):
        path, _ = quant_export
        fp32 = os.path.getsize(os.path.join(path, "variables.msgpack"))
        int8 = os.path.getsize(os.path.join(path, quant_payload_relpath("int8")))
        fp16 = os.path.getsize(os.path.join(path, quant_payload_relpath("fp16")))
        assert fp32 / int8 >= 3.5
        assert fp32 / fp16 >= 1.8

    def test_quant_stablehlo_carries_no_weight_constants(self, quant_export):
        path, _ = quant_export
        default = os.path.getsize(
            os.path.join(path, "stablehlo", "predict_fn.bin")
        )
        int8 = os.path.getsize(
            os.path.join(path, "stablehlo", "predict_fn_int8.bin")
        )
        # The default artifact embeds the full fp32 weights; the quant
        # program takes its payload as arguments.
        assert int8 < 0.5 * default


# -- the load path -------------------------------------------------------------


class TestLoadRegimes:
    def test_none_is_bit_exact_to_a_plain_export(
        self, quant_export, plain_export
    ):
        qpath, _ = quant_export
        ppath, _ = plain_export
        # Same weights -> byte-identical variables file.
        with open(os.path.join(qpath, "variables.msgpack"), "rb") as f:
            qbytes = f.read()
        with open(os.path.join(ppath, "variables.msgpack"), "rb") as f:
            pbytes = f.read()
        assert qbytes == pbytes
        # ...and bit-identical outputs through regime 'none'.
        x = np.random.RandomState(0).uniform(-1, 1, (4, 3)).astype(np.float32)
        out_q = ExportedModel(qpath, quant_regime="none").predict({"x": x})
        out_p = ExportedModel(ppath, quant_regime="none").predict({"x": x})
        np.testing.assert_array_equal(
            out_q["a_predicted"], out_p["a_predicted"]
        )

    def test_regimes_serve_within_their_recorded_parity(self, quant_export):
        path, _ = quant_export
        with open(os.path.join(path, "t2r_metadata.json")) as f:
            tolerances = {
                regime: entry["tolerance"]
                for regime, entry in json.load(f)["serve_quant"][
                    "parity"
                ].items()
            }
        x = np.random.RandomState(1).uniform(-1, 1, (2, 3)).astype(np.float32)
        ref = ExportedModel(path, quant_regime="none").predict({"x": x})
        for regime in ("fp16", "int8"):
            out = ExportedModel(path, quant_regime=regime).predict({"x": x})
            diff = np.max(np.abs(out["a_predicted"] - ref["a_predicted"]))
            assert diff <= tolerances[regime]
            # ...and really served the quantized path, not fp32.
            assert diff > 0 or regime == "fp16"

    def test_missing_regime_fails_loudly(self, plain_export):
        path, _ = plain_export
        with pytest.raises(ValueError, match="T2R_SERVE_QUANT=int8"):
            ExportedModel(path, quant_regime="int8")

    def test_model_code_predictor_refuses_quant_regime(
        self, quant_export, monkeypatch
    ):
        """SavedModelCodePredictor rebuilds an fp32 forward from model
        code — under a quant regime that would be silent full-precision
        serving, so restore must fail loudly instead."""
        from tensor2robot_tpu.predictors.saved_model_v2_predictor import (
            SavedModelCodePredictor,
        )
        from tensor2robot_tpu.utils.mocks import MockT2RModel

        _, root = quant_export
        monkeypatch.setenv("T2R_SERVE_QUANT", "int8")
        predictor = SavedModelCodePredictor(
            root, t2r_model=MockT2RModel(device_type="cpu")
        )
        with pytest.raises(ValueError, match="cannot honor quant regime"):
            predictor.restore()

    def test_predictor_resolves_regime_from_flag(
        self, quant_export, monkeypatch
    ):
        _, root = quant_export
        monkeypatch.setenv("T2R_SERVE_QUANT", "int8")
        predictor = ExportedSavedModelPredictor(export_dir=root)
        assert predictor.restore()
        assert predictor.quant_regime == "int8"
        assert predictor.loaded_model.quant_regime == "int8"
        out = predictor.predict(
            {"x": np.zeros((1, 3), np.float32)}
        )
        assert np.all(np.isfinite(out["a_predicted"]))

    def test_flag_declared(self):
        assert t2r_flags.get_enum("T2R_SERVE_QUANT") == "none"
        spec = t2r_flags.get_flag("T2R_SERVE_QUANT")
        assert spec.choices == (
            "none", "fp16", "int8", "fp8_e4m3", "fp8_e5m2"
        )
        assert t2r_flags.get_str("T2R_SERVE_NATIVE_LAYERS") is None


# -- exporter -> predictor -> server round trip --------------------------------


class _RecordingPredictor:
    """Wraps the real predictor recording every served batch size — the
    no-fresh-compile contract is 'every served shape is a warmup
    bucket' (mirrors tests/test_serving.py)."""

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


class TestServerRoundTrip:
    @pytest.mark.parametrize("regime", ["none", "fp16", "int8"])
    def test_every_bucket_serves_quantized_with_no_novel_shapes(
        self, quant_export, monkeypatch, regime
    ):
        _, root = quant_export
        monkeypatch.setenv("T2R_SERVE_QUANT", regime)
        inner = ExportedSavedModelPredictor(export_dir=root)
        assert inner.restore()
        predictor = _RecordingPredictor(inner)
        with PolicyServer(predictor, max_wait_ms=60).start() as server:
            assert server.buckets == BUCKETS
            assert server.snapshot()["serve_quant"] == regime
            predictor.batch_sizes.clear()  # drop prewarm
            # Drive each bucket: 1, 2, and 3->padded-to-4 concurrent rows.
            for group in (1, 2, 3):
                futures = [
                    server.submit(
                        {"x": np.full((3,), 0.1 * (i + 1), np.float32)},
                        deadline_ms=30000,
                    )
                    for i in range(group)
                ]
                responses = [f.result(30) for f in futures]
                for response in responses:
                    assert np.all(np.isfinite(response.outputs["a_predicted"]))
        assert set(predictor.batch_sizes) <= set(BUCKETS)

    def test_server_outputs_match_direct_quant_predict(
        self, quant_export, monkeypatch
    ):
        path, root = quant_export
        monkeypatch.setenv("T2R_SERVE_QUANT", "int8")
        predictor = ExportedSavedModelPredictor(export_dir=root)
        assert predictor.restore()
        row = {"x": np.asarray([0.3, -0.2, 0.9], np.float32)}
        with PolicyServer(predictor, max_wait_ms=1).start() as server:
            served = server.call(row, timeout=30).outputs["a_predicted"]
        direct = ExportedModel(path, quant_regime="int8").predict(
            {"x": row["x"][None, :]}
        )["a_predicted"][0]
        np.testing.assert_allclose(served, direct, rtol=1e-6, atol=1e-6)

    def test_float64_client_coerced_under_quant(
        self, quant_export, monkeypatch
    ):
        """A plain-Python-list client (float64) must be coerced at
        admission even when the serving path is quantized — the dtype
        contract is the spec's, regardless of regime."""
        _, root = quant_export
        monkeypatch.setenv("T2R_SERVE_QUANT", "int8")
        predictor = ExportedSavedModelPredictor(export_dir=root)
        assert predictor.restore()
        with PolicyServer(predictor, max_wait_ms=1).start() as server:
            response = server.call({"x": [0.1, 0.2, 0.3]}, timeout=30)
            assert response.outputs["a_predicted"].shape == (1,)
            assert np.all(np.isfinite(response.outputs["a_predicted"]))

    def test_hot_swap_keeps_regime(self, trained, tmp_path, monkeypatch):
        compiled, state = trained
        monkeypatch.setenv("T2R_SERVE_QUANT", "fp16")
        exporter = LatestExporter(
            name="latest", warmup_batch_sizes=(1, 2),
            serve_quant=("fp16",),
        )
        exporter.maybe_export(
            step=1, state=state, eval_metrics={"loss": 1.0},
            compiled=compiled, model_dir=str(tmp_path),
        )
        root = exporter.export_root(str(tmp_path))
        predictor = ExportedSavedModelPredictor(export_dir=root)
        assert predictor.restore()
        v1 = predictor.model_version
        with PolicyServer(predictor, max_wait_ms=1).start() as server:
            exporter.maybe_export(
                step=2, state=state, eval_metrics={"loss": 0.9},
                compiled=compiled, model_dir=str(tmp_path),
            )
            assert server.hot_swap(wait=True)
            response = server.call(
                {"x": np.zeros((3,), np.float32)}, timeout=30
            )
        assert response.model_version > v1
        assert predictor.quant_regime == "fp16"


# -- persistent serving compile cache ------------------------------------------


class TestCompileCache:
    @pytest.fixture(autouse=True)
    def _restore_jax_cache_config(self):
        """These tests place a cache in the GLOBAL jax config; leaking
        a pytest tmp dir as the cache dir (plus min-compile-time 0) into
        the rest of the suite means every later compile writes cache
        entries to a doomed path. Restore the config and drop the
        latched cache state after each test."""
        import jax
        from jax._src import compilation_cache

        previous_dir = jax.config.jax_compilation_cache_dir
        previous_min = jax.config.jax_persistent_cache_min_compile_time_secs
        yield
        jax.config.update("jax_compilation_cache_dir", previous_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", previous_min
        )
        compilation_cache.reset_cache()

    @staticmethod
    def _place_cache(path) -> None:
        jax.config.update("jax_compilation_cache_dir", str(path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    def test_library_engages_only_a_placed_cache(self, tmp_path):
        from tensor2robot_tpu.utils.compile_cache import (
            engage_compile_cache,
        )

        jax.config.update("jax_compilation_cache_dir", None)
        assert engage_compile_cache() is None  # nothing placed = no-op
        assert jax.config.jax_compilation_cache_dir is None
        self._place_cache(tmp_path)
        assert engage_compile_cache() == str(tmp_path)

    # ~14s (two full server boots) on 1 cpu: slow slice; the cache
    # enable/scope pins above and the AOT restore-ladder tests keep
    # the warm-boot contract fast.
    @pytest.mark.slow
    def test_second_server_boot_hits_the_cache(
        self, quant_export, tmp_path, monkeypatch
    ):
        """Boot a policy server (prewarm compiles every bucket) with the
        persistent cache on; clear jax's in-memory executable caches
        (what a process restart discards); boot a second server over the
        same export. The second boot must add NO new cache entries —
        every compile was served from disk — and still serve correctly.

        AOT restore is forced OFF: this test pins the CACHE tier of the
        restore ladder, and an AOT-hit boot never compiles at all (so it
        would write no cache entries — tests/test_aot.py covers that
        tier).
        """
        _, root = quant_export
        monkeypatch.setenv("T2R_SERVE_AOT", "0")
        self._place_cache(tmp_path)

        def boot_and_serve():
            predictor = ExportedSavedModelPredictor(export_dir=root)
            assert predictor.restore()
            with PolicyServer(predictor, max_wait_ms=1).start() as server:
                response = server.call(
                    {"x": np.zeros((3,), np.float32)}, timeout=30
                )
            return response.outputs["a_predicted"]

        # Earlier tests in this process may have compiled these shapes
        # already; drop the in-memory executables so the first boot
        # really compiles (and therefore really writes cache entries).
        jax.clear_caches()
        first = boot_and_serve()
        entries_after_first = set(os.listdir(str(tmp_path)))
        assert entries_after_first, "first boot wrote no cache entries"
        jax.clear_caches()
        second = boot_and_serve()
        entries_after_second = set(os.listdir(str(tmp_path)))
        assert entries_after_second == entries_after_first, (
            "second boot recompiled: new persistent-cache entries "
            f"{entries_after_second - entries_after_first}"
        )
        np.testing.assert_array_equal(first, second)

    def test_restore_path_engages_cache_before_first_compile(
        self, monkeypatch, tmp_path
    ):
        """Cache engagement moved from the replica factory into the
        predictor's restore path (enable_compile_cache_for): it still
        runs BEFORE the incoming version's first compile, but is skipped
        per swap when AOT executables cover every warmup bucket (that
        version never compiles). Source-level pin on the restore path,
        behavioral pin on the skip condition."""
        import inspect

        from tensor2robot_tpu.predictors import exported_savedmodel_predictor
        from tensor2robot_tpu.serving.compile_cache import (
            enable_compile_cache_for,
        )

        source = inspect.getsource(
            exported_savedmodel_predictor.ExportedSavedModelPredictor
            ._restore_sync
        )
        assert "enable_compile_cache_for" in source

        class _Loaded:
            aot_covered = True
            aot_executables = {1: object(), 2: object()}
            metadata = {"warmup_batch_sizes": [1, 2]}

        # AOT covers the resolved ladder -> the cache round-trip is
        # skipped even though a directory is placed.
        self._place_cache(tmp_path)
        monkeypatch.delenv("T2R_SERVE_BUCKETS", raising=False)
        assert enable_compile_cache_for(_Loaded()) is None


# -- native low-precision compute (round 16) -----------------------------------


@pytest.fixture(scope="module")
def native_export(trained, tmp_path_factory):
    """One export carrying every native-compute regime alongside the
    default artifact (MockT2RModel: Dense_0 is a 3-row kernel — too
    shallow for native eligibility — so the payload is genuinely MIXED
    granularity and the audit shows both native and f32 contractions)."""
    return _export(
        trained,
        tmp_path_factory.mktemp("native_export"),
        serve_quant=("int8", "fp8_e4m3", "fp8_e5m2"),
    )


NATIVE_REGIMES = ("int8", "fp8_e4m3", "fp8_e5m2")


def _mlp_tree(seed=0, din=64, dh=96):
    rng = np.random.RandomState(seed)
    return {
        "params": {
            "Dense_0": {
                "kernel": (rng.randn(din, dh) * 0.3).astype(np.float32),
                "bias": (rng.randn(dh) * 0.1).astype(np.float32),
            },
            "Dense_1": {
                "kernel": (rng.randn(dh, 4) * 0.3).astype(np.float32),
                "bias": (rng.randn(4) * 0.1).astype(np.float32),
            },
        }
    }


class TestNativeEligibility:
    def test_default_map_takes_deep_dense_and_conv_kernels(self):
        tree = {
            "params": {
                "deep": {"kernel": np.ones((64, 32), np.float32)},
                "shallow": {"kernel": np.ones((3, 128), np.float32)},
                # Conv kernels joined the map in round 18: contraction
                # depth = window x input channels (3*3*8 = 72 here).
                "conv": {"kernel": np.ones((3, 3, 8, 8), np.float32)},
                # ...but a shallow conv window stays blockwise exactly
                # like a shallow dense kernel (1*1*2 = 2 rows).
                "conv1x1": {"kernel": np.ones((1, 1, 2, 64), np.float32)},
                "deep2": {"bias": np.ones((64,), np.float32)},
            }
        }
        eligible = sq.default_native_eligibility(tree, "int8")
        assert eligible == ("params/conv/kernel", "params/deep/kernel")
        # fp16 is a cast regime: no native leg at all.
        assert sq.default_native_eligibility(tree, "fp16") == ()

    def test_override_flag_none_and_globs(self, monkeypatch):
        tree = {
            "params": {
                "a": {"kernel": np.ones((64, 32), np.float32)},
                "b": {"kernel": np.ones((64, 32), np.float32)},
            }
        }
        monkeypatch.setenv("T2R_SERVE_NATIVE_LAYERS", "none")
        assert sq.resolve_native_eligibility(tree, "int8") == ()
        monkeypatch.setenv("T2R_SERVE_NATIVE_LAYERS", "auto")
        assert len(sq.resolve_native_eligibility(tree, "int8")) == 2
        monkeypatch.setenv("T2R_SERVE_NATIVE_LAYERS", "params/a/*")
        assert sq.resolve_native_eligibility(tree, "int8") == (
            "params/a/kernel",
        )
        # A glob can only DEMOTE among structural candidates, never
        # promote an ineligible leaf.
        monkeypatch.setenv("T2R_SERVE_NATIVE_LAYERS", "params/*/bias")
        assert sq.resolve_native_eligibility(tree, "int8") == ()

    def test_quantize_tree_validates_native_paths(self):
        tree = {"params": {"d": {"kernel": np.ones((64, 8), np.float32)}}}
        with pytest.raises(ValueError, match="not found"):
            sq.quantize_tree(tree, "int8", native=("params/missing/kernel",))
        bad = {"params": {"d": {"kernel": np.ones((64,), np.float32)}}}
        with pytest.raises(ValueError, match="2-D"):
            sq.quantize_tree(bad, "int8", native=("params/d/kernel",))
        with pytest.raises(ValueError, match="native dot lowering"):
            sq.quantize_tree(tree, "fp16", native=("params/d/kernel",))

    def test_regime_error_names_the_flag(self):
        with pytest.raises(ValueError, match="T2R_SERVE_QUANT"):
            sq.quantize_tree({}, "int4")


class TestChannelPayload:
    @pytest.mark.parametrize("regime", NATIVE_REGIMES)
    def test_channel_nodes_keep_shape_and_storage_dtype(self, regime):
        tree = _mlp_tree()
        native = sq.default_native_eligibility(tree, regime)
        assert native == (
            "params/Dense_0/kernel", "params/Dense_1/kernel",
        )
        payload, layout = sq.quantize_tree(tree, regime, native=native)
        node = payload["params"]["Dense_0"]["kernel"]
        kernel = tree["params"]["Dense_0"]["kernel"]
        assert node[sq.Q_KEY].shape == kernel.shape  # NOT raveled
        assert node[sq.Q_KEY].dtype.itemsize == 1
        assert node[sq.S_KEY].shape == (kernel.shape[1],)  # per channel
        assert layout["params/Dense_0/kernel"]["granularity"] == "channel"
        assert layout["params/Dense_0/bias"]["granularity"] == "block"
        # Channel dequant reconstructs within the format's step.
        deq = np.asarray(
            sq.dequantize_tree(payload, layout, regime)["params"]["Dense_0"][
                "kernel"
            ]
        )
        col_max = np.abs(kernel).max(axis=0)
        step = {
            "int8": col_max / 127.0,
            "fp8_e4m3": col_max * 2.0 ** -3,
            "fp8_e5m2": col_max * 2.0 ** -2,
        }[regime]
        assert (np.abs(deq - kernel) <= step[None, :] * 0.5 * 1.01).all()

    @pytest.mark.parametrize("regime", NATIVE_REGIMES)
    def test_native_dot_matches_dequant_reference(self, regime):
        """native_dot (quantized operands, scales on the accumulator) vs
        the dequantize-then-f32-matmul reference over the SAME payload:
        the only extra error is the per-row activation quantization."""
        tree = _mlp_tree(seed=3)
        kernel = tree["params"]["Dense_0"]["kernel"]
        payload, layout = sq.quantize_tree(
            tree, regime, native=("params/Dense_0/kernel",)
        )
        node = payload["params"]["Dense_0"]["kernel"]
        x = np.random.RandomState(4).uniform(-2, 2, (8, 64)).astype(
            np.float32
        )
        native = np.asarray(
            sq.native_dot(
                jnp.asarray(x),
                jnp.asarray(node[sq.Q_KEY]),
                jnp.asarray(node[sq.S_KEY]),
                regime,
            )
        )
        deq = np.asarray(
            sq.dequantize_tree(payload, layout, regime)["params"]["Dense_0"][
                "kernel"
            ]
        )
        reference = x @ deq
        # Activation rounding: half a step per element, depth-64 dot.
        act_step = {"int8": 1 / 127.0, "fp8_e4m3": 2.0 ** -3,
                    "fp8_e5m2": 2.0 ** -2}[regime]
        bound = (
            0.5 * act_step * np.abs(x).max(axis=-1, keepdims=True)
            * np.abs(deq).sum(axis=0)[None, :]
        )
        assert (np.abs(native - reference) <= bound + 1e-5).all()

    def test_zero_row_is_safe(self):
        """An all-zero activation row (bucket padding) must not divide
        by zero or emit NaN through the dynamic per-row scale."""
        tree = _mlp_tree()
        payload, _ = sq.quantize_tree(
            tree, "int8", native=("params/Dense_0/kernel",)
        )
        node = payload["params"]["Dense_0"]["kernel"]
        out = np.asarray(
            sq.native_dot(
                jnp.zeros((2, 64)), jnp.asarray(node[sq.Q_KEY]),
                jnp.asarray(node[sq.S_KEY]), "int8",
            )
        )
        np.testing.assert_array_equal(out, np.zeros_like(out))


class TestNativeLoweringInterception:
    @pytest.mark.parametrize("regime", NATIVE_REGIMES)
    def test_intercepts_eligible_dense_only(self, regime):
        import flax.linen as nn

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x):
                x = nn.relu(nn.Dense(96)(x))
                return nn.Dense(4)(x)

        tree = _mlp_tree(seed=5)
        # Only Dense_0 native; Dense_1 stays on the dequant path.
        payload, layout = sq.quantize_tree(
            tree, regime, native=("params/Dense_0/kernel",)
        )
        bound = sq.dequantize_tree(payload, layout, regime)
        net = Net()
        x = np.random.RandomState(6).uniform(-1, 1, (4, 64)).astype(
            np.float32
        )
        plain = np.asarray(net.apply({"params": bound["params"]}, x))
        with sq.native_lowering(payload, layout, regime, bound):
            lowered = np.asarray(net.apply({"params": bound["params"]}, x))
        # The native path genuinely diverges from the dequant matmul
        # (activation quantization) but stays within the regime's step.
        assert np.abs(lowered - plain).max() > 0
        assert np.abs(lowered - plain).max() < 0.5
        # Outside the context the plain path is untouched.
        again = np.asarray(net.apply({"params": bound["params"]}, x))
        np.testing.assert_array_equal(again, plain)

    def test_empty_eligibility_is_identity(self):
        tree = _mlp_tree(seed=7)
        payload, layout = sq.quantize_tree(tree, "int8", native=())
        bound = sq.dequantize_tree(payload, layout, "int8")
        import flax.linen as nn

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x):
                return nn.Dense(96)(x)

        net = Net()
        x = np.ones((2, 64), np.float32)
        plain = np.asarray(net.apply({"params": bound["params"]}, x))
        with sq.native_lowering(payload, layout, "int8", bound):
            lowered = np.asarray(net.apply({"params": bound["params"]}, x))
        np.testing.assert_array_equal(lowered, plain)


class TestNativeExport:
    def test_metadata_records_native_contract(self, native_export):
        path, _ = native_export
        with open(os.path.join(path, "t2r_metadata.json")) as f:
            quant = json.load(f)["serve_quant"]
        assert quant["regimes"] == sorted(NATIVE_REGIMES)
        for regime in NATIVE_REGIMES:
            native = quant["native"][regime]
            assert native["demoted"] is False
            # Dense_0 (3 rows) is too shallow; the deep kernels lower.
            assert native["layers"] == [
                "params/Dense_1/kernel", "params/Dense_2/kernel",
            ]
            granularity = quant["granularity"][regime]
            assert granularity["channel"] == 2
            assert granularity["block"] > 0  # biases, batch stats, Dense_0
            parity = quant["parity"][regime]
            assert max(
                parity["max_divergence"].values()
            ) <= parity["tolerance"]

    @pytest.mark.parametrize("regime", NATIVE_REGIMES)
    def test_artifact_program_audit_proves_native_dots(
        self, native_export, regime
    ):
        """The acceptance check: the SERIALIZED serving program carries
        >= 1 contraction on int8/fp8 operands — the matmuls stayed
        low-precision in the compiled artifact, not dequant-then-f32."""
        path, _ = native_export
        with open(
            os.path.join(path, "stablehlo", f"predict_fn_{regime}.bin"), "rb"
        ) as f:
            audit = sq.audit_dot_dtypes(f.read())
        native_key = {"int8": "i8", "fp8_e4m3": "f8e4m3",
                      "fp8_e5m2": "f8e5m2"}[regime]
        assert audit.get(native_key, 0) >= 1, audit
        # The shallow Dense_0 stays on the dequant path: mixed audit.
        assert audit.get("f32", 0) >= 1, audit
        # ...and the export recorded the same audit in its metadata.
        with open(os.path.join(path, "t2r_metadata.json")) as f:
            recorded = json.load(f)["serve_quant"]["dot_audit"][regime]
        assert recorded == audit

    def test_dequant_only_regime_audits_all_f32(self, quant_export):
        """The pre-round-16 regimes (and any demoted map) show ZERO
        low-precision contractions — the audit genuinely discriminates."""
        path, _ = quant_export
        with open(
            os.path.join(path, "stablehlo", "predict_fn_fp16.bin"), "rb"
        ) as f:
            audit = sq.audit_dot_dtypes(f.read())
        assert audit.get("i8", 0) == 0
        assert audit.get("f32", 0) >= 1

    @pytest.mark.parametrize("regime", NATIVE_REGIMES)
    def test_native_regimes_serve_within_recorded_parity(
        self, native_export, regime
    ):
        path, _ = native_export
        with open(os.path.join(path, "t2r_metadata.json")) as f:
            tolerance = json.load(f)["serve_quant"]["parity"][regime][
                "tolerance"
            ]
        x = np.random.RandomState(2).uniform(-1, 1, (4, 3)).astype(
            np.float32
        )
        ref = ExportedModel(path, quant_regime="none").predict({"x": x})
        out = ExportedModel(path, quant_regime=regime).predict({"x": x})
        diff = np.max(np.abs(out["a_predicted"] - ref["a_predicted"]))
        assert 0 < diff <= tolerance

    def test_server_snapshot_carries_native_layers(
        self, native_export, monkeypatch
    ):
        _, root = native_export
        monkeypatch.setenv("T2R_SERVE_QUANT", "int8")
        predictor = ExportedSavedModelPredictor(export_dir=root)
        assert predictor.restore()
        assert predictor.native_dot_layers == (
            "params/Dense_1/kernel", "params/Dense_2/kernel",
        )
        with PolicyServer(predictor, max_wait_ms=1).start() as server:
            snap = server.snapshot()
        assert snap["serve_quant"] == "int8"
        assert snap["serve_quant_native_layers"] == [
            "params/Dense_1/kernel", "params/Dense_2/kernel",
        ]

    def test_override_flag_exports_dequant_only(
        self, trained, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("T2R_SERVE_NATIVE_LAYERS", "none")
        path, _ = _export(trained, tmp_path, serve_quant=("int8",))
        with open(os.path.join(path, "t2r_metadata.json")) as f:
            quant = json.load(f)["serve_quant"]
        assert quant["native"]["int8"]["layers"] == []
        assert quant["granularity"]["int8"]["channel"] == 0
        audit = quant["dot_audit"]["int8"]
        assert audit.get("i8", 0) == 0


class TestNativeDemotion:
    def _stub(self, outputs):
        def fn(payload, batch):
            return dict(outputs)

        fn.quant_payload = {}
        fn.quant_native = ("params/d/kernel",)
        return fn

    def test_failing_native_fn_demotes_to_dequant(self):
        from tensor2robot_tpu.export.exporters import _native_pre_gate

        batches = [{"x": np.zeros((1,), np.float32)}]
        fp32 = [{"q": np.zeros((2,), np.float32)}]
        bad = self._stub({"q": np.full((2,), 0.9, np.float32)})
        good = self._stub({"q": np.full((2,), 0.01, np.float32)})
        good.quant_native = ()
        fn, demoted = _native_pre_gate(
            bad, lambda: good, fp32, batches, tolerance=0.1
        )
        assert demoted
        assert fn is good
        assert fn.quant_native_demoted is True

    def test_passing_native_fn_rides_untouched(self):
        from tensor2robot_tpu.export.exporters import _native_pre_gate

        batches = [{"x": np.zeros((1,), np.float32)}]
        fp32 = [{"q": np.zeros((2,), np.float32)}]
        ok = self._stub({"q": np.full((2,), 0.05, np.float32)})
        fn, demoted = _native_pre_gate(
            ok, lambda: pytest.fail("must not rebuild"),
            fp32, batches, tolerance=0.1,
        )
        assert not demoted
        assert fn is ok
        assert not getattr(fn, "quant_native_demoted", False)

    def test_nan_native_forward_demotes(self):
        """A NaN-emitting native lowering must demote (and the final
        gate still guards the demoted path) — the measure_parity NaN
        guard rides into the triage."""
        from tensor2robot_tpu.export.exporters import _native_pre_gate

        batches = [{"x": np.zeros((1,), np.float32)}]
        fp32 = [{"q": np.zeros((2,), np.float32)}]
        nan_fn = self._stub(
            {"q": np.asarray([np.nan, 0.0], np.float32)}
        )
        good = self._stub({"q": np.zeros((2,), np.float32)})
        fn, demoted = _native_pre_gate(
            nan_fn, lambda: good, fp32, batches, tolerance=1e9
        )
        assert demoted and fn is good


class TestGateMeasuresTheNativePath:
    def test_eager_gate_call_runs_the_interceptor_not_a_stale_jit_cache(
        self, trained
    ):
        """Regression: the export parity gates call the quant serving fn
        EAGERLY, and the fp32 baseline always trains the jitted
        predict_step's executable cache first with identical avals — if
        the quant fn routed through that jit, the eager call would
        execute the cached no-interception program (gate measures the
        dequant path, artifact serves the native one). Pin: the eager
        native output must differ from the dequant-matmul twin computed
        over the SAME per-channel payload."""
        from tensor2robot_tpu.export.export_generators import (
            DefaultExportGenerator,
        )
        from tensor2robot_tpu.specs import TensorSpecStruct

        compiled, state = trained
        generator = DefaultExportGenerator()
        generator.set_specification_from_model(compiled.model)
        variables = state.export_variables()
        batch = {
            "x": np.random.RandomState(0)
            .uniform(-1, 1, (4, 3))
            .astype(np.float32)
        }
        # Train the jit cache exactly like save_exported_model does.
        serving_fn = generator.create_serving_fn(compiled, variables)
        serving_fn(batch)
        fn = generator.create_quant_serving_fn(
            compiled, variables, regime="int8", calibration={}
        )
        assert fn.quant_native  # the native map is live
        eager = np.asarray(
            fn(fn.quant_payload, batch)["a_predicted"]
        )
        # The dequant twin: same payload, same pre/post-processing,
        # matmuls on the channel-dequantized f32 kernels — what a stale
        # cache would silently compute.
        bound = sq.dequantize_tree(fn.quant_payload, fn.quant_layout, "int8")
        features = TensorSpecStruct(dict(batch))
        features, _ = generator._preprocessor.preprocess(
            features, None, mode="predict", rng=None
        )
        twin = np.asarray(
            compiled.predict_step(bound, features)["a_predicted"]
        )
        assert np.abs(eager - twin).max() > 0


class TestAuditCountsConvolutions:
    def test_convolution_signature_is_counted(self):
        """Regression: stablehlo.convolution lines carry colons inside
        their attribute dict (`batch_group_count = 1 : i64`), which a
        naive [^:]* prefix regex trips over — the audit must still see
        the op's trailing type signature."""
        import flax.linen as nn
        from jax import export as jax_export

        class Conv(nn.Module):
            @nn.compact
            def __call__(self, x):
                return nn.Conv(4, (3, 3))(x)

        module = Conv()
        x = np.zeros((1, 8, 8, 3), np.float32)
        variables = module.init(jax.random.PRNGKey(0), x)

        def forward(v, inputs):
            return module.apply(v, inputs)

        exported = jax_export.export(jax.jit(forward))(
            variables, jax.ShapeDtypeStruct(x.shape, x.dtype)
        )
        audit = sq.audit_dot_dtypes(exported.serialize())
        assert audit.get("f32", 0) >= 1, audit
        assert audit["total"] >= 1


class TestClaimedVsFired:
    def test_fired_records_only_intercepted_dense_kernels(self):
        """The eligibility map is structural; the lowering only fires
        for nn.Dense-owned kernels. A deep 2-D 'kernel' param on a
        custom module is claimable but never intercepts — the fired set
        (what the export records as `layers`) must exclude it."""
        import flax.linen as nn

        class Custom(nn.Module):
            @nn.compact
            def __call__(self, x):
                k = self.param(
                    "kernel", nn.initializers.lecun_normal(), (96, 8)
                )
                return x @ k

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x):
                return Custom()(nn.relu(nn.Dense(96)(x)))

        net = Net()
        x = np.ones((2, 64), np.float32)
        variables = jax.device_get(net.init(jax.random.PRNGKey(0), x))
        tree = {"params": variables["params"]}
        native = sq.default_native_eligibility(tree, "int8")
        assert set(native) == {
            "params/Custom_0/kernel", "params/Dense_0/kernel",
        }
        payload, layout = sq.quantize_tree(tree, "int8", native=native)
        bound = sq.dequantize_tree(payload, layout, "int8")
        fired = set()
        with sq.native_lowering(payload, layout, "int8", bound, fired=fired):
            net.apply({"params": bound["params"]}, x)
        assert fired == {"params/Dense_0/kernel"}


# -- static activation calibration + conv/attention lowering (round 18) --------


class TestCalibModeResolution:
    def test_flag_declared_with_static_default(self):
        spec = t2r_flags.get_flag("T2R_SERVE_CALIB")
        assert spec.choices == ("static", "dynamic")
        assert spec.default == "static"
        assert t2r_flags.get_flag("T2R_SERVE_NATIVE_ATTN").default is None

    def test_explicit_mode_resolves_without_the_flag(self, monkeypatch):
        monkeypatch.setenv("T2R_SERVE_CALIB", "dynamic")
        assert sq.resolve_calib_mode("static") == "static"
        assert sq.resolve_calib_mode() == "dynamic"

    def test_bad_mode_names_values_and_flag(self):
        """PR 12 convention at the new call site: the resolution error
        must name the available values AND the selecting flag."""
        with pytest.raises(ValueError) as err:
            sq.resolve_calib_mode("percentile")
        message = str(err.value)
        assert "static" in message and "dynamic" in message
        assert "T2R_SERVE_CALIB" in message

    def test_bad_env_value_names_choices_and_flag(self, monkeypatch):
        monkeypatch.setenv("T2R_SERVE_CALIB", "per-row")
        with pytest.raises(ValueError, match="T2R_SERVE_CALIB"):
            sq.resolve_calib_mode()

    def test_exporter_validates_calib_at_config_time(self):
        with pytest.raises(ValueError, match="T2R_SERVE_CALIB"):
            LatestExporter(
                name="q", warmup_batch_sizes=(1,), serve_quant=("int8",),
                serve_calib="quantile",
            )


class TestLayerCalibration:
    def test_constant_zero_layer_gets_floor_clip_and_safe_dot(self):
        """An all-zero activation pool must produce a USABLE step (clip
        floor 1.0), and the static-quantized dot over it must emit
        zeros, not NaN."""
        calibration = sq.calibrate_layer_activations(
            {"params/d/kernel": [np.zeros((64,), np.float32)]}
        )
        entry = calibration["params/d/kernel"]
        assert entry["clip"] == 1.0
        assert entry["observed_max"] == 0.0
        payload, _ = sq.quantize_tree(
            _mlp_tree(), "int8", native=("params/Dense_0/kernel",)
        )
        node = payload["params"]["Dense_0"]["kernel"]
        out = np.asarray(
            sq.native_dot(
                jnp.zeros((2, 64)), jnp.asarray(node[sq.Q_KEY]),
                jnp.asarray(node[sq.S_KEY]), "int8",
                a_clip=entry["clip"],
            )
        )
        np.testing.assert_array_equal(out, np.zeros_like(out))

    def test_single_sample_corpus_calibrates(self):
        calibration = sq.calibrate_layer_activations(
            {"k": [np.asarray([0.5], np.float32)]}
        )
        assert calibration["k"]["samples"] == 1
        assert calibration["k"]["clip"] > 0

    def test_nan_pool_raises_typed_error_naming_the_layer(self):
        with pytest.raises(sq.CalibrationError, match="params/d/kernel"):
            sq.calibrate_layer_activations(
                {"params/d/kernel": [np.asarray([1.0, np.nan], np.float32)]}
            )
        with pytest.raises(sq.CalibrationError, match="inf|Inf"):
            sq.calibrate_layer_activations(
                {"params/d/kernel": [np.asarray([np.inf], np.float32)]}
            )

    def test_nan_warmup_batch_fails_input_calibration_loudly(self):
        with pytest.raises(sq.CalibrationError, match="'x'"):
            sq.calibrate_activations(
                [{"x": np.asarray([0.1, np.nan], np.float32)}]
            )

    def test_percentile_monotonicity(self):
        pool = np.random.RandomState(0).uniform(0, 3, 10000).astype(
            np.float32
        )
        records = {"k": [pool]}
        p50 = sq.calibrate_layer_activations(records, percentile=50.0)
        p999 = sq.calibrate_layer_activations(records, percentile=99.9)
        assert p50["k"]["clip"] <= p999["k"]["clip"]
        assert p999["k"]["clip"] <= p999["k"]["observed_max"]

    def test_overshoot_demotes_per_layer_and_records_magnitude(self):
        """One heavy-tailed layer (a single far outlier) demotes back to
        dynamic; the well-behaved layer stays static."""
        tame = np.random.RandomState(1).uniform(0, 1, 5000).astype(
            np.float32
        )
        spiky = tame.copy()
        spiky[0] = 100.0
        calibration = sq.calibrate_layer_activations(
            {"tame": [tame], "spiky": [spiky]}
        )
        static, demoted = sq.resolve_static_scales(calibration)
        assert "tame" in static and "tame" not in demoted
        assert "spiky" in demoted and "spiky" not in static
        assert demoted["spiky"] > sq.DEFAULT_STATIC_OVERSHOOT


class TestStaticNativeDot:
    @pytest.mark.parametrize("regime", NATIVE_REGIMES)
    def test_static_dot_matches_dequant_reference_within_step(self, regime):
        tree = _mlp_tree(seed=11)
        payload, layout = sq.quantize_tree(
            tree, regime, native=("params/Dense_0/kernel",)
        )
        node = payload["params"]["Dense_0"]["kernel"]
        x = np.random.RandomState(12).uniform(-2, 2, (8, 64)).astype(
            np.float32
        )
        clip = float(np.abs(x).max())
        static = np.asarray(
            sq.native_dot(
                jnp.asarray(x), jnp.asarray(node[sq.Q_KEY]),
                jnp.asarray(node[sq.S_KEY]), regime, a_clip=clip,
            )
        )
        deq = np.asarray(
            sq.dequantize_tree(payload, layout, regime)["params"]["Dense_0"][
                "kernel"
            ]
        )
        reference = x @ deq
        act_step = {"int8": 1 / 127.0, "fp8_e4m3": 2.0 ** -3,
                    "fp8_e5m2": 2.0 ** -2}[regime]
        bound = 0.5 * act_step * clip * np.abs(deq).sum(axis=0)[None, :]
        assert (np.abs(static - reference) <= bound + 1e-5).all()

    def test_static_program_has_zero_quant_reduces_dynamic_has_them(self):
        """The tentpole acceptance at op level: the SERIALIZED program
        of a statically-calibrated dot carries zero activation-quant
        reductions; its dynamic twin carries one per contraction."""
        from jax import export as jax_export

        tree = _mlp_tree(seed=13)
        native = ("params/Dense_0/kernel", "params/Dense_1/kernel")
        payload, layout = sq.quantize_tree(tree, "int8", native=native)

        import flax.linen as nn

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x):
                h = nn.relu(nn.Dense(96)(x))
                return nn.Dense(4)(h)

        net = Net()
        x_spec = jax.ShapeDtypeStruct((2, 64), jnp.float32)

        def export_program(static_scales):
            def f(p, xx):
                bound = sq.dequantize_tree(p, layout, "int8")
                with sq.native_lowering(
                    p, layout, "int8", bound, static_scales=static_scales
                ):
                    return net.apply({"params": bound["params"]}, xx)

            return jax_export.export(jax.jit(f))(payload, x_spec).serialize()

        def export_baseline():
            def f(xx):
                return net.apply({"params": _mlp_tree(seed=13)["params"]}, xx)

            return jax_export.export(jax.jit(f))(x_spec).serialize()

        baseline = export_baseline()
        static_scales = {path: 2.0 for path in native}
        static_prog = export_program(static_scales)
        dynamic_prog = export_program(None)
        static_audit = sq.audit_quant_reduces(static_prog, baseline)
        dynamic_audit = sq.audit_quant_reduces(dynamic_prog, baseline)
        assert static_audit["activation_quant_reduces"] == 0
        assert dynamic_audit["activation_quant_reduces"] == len(native)
        # Both programs still contract natively (the audit pair is the
        # proof the static path removed reduces WITHOUT giving up the
        # int8 dots).
        assert sq.audit_dot_dtypes(static_prog).get("i8", 0) == len(native)

    def test_reduce_parser_ignores_applierless_region_bodies(self):
        """An argmax-style region reduce (compare/select body, none of
        the counted appliers) must not leave the parser in a pending
        state that miscounts a later ELEMENTWISE maximum/add line as a
        reduce (review regression: the inflated 'max' count feeds the
        activation_quant_reduces acceptance delta)."""
        module = "\n".join([
            "  %0 = stablehlo.reduce(%arg0 init: %c) across"
            " dimensions = [1]",
            "    reducer(%a: tensor<f32>, %b: tensor<f32>) {",
            "      %p = stablehlo.compare GT, %a, %b : tensor<i1>",
            "      %s = stablehlo.select %p, %a, %b : tensor<f32>",
            "      stablehlo.return %s : tensor<f32>",
            "    }",
            "  %relu = stablehlo.maximum %1, %zero : tensor<2x4xf32>",
            "  %res = stablehlo.add %relu, %bias : tensor<2x4xf32>",
        ])
        counts = sq._count_reduce_kinds(module)
        assert counts.get("max", 0) == 0
        assert counts.get("add", 0) == 0
        assert counts["total"] == 0
        # A real region-form max reduce still counts.
        real = "\n".join([
            "  %0 = stablehlo.reduce(%arg0 init: %c) across"
            " dimensions = [1]",
            "    reducer(%a: tensor<f32>, %b: tensor<f32>) {",
            "      %m = stablehlo.maximum %a, %b : tensor<f32>",
            "      stablehlo.return %m : tensor<f32>",
            "    }",
        ])
        assert sq._count_reduce_kinds(real)["max"] == 1


class TestNativeConv:
    @pytest.mark.parametrize("regime", NATIVE_REGIMES)
    def test_conv_lowering_matches_dequant_reference(self, regime):
        import flax.linen as nn

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x):
                return nn.Conv(8, (3, 3))(x)

        net = Net()
        x = np.random.RandomState(14).uniform(-1, 1, (2, 8, 8, 4)).astype(
            np.float32
        )
        variables = jax.device_get(net.init(jax.random.PRNGKey(1), x))
        tree = {"params": variables["params"]}
        native = sq.default_native_eligibility(tree, regime)
        assert native == ("params/Conv_0/kernel",)
        payload, layout = sq.quantize_tree(tree, regime, native=native)
        assert layout["params/Conv_0/kernel"]["granularity"] == "channel"
        node = payload["params"]["Conv_0"]["kernel"]
        assert node[sq.Q_KEY].shape == tree["params"]["Conv_0"][
            "kernel"
        ].shape
        assert node[sq.S_KEY].shape == (8,)  # one scale per out channel
        bound = sq.dequantize_tree(payload, layout, regime)
        plain = np.asarray(net.apply({"params": bound["params"]}, x))
        fired = set()
        with sq.native_lowering(payload, layout, regime, bound, fired=fired):
            lowered = np.asarray(net.apply({"params": bound["params"]}, x))
        assert fired == {"params/Conv_0/kernel"}
        # The native conv genuinely diverges (activation quant) but
        # stays within the regime's step regime over a depth-36 window.
        assert np.abs(lowered - plain).max() > 0
        assert np.abs(lowered - plain).max() < 0.5

    def test_static_conv_uses_the_calibrated_clip(self):
        import flax.linen as nn

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x):
                return nn.Conv(8, (3, 3))(x)

        net = Net()
        x = np.random.RandomState(15).uniform(-1, 1, (2, 8, 8, 4)).astype(
            np.float32
        )
        variables = jax.device_get(net.init(jax.random.PRNGKey(2), x))
        tree = {"params": variables["params"]}
        payload, layout = sq.quantize_tree(
            tree, "int8", native=("params/Conv_0/kernel",)
        )
        bound = sq.dequantize_tree(payload, layout, "int8")
        records = {}
        with sq.capture_activations(records):
            reference = np.asarray(net.apply({"params": tree["params"]}, x))
        assert "params/Conv_0/kernel" in records
        static, demoted = sq.resolve_static_scales(
            sq.calibrate_layer_activations(records)
        )
        assert not demoted
        with sq.native_lowering(
            payload, layout, "int8", bound, static_scales=static
        ):
            lowered = np.asarray(net.apply({"params": bound["params"]}, x))
        assert np.abs(lowered - reference).max() < 0.1

    def test_unsupported_conv_configs_stay_on_dequant_path(self):
        """CIRCULAR padding has pre-padding semantics native_conv does
        not replicate — the interceptor must bail (claimed-but-unfired),
        not lower approximately."""
        import flax.linen as nn

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x):
                return nn.Conv(8, (3, 3), padding="CIRCULAR")(x)

        net = Net()
        x = np.random.RandomState(16).uniform(-1, 1, (2, 8, 8, 4)).astype(
            np.float32
        )
        variables = jax.device_get(net.init(jax.random.PRNGKey(3), x))
        tree = {"params": variables["params"]}
        payload, layout = sq.quantize_tree(
            tree, "int8", native=("params/Conv_0/kernel",)
        )
        bound = sq.dequantize_tree(payload, layout, "int8")
        plain = np.asarray(net.apply({"params": bound["params"]}, x))
        fired = set()
        with sq.native_lowering(payload, layout, "int8", bound, fired=fired):
            lowered = np.asarray(net.apply({"params": bound["params"]}, x))
        assert fired == set()
        np.testing.assert_array_equal(lowered, plain)

    def test_exported_conv_program_audits_native_convolution(self):
        """audit_dot_dtypes counts conv_general_dilated operand dtypes:
        the serialized program of a lowered conv shows an i8
        convolution, closing the audit over EVERY contraction kind."""
        import flax.linen as nn
        from jax import export as jax_export

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x):
                return nn.Conv(8, (3, 3), strides=(2, 2))(x)

        net = Net()
        x = np.zeros((1, 8, 8, 4), np.float32)
        variables = jax.device_get(net.init(jax.random.PRNGKey(4), x))
        tree = {"params": variables["params"]}
        payload, layout = sq.quantize_tree(
            tree, "int8", native=("params/Conv_0/kernel",)
        )

        def f(p, xx):
            bound = sq.dequantize_tree(p, layout, "int8")
            with sq.native_lowering(p, layout, "int8", bound):
                return net.apply({"params": bound["params"]}, xx)

        artifact = jax_export.export(jax.jit(f))(
            payload, jax.ShapeDtypeStruct(x.shape, x.dtype)
        ).serialize()
        audit = sq.audit_dot_dtypes(artifact)
        assert audit.get("i8", 0) >= 1, audit


class _AttnNet:
    """Tiny attention net shared by the attention-lowering tests."""

    @staticmethod
    def build():
        import flax.linen as nn

        from tensor2robot_tpu.layers.transformer import MultiHeadAttention

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x):
                h = nn.Dense(32)(x)
                return MultiHeadAttention(num_heads=2, head_dim=8)(h)

        return Net()


class TestNativeAttention:
    def _setup(self, seed=17):
        net = _AttnNet.build()
        x = np.random.RandomState(seed).uniform(-1, 1, (2, 6, 16)).astype(
            np.float32
        )
        variables = jax.device_get(net.init(jax.random.PRNGKey(5), x))
        tree = {"params": variables["params"]}
        native = sq.default_native_eligibility(tree, "int8")
        payload, layout = sq.quantize_tree(tree, "int8", native=native)
        bound = sq.dequantize_tree(payload, layout, "int8")
        return net, x, tree, payload, layout, bound

    def test_qk_pv_contractions_lower_and_stay_within_step(self):
        net, x, tree, payload, layout, bound = self._setup()
        reference = np.asarray(net.apply({"params": tree["params"]}, x))
        fired = set()
        with sq.native_lowering(payload, layout, "int8", bound, fired=fired):
            lowered = np.asarray(net.apply({"params": bound["params"]}, x))
        assert "attn/MultiHeadAttention_0" in fired
        assert np.abs(lowered - reference).max() > 0
        assert np.abs(lowered - reference).max() < 0.2

    def test_attn_flag_none_keeps_f32_attention(self, monkeypatch):
        net, x, tree, payload, layout, bound = self._setup()
        monkeypatch.setenv("T2R_SERVE_NATIVE_ATTN", "none")
        fired = set()
        with sq.native_lowering(payload, layout, "int8", bound, fired=fired):
            net.apply({"params": bound["params"]}, x)
        assert not any(key.startswith("attn/") for key in fired)
        # ...while the Dense kernels still lowered.
        assert any(key.endswith("/kernel") for key in fired)

    def test_attn_globs_select_heads(self, monkeypatch):
        net, x, tree, payload, layout, bound = self._setup()
        monkeypatch.setenv("T2R_SERVE_NATIVE_ATTN", "NoSuchModule*")
        fired = set()
        with sq.native_lowering(payload, layout, "int8", bound, fired=fired):
            net.apply({"params": bound["params"]}, x)
        assert not any(key.startswith("attn/") for key in fired)
        monkeypatch.setenv("T2R_SERVE_NATIVE_ATTN", "MultiHead*")
        fired = set()
        with sq.native_lowering(payload, layout, "int8", bound, fired=fired):
            net.apply({"params": bound["params"]}, x)
        assert "attn/MultiHeadAttention_0" in fired

    def test_flash_configured_heads_never_lower_even_on_fallback(self):
        """A use_flash=True head off-TPU falls back to the reference
        einsum INSIDE flash_attention — that fallback must not pick up
        the quantized contractions, or the artifact's attention
        numerics would depend on the export host / block divisibility
        while T2R_SERVE_NATIVE_ATTN promises flash heads never lower."""
        import flax.linen as nn

        from tensor2robot_tpu.layers.transformer import MultiHeadAttention

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x):
                h = nn.Dense(32)(x)
                return MultiHeadAttention(
                    num_heads=2, head_dim=8, use_flash=True
                )(h)

        net = Net()
        x = np.random.RandomState(21).uniform(-1, 1, (2, 6, 16)).astype(
            np.float32
        )
        variables = jax.device_get(net.init(jax.random.PRNGKey(6), x))
        tree = {"params": variables["params"]}
        native = sq.default_native_eligibility(tree, "int8")
        payload, layout = sq.quantize_tree(tree, "int8", native=native)
        bound = sq.dequantize_tree(payload, layout, "int8")
        fired = set()
        with sq.native_lowering(payload, layout, "int8", bound, fired=fired):
            net.apply({"params": bound["params"]}, x)
        # Dense kernels lower; the flash-configured attention does not.
        assert any(key.endswith("/kernel") for key in fired)
        assert not any(key.startswith("attn/") for key in fired)

    def test_static_attention_program_has_zero_quant_reduces(self):
        """Capture records q/k/v operand pools; with their static clips
        the attention program keeps its int8 contractions and drops
        every activation-quant reduce (softmax's own max reduce cancels
        against the fp32 baseline)."""
        from jax import export as jax_export

        net, x, tree, payload, layout, bound = self._setup(seed=18)
        records = {}
        with sq.capture_activations(records):
            net.apply({"params": tree["params"]}, x)
        assert {"attn/MultiHeadAttention_0:q", "attn/MultiHeadAttention_0:k",
                "attn/MultiHeadAttention_0:v"} <= set(records)
        static, _ = sq.resolve_static_scales(
            sq.calibrate_layer_activations(records)
        )
        x_spec = jax.ShapeDtypeStruct(x.shape, x.dtype)

        def export_program(static_scales):
            def f(p, xx):
                b = sq.dequantize_tree(p, layout, "int8")
                with sq.native_lowering(
                    p, layout, "int8", b, static_scales=static_scales
                ):
                    return net.apply({"params": b["params"]}, xx)

            return jax_export.export(jax.jit(f))(payload, x_spec).serialize()

        def export_baseline():
            params = tree["params"]

            def f(xx):
                return net.apply({"params": params}, xx)

            return jax_export.export(jax.jit(f))(x_spec).serialize()

        baseline = export_baseline()
        static_prog = export_program(static)
        dynamic_prog = export_program(None)
        assert sq.audit_quant_reduces(static_prog, baseline)[
            "activation_quant_reduces"
        ] == 0
        # Dynamic: one reduce per Dense (qkv, out, Dense_0) + q,k rows
        # + v columns; probs NEVER pays one (static 1.0 bound).
        assert sq.audit_quant_reduces(dynamic_prog, baseline)[
            "activation_quant_reduces"
        ] >= 5
        # Both keep the attention contractions on int8 operands: 3
        # Dense matmuls + QK^T + PV.
        assert sq.audit_dot_dtypes(static_prog).get("i8", 0) == 5


@pytest.fixture(scope="module")
def dynamic_export(trained, tmp_path_factory):
    """An int8 export pinned to DYNAMIC calibration via the exporter
    param (the programmatic twin of T2R_SERVE_CALIB=dynamic). No AOT
    executables — these tests read programs/metadata, and the bucket
    compiles would only cost tier-1 wall clock."""
    return _export(
        trained,
        tmp_path_factory.mktemp("dynamic_export"),
        serve_quant=("int8",),
        serve_calib="dynamic",
        aot_executables=False,
    )


class TestStaticCalibExport:
    def test_metadata_records_static_contract(self, native_export):
        """The default export is statically calibrated: per-regime mode
        'static', per-layer clips recorded, nothing demoted on the
        well-behaved mock corpus."""
        path, _ = native_export
        with open(os.path.join(path, "t2r_metadata.json")) as f:
            quant = json.load(f)["serve_quant"]
        for regime in NATIVE_REGIMES:
            calib = quant["calib"][regime]
            assert calib["mode"] == "static"
            # Every native layer has a static clip; the capture also
            # calibrated the shallow Dense_0 (harmlessly — it never
            # intercepts).
            for layer in quant["native"][regime]["layers"]:
                assert calib["static_scales"][layer] > 0
            assert calib["demoted_to_dynamic"] == {}
        # The per-layer calibration table is regime-independent and
        # recorded ONCE, not duplicated into every regime entry.
        stats = quant["layer_calibration"]
        for layer, entry in stats.items():
            assert entry["clip"] <= entry["observed_max"] * 1.0001
            assert entry["samples"] > 0
        for regime in NATIVE_REGIMES:
            assert "layer_calibration" not in quant["calib"][regime]

    @pytest.mark.parametrize("regime", NATIVE_REGIMES)
    def test_reduce_audit_proves_zero_activation_quant_reduces(
        self, native_export, regime
    ):
        """The tentpole acceptance on the REAL artifact: the serialized
        static-calib program carries ZERO activation-quant reductions,
        and the metadata audit matches a re-audit of the bytes."""
        path, _ = native_export
        with open(os.path.join(path, "t2r_metadata.json")) as f:
            recorded = json.load(f)["serve_quant"]["reduce_audit"][regime]
        assert recorded["activation_quant_reduces"] == 0
        with open(
            os.path.join(path, "stablehlo", f"predict_fn_{regime}.bin"), "rb"
        ) as f:
            quant_bytes = f.read()
        with open(
            os.path.join(path, "stablehlo", "predict_fn.bin"), "rb"
        ) as f:
            baseline_bytes = f.read()
        assert sq.audit_quant_reduces(quant_bytes, baseline_bytes) == recorded

    def test_dynamic_mode_keeps_per_row_reduces(self, dynamic_export):
        """T2R_SERVE_CALIB=dynamic (here the exporter-param twin) is the
        round-16 program: one activation-quant reduce per native layer,
        mode recorded 'dynamic', no static scales."""
        path, _ = dynamic_export
        with open(os.path.join(path, "t2r_metadata.json")) as f:
            quant = json.load(f)["serve_quant"]
        calib = quant["calib"]["int8"]
        assert calib["mode"] == "dynamic"
        assert calib["static_scales"] == {}
        audit = quant["reduce_audit"]["int8"]
        assert audit["activation_quant_reduces"] == len(
            quant["native"]["int8"]["layers"]
        )

    def test_dynamic_flag_and_param_produce_identical_programs(
        self, trained, dynamic_export, tmp_path, monkeypatch
    ):
        """The byte-for-byte pin: an export under T2R_SERVE_CALIB=dynamic
        serializes the IDENTICAL int8 serving program as the
        serve_calib='dynamic' exporter param — the flag path adds no
        ops, reorders nothing. (Programs are compared op-for-op with
        source-location metadata stripped: jax's loc() records the
        CALLER's file:line, so two exports invoked from different test
        lines differ in exactly those bytes and nothing else — exports
        through the same call site are raw-byte identical, which the
        bench's calib A/B leg relies on.)"""
        import re

        from jax import export as jax_export

        monkeypatch.setenv("T2R_SERVE_CALIB", "dynamic")
        flag_path, _ = _export(
            trained, tmp_path, serve_quant=("int8",), aot_executables=False
        )
        param_path, _ = dynamic_export

        def program_ops(export_dir):
            with open(
                os.path.join(export_dir, "stablehlo", "predict_fn_int8.bin"),
                "rb",
            ) as f:
                text = jax_export.deserialize(f.read()).mlir_module()
            return re.sub(r'#loc\d* = loc\("[^"]*"[^)]*\)', "", text)

        assert program_ops(flag_path) == program_ops(param_path)

    def test_static_and_dynamic_serve_within_tolerance_of_each_other(
        self, native_export, dynamic_export
    ):
        """Static calibration changes the activation step, not the
        contract: both artifacts serve within their recorded parity."""
        spath, _ = native_export
        dpath, _ = dynamic_export
        x = np.random.RandomState(3).uniform(-1, 1, (4, 3)).astype(
            np.float32
        )
        static_out = ExportedModel(spath, quant_regime="int8").predict(
            {"x": x}
        )["a_predicted"]
        dynamic_out = ExportedModel(dpath, quant_regime="int8").predict(
            {"x": x}
        )["a_predicted"]
        with open(os.path.join(spath, "t2r_metadata.json")) as f:
            tolerance = json.load(f)["serve_quant"]["parity"]["int8"][
                "tolerance"
            ]
        assert np.abs(static_out - dynamic_out).max() <= 2 * tolerance

    def test_loaded_model_and_snapshot_surface_calib_and_audit(
        self, native_export, monkeypatch
    ):
        path, root = native_export
        loaded = ExportedModel(path, quant_regime="int8")
        assert loaded.calib_mode == "static"
        assert loaded.quant_reduce_audit["activation_quant_reduces"] == 0
        assert ExportedModel(path, quant_regime="none").calib_mode is None
        monkeypatch.setenv("T2R_SERVE_QUANT", "int8")
        predictor = ExportedSavedModelPredictor(export_dir=root)
        assert predictor.restore()
        assert predictor.calib_mode == "static"
        with PolicyServer(predictor, max_wait_ms=1).start() as server:
            snap = server.snapshot()
        assert snap["serve_quant_calib"] == "static"
        assert snap["serve_quant_reduce_audit"][
            "activation_quant_reduces"
        ] == 0

    def test_aot_block_records_parallel_compile_ms(self, native_export):
        """Satellite: the thread-pooled export-time AOT compiles record
        per-bucket wall-clock in the metadata aot block."""
        path, _ = native_export
        with open(os.path.join(path, "t2r_metadata.json")) as f:
            aot = json.load(f)["aot"]
        for regime, buckets in aot["buckets"].items():
            timings = aot["compile_ms"][regime]
            assert sorted(int(b) for b in timings) == buckets
            assert all(ms > 0 for ms in timings.values())

    def test_static_calib_aot_boot_is_bitwise_and_trace_free(
        self, native_export, monkeypatch
    ):
        """The artifact-ladder acceptance for the static regimes: an
        AOT-restored static-calib int8 artifact serves BITWISE what the
        fresh-trace twin serves, with zero stablehlo-path dispatches."""
        path, _ = native_export
        x = {"x": np.random.RandomState(4).uniform(-1, 1, (2, 3)).astype(
            np.float32
        )}
        monkeypatch.setenv("T2R_SERVE_AOT", "1")
        aot_model = ExportedModel(path, quant_regime="int8")
        assert aot_model.aot_covered
        aot_out = aot_model.predict(x)
        assert aot_model.fresh_trace_calls == 0
        monkeypatch.setenv("T2R_SERVE_AOT", "0")
        fresh_model = ExportedModel(path, quant_regime="int8")
        fresh_out = fresh_model.predict(x)
        assert fresh_model.fresh_trace_calls == 1
        np.testing.assert_array_equal(
            aot_out["a_predicted"], fresh_out["a_predicted"]
        )


class TestReviewFixes:
    def test_capture_pool_bounded_with_exact_max(self):
        """A conv tower's per-layer |activation| capture must stay
        bounded in host memory (stride subsample above the cap) while
        the demotion gate's observed_max stays EXACT."""
        import flax.linen as nn

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x):
                return nn.Dense(4)(x)

        net = Net()
        x = np.random.RandomState(20).uniform(
            -1, 1, (4, 1 << 17)
        ).astype(np.float32)
        x[2, 12345] = 7.5  # the true max, somewhere a stride could miss
        variables = net.init(jax.random.PRNGKey(0), x)
        records = {}
        with sq.capture_activations(records):
            net.apply(variables, x)
        (pool,) = records["params/Dense_0/kernel"]
        assert pool.size <= sq.CAPTURE_SAMPLES_PER_CALL + 2
        calibration = sq.calibrate_layer_activations(records)
        assert calibration["params/Dense_0/kernel"]["observed_max"] == 7.5

    def test_cast_regime_calib_mode_is_none(self, quant_export):
        """fp16 has no native contractions — nothing to calibrate, so
        the metadata/fleet surface must say None, not 'dynamic'."""
        path, _ = quant_export
        with open(os.path.join(path, "t2r_metadata.json")) as f:
            quant = json.load(f)["serve_quant"]
        assert quant["calib"]["fp16"]["mode"] is None
        assert quant["calib"]["int8"]["mode"] == "static"
        assert ExportedModel(path, quant_regime="fp16").calib_mode is None

    def test_metadata_records_attention_fired_vs_eligibility(
        self, native_export
    ):
        """Attention attribution is fired-only (no structural claim):
        the MLP export records [] fired under 'auto' eligibility, so
        auto-with-nothing-lowered is visible instead of silent."""
        path, _ = native_export
        with open(os.path.join(path, "t2r_metadata.json")) as f:
            quant = json.load(f)["serve_quant"]
        for regime in NATIVE_REGIMES:
            native = quant["native"][regime]
            assert native["attention"] == []
            assert native["attention_eligibility"] == "auto"
        assert ExportedModel(path, quant_regime="int8").native_attention == ()
