"""Serialized AOT executables: artifact layout, the restore ladder, and
the loud-fallback contract.

The load-bearing claims, each pinned here:

  * an AOT-hit restore serves BIT-IDENTICALLY to the fresh-compile path
    (the executable is the compile of the rehydrated serving program —
    same bytes a cold restore would compile);
  * zero fresh compiles on an AOT-hit boot (`fresh_trace_calls == 0`
    after a full prewarm, recording-predictor bucket discipline intact);
  * every mismatch — artifact fingerprint, device topology, jax
    version, truncated/bitflipped file (analysis/corpus.py corruption
    families) — falls back to the next tier LOUDLY (typed, logged,
    counted, surfaced per bucket in `snapshot()["prewarm_source"]` /
    `aot_fallbacks`) and the fallback serves the CORRECT artifact's
    outputs, never a stale executable's;
  * `T2R_SERVE_AOT=0` (or an artifact without `aot/`) reproduces the
    pre-AOT restore path.
"""

import json
import logging
import os
import shutil

import jax
import numpy as np
import pytest

from tensor2robot_tpu import flags as t2r_flags
from tensor2robot_tpu.analysis import corpus
from tensor2robot_tpu.export import aot as aot_lib
from tensor2robot_tpu.export.exporters import LatestExporter
from tensor2robot_tpu.export.saved_model import (
    ExportedModel,
    latest_export_dir,
)
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


def _export(trained, model_dir, *, step=1, state=None, **kwargs):
    compiled, default_state = trained
    exporter = LatestExporter(
        name="latest", warmup_batch_sizes=BUCKETS, **kwargs
    )
    exporter.maybe_export(
        step=step,
        state=default_state if state is None else state,
        eval_metrics={"loss": 1.0},
        compiled=compiled,
        model_dir=model_dir,
    )
    return exporter.export_root(model_dir)


@pytest.fixture(scope="module")
def export_root(trained, tmp_path_factory):
    """One AOT-carrying export (flag-default path: T2R_AOT_EXPORT=1)."""
    return _export(trained, str(tmp_path_factory.mktemp("aot_export")))


@pytest.fixture(scope="module")
def quant_export_root(trained, tmp_path_factory):
    """int8 + fp8_e4m3 regimes — both NATIVE by default since round 16
    (eligible kernels contract in the storage dtype), so the AOT tests
    below also pin the restore ladder for native-compute artifacts."""
    return _export(
        trained,
        str(tmp_path_factory.mktemp("aot_quant")),
        serve_quant=("int8", "fp8_e4m3"),
    )


def _copy_export(export_root, tmp_path):
    """Private writable copy of the newest export dir (corruption tests
    must never mutate the module-scoped artifact)."""
    src = latest_export_dir(export_root)
    dst = os.path.join(str(tmp_path), os.path.basename(src))
    shutil.copytree(src, dst)
    return dst


def _example(n=2, seed=0):
    return {
        "x": np.random.RandomState(seed)
        .uniform(-1, 1, (n, 3))
        .astype(np.float32)
    }


def _fresh_outputs(export_dir, features, quant_regime=None, monkeypatch=None):
    """The compile-tier twin: same artifact, T2R_SERVE_AOT=0."""
    monkeypatch.setenv("T2R_SERVE_AOT", "0")
    try:
        loaded = ExportedModel(export_dir, quant_regime=quant_regime)
        assert not loaded.aot_executables
        return loaded.predict(features)
    finally:
        monkeypatch.delenv("T2R_SERVE_AOT")


class TestArtifactLayout:
    def test_aot_dir_and_metadata_contract(self, export_root):
        path = latest_export_dir(export_root)
        with open(os.path.join(path, "t2r_metadata.json")) as f:
            meta = json.load(f)
        aot = meta["aot"]
        assert aot["format_version"] == aot_lib.AOT_FORMAT_VERSION
        assert aot["topology"] == aot_lib.device_topology()
        assert aot["buckets"]["none"] == list(BUCKETS)
        assert aot["nbytes"]["none"] > 0
        assert len(aot["fingerprint"]["none"]) == 64
        for bucket in BUCKETS:
            assert os.path.exists(
                os.path.join(path, aot_lib.aot_relpath("none", bucket))
            )

    def test_quant_regimes_get_their_own_executables(self, quant_export_root):
        path = latest_export_dir(quant_export_root)
        with open(os.path.join(path, "t2r_metadata.json")) as f:
            meta = json.load(f)
        assert meta["aot"]["buckets"]["int8"] == list(BUCKETS)
        assert (
            meta["aot"]["fingerprint"]["int8"]
            != meta["aot"]["fingerprint"]["none"]
        )
        for bucket in BUCKETS:
            assert os.path.exists(
                os.path.join(path, aot_lib.aot_relpath("int8", bucket))
            )

    def test_export_flag_off_writes_pre_aot_layout(
        self, trained, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("T2R_AOT_EXPORT", "0")
        root = _export(trained, str(tmp_path))
        path = latest_export_dir(root)
        assert not os.path.exists(os.path.join(path, aot_lib.AOT_DIR))
        with open(os.path.join(path, "t2r_metadata.json")) as f:
            assert "aot" not in json.load(f)
        # ... and the loader serves it exactly like any pre-AOT artifact.
        loaded = ExportedModel(path)
        assert loaded.aot_declared == ()
        assert not loaded.aot_executables
        assert loaded.predict(_example())["a_predicted"].shape[0] == 2

    def test_failed_default_program_still_exports_quant_executables(
        self, trained, tmp_path, monkeypatch
    ):
        """A failed DEFAULT StableHLO export must not silently drop the
        quant regimes' executables (their programs serialized fine) —
        and the skipped regime must leave a breadcrumb in metadata."""
        import tensor2robot_tpu.export.saved_model as sm

        original = sm._export_stablehlo

        def default_only_fails(predict_fn, example_features,
                               variables_in_args=None):
            if variables_in_args is None:  # the closure-style default
                raise RuntimeError("default lowering exploded")
            return original(
                predict_fn, example_features,
                variables_in_args=variables_in_args,
            )

        monkeypatch.setattr(sm, "_export_stablehlo", default_only_fails)
        root = _export(trained, str(tmp_path), serve_quant=("int8",))
        path = latest_export_dir(root)
        with open(os.path.join(path, "t2r_metadata.json")) as f:
            meta = json.load(f)
        assert meta["stablehlo"] is False
        aot = meta["aot"]
        assert aot["buckets"]["int8"] == list(BUCKETS)
        assert "none" not in aot["buckets"]
        assert "no serving program" in aot["errors"]["none"]
        loaded = ExportedModel(path, quant_regime="int8")
        assert sorted(loaded.aot_executables) == list(BUCKETS)

    def test_exporter_config_validation(self):
        with pytest.raises(ValueError, match="warmup_batch_sizes"):
            LatestExporter(name="latest", aot_executables=True)
        with pytest.raises(ValueError, match="serialize_stablehlo"):
            LatestExporter(
                name="latest",
                warmup_batch_sizes=BUCKETS,
                aot_executables=True,
                serialize_stablehlo=False,
            )


class TestRestoreLadder:
    def test_aot_hit_is_bitwise_equal_to_fresh_compile(
        self, export_root, monkeypatch
    ):
        path = latest_export_dir(export_root)
        loaded = ExportedModel(path)
        assert sorted(loaded.aot_executables) == list(BUCKETS)
        assert loaded.aot_fallbacks == {}
        features = _example()
        got = loaded.predict(features)
        assert loaded.fresh_trace_calls == 0  # never touched the trace path
        want = _fresh_outputs(path, features, monkeypatch=monkeypatch)
        for key in want:
            np.testing.assert_array_equal(got[key], want[key])

    @pytest.mark.parametrize("regime", ["int8", "fp8_e4m3"])
    def test_quant_regime_aot_hit_bitwise(
        self, quant_export_root, monkeypatch, regime
    ):
        path = latest_export_dir(quant_export_root)
        loaded = ExportedModel(path, quant_regime=regime)
        assert sorted(loaded.aot_executables) == list(BUCKETS)
        # The regime under test is genuinely NATIVE (its program carries
        # int8/fp8 contractions) — the claim is an AOT cold boot of a
        # native-compute artifact with zero fresh compiles, not just a
        # dequant payload riding serialized executables.
        assert loaded.native_dot_layers, loaded.metadata["serve_quant"]
        with open(os.path.join(path, "t2r_metadata.json")) as f:
            audit = json.load(f)["serve_quant"]["dot_audit"][regime]
        native_key = {"int8": "i8", "fp8_e4m3": "f8e4m3"}[regime]
        assert audit.get(native_key, 0) >= 1, audit
        features = _example()
        got = loaded.predict(features)
        assert loaded.fresh_trace_calls == 0
        want = _fresh_outputs(
            path, features, quant_regime=regime, monkeypatch=monkeypatch
        )
        for key in want:
            np.testing.assert_array_equal(got[key], want[key])

    def test_serve_aot_off_reproduces_the_pre_aot_path(
        self, export_root, monkeypatch
    ):
        monkeypatch.setenv("T2R_SERVE_AOT", "0")
        loaded = ExportedModel(latest_export_dir(export_root))
        assert not loaded.aot_enabled
        assert not loaded.aot_executables
        assert not loaded.aot_fallbacks  # off is a choice, not a fallback
        out = loaded.predict(_example())
        assert loaded.fresh_trace_calls > 0  # the trace path served it
        assert out["a_predicted"].shape[0] == 2

    def test_novel_batch_size_rides_the_fresh_path(
        self, export_root, monkeypatch
    ):
        path = latest_export_dir(export_root)
        loaded = ExportedModel(path)
        features = _example(n=3)  # 3 is not a bucket
        got = loaded.predict(features)
        assert loaded.fresh_trace_calls == 1
        want = _fresh_outputs(path, features, monkeypatch=monkeypatch)
        np.testing.assert_array_equal(got["a_predicted"], want["a_predicted"])

    def test_transplanted_aot_dir_never_serves_stale_weights(
        self, trained, export_root, tmp_path, monkeypatch, caplog
    ):
        """The fingerprint check: aot/ from artifact A spliced into
        artifact B (different weights) must fall back on every bucket —
        and the fallback must serve B's outputs, not A's executables."""
        compiled, _ = trained
        generator = MockInputGenerator(batch_size=8)
        generator.set_specification_from_model(compiled.model, "train")
        batch = next(iter(generator.create_dataset("train")))
        other_state = compiled.init_state(jax.random.PRNGKey(7), batch)
        other_root = _export(
            trained, str(tmp_path / "other"), step=2, state=other_state
        )
        victim = _copy_export(other_root, tmp_path)
        stale = os.path.join(latest_export_dir(export_root), aot_lib.AOT_DIR)
        shutil.rmtree(os.path.join(victim, aot_lib.AOT_DIR))
        shutil.copytree(stale, os.path.join(victim, aot_lib.AOT_DIR))
        with caplog.at_level(logging.WARNING):
            loaded = ExportedModel(victim)
        assert loaded.aot_executables == {}
        assert set(loaded.aot_fallbacks) == set(BUCKETS)
        assert all(
            reason == "AOTKeyMismatch"
            for reason in loaded.aot_fallbacks.values()
        )
        assert any("fingerprint" in r.message for r in caplog.records)
        features = _example()
        got = loaded.predict(features)
        want = _fresh_outputs(victim, features, monkeypatch=monkeypatch)
        np.testing.assert_array_equal(got["a_predicted"], want["a_predicted"])

    def test_topology_mismatch_never_loads_silently(
        self, export_root, tmp_path, monkeypatch, caplog
    ):
        """An executable lowered for another mesh must not deserialize —
        one loud line, every bucket counted, fresh path serves."""
        path = _copy_export(export_root, tmp_path)
        real = aot_lib.device_topology()
        monkeypatch.setattr(
            aot_lib,
            "device_topology",
            lambda: {**real, "device_count": real["device_count"] + 8},
        )
        with caplog.at_level(logging.WARNING):
            loaded = ExportedModel(path)
        assert loaded.aot_executables == {}
        assert all(
            reason == "topology_mismatch"
            for reason in loaded.aot_fallbacks.values()
        )
        assert set(loaded.aot_fallbacks) == set(BUCKETS)
        assert any("topology" in r.message for r in caplog.records)
        assert loaded.predict(_example())["a_predicted"].shape[0] == 2
        assert loaded.fresh_trace_calls > 0

    def test_per_file_topology_key_is_checked(self, export_root, tmp_path):
        """Even with a lying metadata block, the per-file header key
        refuses a foreign-topology executable (defense in depth: the
        file is the thing that deserializes)."""
        path = _copy_export(export_root, tmp_path)
        target = os.path.join(path, aot_lib.aot_relpath("none", 1))
        with open(target, "rb") as f:
            header, payload = aot_lib._unpack(f.read())
        header["topology"] = {**header["topology"], "device_kind": "tpu-v4"}
        with open(target, "wb") as f:
            f.write(aot_lib._pack(header, payload))
        with open(target, "rb") as f:
            blob = f.read()
        with pytest.raises(aot_lib.AOTKeyMismatch, match="topology"):
            aot_lib.load_executable(
                blob, expect_topology=aot_lib.device_topology()
            )
        loaded = ExportedModel(path)
        assert 1 not in loaded.aot_executables
        assert loaded.aot_fallbacks == {1: "AOTKeyMismatch"}
        assert sorted(loaded.aot_executables) == [2, 4]  # siblings intact

    def test_jax_version_mismatch_is_a_typed_fallback(
        self, export_root, tmp_path
    ):
        path = _copy_export(export_root, tmp_path)
        target = os.path.join(path, aot_lib.aot_relpath("none", 2))
        with open(target, "rb") as f:
            header, payload = aot_lib._unpack(f.read())
        header["jax"] = "0.0.0-foreign"
        with open(target, "wb") as f:
            f.write(aot_lib._pack(header, payload))
        loaded = ExportedModel(path)
        assert loaded.aot_fallbacks == {2: "AOTKeyMismatch"}
        assert sorted(loaded.aot_executables) == [1, 4]

    def test_every_corruption_variant_is_typed_never_partial(
        self, export_root
    ):
        """analysis/corpus.py discipline over the envelope: structural
        truncations, seeded bitflips, forged/past-EOF lengths, bad magic
        — each must raise AOTCorrupt from load_executable (whole-file-
        or-nothing; no partial deserialize, no unpickle of bad bytes)."""
        path = latest_export_dir(export_root)
        with open(os.path.join(path, aot_lib.aot_relpath("none", 1)), "rb") as f:
            blob = f.read()
        variants = corpus.corrupt_frame_variants(blob)
        assert len(variants) >= 15
        for name, bad in variants.items():
            with pytest.raises(aot_lib.AOTCorrupt):
                aot_lib.load_executable(bad)
            # corrupt bytes must be rejected at integrity, BEFORE the
            # key check could even run
            with pytest.raises(aot_lib.AOTCorrupt):
                aot_lib.load_executable(
                    bad,
                    expect_fingerprint="0" * 64,
                    expect_topology=aot_lib.device_topology(),
                )

    @pytest.mark.parametrize(
        "variant", ["frame_trunc", "frame_bitflip", "frame_bad_magic"]
    )
    def test_corrupt_file_falls_back_and_serves_correctly(
        self, export_root, tmp_path, monkeypatch, caplog, variant
    ):
        path = _copy_export(export_root, tmp_path)
        target = os.path.join(path, aot_lib.aot_relpath("none", 1))
        with open(target, "rb") as f:
            blob = f.read()
        name, bad = next(
            (n, b)
            for n, b in sorted(corpus.corrupt_frame_variants(blob).items())
            if n.startswith(variant)
        )
        with open(target, "wb") as f:
            f.write(bad)
        with caplog.at_level(logging.WARNING):
            loaded = ExportedModel(path)
        assert loaded.aot_fallbacks == {1: "AOTCorrupt"}, name
        assert sorted(loaded.aot_executables) == [2, 4]
        features = _example(n=1, seed=3)
        got = loaded.predict(features)  # bucket 1 -> fresh path
        assert loaded.fresh_trace_calls == 1
        want = _fresh_outputs(path, features, monkeypatch=monkeypatch)
        np.testing.assert_array_equal(got["a_predicted"], want["a_predicted"])

    def test_require_mode_fails_loudly_instead_of_falling_back(
        self, export_root, tmp_path, monkeypatch
    ):
        path = _copy_export(export_root, tmp_path)
        monkeypatch.setenv("T2R_AOT_REQUIRE", "1")
        assert ExportedModel(path).aot_covered  # clean artifact boots
        os.remove(os.path.join(path, aot_lib.aot_relpath("none", 2)))
        with pytest.raises(aot_lib.AOTError, match="T2R_AOT_REQUIRE"):
            ExportedModel(path)

    def test_require_with_serve_aot_off_names_the_flag_conflict(
        self, export_root, monkeypatch
    ):
        """REQUIRE + SERVE_AOT=0 is an operator contradiction: the error
        must blame the flag pair, never the (perfectly good) artifact."""
        monkeypatch.setenv("T2R_AOT_REQUIRE", "1")
        monkeypatch.setenv("T2R_SERVE_AOT", "0")
        with pytest.raises(aot_lib.AOTError, match="conflicts with"):
            ExportedModel(latest_export_dir(export_root))


class _RecordingPredictor:
    """Served-batch-size recorder (the test_serving discipline)."""

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


class TestServerIntegration:
    def test_aot_boot_prewarm_source_and_zero_fresh_compiles(
        self, export_root
    ):
        inner = ExportedSavedModelPredictor(export_dir=export_root)
        assert inner.restore()
        predictor = _RecordingPredictor(inner)
        with PolicyServer(predictor, max_wait_ms=1).start() as server:
            response = server.call(
                {"x": np.zeros((3,), np.float32)}, timeout=30
            )
            snap = server.snapshot()
        # Every bucket prewarmed (recording predictor saw the ladder) ...
        assert sorted(set(predictor.batch_sizes)) == list(BUCKETS)
        # ... from deserialized executables, with ZERO fresh compiles.
        assert snap["prewarm_source"] == {
            str(b): "aot" for b in BUCKETS
        }
        assert snap["counters"]["aot_hits"] == len(BUCKETS)
        assert snap["counters"]["aot_misses"] == 0
        assert "aot_fallbacks" not in snap
        assert inner.loaded_model.fresh_trace_calls == 0
        assert response.outputs["a_predicted"].shape == (1,)

    def test_fallback_bucket_is_counted_and_surfaced(
        self, export_root, tmp_path
    ):
        root = os.path.join(str(tmp_path), "root")
        os.makedirs(root)
        _copy_export(export_root, root)
        path = latest_export_dir(root)
        target = os.path.join(path, aot_lib.aot_relpath("none", 4))
        with open(target, "rb") as f:
            blob = f.read()
        with open(target, "wb") as f:
            f.write(blob[: len(blob) // 2])  # torn mid-payload
        predictor = ExportedSavedModelPredictor(export_dir=root)
        assert predictor.restore()
        with PolicyServer(predictor, max_wait_ms=1).start() as server:
            snap = server.snapshot()
        assert snap["prewarm_source"]["1"] == "aot"
        assert snap["prewarm_source"]["2"] == "aot"
        assert snap["prewarm_source"]["4"] in ("cache", "compile")
        assert snap["counters"]["aot_hits"] == 2
        assert snap["counters"]["aot_misses"] == 1
        assert snap["aot_fallbacks"] == {"4": "AOTCorrupt"}

    def test_failed_swap_prewarm_keeps_serving_version_sources(
        self, export_root
    ):
        """A swap aborted by a failed prewarm keeps the OLD version
        serving — its prewarm_source record and aot counters must not
        be overwritten by a version that never served."""
        predictor = ExportedSavedModelPredictor(export_dir=export_root)
        assert predictor.restore()
        with PolicyServer(predictor, max_wait_ms=1).start() as server:
            before = server.snapshot()
            assert before["prewarm_source"] == {
                str(b): "aot" for b in BUCKETS
            }

            class _IncomingWithoutAOT:
                aot_executables = {}
                aot_enabled = True

            def broken_serve_fn(batch):
                raise RuntimeError("incoming version cannot serve")

            with pytest.raises(RuntimeError, match="cannot serve"):
                server._prewarm_restored(_IncomingWithoutAOT(), broken_serve_fn)
            after = server.snapshot()
        assert after["prewarm_source"] == before["prewarm_source"]
        assert after["counters"]["aot_hits"] == before["counters"]["aot_hits"]
        assert (
            after["counters"]["aot_misses"]
            == before["counters"]["aot_misses"]
        )

    def test_hot_swap_records_incoming_version_sources(
        self, trained, tmp_path
    ):
        root = _export(trained, str(tmp_path))
        predictor = ExportedSavedModelPredictor(export_dir=root)
        assert predictor.restore()
        with PolicyServer(predictor, max_wait_ms=1).start() as server:
            v1 = predictor.model_version
            _export(trained, str(tmp_path), step=2)
            assert server.hot_swap(wait=True)
            assert predictor.model_version > v1
            response = server.call(
                {"x": np.zeros((3,), np.float32)}, timeout=30
            )
            snap = server.snapshot()
        # Swap prewarm re-recorded the (AOT) sources for the incoming
        # version and the counters accumulated across boot + swap.
        assert snap["prewarm_source"] == {str(b): "aot" for b in BUCKETS}
        assert snap["counters"]["aot_hits"] == 2 * len(BUCKETS)
        assert predictor.loaded_model.fresh_trace_calls == 0
        assert response.model_version > v1


class TestCompileTierEngagement:
    """The cache-skip must be exactly as wide as the AOT coverage of the
    ladder that will actually SERVE — a serving ladder wider than the
    warmup ladder (T2R_SERVE_BUCKETS or explicit batch_buckets) has
    compile-tier buckets, and skipping the cache for them would
    silently un-amortize every boot (review regression)."""

    @pytest.fixture(autouse=True)
    def _restore_jax_cache_config(self):
        previous_dir = jax.config.jax_compilation_cache_dir
        previous_min = jax.config.jax_persistent_cache_min_compile_time_secs
        yield
        jax.config.update("jax_compilation_cache_dir", previous_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", previous_min
        )
        from jax._src import compilation_cache

        compilation_cache.reset_cache()

    def test_flag_ladder_beyond_aot_engages_cache(
        self, export_root, tmp_path, monkeypatch
    ):
        from tensor2robot_tpu.serving.compile_cache import (
            enable_compile_cache_for,
        )

        loaded = ExportedModel(latest_export_dir(export_root))
        assert loaded.aot_covered
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        # Resolved ladder == warmup ladder, fully AOT-covered -> skip.
        assert enable_compile_cache_for(loaded) is None
        # T2R_SERVE_BUCKETS adds a bucket with no executable -> the
        # compile tier is live and the cache must engage.
        monkeypatch.setenv("T2R_SERVE_BUCKETS", "1,2,4,8")
        assert enable_compile_cache_for(loaded) == str(tmp_path)

    def test_explicit_server_ladder_beyond_aot_engages_cache(
        self, export_root, tmp_path, monkeypatch
    ):
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        predictor = ExportedSavedModelPredictor(export_dir=export_root)
        assert predictor.restore()
        with PolicyServer(
            predictor, batch_buckets=(1, 2, 4, 8), max_wait_ms=1
        ).start() as server:
            snap = server.snapshot()
        # The constructor ladder's extra bucket rides the cache tier —
        # labeled as such AND actually engaged (start() re-engages for
        # any bucket outside the AOT table).
        assert snap["prewarm_source"]["8"] == "cache"
        assert {snap["prewarm_source"][str(b)] for b in BUCKETS} == {"aot"}
        assert snap["counters"]["aot_misses"] == 1
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)


class TestWarmCompileCacheBuild:
    """A WARM persistent compilation cache must never serve the AOT
    build's compiles: a cache HIT returns an executable whose
    serialization drops its object code, and the shipped blob then
    fails every deserialize_and_load with "Symbols not found" — in the
    exporting process too, so every boot of the artifact becomes a
    logged fallback. Any process that compiled the same program before
    exporting (a bench re-run, a serving replica that exports) is a
    warm-cache exporter."""

    @pytest.fixture(autouse=True)
    def _restore_jax_cache_config(self):
        previous_dir = jax.config.jax_compilation_cache_dir
        previous_min = jax.config.jax_persistent_cache_min_compile_time_secs
        yield
        jax.config.update("jax_compilation_cache_dir", previous_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", previous_min
        )
        from jax._src import compilation_cache

        compilation_cache.reset_cache()

    def test_build_under_warm_cache_round_trips(self, export_root, tmp_path):
        from jax import export as jax_export

        from tensor2robot_tpu.export.saved_model import (
            STABLEHLO_DIR,
            STABLEHLO_FILENAME,
        )
        from tensor2robot_tpu.utils.compile_cache import (
            engage_compile_cache,
        )

        with open(
            os.path.join(
                latest_export_dir(export_root), STABLEHLO_DIR,
                STABLEHLO_FILENAME,
            ),
            "rb",
        ) as f:
            program_bytes = f.read()
        cache_dir = str(tmp_path / "jaxcache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        assert engage_compile_cache() == cache_dir
        # Warm the cache with this exact program/bucket OUTSIDE the
        # build — the position every re-exporting process is in.
        batch = _example(2)
        jax.jit(jax_export.deserialize(program_bytes).call).lower(
            batch
        ).compile()
        assert os.listdir(cache_dir), "cache never engaged — no warm hit"

        timings = {}
        blobs = aot_lib.build_bucket_executables(
            program_bytes, [batch], regime="none", fingerprint="0" * 64,
            timings_ms=timings,
        )
        # Pre-fix, this deserialize died with "Symbols not found".
        _compiled, header = aot_lib.load_executable(blobs[2])
        assert header["bucket"] == 2
        assert timings[2] > 0
        # SECOND build, same process, cache still configured: jax folds
        # config state into the cache key, so a build that merely
        # flipped the enable flag would have WRITTEN re-keyed entries
        # above and would HIT them here — the re-export scenario (bench
        # re-run, online-loop learner) that corrupts every bucket
        # unless reads AND writes are both dead during the build.
        blobs2 = aot_lib.build_bucket_executables(
            program_bytes, [batch], regime="none", fingerprint="0" * 64,
        )
        aot_lib.load_executable(blobs2[2])
        # The bypass is scoped to the builds: the cache is back on.
        assert jax.config.jax_enable_compilation_cache
        assert jax.config.jax_compilation_cache_dir == cache_dir


def test_one_device_executable_restores_on_a_multi_device_host():
    """jax 0.9's deserialize_and_load defaults `execution_devices` to
    EVERY device of the backend, so a one-device executable restored
    that way expects one argument shard per device and fails its first
    call ("Expected args to execute_sharded_on_local_devices to have 8
    shards, got [1]") on any multi-device host. The restore names the
    one serving device instead, and the topology key describes it."""
    assert jax.device_count() == 8
    batch = {"x": np.arange(6, dtype=np.float32).reshape(2, 3)}
    compiled = jax.jit(lambda b: {"y": b["x"] * 2.0}).lower(batch).compile()
    blob = aot_lib.serialize_compiled(
        compiled,
        {
            "format_version": aot_lib.AOT_FORMAT_VERSION,
            "jax": jax.__version__,
            "topology": aot_lib.device_topology(),
        },
    )
    restored, _ = aot_lib.load_executable(
        blob, expect_topology=aot_lib.device_topology()
    )
    np.testing.assert_array_equal(restored(batch)["y"], batch["x"] * 2.0)
    device = aot_lib.serving_device()
    assert {
        d
        for sharding in jax.tree_util.tree_leaves(restored.output_shardings)
        for d in sharding.device_set
    } == {device}
    assert aot_lib.device_topology() == {
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": 8,
    }


class TestFlagsDeclared:
    def test_aot_flags_in_registry(self):
        assert t2r_flags.get_flag("T2R_SERVE_AOT").kind == "bool"
        assert t2r_flags.get_flag("T2R_AOT_EXPORT").kind == "bool"
        assert t2r_flags.get_flag("T2R_AOT_REQUIRE").kind == "bool"
        assert t2r_flags.get_bool("T2R_SERVE_AOT") is True
        assert t2r_flags.get_bool("T2R_AOT_EXPORT") is True
        assert t2r_flags.get_bool("T2R_AOT_REQUIRE") is False
