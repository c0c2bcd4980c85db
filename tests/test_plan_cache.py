"""The persistent plan cache (parallel/plan_cache.py) and the measured
plan-search probe it fronts (train_eval.measure_plan_candidate).

Pins the PR's contracts:
  * envelope integrity: every corpus corruption variant of a valid entry
    is a typed PlanCacheCorrupt, and the tolerant `load()` falls back to
    None (fresh search) instead of trusting the bytes;
  * all-or-nothing cache key: a changed model fingerprint, device
    topology, jax version, or planner schema version is a typed
    PlanCacheKeyMismatch — a winner ranked under different rules never
    shadows a fresh search;
  * the zero-compile warm path: the second T2R_PLAN=auto run on the same
    (model, topology) key deserializes the FIRST run's winner
    byte-for-byte and pays zero search compiles (audited via the probe
    compile counter);
  * the measured probe bypasses jax's persistent compilation cache — a
    cache-hit executable has near-zero compile time and would poison the
    ranking.
"""

import os

import pytest

import jax

from tensor2robot_tpu import flags
from tensor2robot_tpu.analysis import corpus
from tensor2robot_tpu.export import aot
from tensor2robot_tpu.parallel import plan_cache, planner
from tensor2robot_tpu.train import train_eval
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

N = 8


def _mock_model_and_batch():
    model = MockT2RModel(device_type="cpu", use_batch_norm=False)
    generator = MockInputGenerator(batch_size=16, seed=0)
    generator.set_specification_from_model(model, "train")
    batch = next(iter(generator.create_dataset("train")))
    return model, batch


def _mock_spec():
    model, batch = _mock_model_and_batch()
    return planner.ModelSpec.from_model(model, batch)


def _payload_doc(spec=None):
    spec = spec if spec is not None else _mock_spec()
    result = planner.plan(spec, planner.Topology(num_devices=N))
    return {"plan": result.best.to_json(), "table": list(result.table)}


_TOPOLOGY = {"platform": "cpu", "device_kind": "host", "device_count": N}


class TestEnvelope:
    def test_pack_unpack_roundtrip(self):
        doc = _payload_doc()
        blob = plan_cache.pack_entry("f" * 64, doc, topology=_TOPOLOGY)
        header, payload = plan_cache.unpack_entry(
            blob, expect_fingerprint="f" * 64, expect_topology=_TOPOLOGY
        )
        assert header["format_version"] == plan_cache.PLAN_CACHE_FORMAT_VERSION
        assert header["jax"] == jax.__version__
        assert payload == doc
        # The winner survives serialization byte-for-byte: the plan json
        # re-hydrates into an identical ShardingPlan.
        plan = planner.ShardingPlan.from_json(payload["plan"])
        assert plan.to_json() == doc["plan"]

    def test_store_load_hit_is_byte_identical(self, tmp_path):
        spec = _mock_spec()
        fingerprint = plan_cache.model_fingerprint(spec)
        doc = _payload_doc(spec)
        path = plan_cache.store(fingerprint, doc, str(tmp_path))
        assert path and os.path.exists(path)
        payload = plan_cache.load(
            fingerprint, str(tmp_path), topology=None
        )
        assert payload is not None
        assert payload["plan"] == doc["plan"]
        assert payload["table"] == doc["table"]

    def test_store_disabled_without_directory(self):
        saved = flags.read_raw("T2R_PLAN_CACHE_DIR")
        try:
            flags.restore_env("T2R_PLAN_CACHE_DIR", None)
            assert plan_cache.cache_dir() is None
            assert plan_cache.store("f" * 64, {"plan": {}}) is None
            assert plan_cache.load("f" * 64) is None
        finally:
            flags.restore_env("T2R_PLAN_CACHE_DIR", saved)

    def test_forged_length_bounded_before_allocation(self):
        import struct

        blob = plan_cache.pack_entry("f" * 64, {"plan": {}})
        forged = (
            blob[:4]
            + struct.pack("<I", plan_cache.MAX_PLAN_ENTRY_BYTES + 1)
            + blob[8:]
        )
        with pytest.raises(plan_cache.PlanCacheCorrupt, match="forged"):
            plan_cache.unpack_entry(forged)

    def test_fingerprint_sensitive_to_model_shape(self):
        spec = _mock_spec()
        fp = plan_cache.model_fingerprint(spec)
        assert fp == plan_cache.model_fingerprint(spec)  # deterministic
        import dataclasses

        other = dataclasses.replace(spec, batch_size=spec.batch_size * 2)
        assert plan_cache.model_fingerprint(other) != fp


class TestCorruption:
    """Every corpus corruption family member is a TYPED corrupt error
    from the strict reader, and a logged None from the tolerant one —
    never a trusted payload."""

    def test_every_variant_typed(self):
        blob = plan_cache.pack_entry(
            "f" * 64, _payload_doc(), topology=_TOPOLOGY
        )
        variants = corpus.corrupt_frame_variants(blob)
        assert len(variants) >= 20
        for name, bad in sorted(variants.items()):
            with pytest.raises(plan_cache.PlanCacheCorrupt):
                plan_cache.unpack_entry(
                    bad,
                    expect_fingerprint="f" * 64,
                    expect_topology=_TOPOLOGY,
                )

    def test_load_falls_back_on_corrupt_file(self, tmp_path):
        spec = _mock_spec()
        fingerprint = plan_cache.model_fingerprint(spec)
        path = plan_cache.store(fingerprint, _payload_doc(spec), str(tmp_path))
        with open(path, "rb") as f:
            blob = f.read()
        for name, bad in sorted(
            corpus.corrupt_frame_variants(blob).items()
        ):
            with open(path, "wb") as f:
                f.write(bad)
            assert (
                plan_cache.load(fingerprint, str(tmp_path)) is None
            ), name

    def test_load_missing_file_is_quiet_miss(self, tmp_path):
        assert plan_cache.load("0" * 64, str(tmp_path)) is None


class TestKeyInvalidation:
    """The all-or-nothing cache key: each component differing forces a
    fresh search, loudly typed."""

    def _blob(self, **kwargs):
        return plan_cache.pack_entry(
            "f" * 64, {"plan": {}}, topology=_TOPOLOGY, **kwargs
        )

    def test_fingerprint_mismatch(self):
        with pytest.raises(
            plan_cache.PlanCacheKeyMismatch, match="fingerprint"
        ):
            plan_cache.unpack_entry(
                self._blob(), expect_fingerprint="0" * 64,
                expect_topology=_TOPOLOGY,
            )

    def test_device_count_mismatch(self):
        grown = dict(_TOPOLOGY, device_count=2 * N)
        with pytest.raises(
            plan_cache.PlanCacheKeyMismatch, match="topology"
        ):
            plan_cache.unpack_entry(
                self._blob(), expect_fingerprint="f" * 64,
                expect_topology=grown,
            )

    def test_device_kind_mismatch(self):
        tpu = dict(_TOPOLOGY, platform="tpu", device_kind="TPU v4")
        with pytest.raises(
            plan_cache.PlanCacheKeyMismatch, match="topology"
        ):
            plan_cache.unpack_entry(
                self._blob(), expect_fingerprint="f" * 64,
                expect_topology=tpu,
            )

    def test_jax_version_mismatch(self):
        with pytest.raises(plan_cache.PlanCacheKeyMismatch, match="jax"):
            plan_cache.unpack_entry(
                self._blob(jax_version="0.0.0-other"),
                expect_fingerprint="f" * 64,
                expect_topology=_TOPOLOGY,
            )

    def test_schema_bump_invalidates(self):
        """A winner chosen from a narrower search space must not shadow
        the wider one: bumping PLAN_CACHE_FORMAT_VERSION orphans every
        old entry."""
        stale = self._blob(
            format_version=plan_cache.PLAN_CACHE_FORMAT_VERSION + 1
        )
        with pytest.raises(
            plan_cache.PlanCacheKeyMismatch, match="schema"
        ):
            plan_cache.unpack_entry(
                stale, expect_fingerprint="f" * 64,
                expect_topology=_TOPOLOGY,
            )

    def test_load_falls_back_on_key_mismatch(self, tmp_path):
        """The tolerant reader treats a keyed-out entry like a miss: the
        caller re-searches rather than crashing or trusting it."""
        spec = _mock_spec()
        fingerprint = plan_cache.model_fingerprint(spec)
        # An entry keyed for a DIFFERENT jax runtime at this model's path.
        blob = plan_cache.pack_entry(
            fingerprint, _payload_doc(spec), jax_version="0.0.0-other"
        )
        os.makedirs(str(tmp_path), exist_ok=True)
        with open(
            plan_cache.entry_path(str(tmp_path), fingerprint), "wb"
        ) as f:
            f.write(blob)
        assert plan_cache.load(fingerprint, str(tmp_path)) is None


class TestParseMeasureSetting:
    def test_off_and_shortlist(self):
        assert planner.parse_measure_setting("off") is None
        assert planner.parse_measure_setting("") is None
        assert planner.parse_measure_setting(None) is None
        assert planner.parse_measure_setting("shortlist-1") == 1
        assert planner.parse_measure_setting("shortlist-8") == 8

    @pytest.mark.parametrize(
        "bad", ["on", "shortlist-0", "shortlist-x", "shortlist-", "4"]
    )
    def test_typo_is_loud(self, bad):
        with pytest.raises(ValueError, match="T2R_PLAN_MEASURE"):
            planner.parse_measure_setting(bad)


class TestCompileCacheBypass:
    """The measured probe must never time a persistent-compile-cache
    HIT: a cached executable carries near-zero compile time and object
    code XLA didn't just build, poisoning both the ranking and the
    compile counter the warm-path audit reads."""

    def test_bypass_disables_and_restores(self):
        prev = bool(jax.config.jax_enable_compilation_cache)
        jax.config.update("jax_enable_compilation_cache", True)
        try:
            with train_eval.compile_cache_bypass():
                assert not jax.config.jax_enable_compilation_cache
            assert jax.config.jax_enable_compilation_cache
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)

    def test_bypass_restores_on_error(self):
        prev = bool(jax.config.jax_enable_compilation_cache)
        jax.config.update("jax_enable_compilation_cache", True)
        try:
            with pytest.raises(RuntimeError, match="boom"):
                with train_eval.compile_cache_bypass():
                    raise RuntimeError("boom")
            assert jax.config.jax_enable_compilation_cache
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)

    def test_probe_skips_plans_the_model_cannot_run(self):
        """A shortlisted plan the given model cannot execute (pipeline
        axes on a stage-less model) is a recorded skip, not a crash —
        and pays no compile."""
        model, batch = _mock_model_and_batch()
        before = train_eval.plan_probe_compile_count()
        record = train_eval.measure_plan_candidate(
            model,
            planner.ShardingPlan(name="dp4_pp2", data=4, pipe=2),
            batch,
        )
        assert "skipped" in record
        assert "step_time_ms" not in record
        assert train_eval.plan_probe_compile_count() == before


class TestAutoSearchCache:
    """The acceptance contract end-to-end on the 8-device host mesh: a
    cold T2R_PLAN=auto run searches, measures, and stores; the warm run
    returns the SAME plan byte-for-byte with ZERO search compiles."""

    def _with_auto_flags(self, cache_dir, measure):
        saved = {
            name: flags.read_raw(name)
            for name in (
                "T2R_PLAN",
                "T2R_PLAN_CACHE_DIR",
                "T2R_PLAN_MEASURE",
                "T2R_PLAN_MEASURE_STEPS",
            )
        }
        flags.write_env("T2R_PLAN", "auto")
        flags.write_env("T2R_PLAN_CACHE_DIR", cache_dir)
        flags.write_env("T2R_PLAN_MEASURE", measure)
        flags.write_env("T2R_PLAN_MEASURE_STEPS", 1)
        return saved

    def _restore(self, saved):
        for name, value in saved.items():
            flags.restore_env(name, value)

    def test_cold_measures_then_warm_is_zero_compile(self, tmp_path):
        model, batch = _mock_model_and_batch()
        saved = self._with_auto_flags(str(tmp_path), "shortlist-2")
        try:
            cold = planner.resolve_plan_from_flag(model, batch)
            cold_stats = planner.last_search()
            assert cold_stats["source"] == "measured"
            assert cold_stats["probe_compiles"] >= 1
            assert cold_stats["stored"]
            assert cold_stats["measured"]["shortlist"] >= 1

            warm = planner.resolve_plan_from_flag(model, batch)
            warm_stats = planner.last_search()
            assert warm_stats["source"] == "cache"
            assert warm_stats["probe_compiles"] == 0
            assert warm.to_json() == cold.to_json()
            assert warm_stats["fingerprint"] == cold_stats["fingerprint"]
        finally:
            self._restore(saved)

    def test_analytic_only_when_measure_off(self, tmp_path):
        model, batch = _mock_model_and_batch()
        saved = self._with_auto_flags(str(tmp_path), "off")
        try:
            plan = planner.resolve_plan_from_flag(model, batch)
            stats = planner.last_search()
            assert stats["source"] == "analytic"
            assert stats["probe_compiles"] == 0
            # Still cached: the second run is a hit.
            warm = planner.resolve_plan_from_flag(model, batch)
            assert planner.last_search()["source"] == "cache"
            assert warm.to_json() == plan.to_json()
        finally:
            self._restore(saved)

    def test_corrupt_entry_forces_fresh_search(self, tmp_path):
        model, batch = _mock_model_and_batch()
        saved = self._with_auto_flags(str(tmp_path), "off")
        try:
            planner.resolve_plan_from_flag(model, batch)
            fingerprint = planner.last_search()["fingerprint"]
            path = plan_cache.entry_path(str(tmp_path), fingerprint)
            with open(path, "rb") as f:
                blob = f.read()
            with open(path, "wb") as f:
                f.write(blob[: len(blob) // 2])
            planner.resolve_plan_from_flag(model, batch)
            stats = planner.last_search()
            assert stats["source"] == "analytic"  # not "cache"
            assert stats["stored"]  # and the entry was repaired
            planner.resolve_plan_from_flag(model, batch)
            assert planner.last_search()["source"] == "cache"
        finally:
            self._restore(saved)


class TestTopologyKeySource:
    def test_device_topology_matches_live_mesh(self):
        topo = aot.device_topology()
        assert topo["device_count"] == N
        assert topo["platform"] == "cpu"
