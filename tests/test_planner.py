"""The unified sharding planner (parallel/planner.py).

Pins the tentpole contracts:
  * factorization enumeration: every candidate's axes multiply to the
    device count; memory-infeasible plans are rejected with the estimate
    in the error;
  * preset byte-equality: every hand-wired regime's planner preset
    places a TrainState with LEAF-FOR-LEAF identical shardings, and the
    `none`-regime train step is bitwise equal to the hand-wired twin;
  * checkpoint round-trip: a planner-built state restores bitwise into
    the same plan and fails loudly into a different-layout plan;
  * composition with the T2R_COLLECTIVE_QUANT regimes (the plan is
    authoritative — ambient env flags cannot change a pinned plan);
  * the 3D DP x SP x PP regime (fast one-step sibling here; the slow
    slice runs the multi-step loss-parity twin).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.flatten_util

from tensor2robot_tpu import flags
from tensor2robot_tpu.parallel import mesh as mesh_lib
from tensor2robot_tpu.parallel import planner
from tensor2robot_tpu.specs import make_random_numpy
from tensor2robot_tpu.train import train_eval
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

N = 8  # conftest forces the 8-device host mesh
BLOCK = 64


def _mock_setup(plan=None, batch_size=16, **kwargs):
    model = MockT2RModel(device_type="cpu", use_batch_norm=False)
    generator = MockInputGenerator(batch_size=batch_size, seed=0)
    generator.set_specification_from_model(model, "train")
    batch = next(iter(generator.create_dataset("train")))
    compiled = train_eval.CompiledModel(
        model, donate_state=False, plan=plan, **kwargs
    )
    state = compiled.init_state(jax.random.PRNGKey(0), batch)
    return compiled, state, batch


def _mock_model_spec():
    model = MockT2RModel(device_type="cpu", use_batch_norm=False)
    generator = MockInputGenerator(batch_size=16, seed=0)
    generator.set_specification_from_model(model, "train")
    batch = next(iter(generator.create_dataset("train")))
    return planner.ModelSpec.from_model(model, batch)


def _transformer(mesh, **kwargs):
    from tensor2robot_tpu.models.transformer_models import TransformerBCModel

    kwargs = dict(
        dict(
            action_size=2, episode_length=8, image_size=(16, 16),
            num_layers=2, num_heads=4, use_flash=False,
        ),
        **kwargs,
    )
    return TransformerBCModel(mesh=mesh, **kwargs)


def _transformer_batch(model, batch_size=8, seed=0):
    features = make_random_numpy(
        model.get_feature_specification("train"),
        batch_size=batch_size, seed=seed,
    )
    labels = make_random_numpy(
        model.get_label_specification("train"),
        batch_size=batch_size, seed=seed + 1,
    )
    return {"features": features, "labels": labels}


def _transformer_model_spec():
    mesh = mesh_lib.make_mesh(data=N)
    model = _transformer(mesh)
    return planner.ModelSpec.from_model(model, _transformer_batch(model))


def _big_synthetic_spec():
    """A hand-built ModelSpec with 8-divisible shapes, for estimate
    tests where the mock's 100-wide (8-indivisible) layers would keep
    every leaf replicated."""
    import jax.numpy as jnp

    w = jax.ShapeDtypeStruct((4096, 4096), jnp.float32)
    return planner.ModelSpec(
        param_shapes={"w": w},
        opt_shapes={"mu": {"w": w}, "nu": {"w": w}},
        batch_shapes={"x": jax.ShapeDtypeStruct((16, 8), jnp.float32)},
        batch_size=16,
    )


def _leaf_shardings(state):
    return [
        (jax.tree_util.keystr(path), str(leaf.sharding))
        for path, leaf in jax.tree_util.tree_leaves_with_path(state)
        if hasattr(leaf, "sharding")
    ]


def _flat_params(state):
    return jax.flatten_util.ravel_pytree(jax.device_get(state.params))[0]


def _run_steps(compiled, state, batch, steps, rng_seed=7):
    rng = jax.random.PRNGKey(rng_seed)
    for _ in range(steps):
        state, metrics = compiled.train_step(
            state, compiled.shard_batch(batch), rng
        )
    return state, metrics


class TestFactorization:
    def test_every_candidate_multiplies_to_device_count(self):
        result = planner.plan(
            _transformer_model_spec(), planner.Topology(num_devices=N)
        )
        assert len(result.table) >= 4
        for entry in result.table:
            axes = entry["plan"]
            product = (
                axes["data"] * axes["sequence"] * axes["pipe"]
                * axes["fsdp"] * axes["model"] * axes["expert"]
            )
            assert product == N, entry["plan"]["name"]
        assert result.best.num_devices == N

    def test_divisibility_constraints_mark_infeasible(self):
        """sp must divide the sequence length, pp the layer count; a
        spec with neither marks every composed candidate infeasible with
        the reason recorded."""
        result = planner.plan(
            _mock_model_spec(), planner.Topology(num_devices=N)
        )
        composed = [
            e for e in result.table
            if e["plan"]["sequence"] > 1 or e["plan"]["pipe"] > 1
        ]
        assert composed and all(not e["feasible"] for e in composed)
        assert all(e["reasons"] for e in composed)
        # Pure DP survives: the mock has no sequence/pipe structure.
        assert result.best.sequence == 1 and result.best.pipe == 1

    def test_memory_infeasible_rejected_with_estimate_in_error(self):
        spec = _mock_model_spec()
        with pytest.raises(planner.PlanError) as err:
            planner.plan(
                spec, planner.Topology(num_devices=N), memory_budget=64
            )
        message = str(err.value)
        assert "64 B" in message
        assert "B/device" in message  # the estimate rides the error

    def test_budget_flag_consulted(self):
        saved = flags.read_raw("T2R_PLAN_MEM_BUDGET")
        try:
            # 1 MB is far below a 64 MB parameter matrix's footprint.
            flags.write_env("T2R_PLAN_MEM_BUDGET", 1)
            with pytest.raises(planner.PlanError):
                planner.plan(
                    _big_synthetic_spec(), planner.Topology(num_devices=N)
                )
        finally:
            flags.restore_env("T2R_PLAN_MEM_BUDGET", saved)

    def test_comm_scoring_uses_wire_formats(self):
        """A quantized constraint must cut the DP comm estimate by the
        collective's real wire ratio (~3.9x for int8 at block 512 on a
        large tree; block padding softens it on tiny trees)."""
        spec = _big_synthetic_spec()
        exact = planner.plan(
            spec, planner.Topology(num_devices=N),
            constraints=planner.Constraints(collective_quant="none"),
        )
        quant = planner.plan(
            spec, planner.Topology(num_devices=N),
            constraints=planner.Constraints(collective_quant="int8"),
        )
        ratio = exact.best.comm_bytes / quant.best.comm_bytes
        assert ratio > 3.5

    def test_pinned_axes_respected(self):
        result = planner.plan(
            _transformer_model_spec(),
            planner.Topology(num_devices=N),
            constraints=planner.Constraints(pinned={"pipe": 2}),
        )
        assert all(e["plan"]["pipe"] == 2 for e in result.table)
        assert result.best.pipe == 2


class TestPresets:
    """Byte-equality pins: the planner preset and the hand-wired twin
    place LEAF-FOR-LEAF identical layouts, and `none`-regime training is
    bitwise."""

    @pytest.mark.parametrize(
        "preset,kwargs",
        [
            ("dp", {}),
            ("dp_zero2", dict(shard_weight_update=True)),
            (
                "dp_zero2_fp16",
                dict(
                    shard_weight_update=True,
                    collective_quant="fp16",
                    collective_block=BLOCK,
                ),
            ),
            (
                "dp_zero2_int8",
                dict(
                    shard_weight_update=True,
                    collective_quant="int8",
                    collective_block=BLOCK,
                ),
            ),
            (
                "dp_zero2_fp8_e4m3",
                dict(
                    shard_weight_update=True,
                    collective_quant="fp8_e4m3",
                    collective_block=BLOCK,
                ),
            ),
        ],
    )
    def test_dp_family_byte_equality_and_bitwise_step(self, preset, kwargs):
        plan = planner.resolve_preset(preset)
        if "collective_block" in kwargs:
            plan = dataclasses.replace(plan, collective_block=BLOCK)
        hand, state_h, batch = _mock_setup(**kwargs)
        planned, state_p, _ = _mock_setup(plan=plan)
        assert _leaf_shardings(state_h) == _leaf_shardings(state_p)
        audit = planner.audit_state_layout(plan, planned.mesh, state_p)
        assert audit["leaves"] > 0 and not audit["mismatches"]
        # Identical regime -> identical program -> bitwise trajectory
        # (for 'none' this IS the pre-PR GSPMD step).
        state_h, _ = _run_steps(hand, state_h, batch, 3)
        state_p, _ = _run_steps(planned, state_p, batch, 3)
        np.testing.assert_array_equal(
            _flat_params(state_h), _flat_params(state_p)
        )

    @pytest.mark.parametrize(
        "preset,mesh_kwargs,model_kwargs,compiled_kwargs",
        [
            # The two ring-attention twins pay ~75s of manual-mode
            # shard_map compiles (x2: hand + planned) for a layout-only
            # assertion — they ride the slow slice per the PR 5 budget
            # discipline. Round 21 moved sp_ulysses (~12s) and plain pp
            # (~8s) there too: dp_pp/dp_pp_zero2 below keep composed
            # pipeline coverage in tier-1 (pp is their strict subset),
            # and ulysses stays fast via test_sp_ulysses_preset_runs +
            # the planner's ulysses-in-pipe enumeration pin.
            pytest.param(
                "dp_sp", dict(data=2, sequence=4), {}, {},
                marks=pytest.mark.slow,
            ),
            pytest.param(
                "sp_ring", dict(data=1, sequence=8), {}, {},
                marks=pytest.mark.slow,
            ),
            pytest.param(
                "sp_ulysses",
                dict(data=1, sequence=8),
                dict(
                    sequence_parallel_mode="ulysses",
                    num_heads=8, head_dim=8,
                ),
                {},
                marks=pytest.mark.slow,
            ),
            pytest.param(
                "pp",
                dict(data=1, pipe=2),
                dict(pipeline_stages=2, pipeline_microbatches=2),
                {},
                marks=pytest.mark.slow,
            ),
            (
                "dp_pp",
                dict(data=2, pipe=2),
                dict(pipeline_stages=2, pipeline_microbatches=2),
                {},
            ),
            (
                "dp_pp_zero2",
                dict(data=2, pipe=2),
                dict(pipeline_stages=2, pipeline_microbatches=2),
                dict(shard_weight_update=True, param_min_shard_size=0),
            ),
        ],
    )
    def test_composed_presets_byte_equal(
        self, preset, mesh_kwargs, model_kwargs, compiled_kwargs
    ):
        plan = planner.resolve_preset(preset)
        if compiled_kwargs.get("param_min_shard_size") == 0:
            plan = dataclasses.replace(plan, param_min_shard_size=0)
        n_dev = int(np.prod(list(mesh_kwargs.values())))
        mesh = mesh_lib.make_mesh(
            devices=jax.devices()[:n_dev], **mesh_kwargs
        )
        model = _transformer(mesh, **model_kwargs)
        batch = _transformer_batch(model)
        hand = train_eval.CompiledModel(
            model, mesh=mesh, donate_state=False, **compiled_kwargs
        )
        state_h = hand.init_state(jax.random.PRNGKey(0), batch)
        plan_mesh = plan.build_mesh()
        model_p = _transformer(plan_mesh, **model_kwargs)
        planned = train_eval.CompiledModel(
            model_p, donate_state=False, plan=plan
        )
        state_p = planned.init_state(jax.random.PRNGKey(0), batch)
        assert _leaf_shardings(state_h) == _leaf_shardings(state_p)
        audit = planner.audit_state_layout(plan, planned.mesh, state_p)
        assert audit["leaves"] > 0 and not audit["mismatches"]

    def test_sp_ulysses_preset_runs(self):
        plan = planner.resolve_preset("sp_ulysses")
        mesh = plan.build_mesh()
        # Ulysses scatters HEADS: an 8-way axis needs heads % 8 == 0.
        model = _transformer(
            mesh, num_heads=8, head_dim=8, **plan.model_kwargs()
        )
        planned = train_eval.CompiledModel(
            model, donate_state=False, plan=plan
        )
        batch = _transformer_batch(model)
        state = planned.init_state(jax.random.PRNGKey(0), batch)
        _, metrics = _run_steps(planned, state, batch, 1)
        assert np.isfinite(float(jax.device_get(metrics["loss"])))

    def test_unknown_preset_names_flag_and_menu(self):
        with pytest.raises(KeyError) as err:
            planner.resolve_preset("dp_zero2_int4")
        message = str(err.value)
        assert "T2R_PLAN" in message
        for name in ("dp_zero2_int8", "dp_sp_pp"):
            assert name in message

    def test_model_must_match_plan_structure(self):
        """A plan can place layouts but cannot retrofit model structure:
        a mesh-less model under an SP plan (or a stage-less model under
        a PP plan) would silently train fully replicated behind a green
        replicated-regime audit — it must be rejected at construction."""
        plan = planner.resolve_preset("dp_sp")
        model = _transformer(None)
        with pytest.raises(ValueError, match="sequence"):
            train_eval.CompiledModel(model, donate_state=False, plan=plan)
        plan_pp = planner.resolve_preset("dp_pp")
        model_pp = _transformer(plan_pp.build_mesh())  # pipeline_stages=1
        with pytest.raises(ValueError, match="pipeline_stages"):
            train_eval.CompiledModel(
                model_pp, donate_state=False, plan=plan_pp
            )

    def test_mesh_plan_disagreement_rejected(self):
        plan = planner.resolve_preset("dp_sp")
        mesh = mesh_lib.make_mesh(data=N)
        model = MockT2RModel(device_type="cpu", use_batch_norm=False)
        with pytest.raises(ValueError, match="disagree"):
            train_eval.CompiledModel(model, mesh=mesh, plan=plan)


class TestFlagResolution:
    def test_off_resolves_to_none(self):
        saved = flags.read_raw("T2R_PLAN")
        try:
            flags.restore_env("T2R_PLAN", None)
            assert planner.resolve_plan_from_flag() is None
            flags.write_env("T2R_PLAN", "off")
            assert planner.resolve_plan_from_flag() is None
        finally:
            flags.restore_env("T2R_PLAN", saved)

    def test_preset_name_resolves(self):
        saved = flags.read_raw("T2R_PLAN")
        try:
            flags.write_env("T2R_PLAN", "dp_zero2")
            plan = planner.resolve_plan_from_flag()
            assert plan.name == "dp_zero2"
            assert plan.shard_weight_update
        finally:
            flags.restore_env("T2R_PLAN", saved)

    def test_auto_requires_model(self):
        saved = flags.read_raw("T2R_PLAN")
        try:
            flags.write_env("T2R_PLAN", "auto")
            with pytest.raises(ValueError, match="auto"):
                planner.resolve_plan_from_flag()
        finally:
            flags.restore_env("T2R_PLAN", saved)

    def test_plan_is_authoritative_over_env_quant(self):
        """A pinned plan must not pick up ambient T2R_COLLECTIVE_QUANT:
        dp_zero2 stays exact even with int8 exported fleet-wide."""
        saved = flags.read_raw("T2R_COLLECTIVE_QUANT")
        try:
            flags.write_env("T2R_COLLECTIVE_QUANT", "int8")
            planned, state, _ = _mock_setup(
                plan=planner.resolve_preset("dp_zero2")
            )
            assert planned._quant_collective is None
            assert state.collective_residual is None
            planned_q, state_q, _ = _mock_setup(
                plan=planner.resolve_preset("dp_zero2_fp8_e5m2")
            )
            assert planned_q._quant_collective.name == "fp8_e5m2"
            assert state_q.collective_residual is not None
        finally:
            flags.restore_env("T2R_COLLECTIVE_QUANT", saved)


class TestCheckpointRoundtrip:
    def test_same_plan_restores_bitwise(self, tmp_path):
        plan = dataclasses.replace(
            planner.resolve_preset("dp_zero2_int8"), collective_block=BLOCK
        )
        compiled, state, batch = _mock_setup(plan=plan)
        state, _ = _run_steps(compiled, state, batch, 3)
        manager = train_eval.create_checkpoint_manager(
            str(tmp_path), save_interval_steps=1
        )
        manager.save(
            3,
            args=train_eval.ocp.args.StandardSave(state),
            force=True,
        )
        manager.wait_until_finished()
        compiled_r, _, _ = _mock_setup(plan=plan)
        restored = train_eval.restore_or_init_state(
            manager, compiled_r, jax.random.PRNGKey(0), batch
        )
        manager.close()
        assert int(jax.device_get(restored.step)) == 3
        state, _ = _run_steps(compiled, state, batch, 3, rng_seed=11)
        restored, _ = _run_steps(compiled_r, restored, batch, 3, rng_seed=11)
        np.testing.assert_array_equal(
            _flat_params(state), _flat_params(restored)
        )

    def test_different_plan_fails_loudly(self, tmp_path):
        """A quant-plan checkpoint (flat opt layout) must not silently
        restore into the tree-layout dp_zero2 plan."""
        plan = dataclasses.replace(
            planner.resolve_preset("dp_zero2_int8"), collective_block=BLOCK
        )
        compiled, state, batch = _mock_setup(plan=plan)
        state, _ = _run_steps(compiled, state, batch, 2)
        manager = train_eval.create_checkpoint_manager(
            str(tmp_path), save_interval_steps=1
        )
        manager.save(
            2,
            args=train_eval.ocp.args.StandardSave(state),
            force=True,
        )
        manager.wait_until_finished()
        compiled_other, _, _ = _mock_setup(
            plan=planner.resolve_preset("dp_zero2")
        )
        with pytest.raises(Exception):
            train_eval.restore_or_init_state(
                manager, compiled_other, jax.random.PRNGKey(0), batch
            )
        manager.close()


class Test3DPlan:
    """The regime that did not exist pre-PR: DP x SP x PP with the
    weight update sharded across BOTH replica axes."""

    def _setup_3d(self, weight_update_axes=None):
        plan = dataclasses.replace(
            planner.resolve_preset("dp_sp_pp"), param_min_shard_size=0
        )
        if weight_update_axes is not None:
            plan = dataclasses.replace(
                plan, weight_update_axes=weight_update_axes,
                name=plan.name + "_datawu",
            )
        mesh = plan.build_mesh()
        model = _transformer(
            mesh, pipeline_stages=2, pipeline_microbatches=2
        )
        compiled = train_eval.CompiledModel(
            model, donate_state=False, plan=plan
        )
        batch = _transformer_batch(model)
        state = compiled.init_state(jax.random.PRNGKey(0), batch)
        return plan, compiled, state, batch

    # ~12s: the 3D train-step compile just to see one finite loss; the
    # layout assertions stay fast below (collective-schedule pin runs
    # the same _setup_3d audit surface) and the math contract rides the
    # slow loss-parity twin.
    @pytest.mark.slow
    def test_one_step_runs_with_generalized_weight_update(self):
        plan, compiled, state, batch = self._setup_3d()
        audit = planner.audit_state_layout(plan, compiled.mesh, state)
        assert not audit["mismatches"]
        # Opt leaves genuinely shard over the data x sequence PRODUCT
        # (group 4), not data alone — the generalization.
        specs = {
            str(leaf.sharding.spec)
            for _, leaf in jax.tree_util.tree_leaves_with_path(
                state.opt_state
            )
            if hasattr(leaf, "sharding")
        }
        assert any("('data', 'sequence')" in s for s in specs), specs
        assert any("'pipe'" in s for s in specs), specs
        state, metrics = _run_steps(compiled, state, batch, 1)
        assert np.isfinite(float(jax.device_get(metrics["loss"])))

    def test_collective_schedule_attributes_all_three_axes(self):
        plan, _, _, _ = self._setup_3d()
        schedule = plan.collective_schedule(_transformer_model_spec())
        axes = {axis for entry in schedule for axis in entry["axes"]}
        assert {"data", "sequence", "pipe"} <= axes
        for entry in schedule:
            assert entry["bytes_per_device_step"] is not None
            assert entry["bytes_per_device_step"] > 0

    @pytest.mark.slow
    def test_loss_parity_with_data_axis_weight_update_twin(self):
        """Multi-step 3D training with the generalized ('data',
        'sequence') weight update matches the ('data',)-sharded twin to
        float tolerance — the sharding is a layout change, not a math
        change."""
        _, compiled, state, batch = self._setup_3d()
        _, compiled_t, state_t, _ = self._setup_3d(
            weight_update_axes=(mesh_lib.DATA_AXIS,)
        )
        losses, losses_t = [], []
        rng = jax.random.PRNGKey(1)
        for _ in range(6):
            state, m = compiled.train_step(
                state, compiled.shard_batch(batch), rng
            )
            losses.append(float(jax.device_get(m["loss"])))
            state_t, m_t = compiled_t.train_step(
                state_t, compiled_t.shard_batch(batch), rng
            )
            losses_t.append(float(jax.device_get(m_t["loss"])))
        assert losses[-1] < losses[0]  # it actually learns
        np.testing.assert_allclose(losses, losses_t, atol=1e-4)


class TestMemoryEstimate:
    def test_zero2_shrinks_opt_estimate(self):
        spec = _big_synthetic_spec()
        dp = planner.resolve_preset("dp")
        zero2 = dataclasses.replace(
            planner.resolve_preset("dp_zero2"), param_min_shard_size=0
        )
        mem_dp = planner.estimate_memory(spec, dp)
        mem_z2 = planner.estimate_memory(spec, zero2)
        assert mem_z2["opt_state"] == mem_dp["opt_state"] // N
        assert mem_dp["total"] > 0

    def test_quant_estimate_uses_flat_layout(self):
        spec = _mock_model_spec()
        quant = planner.resolve_preset("dp_zero2_int8")
        mem = planner.estimate_memory(spec, quant)
        # Per-device flat shard: ~2 moments + residuals on n/8 elements.
        assert mem["opt_state"] < 8 * 4 * spec.n_params

    def test_tp_estimate_shards_params_and_mirrors(self):
        """sharded_params plans divide the param/opt footprint by the
        factor param_sharding actually achieves on each leaf — the
        spec-level twin of the placed rule."""
        spec = _big_synthetic_spec()
        dp = planner.resolve_preset("dp")
        tp = dataclasses.replace(
            planner.ShardingPlan(name="dp4_tp2", data=4, fsdp=2),
            param_min_shard_size=0,
        )
        mem_dp = planner.estimate_memory(spec, dp)
        mem_tp = planner.estimate_memory(spec, tp)
        assert mem_tp["params"] == mem_dp["params"] // 2
        assert mem_tp["opt_state"] == mem_dp["opt_state"] // 2


class TestWidenedFactorization:
    """The PR's search-space widening: the fsdp (tensor-parallel) axis
    joins the enumeration, and ulysses attention composes inside the
    pipeline shard_map (the old 'ring mode only' rejection is gone)."""

    def test_tp_points_enumerated_and_attributed(self):
        result = planner.plan(
            _big_synthetic_spec(), planner.Topology(num_devices=N)
        )
        names = {e["plan"]["name"]: e for e in result.table}
        entry = names["dp4_sp1_pp1_tp2"]
        assert entry["feasible"], entry["reasons"]
        assert entry["plan"]["fsdp"] == 2
        assert entry["plan"]["regime"] == "sharded_params"
        # The fsdp axis is attributed in the comm estimate and the
        # collective schedule.
        assert entry["comm"]["fsdp"] > 0
        plan = planner.ShardingPlan.from_json(entry["plan"])
        schedule = plan.collective_schedule(_big_synthetic_spec())
        sites = {e["site"] for e in schedule}
        assert "fsdp_param_gather" in sites
        # TP pays strictly more wire than pure DP on every composition
        # reachable here: the pure-DP winner is unchanged.
        assert result.best.name == "dp8_sp1_pp1"

    def test_tp_rejected_when_no_leaf_shards(self):
        """The mock's tiny leaves fall below param_min_shard_size: every
        tp point is infeasible with the reason recorded, not silently
        scored as if params sharded."""
        result = planner.plan(
            _mock_model_spec(), planner.Topology(num_devices=N)
        )
        tp_entries = [e for e in result.table if e["plan"]["fsdp"] > 1]
        assert tp_entries
        assert all(not e["feasible"] for e in tp_entries)
        # Where tp is the only composition question (pp=1), the recorded
        # reason is the leaf probe; tp x pp points lead with the
        # composition rejection instead.
        solo_tp = [e for e in tp_entries if e["plan"]["pipe"] == 1]
        assert solo_tp
        for entry in solo_tp:
            assert any("no param leaf" in r for r in entry["reasons"]), (
                entry["reasons"]
            )

    def test_tp_disallowed_by_constraint(self):
        result = planner.plan(
            _big_synthetic_spec(),
            planner.Topology(num_devices=N),
            constraints=planner.Constraints(allow_tp=False),
        )
        for entry in result.table:
            if entry["plan"]["fsdp"] > 1:
                assert "tensor parallelism disallowed" in entry["reasons"]

    def test_tp_pp_composition_rejected_with_reason(self):
        result = planner.plan(
            _transformer_model_spec(), planner.Topology(num_devices=N)
        )
        combos = [
            e for e in result.table
            if e["plan"]["fsdp"] > 1 and e["plan"]["pipe"] > 1
        ]
        assert combos
        for entry in combos:
            assert any("tp x pp" in r for r in entry["reasons"])

    def test_ulysses_composes_with_pipeline(self):
        """dp1_sp4_pp2 under ulysses is now a feasible point — PR 13's
        'sp x pp composes in ring mode only' rejection is retired — while
        the heads-divisibility gate still holds."""
        result = planner.plan(
            _transformer_model_spec(),
            planner.Topology(num_devices=N),
            constraints=planner.Constraints(
                sequence_parallel_mode="ulysses"
            ),
        )
        names = {e["plan"]["name"]: e for e in result.table}
        entry = names["dp1_sp4_pp2"]
        assert entry["feasible"], entry["reasons"]
        assert entry["plan"]["sequence_parallel_mode"] == "ulysses"
        # heads=4 cannot split 8 ways: the gate is intact.
        sp8 = names["dp1_sp8_pp1"]
        assert not sp8["feasible"]
        assert any("heads" in r for r in sp8["reasons"])

    def test_plan_json_roundtrip_every_table_entry(self):
        result = planner.plan(
            _big_synthetic_spec(), planner.Topology(num_devices=N)
        )
        for entry in result.table:
            plan = planner.ShardingPlan.from_json(entry["plan"])
            assert plan.to_json() == entry["plan"]

    def test_plan_json_unknown_field_is_loud(self):
        result = planner.plan(
            _big_synthetic_spec(), planner.Topology(num_devices=N)
        )
        doc = dict(result.best.to_json())
        doc["warp_factor"] = 9
        with pytest.raises(ValueError, match="warp_factor"):
            planner.ShardingPlan.from_json(doc)


class TestMeasuredRerank:
    """Tier 2: the compile-and-measure re-rank over the analytic
    shortlist (the mock's single feasible point keeps this cheap)."""

    def test_rerank_measures_and_records(self):
        model = MockT2RModel(device_type="cpu", use_batch_norm=False)
        generator = MockInputGenerator(batch_size=16, seed=0)
        generator.set_specification_from_model(model, "train")
        batch = next(iter(generator.create_dataset("train")))
        spec = planner.ModelSpec.from_model(model, batch)
        result = planner.plan(spec, planner.Topology(num_devices=N))
        before = train_eval.plan_probe_compile_count()
        reranked, stats = planner.measured_rerank(
            model, batch, result, shortlist=2, steps=1
        )
        paid = train_eval.plan_probe_compile_count() - before
        assert paid == stats["shortlist"] >= 1
        assert stats["winner"] == reranked.best.name
        probed = [
            e for e in reranked.table if e.get("measured") is not None
        ]
        assert len(probed) == stats["shortlist"]
        for entry in probed:
            measured = entry["measured"]
            assert measured["step_time_ms"] > 0
            assert measured["steps_timed"] >= 1
            assert measured["analytic_rank"] >= 0
            assert measured["memory_fit"]
            # The analytic-vs-measured memory audit rides the entry
            # whenever the backend exposes memory_analysis().
            if measured.get("memory_per_device_bytes"):
                err = measured["analytic_memory_error"]
                assert err["ratio"] > 0

    def test_rerank_survives_nothing_measuring(self):
        """When every shortlisted plan skips (a model that cannot run
        any of them), the analytic winner stands."""
        model = MockT2RModel(device_type="cpu", use_batch_norm=False)
        generator = MockInputGenerator(batch_size=16, seed=0)
        generator.set_specification_from_model(model, "train")
        batch = next(iter(generator.create_dataset("train")))
        spec = planner.ModelSpec.from_model(model, batch)
        result = planner.plan(spec, planner.Topology(num_devices=N))
        # A memory budget of one byte fails every measured fit.
        reranked, stats = planner.measured_rerank(
            model, batch, result, shortlist=1, steps=1, memory_budget=1
        )
        measured = [
            e for e in reranked.table if e.get("measured") is not None
        ]
        assert measured
        if measured[0]["measured"].get("memory_per_device_bytes"):
            # Budget gate engaged: the analytic winner stands.
            assert not measured[0]["measured"]["memory_fit"]
            assert "winner" not in stats
            assert reranked.best.name == result.best.name


class TestWidenedParity:
    """Loss-parity twins for the two previously-unreachable plan points
    (the PR's twin discipline): each is a layout change, not a math
    change. The twin shares the exact parameter structure — the
    pipelined model inits per-stage from split rngs, so a non-pipelined
    'twin' would start from different weights."""

    def _run_losses(self, plan, model_kwargs=None, steps=3):
        mesh = plan.build_mesh()
        model = _transformer(mesh, **(model_kwargs or {}))
        compiled = train_eval.CompiledModel(
            model, donate_state=False, plan=plan
        )
        batch = _transformer_batch(model)
        state = compiled.init_state(jax.random.PRNGKey(0), batch)
        losses = []
        rng = jax.random.PRNGKey(7)
        for _ in range(steps):
            state, metrics = compiled.train_step(
                state, compiled.shard_batch(batch), rng
            )
            losses.append(float(jax.device_get(metrics["loss"])))
        return losses

    @pytest.mark.slow
    def test_ulysses_in_pipe_matches_ring_in_pipe_twin(self):
        def plan_for(mode):
            return dataclasses.replace(
                planner.ShardingPlan(
                    name=f"sp4_{mode}_pp2", sequence=4, pipe=2,
                    sequence_parallel_mode=mode,
                ),
                param_min_shard_size=0,
            )

        losses_u = self._run_losses(
            plan_for("ulysses"),
            dict(pipeline_stages=2, sequence_parallel_mode="ulysses"),
        )
        losses_r = self._run_losses(
            plan_for("ring"),
            dict(pipeline_stages=2, sequence_parallel_mode="ring"),
        )
        np.testing.assert_allclose(losses_u, losses_r, atol=1e-4)

    @pytest.mark.slow
    def test_tp_matches_dp_twin(self):
        tp = dataclasses.replace(
            planner.ShardingPlan(name="dp4_tp2", data=4, fsdp=2),
            param_min_shard_size=0,
        )
        dp = dataclasses.replace(
            planner.ShardingPlan(name="dp8", data=8),
            param_min_shard_size=0,
        )
        np.testing.assert_allclose(
            self._run_losses(tp), self._run_losses(dp), atol=1e-4
        )
