"""The integration anchor: MockT2RModel trains end-to-end and converges.

Rebuild of the reference's utils/train_eval_test.py acceptance gate (trains
the mock model, checks convergence, output artifacts, and resume). Runs on
the 8-device virtual CPU mesh — the same pjit path a TPU slice uses.
"""

import os

import jax
import numpy as np
import pytest

from tensor2robot_tpu.hooks.hook_builder import Hook, HookBuilder
from tensor2robot_tpu.train import train_eval
from tensor2robot_tpu.train.metrics import read_metrics
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

BATCH_SIZE = 16
TRAIN_STEPS = 200


class CountingHookBuilder(HookBuilder):
    def __init__(self):
        self.hook = self._make()

    def _make(self):
        class CountingHook(Hook):
            def __init__(self):
                self.begun = 0
                self.steps = 0
                self.checkpoints = 0
                self.evals = 0
                self.ended = 0

            def on_train_begin(self, ctx):
                self.begun += 1

            def after_step(self, ctx):
                self.steps += 1

            def after_checkpoint_saved(self, ctx):
                self.checkpoints += 1

            def after_eval(self, ctx):
                self.evals += 1

            def on_train_end(self, ctx):
                self.ended += 1

        return CountingHook()

    def create_hooks(self, t2r_model, trainer=None):
        return [self.hook]


class TestTrainEvalModel:
    def test_train_converges_and_artifacts(self, tmp_path):
        model_dir = str(tmp_path / "run")
        model = MockT2RModel(device_type="cpu")
        hooks = CountingHookBuilder()
        final_metrics = train_eval.train_eval_model(
            t2r_model=model,
            input_generator_train=MockInputGenerator(batch_size=BATCH_SIZE),
            input_generator_eval=MockInputGenerator(batch_size=BATCH_SIZE, seed=7),
            model_dir=model_dir,
            max_train_steps=TRAIN_STEPS,
            eval_steps=8,
            save_checkpoints_steps=100,
            log_every_steps=50,
            hook_builders=[hooks],
        )
        # Convergence: linearly separable data, must beat 0.9 accuracy.
        assert final_metrics["accuracy"] > 0.9, final_metrics
        # Artifacts: checkpoints + train/eval metric streams.
        ckpt_dir = os.path.join(model_dir, "checkpoints")
        assert os.path.isdir(ckpt_dir) and os.listdir(ckpt_dir)
        train_stream = read_metrics(os.path.join(model_dir, "train"))
        assert train_stream and train_stream[-1]["step"] == TRAIN_STEPS
        assert "loss" in train_stream[-1]
        eval_stream = read_metrics(os.path.join(model_dir, "eval"))
        assert eval_stream and "accuracy" in eval_stream[-1]
        # Loss well below an untrained sigmoid-CE baseline (~0.69).
        assert train_stream[-1]["loss"] < 0.4
        # Hooks fired.
        hook = hooks.hook
        assert hook.begun == 1 and hook.ended == 1
        assert hook.steps == TRAIN_STEPS
        assert hook.checkpoints >= 2 and hook.evals >= 2

    def test_resume_from_checkpoint(self, tmp_path):
        model_dir = str(tmp_path / "resume")
        model = MockT2RModel(device_type="cpu")
        train_eval.train_eval_model(
            t2r_model=model,
            input_generator_train=MockInputGenerator(batch_size=BATCH_SIZE),
            model_dir=model_dir,
            max_train_steps=50,
            save_checkpoints_steps=50,
            log_every_steps=25,
        )
        # Second call continues to 100 from the checkpoint at 50.
        train_eval.train_eval_model(
            t2r_model=MockT2RModel(device_type="cpu"),
            input_generator_train=MockInputGenerator(batch_size=BATCH_SIZE),
            model_dir=model_dir,
            max_train_steps=100,
            save_checkpoints_steps=50,
            log_every_steps=25,
        )
        stream = read_metrics(os.path.join(model_dir, "train"))
        steps = [r["step"] for r in stream]
        assert steps[0] <= 50 and steps[-1] == 100
        # No step re-run: the resumed run starts past 50.
        resumed = [s for s in steps if s > 50]
        assert resumed

    def test_tpu_wrapper_path_on_mesh(self, tmp_path):
        """device_type='tpu' exercises the bf16 wrapper + dtype policy end
        to end (on the CPU mesh, the same program a TPU runs)."""
        model_dir = str(tmp_path / "tpu")
        model = MockT2RModel(device_type="tpu")
        final_metrics = train_eval.train_eval_model(
            t2r_model=model,
            input_generator_train=MockInputGenerator(batch_size=BATCH_SIZE),
            input_generator_eval=MockInputGenerator(batch_size=BATCH_SIZE, seed=3),
            model_dir=model_dir,
            max_train_steps=100,
            eval_steps=4,
            save_checkpoints_steps=100,
            log_every_steps=50,
        )
        assert final_metrics["accuracy"] > 0.8, final_metrics

    @pytest.mark.parametrize("model_name", ["mock", "critic"])
    def test_init_state_gets_its_batch_laid_over_the_mesh(
        self, tmp_path, monkeypatch, model_name
    ):
        """The eager preprocessor and model init of `init_state` run over
        the mesh, on each device its share of the first batch, and the
        state for a seed is bit for bit the one a single device gives and
        the one the host batch gave."""
        from tensor2robot_tpu.data.input_generators import (
            DefaultRandomInputGenerator,
        )
        from tensor2robot_tpu.parallel.mesh import make_mesh
        from tensor2robot_tpu.research.qtopt.t2r_models import (
            Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom,
        )

        def make():
            if model_name == "mock":
                return (
                    MockT2RModel(device_type="cpu"),
                    MockInputGenerator(batch_size=8),
                )
            return (
                Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom(
                    device_type="tpu", image_size=(96, 96), num_convs=(2, 2, 1)
                ),
                DefaultRandomInputGenerator(batch_size=8),
            )

        handed, states = [], []
        real_init_state = train_eval.CompiledModel.init_state

        def spy(self, rng, example_batch):
            handed.append({
                len(leaf.sharding.device_set)
                for leaf in jax.tree_util.tree_leaves(example_batch)
            })
            state = real_init_state(self, rng, example_batch)
            states.append(jax.device_get(state))
            return state

        monkeypatch.setattr(train_eval.CompiledModel, "init_state", spy)
        for count in (4, 1):
            model, generator = make()
            train_eval.train_eval_model(
                t2r_model=model,
                input_generator_train=generator,
                model_dir=str(tmp_path / f"devices{count}"),
                max_train_steps=0,
                mesh=make_mesh(devices=jax.devices()[:count]),
                seed=11,
            )
        assert handed == [{4}, {1}]

        # As the trainer did it before: the host batch, one device.
        model, generator = make()
        wrapped = train_eval.maybe_wrap_for_tpu(model)
        train_eval.provide_input_generator_with_model_information(
            generator, wrapped, "train"
        )
        host_batch = next(iter(generator.create_dataset("train")))
        compiled = train_eval.CompiledModel(
            wrapped, mesh=make_mesh(devices=jax.devices()[:1])
        )
        rng_init, _ = jax.random.split(jax.random.PRNGKey(11))
        before = jax.device_get(
            real_init_state(compiled, rng_init, host_batch)
        )
        four, one = states
        for other in (one, before):
            leaves, others = (
                jax.tree_util.tree_leaves(tree) for tree in (four, other)
            )
            assert len(leaves) == len(others) > 4
            for a, b in zip(leaves, others):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_ema_params(self, tmp_path):
        model = MockT2RModel(device_type="cpu", use_avg_model_params=True)
        final_metrics = train_eval.train_eval_model(
            t2r_model=model,
            input_generator_train=MockInputGenerator(batch_size=BATCH_SIZE),
            input_generator_eval=MockInputGenerator(batch_size=BATCH_SIZE, seed=3),
            model_dir=str(tmp_path / "ema"),
            max_train_steps=60,
            eval_steps=4,
            save_checkpoints_steps=60,
            log_every_steps=30,
        )
        assert "accuracy" in final_metrics

    def test_predict_from_model(self, tmp_path):
        model_dir = str(tmp_path / "predict")
        train_eval.train_eval_model(
            t2r_model=MockT2RModel(device_type="cpu"),
            input_generator_train=MockInputGenerator(batch_size=BATCH_SIZE),
            model_dir=model_dir,
            max_train_steps=50,
            save_checkpoints_steps=50,
            log_every_steps=25,
        )
        predictions = next(
            train_eval.predict_from_model(
                MockT2RModel(device_type="cpu"),
                MockInputGenerator(batch_size=4),
                model_dir=model_dir,
            )
        )
        assert predictions["a_predicted"].shape == (4, 1)


class TestMultiStepDispatch:
    """iterations_per_loop: K device steps per host dispatch via lax.scan."""

    def test_scan_matches_per_step_training(self, tmp_path):
        kwargs = dict(
            input_generator_train=MockInputGenerator(batch_size=BATCH_SIZE),
            max_train_steps=40,
            save_checkpoints_steps=20,
            log_every_steps=10,
            seed=3,
        )
        train_eval.train_eval_model(
            t2r_model=MockT2RModel(device_type="cpu"),
            model_dir=str(tmp_path / "per_step"),
            **kwargs,
        )
        train_eval.train_eval_model(
            t2r_model=MockT2RModel(device_type="cpu"),
            model_dir=str(tmp_path / "scan"),
            iterations_per_loop=10,
            **kwargs,
        )
        per_step = read_metrics(str(tmp_path / "per_step" / "train"))
        scanned = read_metrics(str(tmp_path / "scan" / "train"))
        # Same final step reached; loss in the same converged regime.
        assert per_step[-1]["step"] == scanned[-1]["step"] == 40
        assert abs(per_step[-1]["loss"] - scanned[-1]["loss"]) < 0.15

    def test_scan_respects_checkpoint_boundaries_and_hooks(self, tmp_path):
        builder = CountingHookBuilder()
        train_eval.train_eval_model(
            t2r_model=MockT2RModel(device_type="cpu"),
            input_generator_train=MockInputGenerator(batch_size=BATCH_SIZE),
            model_dir=str(tmp_path / "run"),
            max_train_steps=50,
            save_checkpoints_steps=25,
            log_every_steps=25,
            iterations_per_loop=10,
            hook_builders=[builder],
        )
        # Chunks: 10,10,5 | 10,10,5 -> 6 host dispatches, 2 checkpoints.
        assert builder.hook.steps == 6
        assert builder.hook.checkpoints == 2
        ckpt_dir = str(tmp_path / "run" / "checkpoints")
        assert sorted(os.listdir(ckpt_dir)) == ["25", "50"]

    def test_resume_with_scan(self, tmp_path):
        model_dir = str(tmp_path / "run")
        kwargs = dict(
            input_generator_train=MockInputGenerator(batch_size=BATCH_SIZE),
            model_dir=model_dir,
            save_checkpoints_steps=20,
            iterations_per_loop=8,
        )
        train_eval.train_eval_model(
            t2r_model=MockT2RModel(device_type="cpu"), max_train_steps=20, **kwargs
        )
        train_eval.train_eval_model(
            t2r_model=MockT2RModel(device_type="cpu"), max_train_steps=40, **kwargs
        )
        metrics = read_metrics(os.path.join(model_dir, "train"))
        assert metrics[-1]["step"] == 40


class TestInfeed:
    def test_device_prefetch_order_and_exhaustion(self):
        from tensor2robot_tpu.train.infeed import device_prefetch

        puts = []

        def shard(x):
            puts.append(x)
            return x * 10

        out = list(device_prefetch(iter(range(5)), shard, depth=2))
        assert out == [0, 10, 20, 30, 40]
        assert puts == list(range(5))

    def test_stack_and_shard_stacked(self):
        import jax

        from tensor2robot_tpu.parallel import mesh as mesh_lib
        from tensor2robot_tpu.train.infeed import shard_stacked_batch, stack_batches

        batches = [
            {"x": np.full((8, 3), i, np.float32), "s": np.asarray(i, np.int64)}
            for i in range(4)
        ]
        stacked = stack_batches(batches)
        assert stacked["x"].shape == (4, 8, 3)
        assert stacked["s"].shape == (4,)
        mesh = mesh_lib.make_mesh()
        placed = shard_stacked_batch(stacked, mesh)
        # Batch axis (dim 1) sharded over data; scan axis replicated.
        n_data = mesh.shape[mesh_lib.DATA_AXIS]
        shard_shape = placed["x"].sharding.shard_shape(placed["x"].shape)
        assert shard_shape == (4, 8 // n_data, 3)
        np.testing.assert_array_equal(np.asarray(placed["x"]), stacked["x"])

    def test_stack_batches_matches_np_stack_across_leaf_types(self):
        """The preallocated single-copy stack must be value-identical to
        np.stack for numpy, scalar, and device-array leaves."""
        import jax.numpy as jnp

        from tensor2robot_tpu.train.infeed import stack_batches

        batches = [
            {
                "np": np.full((4, 2), i, np.float32),
                "scalar": np.asarray(i, np.int64),
                "dev": jnp.full((2,), i, jnp.int32),
            }
            for i in range(3)
        ]
        stacked = stack_batches(batches)
        assert stacked["np"].dtype == np.float32
        assert stacked["np"].shape == (3, 4, 2)
        for key in ("np", "scalar", "dev"):
            expected = np.stack(
                [np.asarray(b[key]) for b in batches]
            )
            np.testing.assert_array_equal(np.asarray(stacked[key]), expected)

    def test_resolve_depth_reads_central_flag(self):
        from tensor2robot_tpu import flags
        from tensor2robot_tpu.train.infeed import resolve_depth

        assert resolve_depth(5) == 5
        saved = flags.read_raw("T2R_INFEED_DEPTH")
        try:
            flags.restore_env("T2R_INFEED_DEPTH", None)
            assert resolve_depth() == 2  # registry default
            flags.write_env("T2R_INFEED_DEPTH", 4)
            assert resolve_depth() == 4
        finally:
            flags.restore_env("T2R_INFEED_DEPTH", saved)


class TestDeferredMetricsFetch:
    def test_deferred_fetch_semantics(self):
        import jax.numpy as jnp

        from tensor2robot_tpu.train.metrics import DeferredFetch

        deferred = DeferredFetch()
        assert deferred.push(jnp.asarray(1.0)) is None  # nothing pending
        assert float(deferred.push(jnp.asarray(2.0))) == 1.0
        assert float(deferred.push(jnp.asarray(3.0))) == 2.0
        assert float(deferred.drain()) == 3.0
        assert deferred.drain() is None

    def test_long_eval_averages_stay_exact(self):
        """evaluate() crosses several 32-step deferral windows; the
        deferred drain must not perturb the accumulated averages."""
        model = MockT2RModel(device_type="cpu", use_batch_norm=False)
        generator = MockInputGenerator(batch_size=8)
        generator.set_specification_from_model(model, "train")
        batch = next(iter(generator.create_dataset("train")))
        compiled = train_eval.CompiledModel(model, donate_state=False)
        state = compiled.init_state(jax.random.PRNGKey(0), batch)
        eval_generator = MockInputGenerator(batch_size=8, seed=3)
        eval_generator.set_specification_from_model(model, "eval")
        metrics = train_eval.evaluate(
            compiled,
            state,
            iter(eval_generator.create_dataset("eval")),
            eval_steps=70,
        )
        assert 0.0 <= metrics["accuracy"] <= 1.0
        # Reference: the same 70 batches averaged with a plain loop.
        ref_batches = list(
            __import__("itertools").islice(
                iter(eval_generator.create_dataset("eval")), 70
            )
        )
        totals = None
        for ref_batch in ref_batches:
            m = compiled.eval_step(
                state, compiled.shard_batch(ref_batch), False
            )
            m = {k: float(v) for k, v in jax.device_get(m).items()}
            totals = (
                m
                if totals is None
                else {k: totals[k] + v for k, v in m.items()}
            )
        for key, total in totals.items():
            assert abs(metrics[key] - total / 70) < 1e-5


class _SpyManager:
    """Wraps a real orbax CheckpointManager, recording call order."""

    def __init__(self, inner, events):
        self._inner = inner
        self._events = events

    def save(self, step, *args, **kwargs):
        self._events.append(("save", step))
        return self._inner.save(step, *args, **kwargs)

    def wait_until_finished(self):
        self._events.append(("wait", None))
        return self._inner.wait_until_finished()

    def __getattr__(self, name):
        return getattr(self._inner, name)


class TestAsyncCheckpointing:
    """A mid-loop save must NOT block the loop on its own
    wait_until_finished; the write finalizes at exit (or before a
    checkpoint-consuming hook fires)."""

    def _train(self, tmp_path, monkeypatch, hook_builders=None):
        events = []
        real_create = train_eval.create_checkpoint_manager

        def spied(*args, **kwargs):
            return _SpyManager(real_create(*args, **kwargs), events)

        monkeypatch.setattr(
            train_eval, "create_checkpoint_manager", spied
        )
        train_eval.train_eval_model(
            t2r_model=MockT2RModel(device_type="cpu"),
            input_generator_train=MockInputGenerator(batch_size=8),
            model_dir=str(tmp_path / "run"),
            max_train_steps=4,
            eval_steps=None,
            save_checkpoints_steps=2,
            log_every_steps=10,
            hook_builders=hook_builders,
        )
        return events

    def test_midloop_save_does_not_wait(self, tmp_path, monkeypatch):
        events = self._train(tmp_path, monkeypatch)
        saves = [i for i, e in enumerate(events) if e[0] == "save"]
        waits = [i for i, e in enumerate(events) if e[0] == "wait"]
        assert len(saves) == 2, events
        assert waits, "exit must finalize pending saves"
        # No wait between the saves: the mid-loop save overlapped the
        # next train window, and the first wait happened only after the
        # LAST save (the exit finalize).
        assert min(waits) > max(saves), events

    def test_checkpoint_hook_forces_finalize_first(
        self, tmp_path, monkeypatch
    ):
        """A hook that consumes ctx.checkpoint_path (backup/eval hooks)
        requires a durable checkpoint: the save must finalize BEFORE the
        hook fires, i.e. before the next save."""
        durable = []

        class BackupHookBuilder(HookBuilder):
            def create_hooks(self, t2r_model, trainer=None):
                class BackupHook(Hook):
                    def after_checkpoint_saved(self, ctx):
                        durable.append(ctx.checkpoint_path)

                return [BackupHook()]

        events = self._train(
            tmp_path, monkeypatch, hook_builders=[BackupHookBuilder()]
        )
        assert len(durable) == 2
        saves = [i for i, e in enumerate(events) if e[0] == "save"]
        waits = [i for i, e in enumerate(events) if e[0] == "wait"]
        # Each save is followed by a wait before the next save.
        for save_index in saves:
            assert any(i > save_index for i in waits), events
        assert min(w for w in waits) > saves[0]
        assert any(saves[0] < w < saves[1] for w in waits), events


class TestParamSharding:
    def test_tensor_parallel_kernels_column_split(self):
        import jax.numpy as jnp

        from tensor2robot_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.make_mesh(data=1, model=8)
        rule = mesh_lib.param_sharding(mesh, min_weight_size=16)
        kernel = jnp.zeros((64, 128), jnp.float32)
        sharding = rule(kernel)
        assert sharding.spec == (None, mesh_lib.MODEL_AXIS)
        # 1-D (bias) and small leaves stay replicated.
        assert rule(jnp.zeros((128,), jnp.float32)).spec in ((), (None,))
        assert rule(jnp.zeros((2, 2), jnp.float32)).is_fully_replicated

    def test_combined_fsdp_and_model_axes(self):
        import jax.numpy as jnp

        from tensor2robot_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.make_mesh(data=1, fsdp=2, model=4)
        rule = mesh_lib.param_sharding(mesh, min_weight_size=16)
        kernel = jnp.zeros((64, 128), jnp.float32)
        spec = rule(kernel).spec
        assert spec == (mesh_lib.FSDP_AXIS, mesh_lib.MODEL_AXIS)

    def test_trainer_shards_params_on_tp_mesh(self, tmp_path):
        import jax

        from tensor2robot_tpu.parallel import mesh as mesh_lib
        from tensor2robot_tpu.train.train_eval import CompiledModel

        # Mock layers are width 100: 4-way column split divides, 8 doesn't.
        mesh = mesh_lib.make_mesh(data=2, model=4)
        model = MockT2RModel(device_type="cpu")
        generator = MockInputGenerator(batch_size=16)
        generator.set_specification_from_model(model, "train")
        batch = next(iter(generator.create_dataset("train")))
        compiled = CompiledModel(
            model, mesh=mesh, donate_state=False, param_min_shard_size=16
        )
        state = compiled.init_state(
            jax.random.PRNGKey(0), batch
        )
        sharded = [
            leaf
            for leaf in jax.tree_util.tree_leaves(state.params)
            if not leaf.sharding.is_fully_replicated
        ]
        assert sharded, "TP mesh left every parameter replicated"
        state, metrics = compiled.train_step(
            state, compiled.shard_batch(batch), jax.random.PRNGKey(1)
        )
        assert float(jax.device_get(metrics["loss"])) > 0


class TestMemoryLevers:
    """remat and gradient accumulation must be numerically transparent:
    same batch, same rng -> same updated parameters as the plain step."""

    def _setup(self, use_batch_norm=True, **compiled_kwargs):
        model = MockT2RModel(
            device_type="cpu", use_batch_norm=use_batch_norm
        )
        generator = MockInputGenerator(batch_size=8)
        generator.set_specification_from_model(model, "train")
        batch = next(iter(generator.create_dataset("train")))
        compiled = train_eval.CompiledModel(
            model, donate_state=False, **compiled_kwargs
        )
        state = compiled.init_state(jax.random.PRNGKey(0), batch)
        return compiled, state, batch

    def _one_step_params(self, compiled, state, batch):
        state, metrics = compiled.train_step(
            state, compiled.shard_batch(batch), jax.random.PRNGKey(7)
        )
        return (
            jax.device_get(state.params),
            float(jax.device_get(metrics["loss"])),
        )

    def test_remat_matches_plain_step(self):
        compiled, state, batch = self._setup()
        params_plain, loss_plain = self._one_step_params(
            compiled, state, batch
        )
        compiled_r, state_r, _ = self._setup(remat=True)
        params_remat, loss_remat = self._one_step_params(
            compiled_r, state_r, batch
        )
        assert abs(loss_plain - loss_remat) < 1e-6
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7),
            params_plain,
            params_remat,
        )

    def test_grad_accum_matches_plain_step(self):
        """Mean-of-microbatch grads == full-batch grads for a mean loss,
        so the updated params must agree to fp tolerance. Batch norm is
        off: per-microbatch statistics differ from full-batch statistics
        by construction (the standard grad-accumulation caveat), so
        transparency only holds for BN-free models."""
        compiled, state, batch = self._setup(use_batch_norm=False)
        params_plain, loss_plain = self._one_step_params(
            compiled, state, batch
        )
        compiled_a, state_a, _ = self._setup(
            use_batch_norm=False, grad_accum_steps=4
        )
        params_accum, loss_accum = self._one_step_params(
            compiled_a, state_a, batch
        )
        assert abs(loss_plain - loss_accum) < 1e-5
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6),
            params_plain,
            params_accum,
        )

    def test_grad_accum_metric_recombination_is_key_driven(self):
        """Batch-carrying metrics are declared by key prefix, not inferred
        from shape: a fixed-size float vector that coincidentally has
        length B/K must be AVERAGED (shape-preserving), while `golden/` /
        `per_example/` keys concatenate back to the full batch."""
        K, B = 4, 8

        class MetricModel(MockT2RModel):
            def model_train_fn(self, features, labels, outputs, mode):
                loss, metrics = super().model_train_fn(
                    features, labels, outputs, mode
                )
                # Collision case: fixed-size vector of length B/K == 2.
                metrics["hist/fixed_vector"] = jnp.ones(
                    (B // K,), jnp.float32
                )
                # Declared batch-carrying: per-example residuals.
                metrics["per_example/pred"] = outputs["a_predicted"][:, 0]
                return loss, metrics

        import jax.numpy as jnp

        model = MetricModel(device_type="cpu", use_batch_norm=False)
        generator = MockInputGenerator(batch_size=B)
        generator.set_specification_from_model(model, "train")
        batch = next(iter(generator.create_dataset("train")))
        compiled = train_eval.CompiledModel(
            model, donate_state=False, grad_accum_steps=K
        )
        state = compiled.init_state(jax.random.PRNGKey(0), batch)
        _, metrics = compiled.train_step(
            state, compiled.shard_batch(batch), jax.random.PRNGKey(7)
        )
        assert metrics["hist/fixed_vector"].shape == (B // K,)
        np.testing.assert_allclose(
            np.asarray(metrics["hist/fixed_vector"]), np.ones(B // K)
        )
        assert metrics["per_example/pred"].shape == (B,)

    def test_grad_accum_rejects_indivisible_batch(self):
        compiled, state, batch = self._setup(grad_accum_steps=3)
        with pytest.raises(ValueError, match="divisible"):
            compiled.train_step(
                state, compiled.shard_batch(batch), jax.random.PRNGKey(7)
            )

    def test_bad_accum_steps_rejected(self):
        model = MockT2RModel(device_type="cpu")
        with pytest.raises(ValueError, match="grad_accum_steps"):
            train_eval.CompiledModel(model, grad_accum_steps=0)


class TestModelObjectUntouched:
    """CompiledModel reads the model and sets nothing on it."""

    @staticmethod
    def _lowered_text(model, batch, **compiled_kwargs):
        compiled = train_eval.CompiledModel(
            model, donate_state=False, **compiled_kwargs
        )
        state = compiled.init_state(jax.random.PRNGKey(0), batch)
        return compiled.train_step.lower(
            state, compiled.shard_batch(batch), jax.random.PRNGKey(7)
        ).as_text()

    @staticmethod
    def _model_and_batch():
        model = MockT2RModel(device_type="cpu")
        generator = MockInputGenerator(batch_size=8)
        generator.set_specification_from_model(model, "train")
        return model, next(iter(generator.create_dataset("train")))

    def test_no_attribute_set_through_init_and_lowering(self):
        model, batch = self._model_and_batch()
        before = dict(vars(model))
        self._lowered_text(model, batch)
        assert vars(model) == before

    def test_two_trainers_over_one_model_lower_to_the_same_text(self):
        """A trainer built after another over the same model instance
        traces the program it would have traced alone."""
        model, batch = self._model_and_batch()
        alone = self._lowered_text(model, batch)
        self._lowered_text(model, batch, remat=True, grad_accum_steps=2)
        assert self._lowered_text(model, batch) == alone


class TestWeightUpdateSharding:
    """Cross-replica weight-update sharding (ZeRO-2, arXiv:2004.13336):
    optimizer moments shard over the data axis, params stay replicated,
    and the training math is unchanged."""

    def _setup(self, **kwargs):
        model = MockT2RModel(device_type="cpu", use_batch_norm=False)
        generator = MockInputGenerator(batch_size=8)
        generator.set_specification_from_model(model, "train")
        batch = next(iter(generator.create_dataset("train")))
        # data=4: the mock's hidden dim (100) must divide the data axis
        # for the update sharding to engage (100 % 4 == 0, 100 % 8 != 0).
        mesh = train_eval.mesh_lib.make_mesh(
            data=4, devices=jax.devices()[:4]
        )
        compiled = train_eval.CompiledModel(
            model, mesh=mesh, donate_state=False, param_min_shard_size=0,
            **kwargs
        )
        state = compiled.init_state(jax.random.PRNGKey(0), batch)
        return compiled, state, batch

    def _assert_some_opt_leaf_sharded(self, state, context):
        opt_leaves = [
            leaf
            for leaf in jax.tree_util.tree_leaves(state.opt_state)
            if hasattr(leaf, "sharding") and leaf.ndim >= 1
        ]
        assert any(
            not leaf.sharding.is_fully_replicated for leaf in opt_leaves
        ), f"no optimizer-state leaf sharded {context}"

    def test_opt_state_sharded_params_replicated(self):
        compiled, state, _ = self._setup(shard_weight_update=True)
        assert all(
            leaf.sharding.is_fully_replicated
            for leaf in jax.tree_util.tree_leaves(state.params)
        )
        self._assert_some_opt_leaf_sharded(state, "at init")

    def test_training_math_unchanged(self):
        compiled, state, batch = self._setup()
        compiled_s, state_s, _ = self._setup(shard_weight_update=True)

        def step(compiled, state):
            state, metrics = compiled.train_step(
                state, compiled.shard_batch(batch), jax.random.PRNGKey(3)
            )
            return jax.device_get(state.params), float(
                jax.device_get(metrics["loss"])
            )

        params_plain, loss_plain = step(compiled, state)
        params_sharded, loss_sharded = step(compiled_s, state_s)
        assert abs(loss_plain - loss_sharded) < 1e-6
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                a, b, rtol=1e-5, atol=1e-6
            ),
            params_plain,
            params_sharded,
        )

    def test_sharding_survives_the_update(self):
        compiled, state, batch = self._setup(shard_weight_update=True)
        state, _ = compiled.train_step(
            state, compiled.shard_batch(batch), jax.random.PRNGKey(3)
        )
        self._assert_some_opt_leaf_sharded(state, "after the update")
