"""QT-Opt workload tests (reference research/qtopt/{pcgrad,t2r_models}_test.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.research.qtopt import optimizer_builder, pcgrad
from tensor2robot_tpu.research.qtopt.networks import (
    E2E_GRASP_PARAM_BLOCKS,
    Grasping44,
    concat_e2e_grasp_params,
)
from tensor2robot_tpu.research.qtopt.t2r_models import (
    DefaultGrasping44ImagePreprocessor,
    Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom,
)
from tensor2robot_tpu.specs import make_random_numpy


def _task_grads():
    """The reference pcgrad_test fixture (pcgrad_test.py:42-56):
    loss0 = var0.[1,0] + var1.[-1,1]; loss1 = var0.[-1,-1] + var1.[1,0]."""
    params = {
        "first_var/var0": jnp.array([1.0, 2.0]),
        "second_var/var1": jnp.array([3.0, 4.0]),
    }

    def loss0(p):
        return p["first_var/var0"] @ jnp.array([1.0, 0.0]) + p[
            "second_var/var1"
        ] @ jnp.array([-1.0, 1.0])

    def loss1(p):
        return p["first_var/var0"] @ jnp.array([-1.0, -1.0]) + p[
            "second_var/var1"
        ] @ jnp.array([1.0, 0.0])

    return params, [loss0, loss1]


class TestPCGrad:
    # Expected values from the reference test (pcgrad_test.py:91-100):
    # surgery grads var0=[0.5,-1.5] var1=[0.5,1.5]; plain-sum grads
    # var0=[0,-1] var1=[0,1].
    PC0, PC1 = [0.5, -1.5], [0.5, 1.5]
    SUM0, SUM1 = [0.0, -1.0], [0.0, 1.0]

    @pytest.mark.parametrize(
        "denylist,allowlist,expected0,expected1",
        [
            (None, None, PC0, PC1),
            (None, ["*var*"], PC0, PC1),
            (["second*"], None, PC0, SUM1),
            (None, ["first*"], PC0, SUM1),
            (None, ["*0"], PC0, SUM1),
            (["first*"], None, SUM0, PC1),
            (["*var*"], None, SUM0, SUM1),
        ],
    )
    def test_basic_projection(self, denylist, allowlist, expected0, expected1):
        params, losses = _task_grads()
        total, grads = pcgrad.pcgrad_gradients(
            losses, params, allowlist=allowlist, denylist=denylist
        )
        np.testing.assert_allclose(
            grads["first_var/var0"], expected0, atol=1e-5
        )
        np.testing.assert_allclose(
            grads["second_var/var1"], expected1, atol=1e-5
        )
        assert np.isfinite(float(total))

    def test_single_task_is_identity(self):
        params, losses = _task_grads()
        _, grads = pcgrad.pcgrad_gradients([losses[0]], params)
        np.testing.assert_allclose(grads["first_var/var0"], [1.0, 0.0])
        np.testing.assert_allclose(grads["second_var/var1"], [-1.0, 1.0])

    def test_non_conflicting_grads_just_sum(self):
        params = {"w": jnp.array([1.0, 1.0])}
        g = [{"w": jnp.array([1.0, 0.0])}, {"w": jnp.array([1.0, 1.0])}]
        out = pcgrad.project_task_gradients(g)
        np.testing.assert_allclose(out["w"], [2.0, 1.0], atol=1e-5)

    def test_flattened_variant_runs_under_jit(self):
        params, losses = _task_grads()

        @jax.jit
        def run(p):
            return pcgrad.pcgrad_gradients(
                losses, p, per_variable=False, rng=jax.random.PRNGKey(0)
            )

        total, grads = run(params)
        assert grads["first_var/var0"].shape == (2,)
        assert np.isfinite(float(total))


class TestOptimizerBuilder:
    def test_learning_rate_staircase(self):
        hparams = optimizer_builder.QtOptHParams(
            batch_size=10, examples_per_epoch=100, num_epochs_per_decay=1.0,
            learning_rate=1.0, learning_rate_decay_factor=0.5,
        )
        schedule = optimizer_builder.build_learning_rate(hparams)
        assert float(schedule(0)) == 1.0
        assert float(schedule(9)) == 1.0  # staircase: flat within 10 steps
        assert float(schedule(10)) == 0.5
        assert float(schedule(20)) == 0.25

    @pytest.mark.parametrize("opt", ["momentum", "rmsprop", "adam"])
    def test_build_opt_steps(self, opt):
        hparams = optimizer_builder.QtOptHParams(optimizer=opt)
        tx = optimizer_builder.build_opt(hparams)
        params = {"w": jnp.ones((3,))}
        state = tx.init(params)
        updates, _ = tx.update({"w": jnp.ones((3,))}, state, params)
        assert updates["w"].shape == (3,)


class TestGrasping44Network:
    def test_tiled_vs_flat_predictions_shapes(self):
        # Shrunken tower (num_convs=(1,1,1), 96x96) exercises the megabatch
        # tiling logic without the full 472 conv stack.
        net = Grasping44(num_convs=(1, 1, 1))
        images = jnp.zeros((2, 96, 96, 3))
        flat_params = jnp.zeros((2, 10))
        variables = net.init(
            jax.random.PRNGKey(0), images, flat_params, is_training=False
        )
        _, end_points = net.apply(
            variables, images, flat_params, is_training=False
        )
        assert end_points["predictions"].shape == (2,)

        tiled_params = jnp.zeros((2, 5, 10))
        _, end_points = net.apply(
            variables, images, tiled_params, is_training=False
        )
        assert end_points["predictions"].shape == (2, 5)

    def test_named_blocks_and_batch_stats(self):
        net = Grasping44(
            num_convs=(1, 1, 1), grasp_param_blocks=E2E_GRASP_PARAM_BLOCKS
        )
        images = jnp.zeros((2, 96, 96, 3))
        params10 = jnp.zeros((2, 10))
        variables = net.init(
            jax.random.PRNGKey(0), images, params10, is_training=True
        )
        assert "batch_stats" in variables
        # One Dense per named block.
        for name in E2E_GRASP_PARAM_BLOCKS:
            assert name in variables["params"]
        (_, end_points), updates = net.apply(
            variables, images, params10, is_training=True,
            mutable=["batch_stats"],
        )
        assert "batch_stats" in updates
        assert np.all(np.isfinite(np.asarray(end_points["predictions"])))

    def test_width_twin_tower(self):
        """The c128 MXU-alignment twin (bench BENCH_WIDTH leg): every conv
        kernel carries the widened channel count and the forward still
        produces per-example predictions."""
        net = Grasping44(num_convs=(1, 1, 1), width=32)
        images = jnp.zeros((2, 96, 96, 3))
        flat_params = jnp.zeros((2, 10))
        variables = net.init(
            jax.random.PRNGKey(0), images, flat_params, is_training=False
        )
        assert variables["params"]["conv1_1"]["kernel"].shape[-1] == 32
        assert variables["params"]["conv2"]["Conv_0"]["kernel"].shape[-2:] == (
            32,
            32,
        )
        assert variables["params"]["fcgrasp2"]["kernel"].shape[-1] == 32
        _, end_points = net.apply(
            variables, images, flat_params, is_training=False
        )
        assert end_points["predictions"].shape == (2,)

    def test_concat_e2e_grasp_params_layout(self):
        action = {
            "world_vector": jnp.arange(3.0).reshape(1, 3),
            "vertical_rotation": jnp.array([[3.0, 4.0]]),
            "close_gripper": jnp.array([[5.0]]),
            "open_gripper": jnp.array([[6.0]]),
            "terminate_episode": jnp.array([[7.0]]),
            "gripper_closed": jnp.array([[8.0]]),
            "height_to_bottom": jnp.array([[9.0]]),
        }
        packed = concat_e2e_grasp_params(action)
        np.testing.assert_allclose(packed[0], np.arange(10.0))
        # Block table indexes the same layout.
        for name, (offset, size) in E2E_GRASP_PARAM_BLOCKS.items():
            assert 0 <= offset and offset + size <= 10


class TestGrasping44Model:
    def make_model(self, **kwargs):
        return Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom(
            device_type="cpu", **kwargs
        )

    def test_specs(self):
        model = self.make_model()
        spec = model.get_feature_specification("train")
        assert spec["state/image"].shape == (472, 472, 3)
        assert spec["action/world_vector"].shape == (3,)
        label = model.get_label_specification("train")
        assert label["reward"].name == "grasp_success"

    def test_predict_spec_tiles_actions(self):
        model = self.make_model(action_batch_size=4)
        spec = model.get_feature_specification("predict")
        assert spec["action/world_vector"].shape == (4, 3)
        # Packing spec for policies excludes the tiled action.
        packing = model.get_feature_specification_for_packing("predict")
        assert "state/image" in packing.keys()
        assert not any(k.startswith("action") for k in packing.keys())

    def test_preprocessor_crop_and_distort(self):
        model = self.make_model()
        pre = model.preprocessor
        in_spec = pre.get_in_feature_specification("train")
        assert in_spec["state/image"].shape == (512, 640, 3)
        assert in_spec["state/image"].data_format == "jpeg"
        features = make_random_numpy(in_spec, batch_size=2)
        out, _ = pre.preprocess(
            features, None, mode="train", rng=jax.random.PRNGKey(0)
        )
        assert out["state/image"].shape == (2, 472, 472, 3)
        assert out["state/image"].dtype == jnp.float32
        out_eval, _ = pre.preprocess(features, None, mode="eval")
        assert out_eval["state/image"].shape == (2, 472, 472, 3)

    def test_bf16_forward_matches_f32(self):
        """bf16 forward (the TPU wrapper's default policy) stays within
        bf16 tolerance of the f32 forward on identical params — the
        numerics gate for train_in_bfloat16=True (reference bfloat16_scope,
        models/tpu_model_wrapper.py:185-191)."""
        net = Grasping44(
            grasp_param_blocks=E2E_GRASP_PARAM_BLOCKS, num_convs=(2, 2, 1)
        )
        rng = np.random.RandomState(0)
        images = jnp.asarray(rng.rand(2, 96, 96, 3), jnp.float32)
        grasp_params = jnp.asarray(rng.randn(2, 10), jnp.float32)
        variables = net.init(
            jax.random.PRNGKey(0), images, grasp_params, is_training=False
        )
        _, ep_f32 = net.apply(variables, images, grasp_params, is_training=False)
        logits_bf16, ep_bf16 = net.apply(
            variables,
            images.astype(jnp.bfloat16),
            grasp_params.astype(jnp.bfloat16),
            is_training=False,
        )
        # The logit head always computes/emits f32 (loss stability).
        assert logits_bf16.dtype == jnp.float32
        np.testing.assert_allclose(
            np.asarray(ep_bf16["predictions"]),
            np.asarray(ep_f32["predictions"]),
            atol=0.02,
        )

    def test_tpu_wrapper_defaults_to_bf16_forward(self):
        from tensor2robot_tpu.models.tpu_model_wrapper import TPUT2RModelWrapper

        model = Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom(
            device_type="tpu", image_size=(96, 96), num_convs=(2, 2, 1)
        )
        wrapped = TPUT2RModelWrapper(model)
        assert wrapped._train_in_bfloat16
        # The infeed contract is bf16...
        spec = wrapped.get_feature_specification("train")
        assert spec["state/image"].dtype == jnp.bfloat16
        features = make_random_numpy(
            wrapped.preprocessor.get_in_feature_specification("train"),
            batch_size=2,
        )
        pre_features, _ = wrapped.preprocessor.preprocess(
            features, None, mode="eval"
        )
        assert pre_features["state/image"].dtype == jnp.bfloat16
        variables = wrapped.init_variables(
            jax.random.PRNGKey(0),
            pre_features,
        )
        # ...while params stay float32 masters and outputs serve f32.
        kernel = variables["params"]["grasping44"]["conv1_1"]["kernel"]
        assert kernel.dtype == jnp.float32
        _, _, outputs, _ = wrapped.packed_inference(
            variables, pre_features, "eval"
        )
        export = wrapped.create_export_outputs_fn(pre_features, outputs)
        assert export["q_predicted"].dtype == jnp.float32

    @pytest.mark.parametrize("seed", [5, 7, 11])
    def test_jitted_gradients_match_eager(self, seed):
        """The tiny critic's training gradients under jit are the ones
        the same code returns run eagerly (with the scatter-free pool
        backward every leaf upstream of conv5 differed by 0.38-0.52 of
        its norm on the CPU). A leaf's gap is taken over the larger of
        its norm and the median leaf's: pre-BatchNorm biases have no
        gradient to speak of."""
        model = self.make_model(image_size=(96, 96), num_convs=(2, 2, 1))
        features = make_random_numpy(
            model.get_feature_specification("train"), batch_size=8, seed=seed
        )
        reward = np.random.RandomState(seed).randint(0, 2, (8, 1))
        labels = {"reward": reward.astype(np.float32)}
        variables = model.init_variables(jax.random.PRNGKey(seed), features)

        def loss_fn(params):
            f, l, outputs, _ = model.packed_inference(
                {**variables, "params": params}, features, "train",
                labels=labels, rng=jax.random.PRNGKey(1),
            )
            return model.model_train_fn(f, l, outputs, "train")[0]

        jitted = jax.jit(jax.grad(loss_fn))(variables["params"])
        with jax.disable_jit():
            eager = jax.grad(loss_fn)(variables["params"])
        norms = jax.tree_util.tree_map(
            lambda x: float(jnp.linalg.norm(x)), eager
        )
        median = float(np.median(jax.tree_util.tree_leaves(norms)))
        gaps = jax.tree_util.tree_map(
            lambda a, b, n: float(jnp.linalg.norm(a - b)) / max(n, median),
            jitted, eager, norms,
        )
        worst = max(jax.tree_util.tree_leaves(gaps))
        assert worst < 1e-4, gaps

    @pytest.mark.slow
    def test_golden_values(self):
        """Data->checkpoint golden regression for the flagship (reference
        t2r_test_fixture.train_and_check_golden_predictions :142-195):
        two deterministic train steps over the committed TFRecord must
        reproduce the stored q_predicted/loss to decimal=5. Catches drift
        anywhere in parse -> decode -> crop/distort -> forward -> loss.
        Regenerate (intentional changes only) via
        tools/make_qtopt_golden.py."""
        from tools.make_qtopt_golden import (
            VALUES_PATH,
            build_model,
            train_and_capture,
        )

        golden = np.load(VALUES_PATH, allow_pickle=True)
        captures = train_and_capture(build_model())
        assert len(captures) == len(golden)
        for step, (got, want) in enumerate(zip(captures, golden)):
            np.testing.assert_almost_equal(
                got["loss"], want["loss"], decimal=5,
                err_msg=f"loss drifted at step {step}",
            )
            np.testing.assert_almost_equal(
                got["q_predicted"], want["q_predicted"], decimal=5,
                err_msg=f"q_predicted drifted at step {step}",
            )

    @pytest.mark.slow
    def test_train_step_and_tiled_predict(self):
        from tensor2robot_tpu.train.train_eval import CompiledModel

        model = self.make_model(action_batch_size=3)
        compiled = CompiledModel(model, donate_state=False)
        batch = {
            "features": make_random_numpy(
                model.preprocessor.get_in_feature_specification("train"),
                batch_size=2,
            ),
            "labels": {"reward": np.ones((2, 1), np.float32)},
        }
        state = compiled.init_state(jax.random.PRNGKey(0), batch)
        # MULTIPLE steps, each checked finite: the round-4 pool-VJP bug
        # produced a clean step-0 loss while poisoning the step-0 params
        # with inf (a g/0 split when XLA rematerialized the pool max with
        # different numerics inside the fused program) — only the step-1
        # loss went NaN.
        for i in range(3):
            state, metrics = compiled.train_step(
                state, batch, jax.random.PRNGKey(1 + i)
            )
            assert np.isfinite(float(metrics["loss"])), f"step {i}"
        assert int(jax.device_get(state.step)) == 3
        # EMA params maintained (use_avg_model_params default True).
        assert state.ema_params is not None

        # CEM-tiled predict: [B, N, d] actions -> [B, N] q values.
        predict_features = make_random_numpy(
            model.get_feature_specification("predict"), batch_size=2
        )
        outputs = compiled.predict_step(
            state.export_variables(use_ema=True), predict_features
        )
        assert outputs["q_predicted"].shape == (2, 3)
        assert outputs["q_probability"].shape == (2, 3)
