"""Space-to-depth stem lowering: exact equivalence with the plain strided
conv, checkpoint-layout parity, and the Grasping44 wiring."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.layers.s2d_conv import SpaceToDepthConv, stem_s2d_enabled


def _plain(features, kernel, strides):
    return nn.Conv(
        features, kernel, strides=strides, padding="SAME", use_bias=False
    )


class TestEquivalence:
    @pytest.mark.parametrize("hw", [(472, 472), (96, 96), (20, 28)])
    def test_matches_plain_conv_f32(self, hw):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, *hw, 3))
        plain = _plain(64, (6, 6), (2, 2))
        v = plain.init(jax.random.PRNGKey(1), x)
        s2d = SpaceToDepthConv(64, (6, 6), strides=(2, 2))
        # Identical param tree (same name/shape) -> same checkpoint.
        want_shape = v["params"]["kernel"].shape
        v2 = s2d.init(jax.random.PRNGKey(1), x)
        assert v2["params"]["kernel"].shape == want_shape
        got = s2d.apply(v, x)
        want = plain.apply(v, x)
        assert got.shape == want.shape
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )

    def test_matches_plain_conv_bf16(self):
        x = jax.random.normal(
            jax.random.PRNGKey(2), (2, 96, 96, 3), jnp.bfloat16
        )
        plain = _plain(32, (6, 6), (2, 2))
        v = plain.init(jax.random.PRNGKey(3), jnp.asarray(x, jnp.float32))
        got = np.asarray(
            SpaceToDepthConv(32, (6, 6), strides=(2, 2), dtype=jnp.bfloat16)
            .apply(v, x)
            .astype(jnp.float32)
        )
        want = np.asarray(
            nn.Conv(
                32, (6, 6), strides=(2, 2), padding="SAME", use_bias=False,
                dtype=jnp.bfloat16,
            )
            .apply(v, x)
            .astype(jnp.float32)
        )
        # bf16 accumulation order differs between lowerings; budget ~1%.
        np.testing.assert_allclose(got, want, rtol=0.02, atol=0.05)

    def test_gradients_flow(self):
        x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 24, 3))
        s2d = SpaceToDepthConv(8, (6, 6), strides=(2, 2))
        v = s2d.init(jax.random.PRNGKey(5), x)
        g = jax.grad(lambda v, x: jnp.sum(s2d.apply(v, x) ** 2))(v, x)
        gk = g["params"]["kernel"]
        assert gk.shape == v["params"]["kernel"].shape
        assert bool(jnp.isfinite(gk).all()) and float(jnp.abs(gk).sum()) > 0


class TestGuards:
    def test_rejects_kernel_not_multiple_of_stride(self):
        x = jnp.zeros((1, 10, 10, 3))
        with pytest.raises(ValueError, match="multiple of strides"):
            SpaceToDepthConv(4, (5, 5), strides=(2, 2)).init(
                jax.random.PRNGKey(0), x
            )

    def test_rejects_non_block_same_padding(self):
        x = jnp.zeros((1, 12, 12, 3))
        with pytest.raises(ValueError, match="whole number"):
            SpaceToDepthConv(4, (4, 4), strides=(2, 2)).init(
                jax.random.PRNGKey(0), x
            )

    def test_rejects_odd_input(self):
        x = jnp.zeros((1, 11, 12, 3))
        with pytest.raises(ValueError, match="not divisible"):
            SpaceToDepthConv(4, (6, 6), strides=(2, 2)).init(
                jax.random.PRNGKey(0), x
            )

    def test_rejects_bias_carrying_checkpoint(self):
        """A bias param restored from an nn.Conv(use_bias=True) checkpoint
        must raise at apply time, not be silently ignored."""
        x = jnp.zeros((1, 12, 12, 3))
        module = SpaceToDepthConv(4, (6, 6), strides=(2, 2))
        params = module.init(jax.random.PRNGKey(0), x)
        params = {
            "params": {
                **params["params"],
                "bias": jnp.zeros((4,), jnp.float32),
            }
        }
        with pytest.raises(ValueError, match="no bias"):
            module.apply(params, x)

    def test_env_knob_validation(self, monkeypatch):
        monkeypatch.setenv("T2R_STEM_S2D", "yes")
        with pytest.raises(ValueError, match="T2R_STEM_S2D"):
            stem_s2d_enabled()
        monkeypatch.setenv("T2R_STEM_S2D", "auto")
        assert stem_s2d_enabled() is False


class TestGrasping44Wiring:
    def test_same_params_and_outputs_both_lowerings(self, monkeypatch):
        from tensor2robot_tpu.research.qtopt.networks import Grasping44

        model = Grasping44(num_convs=(1, 1, 1))
        images = jax.random.normal(jax.random.PRNGKey(0), (2, 96, 96, 3))
        gp = jax.random.normal(jax.random.PRNGKey(1), (2, 10))

        monkeypatch.setenv("T2R_STEM_S2D", "0")
        v_plain = model.init(jax.random.PRNGKey(2), images, gp,
                             is_training=False)
        (out_plain, _) = model.apply(v_plain, images, gp, is_training=False)

        monkeypatch.setenv("T2R_STEM_S2D", "1")
        v_s2d = model.init(jax.random.PRNGKey(2), images, gp,
                           is_training=False)
        # Checkpoint compatibility: identical tree structure and shapes.
        assert jax.tree_util.tree_structure(
            v_plain
        ) == jax.tree_util.tree_structure(v_s2d)
        # The SAME variables drive both lowerings to the same output.
        (out_s2d, _) = model.apply(v_plain, images, gp, is_training=False)
        np.testing.assert_allclose(
            np.asarray(out_s2d), np.asarray(out_plain), rtol=1e-4, atol=1e-4
        )


class TestStructural:
    # Note: match the HLO op-call form ("gather(") — the plain word also
    # appears in stack-frame METADATA whenever any enclosing Python
    # function name contains it.

    def test_fwd_lowering_is_one_conv_no_indexed_ops(self):
        """The fold must stay reshape/transpose + ONE convolution: a
        gather or scatter in the lowered module would defeat the MXU
        purpose of the transform."""
        s2d = SpaceToDepthConv(32, (6, 6), strides=(2, 2))
        x = jnp.zeros((2, 96, 96, 3))
        v = s2d.init(jax.random.PRNGKey(0), x)
        txt = (
            jax.jit(lambda v, x: s2d.apply(v, x))
            .lower(v, x)
            .compile()
            .as_text()
        )
        assert txt.count(" convolution(") == 1
        assert " gather(" not in txt
        assert " scatter(" not in txt
        assert "select-and-scatter" not in txt

    def test_bwd_lowering_has_no_indexed_ops(self):
        s2d = SpaceToDepthConv(16, (6, 6), strides=(2, 2))
        x = jnp.zeros((2, 48, 48, 3))
        v = s2d.init(jax.random.PRNGKey(0), x)
        txt = (
            jax.jit(
                jax.grad(
                    lambda v, x: jnp.sum(s2d.apply(v, x) ** 2), argnums=(0, 1)
                )
            )
            .lower(v, x)
            .compile()
            .as_text()
        )
        assert " gather(" not in txt
        assert " scatter(" not in txt
        assert "select-and-scatter" not in txt
