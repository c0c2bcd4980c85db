"""Non-overlapping max pool: forward and gradient parity with
nn.max_pool, and the structural pin that its backward is XLA's
SelectAndScatter with no custom_vjp in the way."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.ops.pooling import max_pool

SAME_WINDOWS = [(3, 3), (2, 2), (4, 4), (5, 5)]
SAME_SHAPES = [(2, 236, 236, 4), (2, 79, 79, 4), (1, 6, 6, 3), (3, 7, 11, 2)]
VALID_WINDOWS = [(3, 3), (2, 2)]
VALID_SHAPES = [(2, 7, 11, 3), (1, 6, 6, 2), (2, 9, 8, 4)]


def _grad_pair(x, window, padding):
    """jax.grad of a weighted sum through max_pool and through
    nn.max_pool (weights differ per output, so a cotangent routed to the
    wrong window shows)."""

    def loss(pool):
        def fn(x):
            y = pool(x).astype(jnp.float32)
            weights = jnp.arange(y.size, dtype=jnp.float32).reshape(y.shape)
            return jnp.sum(y * y * (1.0 + weights / y.size))

        return fn

    got = jax.grad(loss(lambda x: max_pool(x, window, padding)))(x)
    want = jax.grad(
        loss(lambda x: nn.max_pool(x, window, strides=window, padding=padding))
    )(x)
    return got, want


class TestForwardParity:
    @pytest.mark.parametrize("window", SAME_WINDOWS)
    @pytest.mark.parametrize("shape", SAME_SHAPES)
    def test_matches_nn_max_pool_same(self, window, shape):
        x = jax.random.normal(jax.random.PRNGKey(0), shape)
        got = max_pool(x, window)
        want = nn.max_pool(x, window, strides=window, padding="SAME")
        assert got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("window", VALID_WINDOWS)
    @pytest.mark.parametrize("shape", VALID_SHAPES)
    def test_matches_nn_max_pool_valid(self, window, shape):
        x = jax.random.normal(jax.random.PRNGKey(4), shape)
        got = max_pool(x, window, "VALID")
        want = nn.max_pool(x, window, strides=window, padding="VALID")
        assert got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_bfloat16(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 9, 8), jnp.bfloat16)
        got = max_pool(x, (3, 3))
        want = nn.max_pool(x, (3, 3), strides=(3, 3), padding="SAME")
        assert got.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(want, np.float32)
        )


class TestGradientParity:
    """jax.grad through max_pool is jax.grad through nn.max_pool, on
    every case of the forward grids (partial windows under SAME, dropped
    remainders under VALID)."""

    @pytest.mark.parametrize("window", SAME_WINDOWS)
    @pytest.mark.parametrize("shape", SAME_SHAPES)
    def test_same(self, window, shape):
        x = jax.random.normal(jax.random.PRNGKey(6), shape)
        got, want = _grad_pair(x, window, "SAME")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("window", VALID_WINDOWS)
    @pytest.mark.parametrize("shape", VALID_SHAPES)
    def test_valid(self, window, shape):
        x = jax.random.normal(jax.random.PRNGKey(8), shape)
        got, want = _grad_pair(x, window, "VALID")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestGradient:
    @pytest.mark.parametrize("window", [(3, 3), (2, 2), (4, 4)])
    def test_matches_select_and_scatter_without_ties(self, window):
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 10, 13, 3))

        def loss(x):
            return jnp.sum(max_pool(x, window) ** 2)

        def loss_xla(x):
            return jnp.sum(
                nn.max_pool(x, window, strides=window, padding="SAME") ** 2
            )

        np.testing.assert_allclose(
            np.asarray(jax.grad(loss)(x)),
            np.asarray(jax.grad(loss_xla)(x)),
            rtol=1e-6,
        )

    def test_gradient_mass_is_preserved(self):
        # Each output's cotangent lands in its window exactly once —
        # including windows that straddle the SAME padding.
        x = jnp.zeros((1, 7, 7, 1))  # all ties everywhere

        def loss(x):
            return jnp.sum(max_pool(x, (3, 3)) * 2.0)

        gx = jax.grad(loss)(x)
        np.testing.assert_allclose(float(jnp.sum(gx)), 2.0 * 3 * 3, rtol=1e-6)

    def test_ties_go_to_the_first_maximum(self):
        x = jnp.array([[1.0, 1.0], [0.0, 1.0]]).reshape(1, 2, 2, 1)
        gx = jax.grad(lambda x: jnp.sum(max_pool(x, (2, 2))))(x)
        np.testing.assert_array_equal(
            np.asarray(gx).reshape(2, 2), np.array([[1.0, 0.0], [0.0, 0.0]])
        )

    def test_valid_gradient_matches_xla_and_zeroes_remainder(self):
        # VALID drops the trailing remainder; those inputs must get zero
        # gradient.
        x = jax.random.normal(jax.random.PRNGKey(5), (2, 7, 11, 3))

        def loss(x):
            return jnp.sum(max_pool(x, (3, 3), "VALID") ** 2)

        def loss_xla(x):
            return jnp.sum(
                nn.max_pool(x, (3, 3), strides=(3, 3), padding="VALID") ** 2
            )

        g = np.asarray(jax.grad(loss)(x))
        g_xla = np.asarray(jax.grad(loss_xla)(x))
        np.testing.assert_allclose(g, g_xla, rtol=1e-6)
        assert np.all(g[:, 6:, :, :] == 0)
        assert np.all(g[:, :, 9:, :] == 0)

    def test_grad_dtype_follows_input(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (1, 6, 6, 2), jnp.bfloat16)
        gx = jax.grad(
            lambda x: jnp.sum(max_pool(x, (2, 2)).astype(jnp.float32))
        )(x)
        assert gx.dtype == jnp.bfloat16


class TestStructural:
    """The backward the CPU tests run is the one the chip runs."""

    def test_backward_is_select_and_scatter(self):
        def loss(x):
            return jnp.sum(max_pool(x, (3, 3)))

        x = jnp.zeros((2, 236, 236, 64), jnp.bfloat16)
        assert "custom_vjp" not in str(jax.make_jaxpr(jax.grad(loss))(x))
        txt = jax.jit(jax.grad(loss)).lower(x).as_text()
        assert txt.count("select_and_scatter") == 1

    def test_grasping44_train_grad_is_select_and_scatter(self):
        """Every pool of the Grasping44 tower goes through max_pool: the
        full network gradient holds a SelectAndScatter and no
        custom_vjp."""
        from tensor2robot_tpu.research.qtopt.networks import Grasping44

        model = Grasping44(num_convs=(1, 1, 1))
        images = jnp.zeros((2, 96, 96, 3), jnp.bfloat16)
        params = jnp.zeros((2, 10), jnp.float32)
        variables = model.init(
            jax.random.PRNGKey(0), images, params, is_training=True
        )

        def loss(v):
            logits, _ = model.apply(
                v, images, params, is_training=True, mutable=["batch_stats"]
            )[0]
            return jnp.sum(logits)

        assert "custom_vjp" not in str(
            jax.make_jaxpr(jax.grad(loss))(variables)
        )
        txt = jax.jit(jax.grad(loss)).lower(variables).as_text()
        assert txt.count("select_and_scatter") == 3


class TestBatchNormDtype:
    def test_tower_activations_stay_bf16(self):
        """BN in compute dtype: with bf16 images no f32 copy of a tower
        activation is produced (the r3 bandwidth finding) — end_points
        carry the compute dtype, while the loss-bearing logits stay f32."""
        from tensor2robot_tpu.research.qtopt.networks import Grasping44

        model = Grasping44(num_convs=(1, 1, 1))
        images = jnp.zeros((2, 96, 96, 3), jnp.bfloat16)
        params = jnp.zeros((2, 10), jnp.float32)
        variables = model.init(
            jax.random.PRNGKey(0), images, params, is_training=True
        )
        (logits, end_points), _ = model.apply(
            variables, images, params, is_training=True,
            mutable=["batch_stats"],
        )
        assert end_points["pool2"].dtype == jnp.bfloat16
        assert end_points["vsum"].dtype == jnp.bfloat16
        assert end_points["final_conv"].dtype == jnp.bfloat16
        assert end_points["fcgrasp"].dtype == jnp.bfloat16
        assert logits.dtype == jnp.float32
        # Running statistics must still accumulate in f32.
        stats = jax.tree_util.tree_leaves(variables["batch_stats"])
        assert all(s.dtype == jnp.float32 for s in stats)
