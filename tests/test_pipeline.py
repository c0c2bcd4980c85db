"""Pipeline parallelism: GPipe scheduling over the pipe mesh axis.

Correctness oracle: pipeline_apply must equal the plain sequential
composition of the stages (and so must its gradients) — the schedule is an
execution strategy, not a semantic change.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.parallel import mesh as mesh_lib
from tensor2robot_tpu.parallel import pipeline


def _stage_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def _make_stages(num_stages, features, seed=0):
    rng = np.random.RandomState(seed)
    return [
        {
            "w": jnp.asarray(
                rng.randn(features, features).astype(np.float32) * 0.3
            ),
            "b": jnp.asarray(rng.randn(features).astype(np.float32) * 0.1),
        }
        for _ in range(num_stages)
    ]


def _sequential(stages, x):
    for params in stages:
        x = _stage_fn(params, x)
    return x


class TestPipelineApply:
    @pytest.mark.parametrize("num_stages,num_micro", [(2, 4), (4, 8), (8, 8)])
    def test_matches_sequential(self, num_stages, num_micro):
        mesh = mesh_lib.make_mesh(pipe=num_stages)
        features, batch = 6, 16
        stages = _make_stages(num_stages, features)
        stacked = pipeline.stack_stage_params(stages)
        stacked = jax.device_put(
            stacked, pipeline.stage_sharding(mesh, stacked)
        )
        x = jnp.asarray(
            np.random.RandomState(1)
            .randn(batch, features)
            .astype(np.float32)
        )
        out = pipeline.pipeline_apply(
            _stage_fn, stacked, x, mesh=mesh, num_microbatches=num_micro
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_sequential(stages, x)),
            rtol=1e-5, atol=1e-5,
        )

    def test_single_stage_identity_schedule(self):
        mesh = mesh_lib.make_mesh(data=8, pipe=1)
        stages = _make_stages(1, 4)
        stacked = pipeline.stack_stage_params(stages)
        x = jnp.ones((8, 4), jnp.float32)
        out = pipeline.pipeline_apply(
            _stage_fn, stacked, x, mesh=mesh, num_microbatches=2
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_sequential(stages, x)),
            rtol=1e-6, atol=1e-6,
        )

    def test_batch_not_divisible_raises(self):
        mesh = mesh_lib.make_mesh(pipe=4)
        stages = _make_stages(4, 4)
        stacked = pipeline.stack_stage_params(stages)
        with pytest.raises(ValueError, match="not divisible"):
            pipeline.pipeline_apply(
                _stage_fn,
                stacked,
                jnp.ones((10, 4)),
                mesh=mesh,
                num_microbatches=3,
            )

    def test_gradients_match_sequential(self):
        """Pipeline-parallel TRAINING: grads through the schedule equal
        grads through the plain composition, for params and inputs."""
        num_stages, num_micro = 4, 4
        mesh = mesh_lib.make_mesh(pipe=num_stages)
        features, batch = 4, 8
        stages = _make_stages(num_stages, features, seed=3)
        stacked = pipeline.stack_stage_params(stages)
        x = jnp.asarray(
            np.random.RandomState(5).randn(batch, features).astype(np.float32)
        )
        target = jnp.ones((batch, features), jnp.float32)

        def pipe_loss(stacked_params, x):
            out = pipeline.pipeline_apply(
                _stage_fn, stacked_params, x, mesh=mesh,
                num_microbatches=num_micro,
            )
            return jnp.mean((out - target) ** 2)

        def seq_loss(stacked_params, x):
            for i in range(num_stages):
                params = jax.tree_util.tree_map(
                    lambda leaf: leaf[i], stacked_params
                )
                x = _stage_fn(params, x)
            return jnp.mean((x - target) ** 2)

        pipe_grads = jax.jit(jax.grad(pipe_loss, argnums=(0, 1)))(stacked, x)
        seq_grads = jax.jit(jax.grad(seq_loss, argnums=(0, 1)))(stacked, x)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
            ),
            pipe_grads,
            seq_grads,
        )

    def test_stage_params_actually_sharded(self):
        mesh = mesh_lib.make_mesh(pipe=8)
        stages = _make_stages(8, 8)
        stacked = pipeline.stack_stage_params(stages)
        placed = jax.device_put(
            stacked, pipeline.stage_sharding(mesh, stacked)
        )
        assert not placed["w"].sharding.is_fully_replicated
        assert placed["w"].sharding.spec[0] == mesh_lib.PIPE_AXIS


class TestShardMapRematScanVma:
    """pipeline_apply runs its shard_map with replication checking ON,
    which rests on jax tracking varying-manual-axes through a scan that
    is differentiated under jax.checkpoint. Older jax lost the carry
    annotations there ("Scan carry input and output got mismatched
    replication types") and the pipeline carried a check-off workaround;
    jax 0.9.0 tracks it. This is the minimal shape of that program —
    scan + collective in the body + remat — so a jax change that breaks
    it again is noticed here, not as a mystery flip in the composed
    trainer test."""

    def test_scan_in_shard_map_differentiates_under_remat(self):
        from tensor2robot_tpu.parallel import collectives

        mesh = mesh_lib.make_mesh(pipe=2, devices=jax.devices()[:2])

        def body_fn(x):
            def tick(carry, _):
                shifted = collectives.ppermute(
                    carry, mesh_lib.PIPE_AXIS, perm=[(0, 1)]
                )
                return shifted + x, None

            carry0 = jax.lax.pcast(
                jnp.zeros_like(x), (mesh_lib.PIPE_AXIS,), to="varying"
            )
            out, _ = jax.lax.scan(tick, carry0, jnp.arange(3))
            return collectives.psum(out, mesh_lib.PIPE_AXIS)

        mapped = collectives.shard_map(
            body_fn,
            mesh=mesh,
            in_specs=pipeline.PartitionSpec(),
            out_specs=pipeline.PartitionSpec(),
        )

        def loss(x):
            return jnp.sum(jax.checkpoint(mapped)(x))

        # jit: eager shard_map cannot evaluate the closed_call remat
        # introduces; the production path (CompiledModel) is always jit.
        grads = jax.jit(jax.grad(loss))(jnp.ones((4,), jnp.float32))
        assert np.all(np.isfinite(np.asarray(grads)))
