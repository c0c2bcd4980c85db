"""Pipeline parallelism: GPipe-style microbatch scheduling over a mesh axis.

Beyond the reference (SURVEY §2.7 lists pipeline parallelism as ABSENT
there): stages live on the `pipe` mesh axis, activations move stage-to-
stage with `lax.ppermute` over ICI, and a `lax.scan` over clock ticks runs
the classic GPipe schedule — with M microbatches and S stages the scan has
M + S - 1 ticks, each device computing its stage on the microbatch
currently resident. The whole schedule is ONE jitted SPMD program: no
host-side orchestration, no per-stage processes like GPU pipeline runtimes
use; the bubble (S-1 idle ticks per device) is the standard GPipe cost and
shrinks as M grows.

Everything is differentiable (ppermute's transpose is the reverse
ppermute), so `jax.grad` through `pipeline_apply` yields pipeline-parallel
training: the backward pass streams gradients through the ring in reverse
— exactly the behavior hand-written 1F1B schedules build manually.

Usage:
    params  = [stage_init(rng_i) for i in range(S)]   # same tree per stage
    stacked = stack_stage_params(params)              # leaves [S, ...]
    stacked = jax.device_put(stacked, stage_sharding(mesh, stacked))
    out     = pipeline_apply(stage_fn, stacked, x, mesh=mesh,
                             num_microbatches=M)
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import NamedSharding, PartitionSpec

from tensor2robot_tpu.parallel import collectives
from tensor2robot_tpu.parallel.collectives import shard_map
from tensor2robot_tpu.parallel.mesh import PIPE_AXIS


def stack_stage_params(stage_params: Sequence[Any]):
    """Stacks S per-stage parameter trees into one tree of [S, ...] leaves
    (the layout `pipeline_apply` consumes; shard dim 0 over the pipe axis).
    All stages must share one tree structure — a pipeline is a chain of
    identical stage programs with different weights."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *stage_params
    )


def stage_sharding(mesh, stacked_params):
    """Shardings placing stacked [S, ...] stage params dim-0 over `pipe`."""
    sharding = NamedSharding(mesh, PartitionSpec(PIPE_AXIS))
    return jax.tree_util.tree_map(lambda _: sharding, stacked_params)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params,
    x: jax.Array,
    *,
    mesh,
    num_microbatches: int,
    axis_name: str = PIPE_AXIS,
    batch_axis: str | None = None,
    sequence_axis: str | None = None,
):
    """Runs x through S chained stages with GPipe microbatch overlap.

    Args:
      stage_fn: (stage_params, microbatch [mb, ...]) -> [mb, ...]; applied
        by every device to its resident microbatch each tick. Input and
        output shapes must match across stages (chainable).
      stacked_params: tree of [S, ...] leaves (see stack_stage_params),
        dim 0 sharded over the pipe axis.
      x: [batch, ...] with batch divisible by num_microbatches.
      mesh: mesh whose `axis_name` axis has size S.
      num_microbatches: M; the bubble fraction is (S-1)/(M+S-1).
      batch_axis: optional mesh axis the batch is data-sharded over
        (dp x pp composition): each microbatch's example dim shards over
        it, the schedule runs on local examples, and gradients psum over
        it via shard_map's transpose. The per-microbatch size must divide
        by that axis.
      sequence_axis: optional mesh axis x's dim 1 (the sequence) is
        sharded over (sp x pp composition, the 3D DP x SP x PP regime of
        parallel/planner.py): each microbatch carries only its local
        sequence shard and stage_fn is expected to run sequence-parallel
        attention in MANUAL mode over this axis
        (ring_attention.ring_attention_manual) — the axis is manual
        inside this shard_map, so ppermute over it composes with the
        pipeline's own rotation. The sequence length must divide by the
        axis size.

    Returns [batch, ...]: the composition stage_{S-1}(...stage_0(x)),
    replicated over the pipe axis (data-sharded over batch_axis /
    sequence-sharded over sequence_axis if given).
    """
    num_stages = mesh.shape[axis_name]
    batch = x.shape[0]
    if batch % num_microbatches != 0:
        raise ValueError(
            f"batch {batch} not divisible by microbatches {num_microbatches}"
        )
    micro = jnp.reshape(x, (num_microbatches, batch // num_microbatches)
                        + x.shape[1:])
    batch_entry = None
    if batch_axis is not None:
        data_size = mesh.shape[batch_axis]
        if (batch // num_microbatches) % data_size != 0:
            raise ValueError(
                f"microbatch size {batch // num_microbatches} not divisible "
                f"by {batch_axis} axis size {data_size}"
            )
        batch_entry = batch_axis
    if sequence_axis is not None:
        seq_size = mesh.shape[sequence_axis]
        if x.ndim < 2 or x.shape[1] % seq_size != 0:
            raise ValueError(
                f"sequence dim {x.shape[1] if x.ndim > 1 else None} not "
                f"divisible by {sequence_axis} axis size {seq_size}"
            )
        x_spec = PartitionSpec(None, batch_entry, sequence_axis)
    elif batch_entry is not None:
        x_spec = PartitionSpec(None, batch_entry)
    else:
        x_spec = PartitionSpec()

    spec_params = jax.tree_util.tree_map(
        lambda _: PartitionSpec(axis_name), stacked_params
    )
    shard_mapped = shard_map(
        functools.partial(
            _pipeline_shard,
            stage_fn=stage_fn,
            num_stages=num_stages,
            num_microbatches=num_microbatches,
            axis_name=axis_name,
            varying_axes=(axis_name,)
            + ((batch_axis,) if batch_axis is not None else ())
            + ((sequence_axis,) if sequence_axis is not None else ()),
        ),
        mesh=mesh,
        in_specs=(spec_params, x_spec),
        out_specs=x_spec,
    )
    out = shard_mapped(stacked_params, micro)
    return jnp.reshape(out, (batch,) + out.shape[2:])


def _pipeline_shard(stacked_params, micro, *, stage_fn, num_stages,
                    num_microbatches, axis_name, varying_axes=None):
    """The per-device program: scan over M+S-1 clock ticks.

    Each device sees its own stage's params ([1, ...] leaves from the pipe
    sharding) and the full (replicated) microbatch stack. Tick t: stage 0
    injects microbatch min(t, M-1) (ticks past M recompute the last
    microbatch — garbage that never reaches the output window), every
    stage applies itself to its resident activation, and ppermute shifts
    results one stage down the chain. The last stage's activation at tick
    t is microbatch t-S+1 fully composed; a masked accumulate collects it.
    """
    stage_idx = lax.axis_index(axis_name)
    local_params = jax.tree_util.tree_map(
        lambda leaf: leaf[0], stacked_params
    )
    num_ticks = num_microbatches + num_stages - 1
    mb_shape = micro.shape[1:]

    def tick(carry, t):
        resident, out_acc = carry
        # Stage 0 picks up the next microbatch; other stages keep what the
        # previous tick's shift delivered.
        inject = lax.dynamic_index_in_dim(
            micro, jnp.minimum(t, num_microbatches - 1), keepdims=False
        )
        current = jnp.where(stage_idx == 0, inject, resident)
        y = stage_fn(local_params, current)
        # The final stage's result for this tick is a finished microbatch
        # (valid once the pipeline has filled: t >= S-1).
        out_t = jnp.where(stage_idx == num_stages - 1, y, jnp.zeros_like(y))
        out_slot = jnp.clip(t - (num_stages - 1), 0, num_microbatches - 1)
        valid = (t >= num_stages - 1).astype(y.dtype)
        out_acc = lax.dynamic_update_index_in_dim(
            out_acc,
            lax.dynamic_index_in_dim(out_acc, out_slot, keepdims=False)
            + valid * out_t,
            out_slot,
            axis=0,
        )
        # Shift activations one stage down the chain (last stage's output
        # falls off the end; stage 0 gets zeros it overwrites next tick).
        shifted = collectives.ppermute(
            y,
            axis_name,
            perm=[(i, i + 1) for i in range(num_stages - 1)],
        )
        return (shifted, out_acc), None

    resident0 = jnp.zeros(mb_shape, micro.dtype)
    out0 = jnp.zeros((num_microbatches,) + mb_shape, micro.dtype)
    # The body makes the carry vary over the pipe axis (stage_idx masks,
    # ppermute) and over the batch axis when the input is data-sharded;
    # mark the initial carry the same way for shard_map's varying-manual-
    # axes tracking.
    axes = tuple(varying_axes or (axis_name,))
    resident0, out0 = jax.tree_util.tree_map(
        lambda leaf: lax.pcast(leaf, axes, to="varying"),
        (resident0, out0),
    )
    (_, out_acc), _ = lax.scan(
        tick, (resident0, out0), jnp.arange(num_ticks)
    )
    # Only the last stage holds real outputs; the masked psum replicates
    # them to every stage (out_specs is replicated), and routes cotangents
    # back to the last stage under differentiation.
    return collectives.psum(
        jnp.where(stage_idx == num_stages - 1, out_acc,
                  jnp.zeros_like(out_acc)),
        axis_name,
    )
