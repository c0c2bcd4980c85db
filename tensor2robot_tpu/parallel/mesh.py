"""Device mesh construction and sharding rules.

The trainer compiles every step against a `jax.sharding.Mesh` with named
axes; parallelism is data-parallel by default (the reference's TPUEstimator
batch-sharding + CrossShardOptimizer all-reduce, which GSPMD reproduces as
psum over the 'data' axis), with optional fsdp/model/sequence axes available
for larger networks — the axes slot into the same mesh without touching
model code.

Multi-host: `initialize_distributed()` wires jax.distributed so each host
contributes its local devices to one global mesh over ICI/DCN; the
file-based learner<->robot bus is unchanged (see export/predictors).
"""

from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
MODEL_AXIS = "model"
SEQUENCE_AXIS = "sequence"
PIPE_AXIS = "pipe"
EXPERT_AXIS = "expert"

#: Minimum leaf size (elements) that earns a sharded layout. Below it a
#: leaf stays replicated: sharding a bias buys nothing and costs
#: collectives. ONE constant shared by every rule here and by the
#: planner's memory/scoring model (parallel/planner.py) — it used to be
#: repeated inline in weight_update_sharding and param_sharding, which is
#: exactly the kind of drift the planner exists to end.
MIN_WEIGHT_SIZE = 2 ** 14

#: Param-tree key under which a pipelined module stores its stacked
#: [S, ...] per-stage parameters (layers/transformer.py pipelined
#: encoder); pipe_stage_param_rule shards that subtree's dim 0 over pipe.
PIPE_STAGES_KEY = "pipe_stages"


def require_devices() -> Sequence[jax.Device]:
    """`jax.devices()` for bring-up and measurement paths: raises unless
    the platform is `tpu` or the CPU was asked for explicitly
    (`JAX_PLATFORMS=cpu` in the environment).

    The system is written for the chip, and a run that quietly lands on
    another backend reports numbers nobody deploys. No probe, no retry,
    no fallback: a machine whose chip cannot be opened fails inside
    `jax.devices()` itself.
    """
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            f"jax found platform {platform!r} "
            f"({devices[0].device_kind!r} x{len(devices)}), not 'tpu'. "
            "This path runs on the chip; for a CPU run ask for it "
            "explicitly with JAX_PLATFORMS=cpu."
        )
    return devices


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host bring-up over DCN. No-ops on single-process runs.

    Args default from the standard env (JAX_COORDINATOR_ADDRESS etc.), the
    JAX-native analogue of the reference's TF_CONFIG cluster plumbing
    (input_generators/default_input_generator.py:32-44).
    """
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    if coordinator_address is None:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def make_mesh(
    data: Optional[int] = None,
    fsdp: int = 1,
    model: int = 1,
    sequence: int = 1,
    pipe: int = 1,
    expert: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Builds a mesh over (data, fsdp, model, sequence, pipe, expert) axes.

    `data=None` absorbs all remaining devices. Axis sizes must multiply to
    the device count. Device order follows jax.devices(), which enumerates
    ICI-contiguous chips first — so the fastest-varying (model/sequence/
    pipe/expert) axes land on ICI neighbors and data-parallel all-reduce
    rides the slower links, the standard TPU layout.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    fixed = fsdp * model * sequence * pipe * expert
    if data is None:
        if n % fixed != 0:
            raise ValueError(
                f"{n} devices not divisible by "
                f"fsdp*model*sequence*pipe*expert={fixed}"
            )
        data = n // fixed
    if data * fixed != n:
        raise ValueError(
            f"Mesh {data}x{fsdp}x{model}x{sequence}x{pipe}x{expert} "
            f"!= {n} devices"
        )
    array = np.asarray(devices).reshape(
        data, fsdp, model, sequence, pipe, expert
    )
    return Mesh(
        array,
        (DATA_AXIS, FSDP_AXIS, MODEL_AXIS, SEQUENCE_AXIS, PIPE_AXIS,
         EXPERT_AXIS),
    )


#: The PartitionSpec twins of the shardings below, for callers (the
#: quantized shard_map step, the planner) that speak specs rather than
#: placed shardings. train/ code must consume these instead of spelling
#: raw PartitionSpec(...) — the sharding-outside-planner lint
#: (analysis/lints.py) enforces it.
REPLICATED_SPEC = PartitionSpec()
BATCH_SPEC = PartitionSpec((DATA_AXIS, FSDP_AXIS))
FLAT_SHARD_SPEC = PartitionSpec(DATA_AXIS)


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Batch sharding: leading dim split over data (and fsdp, which acts as
    extra data parallelism for the input batch in fsdp regimes)."""
    return NamedSharding(mesh, BATCH_SPEC)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, REPLICATED_SPEC)


def flat_shard_sharding(mesh: Mesh) -> NamedSharding:
    """Dim-0 sharding over the data axis: the flat block-padded mirror
    layout of the quantized ZeRO-2 regime (opt state, EMA, residual)."""
    return NamedSharding(mesh, FLAT_SHARD_SPEC)


def stacked_batch_sharding(mesh: Mesh) -> NamedSharding:
    """[K, B, ...] scan-stacked batches: scan dim replicated, batch dim
    split over data/fsdp (train/infeed.shard_stacked_batch's layout)."""
    return NamedSharding(
        mesh, PartitionSpec(None, (DATA_AXIS, FSDP_AXIS))
    )


def batch_partition_spec(mesh: Mesh, shape) -> PartitionSpec:
    """Per-leaf batch spec mirroring shard_batch's tolerance: leading dim
    divisible by the data*fsdp extent shards, everything else replicates."""
    divisor = mesh.shape[DATA_AXIS] * mesh.shape[FSDP_AXIS]
    if len(shape) >= 1 and shape[0] % divisor == 0:
        return BATCH_SPEC
    return REPLICATED_SPEC


def shard_batch(batch, mesh: Mesh):
    """Places a host batch onto the mesh, leading axis split across data.

    Training batches (drop_remainder upstream) divide evenly and shard; a
    leaf whose leading dim does not divide the data axis (small predict
    batches, scalars) is replicated instead — correct, at the cost of
    redundant compute, which only ever happens off the training hot path.
    """
    sharding = data_sharding(mesh)
    replicated_sharding = replicated(mesh)
    divisor = mesh.shape[DATA_AXIS] * mesh.shape[FSDP_AXIS]

    def put(leaf):
        shape = getattr(leaf, "shape", ())
        if len(shape) >= 1 and shape[0] % divisor == 0:
            return jax.device_put(leaf, sharding)
        return jax.device_put(leaf, replicated_sharding)

    return jax.tree_util.tree_map(put, batch)


def _assign_largest_divisible_dim(spec, shape, axis_size, axis_name) -> None:
    """Marks the largest still-unsharded dim divisible by axis_size with
    axis_name (in place); leaves spec untouched when none divides."""
    dims = sorted(range(len(shape)), key=lambda i: shape[i], reverse=True)
    for dim in dims:
        if spec[dim] is None and shape[dim] % axis_size == 0:
            spec[dim] = axis_name
            return


def weight_update_sharding(
    mesh: Mesh,
    min_weight_size: int = MIN_WEIGHT_SIZE,
    axes: Tuple[str, ...] = (DATA_AXIS,),
):
    """Sharding rule for OPTIMIZER-SIDE state under replicated parameters
    (cross-replica weight-update sharding, Xu et al. arXiv:2004.13336 —
    the ZeRO-2 layout): parameters stay replicated for the forward/
    backward, but optimizer moments and the EMA mirror shard their
    largest divisible dim over the replica axes; GSPMD turns the gradient
    all-reduce into reduce-scatter + sharded update + all-gather. Cuts
    the optimizer-state footprint by the replica-group size with no
    model-side change. Leaves with no dim divisible by the group size
    stay replicated (no padding is introduced).

    axes: the mesh axes parameters are replicated over that the update
    shards across. The classic pure-DP regime is ("data",) — a single
    bare axis name in the spec, byte-for-byte today's layout. A composed
    plan (parallel/planner.py) passes every replica axis, e.g.
    ("data", "sequence") on a DP x SP x PP mesh, sharding the update
    over the PRODUCT of the replica axes — the generalization no
    hand-wired regime could spell.
    """
    axes = tuple(axes)
    group_size = int(np.prod([mesh.shape[axis] for axis in axes]))
    # A single axis keeps the bare-name spec entry (PartitionSpec("data"),
    # not PartitionSpec(("data",))) so existing layouts compare equal.
    axis_entry = axes[0] if len(axes) == 1 else axes

    def rule(leaf):
        shape = getattr(leaf, "shape", None)
        if (
            shape is None
            or group_size == 1
            or np.prod(shape) < min_weight_size
        ):
            return NamedSharding(mesh, PartitionSpec())
        spec = [None] * len(shape)
        _assign_largest_divisible_dim(spec, shape, group_size, axis_entry)
        return NamedSharding(mesh, PartitionSpec(*spec))

    return rule


def pipe_stage_param_rule(mesh: Mesh, base_rule):
    """Path-aware sharding rule layering pipeline-stage placement over a
    per-leaf base rule: any leaf under a PIPE_STAGES_KEY tree key whose
    leading dim equals the pipe-axis size shards dim 0 over `pipe` (the
    layout pipeline_apply consumes); every other leaf falls through to
    base_rule. Optimizer moments and the EMA mirror the param tree's
    keys, so the same rule places them without special cases.
    """
    pipe_size = mesh.shape[PIPE_AXIS]
    stage_sharding = NamedSharding(mesh, PartitionSpec(PIPE_AXIS))

    def rule(path, leaf):
        shape = getattr(leaf, "shape", None)
        if (
            pipe_size > 1
            and shape
            and shape[0] == pipe_size
            and any(
                getattr(entry, "key", None) == PIPE_STAGES_KEY
                for entry in path
            )
        ):
            return stage_sharding
        return base_rule(leaf)

    return rule


def param_sharding(mesh: Mesh, min_weight_size: int = MIN_WEIGHT_SIZE):
    """Tree-map-able parameter sharding rule over the fsdp and model axes.

    Tensor parallelism: matrix/conv-kernel leaves shard their OUTPUT dim
    (last axis — flax dense kernels are [in, out], conv kernels HWIO) over
    the `model` axis; GSPMD then propagates the sharding through the
    matmul and inserts the per-layer collectives (the Megatron column
    split). FSDP: the largest remaining divisible dim shards over `fsdp`
    (ZeRO-3-style parameter sharding; gathered on use). Small leaves stay
    replicated — sharding a bias buys nothing and costs collectives.
    """
    model_size = mesh.shape[MODEL_AXIS]
    fsdp_size = mesh.shape[FSDP_AXIS]

    def rule(leaf):
        shape = getattr(leaf, "shape", None)
        if (
            shape is None
            or (model_size == 1 and fsdp_size == 1)
            or np.prod(shape) < min_weight_size
        ):
            return NamedSharding(mesh, PartitionSpec())
        spec = [None] * len(shape)
        if model_size > 1 and len(shape) >= 2 and shape[-1] % model_size == 0:
            spec[-1] = MODEL_AXIS
        if fsdp_size > 1:
            _assign_largest_divisible_dim(spec, shape, fsdp_size, FSDP_AXIS)
        return NamedSharding(mesh, PartitionSpec(*spec))

    return rule


