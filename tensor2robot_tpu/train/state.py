"""TrainState: the complete training snapshot as one pytree.

Holds model variables (params + mutable collections), optimizer state, step,
and — when the model requests moving-average params — an EMA copy. The EMA
replaces the reference's MovingAverageOptimizer + swapping-saver machinery
(models/optimizers.py:133-159): checkpoints persist both raw and averaged
params; export selects the EMA (see export/).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import flax.struct
import jax
import jax.flatten_util  # registers jax.flatten_util.ravel_pytree
import jax.numpy as jnp
import optax


def _is_flat_ema(ema) -> bool:
    """True when the EMA is stored as one concatenated vector (the
    quantized ZeRO-2 regime, train_eval.CompiledModel._init_quant_state)
    rather than a params-shaped tree."""
    return hasattr(ema, "ndim") and ema.ndim == 1


def ema_as_tree(ema_params, params_tree):
    """EMA as a params-shaped tree, whatever the stored layout.

    Every consumer that reads ema_params — live state, restored
    checkpoints (predictors, warm start) — must route through this, not
    use the raw value: the quantized ZeRO-2 regime (the only producer
    of a flat EMA) stores it as a single 1-D vector that only this
    unravel, against the matching params structure, turns back into
    variables. That vector is block-padded past the parameter count
    (parallel/collectives.FlatShardLayout); the zero-gradient tail
    never moves and is dropped here."""
    if _is_flat_ema(ema_params):
        flat, unravel = jax.flatten_util.ravel_pytree(params_tree)
        if ema_params.shape[0] > flat.shape[0]:
            ema_params = ema_params[: flat.shape[0]]
        return unravel(ema_params)
    return ema_params


@flax.struct.dataclass
class TrainState:
    step: jax.Array
    variables: Dict[str, Any]  # {'params': ..., 'batch_stats': ...}
    opt_state: Any
    ema_params: Optional[Any] = None
    #: Error-feedback residual of the quantized gradient collectives
    #: (parallel/collectives.py): {'grad': [N, padded] (dim 0 sharded over
    #: the data axis — each replica's untransmitted gradient remainder),
    #: 'update': [padded] (sharded — each owner-shard's untransmitted
    #: update remainder)}. None outside the quantized ZeRO-2 regime.
    #: Checkpointed with the state so restarts keep the exact trajectory.
    collective_residual: Optional[Any] = None

    @property
    def params(self):
        return self.variables["params"]

    def export_variables(self, use_ema: bool = False) -> Dict[str, Any]:
        """Variables to serve/export: EMA params when present and requested.

        A flat-stored EMA (one concatenated vector; see ema_as_tree) is
        unraveled here against the live params' structure — export/eval
        is the only place the EMA is ever needed as a tree."""
        if use_ema and self.ema_params is not None:
            out = dict(self.variables)
            out["params"] = ema_as_tree(self.ema_params, self.params)
            return out
        return dict(self.variables)


def create_train_state(
    model,
    rng: jax.Array,
    example_features,
    optimizer: optax.GradientTransformation,
) -> TrainState:
    """Initializes variables (with warm-start hook) + optimizer state."""
    variables = model.init_variables(rng, example_features)
    variables = model.maybe_init_from_checkpoint(variables)
    opt_state = optimizer.init(variables["params"])
    if getattr(model, "use_avg_model_params", False):
        ema = jax.tree_util.tree_map(jnp.copy, variables["params"])
    else:
        ema = None
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        variables=variables,
        opt_state=opt_state,
        ema_params=ema,
    )


def update_ema(ema_params, new_params, decay: float):
    """One EMA step, leaf by leaf."""
    return jax.tree_util.tree_map(
        lambda e, p: e * decay + p.astype(e.dtype) * (1.0 - decay),
        ema_params,
        new_params,
    )


def checkpoint_metadata_template(root, step):
    """Abstract restore template read from a checkpoint's OWN metadata,
    with every leaf placed on the local host.

    Restoring with this template makes the read independent of (a) the
    topology the trainer ran on — leaving shardings unset replays the
    checkpoint's sharding file, which cannot be reconstructed on a host
    with a different device count — and (b) the consumer's own guess at
    the saved structure (e.g. which optimizer layout the trainer used).
    Returns a pytree of jax.ShapeDtypeStruct mirroring the on-disk tree.
    """
    import orbax.checkpoint as ocp
    from etils import epath

    meta = ocp.StandardCheckpointHandler().metadata(
        epath.Path(root) / str(step) / "default"
    )
    meta_tree = getattr(meta, "tree", meta)
    host = jax.sharding.SingleDeviceSharding(jax.local_devices()[0])
    return jax.tree_util.tree_map(
        lambda m: jax.ShapeDtypeStruct(m.shape, m.dtype, sharding=host),
        meta_tree,
    )
