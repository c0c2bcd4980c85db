"""BatchNorm with a deferrable running-stats update.

Drop-in for `flax.linen.BatchNorm` (same variables, same normalize
numerics — bit-parity with the flax module is pinned by
tests/test_batch_norm.py across dtypes and modes) with one addition:
when the enclosing apply opens a mutable `batch_stats_new` collection,
TRAIN mode writes this layer's RAW batch mean/var (plus its momentum)
there and leaves the `batch_stats` running stats untouched. The trainer
then folds every layer's stats into the running stats in ONE fused
cross-layer axpy (train_eval.CompiledModel(fuse_batch_stats_update=True))
and the live train state carries all of them as a single vector — one
input buffer instead of ~2 tiny [C]-vector buffers per BN layer, on the
hypothesis that small transfers pay a fixed per-DMA latency (the round-3
on-chip profile billed ~180 ms/step to small BN-param copy-starts; not
re-measured).

Without `batch_stats_new` in the mutable list this module behaves
exactly like flax BatchNorm (in-place EMA when `batch_stats` is
mutable), so policies, predictors, eval, and non-fused trainers see no
difference.

The normalize/stats math is implemented here (not delegated to flax's
private `_normalize`/`_compute_stats` helpers, which carry no stability
guarantee across flax upgrades): statistics promote to float32, the
variance uses the fast E[x^2]-E[x]^2 form clamped at zero, and the
output dtype follows flax's canonicalize_dtype promotion — the exact
recipe flax 0.12 uses, enforced by the parity test rather than by a
private import.

Behavioral reference for the consumers: tensor2robot research models'
slim batch_norm usage (research/qtopt/networks.py:444-458 arg_scope).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen import dtypes as _flax_dtypes
from jax import lax

NEW_STATS_COLLECTION = "batch_stats_new"


def _feature_axes(ndim: int, axis: int) -> tuple:
    return (axis % ndim,)


class BatchNorm(nn.Module):
    """flax.linen.BatchNorm twin whose stats update can be deferred.

    Attribute subset matches the flax module (the ones this codebase
    uses); outputs are bit-identical to `nn.BatchNorm` in every mode
    (tests/test_batch_norm.py).
    """

    use_running_average: Optional[bool] = None
    axis: int = -1
    momentum: float = 0.99
    epsilon: float = 1e-5
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32
    use_bias: bool = True
    use_scale: bool = True
    bias_init: Any = nn.initializers.zeros
    scale_init: Any = nn.initializers.ones

    @nn.compact
    def __call__(self, x, use_running_average: Optional[bool] = None):
        use_running_average = nn.merge_param(
            "use_running_average",
            self.use_running_average,
            use_running_average,
        )
        feature_axes = _feature_axes(x.ndim, self.axis)
        reduction_axes = tuple(
            i for i in range(x.ndim) if i not in feature_axes
        )
        feature_shape = [x.shape[ax] for ax in feature_axes]

        ra_mean = self.variable(
            "batch_stats",
            "mean",
            lambda s: jnp.zeros(s, jnp.float32),
            feature_shape,
        )
        ra_var = self.variable(
            "batch_stats",
            "var",
            lambda s: jnp.ones(s, jnp.float32),
            feature_shape,
        )

        if use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            # Statistics in (at least) float32 — half-precision inputs
            # must not accumulate their own reductions; fast variance
            # E[x^2] - E[x]^2 clamped at zero against round-off.
            stats_dtype = jnp.promote_types(
                self.dtype or x.dtype, jnp.float32
            )
            x32 = x.astype(stats_dtype)
            mean = x32.mean(reduction_axes)
            mean2 = lax.square(x32).mean(reduction_axes)
            var = jnp.maximum(0.0, mean2 - lax.square(mean))
            if not self.is_initializing():
                if self.is_mutable_collection(NEW_STATS_COLLECTION):
                    # Deferred: raw batch stats (and this layer's decay)
                    # go to their own collection; the trainer applies the
                    # EMA for every layer at once.
                    self.variable(
                        NEW_STATS_COLLECTION,
                        "mean",
                        lambda: jnp.zeros(feature_shape, jnp.float32),
                    ).value = mean
                    self.variable(
                        NEW_STATS_COLLECTION,
                        "var",
                        lambda: jnp.ones(feature_shape, jnp.float32),
                    ).value = var
                    self.variable(
                        NEW_STATS_COLLECTION,
                        "momentum",
                        lambda: jnp.asarray(self.momentum, jnp.float32),
                    ).value = jnp.asarray(self.momentum, jnp.float32)
                elif self.is_mutable_collection("batch_stats"):
                    # flax-identical in-place EMA.
                    ra_mean.value = (
                        self.momentum * ra_mean.value
                        + (1 - self.momentum) * mean
                    )
                    ra_var.value = (
                        self.momentum * ra_var.value
                        + (1 - self.momentum) * var
                    )

        # Normalize exactly as flax does: subtract, rsqrt-scale (scale
        # folded into the multiplier), bias, then canonical dtype.
        stats_shape = [1] * x.ndim
        for ax in feature_axes:
            stats_shape[ax] = x.shape[ax]
        mean_b = mean.reshape(stats_shape)
        var_b = var.reshape(stats_shape)
        y = x - mean_b
        mul = lax.rsqrt(var_b + self.epsilon)
        args = [x]
        if self.use_scale:
            scale = self.param(
                "scale", self.scale_init, feature_shape, self.param_dtype
            ).reshape(stats_shape)
            mul *= scale
            args.append(scale)
        y *= mul
        if self.use_bias:
            bias = self.param(
                "bias", self.bias_init, feature_shape, self.param_dtype
            ).reshape(stats_shape)
            y += bias
            args.append(bias)
        out_dtype = _flax_dtypes.canonicalize_dtype(*args, dtype=self.dtype)
        return jnp.asarray(y, out_dtype)
