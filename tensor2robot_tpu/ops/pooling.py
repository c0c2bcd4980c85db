"""Non-overlapping max pooling with a backend-dispatched backward.

Two backward formulations exist for a non-overlapping (window == stride)
max pool — every pool in the Grasping44 tower is of this form (reference
research/qtopt/networks.py:446,460,540):

* XLA-native: `lax.reduce_window`'s registered gradient, which lowers to
  SelectAndScatter.
* Scatter-free (`max_pool_nonoverlap` below): reshape the input into its
  disjoint windows, compare against the broadcast pooled maximum, and
  split the incoming gradient over the mask — pure elementwise/reduce
  work.

Which one wins is a HARDWARE question, and the two measurements disagree:
on CPU the scatter-free VJP removed the top non-gather op of the step
(round-4 HLO census), but TPU's native SelectAndScatter pool gradient
beat the reshape/mask formulation ~3x at the stem activation size
(bs64 236x236x64; round-5 on-chip A/B, not re-measured). `max_pool`
therefore dispatches on the platform each lowering targets: native on
TPU, scatter-free elsewhere; `T2R_POOL_BACKWARD=scatterfree|native`
forces either path (the bench A/B uses this).

The forward stays `lax.reduce_window` (already optimal on TPU); only the
VJP is replaced via `jax.custom_vjp`.

Gradient tie-breaking: where a window holds several elements equal to the
maximum (common after relu: exact zeros), the incoming gradient is split
EQUALLY among them, whereas SelectAndScatter routes it all to the first.
Both are valid subgradients of the same function; the equal split is the
same choice `jnp.max`'s native gradient makes.

Known limitation: `jax.custom_vjp` forecloses FORWARD-mode autodiff —
`jax.jvp`/`jax.jacfwd` through any model containing these pools raises
TypeError, a capability `nn.max_pool` had. No in-repo caller uses
forward mode; if one ever does, the equal-split rule has a natural
linear JVP (mask-weighted tangent average) and the op can be
restructured as `jax.custom_jvp` to support both modes.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from tensor2robot_tpu import flags


def resolve_backward_mode() -> str:
    """Resolves T2R_POOL_BACKWARD to the concrete VJP path.

    Returns "native" or "scatterfree"; unknown values fail fast (a typo
    silently selecting the slow backward would poison a benchmark round).

    "auto" reports the path the CURRENT DEFAULT BACKEND would run — a
    provenance answer (bench payloads), not a promise about every
    execution: `max_pool`'s auto mode dispatches via
    `lax.platform_dependent`, so the VJP is selected by each lowering's
    actual platform and an AOT export compiled for a different backend
    gets THAT backend's path, not this process's. The
    forced modes bake the named path in at trace time on every platform.
    """
    mode = flags.get_enum("T2R_POOL_BACKWARD")
    if mode == "auto":
        return "native" if jax.default_backend() == "tpu" else "scatterfree"
    return mode


def _native_pool(
    x: jax.Array, window: Tuple[int, int], padding: str
) -> jax.Array:
    dims = (1, window[0], window[1], 1)
    # Init must be the -inf LITERAL: jax's reverse-mode rule for max
    # pooling pattern-matches (literal init, lax.max) — a device-array
    # init turns this into a general reduce_window with no transpose.
    return lax.reduce_window(
        x, -jnp.inf, lax.max, dims, dims, padding.upper()
    )


def max_pool(
    x: jax.Array, window: Tuple[int, int], padding: str = "SAME"
) -> jax.Array:
    """Non-overlapping max pool with the fastest backward for the backend.

    Forward is `lax.reduce_window` on every path (bit-identical results);
    the paths differ only in the VJP (and in subgradient tie-breaking:
    native SelectAndScatter routes tied gradients to the first maximal
    element, scatter-free splits them equally — both valid subgradients).

    Auto mode binds at LOWERING, not trace: `lax.platform_dependent`
    embeds both formulations and selects by the platform each lowering
    actually targets, so a computation traced on one backend but compiled
    for another (AOT export, explicit backend= jit) runs the VJP that is
    fast THERE. Forced modes (T2R_POOL_BACKWARD=native|scatterfree) stay
    trace-time on purpose — they exist for A/B benches that must pin one
    path everywhere.
    """
    mode = flags.get_enum("T2R_POOL_BACKWARD")
    if mode == "auto":
        return lax.platform_dependent(
            x,
            tpu=lambda x: _native_pool(x, window, padding),
            default=lambda x: max_pool_nonoverlap(x, window, padding),
        )
    if resolve_backward_mode() == "native":
        return _native_pool(x, window, padding)
    return max_pool_nonoverlap(x, window, padding)


def _pool_pads(shape, window: Tuple[int, int], padding: str):
    """Per-dimension (low, high) pads on an NHWC input, matching
    lax.reduce_window's padtype_to_pads for stride == window."""
    dims = (1, window[0], window[1], 1)
    return lax.padtype_to_pads(shape, dims, dims, padding)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def max_pool_nonoverlap(
    x: jax.Array, window: Tuple[int, int], padding: str = "SAME"
) -> jax.Array:
    """Max pool over NHWC with stride == window, SAME or VALID padding."""
    dims = (1, window[0], window[1], 1)
    init = jnp.asarray(-jnp.inf, x.dtype)
    return lax.reduce_window(x, init, lax.max, dims, dims, padding)


def _fwd(x, window, padding):
    return max_pool_nonoverlap(x, window, padding), x


def _bwd(window, padding, x, g):
    # The window maximum is RECOMPUTED here from the same reshaped-window
    # tensor the mask compares against, rather than reusing the forward's
    # output: inside a large fused program XLA may rematerialize the
    # forward max with different intermediate numerics (e.g. a different
    # relu/cast fusion upstream), and an equality test against a
    # not-bit-identical max can match zero elements in a window —
    # turning the g/count split into inf. Self-consistency by
    # construction guarantees count >= 1. (It also shrinks the residual
    # to just x.)
    #
    # SAME pads with -inf so partial windows align; VALID instead DROPS
    # the trailing remainder (those inputs get zero gradient, matching
    # reduce_window's VALID semantics).
    wh, ww = window
    b, h, w, c = x.shape
    # reduce_window uppercases padding strings in the forward; match it,
    # or a lowercase "valid" would take the SAME branch here.
    padding = padding.upper()
    if padding == "VALID":
        oh, ow = h // wh, w // ww
        xp = x[:, : oh * wh, : ow * ww, :]
        hp, wp = oh * wh, ow * ww
        pads = None
    else:
        pads = _pool_pads(x.shape, window, padding)
        xp = jnp.pad(x, pads, constant_values=-jnp.inf)
        hp, wp = xp.shape[1], xp.shape[2]
        oh, ow = hp // wh, wp // ww
    windows = xp.reshape(b, oh, wh, ow, ww, c)
    mask = windows == jnp.max(windows, axis=(2, 4), keepdims=True)
    count = jnp.sum(mask, axis=(2, 4), keepdims=True)
    share = (g[:, :, None, :, None, :] / count.astype(g.dtype)) * mask
    gx = share.reshape(b, hp, wp, c)
    if padding == "VALID":
        gx = jnp.pad(gx, ((0, 0), (0, h - hp), (0, w - wp), (0, 0)))
    else:
        gx = gx[
            :,
            pads[1][0] : hp - pads[1][1],
            pads[2][0] : wp - pads[2][1],
            :,
        ]
    return (gx.astype(x.dtype),)


max_pool_nonoverlap.defvjp(_fwd, _bwd)
