"""The delta rule's pair scores of a chunk as Pallas TPU kernels, forward and
backward: the [c, c, K] decay differences of a sub-block are formed in VMEM
and never written to HBM.

The contract is `layers/kda._slab_pair_scores`'s (which stays the form every
other platform, dtype and shape runs):

    scores[r, i, j] = sum_d x[r, i, d] k[j, d] exp(cum[i, d] - cum[j, d])

for j <= i of one chunk where `visible`, 0 elsewhere; x [.., R, C, K] and k
[.., C, K] in the compute dtype, cum [.., C, K] float32 and non-increasing
along C. Between two sub-blocks of `sub` positions the decay goes through
the later one's first position and the scores are matrix products on the
MXU, operands rounded to the compute dtype, float32 accumulation. Inside a
sub-block everything is float32 on the vector unit, and laid out so that no
sum runs across lanes: a grid step holds 128 positions (two heads' chunks of
64) *on the lanes* and the K channels on the sublanes (the tiles are
transposed in VMEM as they arrive). Shift s of 0 .. sub - 1 then pairs every
position p with p - s by one lane rotation of k and cum, one exponential of
the difference (masked where p - s leaves the sub-block: every exponent
formed is <= 0) and a sum over the sublanes, which is the s-th diagonal of
every sub-block at once.

The backward kernel (`jax.custom_vjp`; residuals are the inputs alone) forms
the same differences again, shift by shift, and accumulates the gradients of
x, k and cum in the transposed tiles; the between-sub-block part by the same
rule through its reference position (the clipped `minimum` passes no
gradient where it clips). Its products take the scores' gradient rounded to
the compute dtype, as the einsum's transpose does on the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Positions a grid step holds on its lanes: whole chunks of whole heads.
LANES = 128

_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))    # a @ b.T
_TN = (((0,), (0,)), ((), ()))    # a.T @ b


def tiles(x: jax.Array, k: jax.Array, sub: int) -> bool:
    """Whether the kernels can take these operands as they are: static,
    from shape and dtype alone (x [.., H, R, C, K], k [.., H, C, K])."""
    heads, chunk, width = k.shape[-3:]
    return (
        x.dtype == k.dtype == jnp.bfloat16
        and width % 128 == 0
        and chunk % sub == 0
        and LANES % chunk == 0
        and heads % (LANES // chunk) == 0
    )


def _transposed(tile):
    """[.., C, K] of a block's heads -> float32 [K, LANES]."""
    return tile.reshape(LANES, tile.shape[-1]).astype(_F32).T


def _shifted(kt_ref, gt_ref, shift, in_sub):
    """(k[p - shift], exp(cum[p] - cum[p - shift])) on [K, LANES], the decay
    0 where p - shift is in another sub-block: masked before the
    exponential, as `masked_exp` does. `shift` is static: the loops over the
    shifts are unrolled, which the race of PR 33 read at half the time of a
    `fori_loop`, whether that rotates by its index or carries what it
    rotates one lane a trip (a shift is a chain of rotation, exponential,
    products and sum, and only the unrolled loop overlaps one shift's
    chain with the next's)."""
    if not shift:
        return kt_ref[...], None
    gt = gt_ref[...]
    decay = jnp.exp(
        jnp.where(in_sub >= shift, gt - pltpu.roll(gt, shift, 1), -jnp.inf)
    )
    return pltpu.roll(kt_ref[...], shift, 1), decay


def _lane_iotas(chunk, sub):
    """(on_shift [C, LANES]: p - j for the lane's position p in its chunk and
    the sublane's j, the shift on whose diagonal scores[p, j] lies; in_sub
    [1, LANES]: p's offset in its sub-block)."""
    position = lax.broadcasted_iota(jnp.int32, (chunk, LANES), 1) % chunk
    column = lax.broadcasted_iota(jnp.int32, (chunk, LANES), 0)
    in_sub = lax.broadcasted_iota(jnp.int32, (1, LANES), 1) % sub
    return position - column, in_sub


def _between_operands(x_ref, k_head, g_head, head, block, sub):
    """The two rounded operands of sub-block `block`'s product with every
    earlier position of its chunk, and their float32 decays: x_in [R * sub,
    K] (rows r * sub + i), k_out [C, K], decay_in [sub, K], decay_out [C, K],
    reach [C, K] = ref - cum (<= 0 where it is not clipped)."""
    rows = x_ref.shape[2]
    dtype = x_ref.dtype
    at = block * sub
    ref = g_head[at:at + 1]
    decay_in = jnp.exp(g_head[at:at + sub] - ref)
    reach = ref - g_head
    decay_out = jnp.exp(jnp.minimum(reach, 0.0))
    x_in = jnp.concatenate([
        (x_ref[0, head, r, at:at + sub, :] * decay_in).astype(dtype)
        for r in range(rows)
    ], axis=0)
    k_out = (k_head * decay_out).astype(dtype)
    return x_in, k_out, decay_in, decay_out, reach


def _scores_kernel(x_ref, k_ref, cum_ref, vis_ref, out_ref,
                   xt_ref, kt_ref, gt_ref, acc_ref, *, sub):
    _, heads, rows, chunk, _ = x_ref.shape
    kt_ref[...] = _transposed(k_ref[0])
    gt_ref[...] = _transposed(cum_ref[0])
    for r in range(rows):
        xt_ref[r] = _transposed(x_ref[0, :, r])
    on_shift, in_sub = _lane_iotas(chunk, sub)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    for shift in range(sub):
        weighted, decay = _shifted(kt_ref, gt_ref, shift, in_sub)
        if shift:
            weighted = weighted * decay
        for r in range(rows):
            found = jnp.sum(xt_ref[r] * weighted, axis=0, keepdims=True)
            acc_ref[r] = jnp.where(on_shift == shift, found, acc_ref[r])

    column = lax.broadcasted_iota(jnp.int32, (sub, chunk), 1)
    inside = [acc_ref[r].T for r in range(rows)]          # [LANES, C] each
    for head in range(heads):
        k_head = k_ref[0, head].astype(_F32)
        g_head = cum_ref[0, head]
        for block in range(chunk // sub):
            at = block * sub
            if block:
                x_in, k_out, *_ = _between_operands(
                    x_ref, k_head, g_head, head, block, sub)
                between = lax.dot_general(
                    x_in, k_out, _NT, preferred_element_type=_F32)
            for r in range(rows):
                tile = inside[r][head * chunk + at:head * chunk + at + sub]
                if block:
                    tile = jnp.where(
                        column < at, between[r * sub:(r + 1) * sub], tile)
                out_ref[0, head, r, at:at + sub, :] = jnp.where(
                    vis_ref[0, at:at + sub, :] > 0, tile, 0.0)


def _scores_backward_kernel(x_ref, k_ref, cum_ref, vis_ref, ds_ref,
                            dx_ref, dk_ref, dcum_ref,
                            xt_ref, kt_ref, gt_ref, dst_ref,
                            dxt_ref, dkt_ref, dgt_ref,
                            dxn_ref, dkn_ref, dgn_ref, *, sub):
    _, heads, rows, chunk, width = x_ref.shape
    dtype = x_ref.dtype
    kt_ref[...] = _transposed(k_ref[0])
    gt_ref[...] = _transposed(cum_ref[0])
    visible = vis_ref[0]                                   # 1.0 / 0.0
    for r in range(rows):
        xt_ref[r] = _transposed(x_ref[0, :, r])
        dst_ref[r] = (ds_ref[0, :, r] * visible).reshape(LANES, chunk).T
    on_shift, in_sub = _lane_iotas(chunk, sub)
    dxt_ref[...] = jnp.zeros_like(dxt_ref)
    dkt_ref[...] = jnp.zeros_like(dkt_ref)
    dgt_ref[...] = jnp.zeros_like(dgt_ref)

    for shift in range(sub):
        k_back, decay = _shifted(kt_ref, gt_ref, shift, in_sub)
        weighted = k_back * decay if shift else k_back
        # q[d, p] = sum_r dS[r, p, p - shift] x[r, p, d] D: k's gradient at
        # p - shift, and times k the two ends of cum's.
        q = None
        for r in range(rows):
            ds = jnp.sum(
                jnp.where(on_shift == shift, dst_ref[r], 0.0), axis=0,
                keepdims=True,
            )                                              # dS[r, p, p - shift]
            dxt_ref[r] += ds * weighted
            q = ds * xt_ref[r] if q is None else q + ds * xt_ref[r]
        if not shift:                                      # D = 1, nothing of cum's
            dkt_ref[...] += q
            continue
        q = q * decay
        pairs = q * k_back
        dkt_ref[...] += pltpu.roll(q, LANES - shift, 1)
        dgt_ref[...] += pairs - pltpu.roll(pairs, LANES - shift, 1)

    for r in range(rows):
        dxn_ref[r] = dxt_ref[r].T
    dkn_ref[...] = dkt_ref[...].T
    dgn_ref[...] = dgt_ref[...].T

    column = lax.broadcasted_iota(jnp.int32, (rows * sub, chunk), 1)
    for head in range(heads):
        k_head = k_ref[0, head].astype(_F32)
        g_head = cum_ref[0, head]
        first = head * chunk
        for block in range(1, chunk // sub):
            at = block * sub
            x_in, k_out, decay_in, decay_out, reach = _between_operands(
                x_ref, k_head, g_head, head, block, sub)
            ds = jnp.concatenate([
                ds_ref[0, head, r, at:at + sub, :] * visible[at:at + sub]
                for r in range(rows)
            ], axis=0)
            ds = jnp.where(column < at, ds, 0.0).astype(dtype)   # [R * sub, C]
            dx_in = jnp.dot(ds, k_out, preferred_element_type=_F32)
            dk_out = lax.dot_general(
                ds, x_in, _TN, preferred_element_type=_F32)       # [C, K]
            through = jnp.zeros((sub, width), _F32)
            for r in range(rows):
                part = dx_in[r * sub:(r + 1) * sub] * decay_in
                dxn_ref[r, first + at:first + at + sub, :] += part
                through += part * x_ref[0, head, r, at:at + sub, :]
            dk_part = dk_out * decay_out
            dkn_ref[first:first + chunk, :] += dk_part
            # d/d reach of exp(min(reach, 0)): 1 below 0, a half at 0 (as
            # `minimum`'s derivative has it), 0 where it clips.
            passes = jnp.where(reach < 0, 1.0, jnp.where(reach == 0, 0.5, 0.0))
            reached = dk_part * k_head * passes
            dgn_ref[first + at:first + at + sub, :] += through
            dgn_ref[first:first + chunk, :] -= reached
            dgn_ref[first + at:first + at + 1, :] += (
                jnp.sum(reached, axis=0, keepdims=True)
                - jnp.sum(through, axis=0, keepdims=True)
            )

    for r in range(rows):
        dx_ref[0, :, r] = dxn_ref[r].reshape(heads, chunk, width).astype(dx_ref.dtype)
    dk_ref[0] = dkn_ref[...].reshape(heads, chunk, width).astype(dk_ref.dtype)
    dcum_ref[0] = dgn_ref[...].reshape(heads, chunk, width)


def _specs(x, k):
    """(grid, block specs of x, of k and cum, of the mask, of the scores)
    over operands whose leading axes are flattened to one."""
    _, heads, rows, chunk, width = x.shape
    held = LANES // chunk
    grid = (x.shape[0], heads // held)
    wide = pl.BlockSpec((1, held, rows, chunk, width), lambda m, h: (m, h, 0, 0, 0))
    narrow = pl.BlockSpec((1, held, chunk, width), lambda m, h: (m, h, 0, 0))
    mask = pl.BlockSpec((1, chunk, chunk), lambda m, h: (m, 0, 0))
    scores = pl.BlockSpec((1, held, rows, chunk, chunk), lambda m, h: (m, h, 0, 0, 0))
    return grid, wide, narrow, mask, scores


def _flat(x, k, cum, visible):
    """Leading axes [B, N] -> one; the mask [B, N, 1, C, C] -> float32 [B * N,
    C, C] (shared by the heads)."""
    chunk = k.shape[-2]
    return (
        x.reshape((-1,) + x.shape[-4:]), k.reshape((-1,) + k.shape[-3:]),
        cum.reshape((-1,) + cum.shape[-3:]),
        visible.reshape(-1, chunk, chunk).astype(_F32),
    )


_PARALLEL = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


def _forward(x, k, cum, visible, sub, interpret):
    lead = k.shape[:-3]
    x, k, cum, visible = _flat(x, k, cum, visible)
    _, heads, rows, chunk, width = x.shape
    grid, wide, narrow, mask, scores = _specs(x, k)
    tile = pltpu.VMEM((width, LANES), _F32)
    out = pl.pallas_call(
        functools.partial(_scores_kernel, sub=sub),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], heads, rows, chunk, chunk), _F32),
        grid=grid, in_specs=[wide, narrow, narrow, mask], out_specs=scores,
        scratch_shapes=[
            pltpu.VMEM((rows, width, LANES), _F32), tile, tile,
            pltpu.VMEM((rows, chunk, LANES), _F32),
        ],
        compiler_params=_PARALLEL, interpret=interpret,
        name="kda_pair_scores",
    )(x, k, cum, visible)
    return out.reshape(lead + out.shape[1:])


def _backward(x, k, cum, visible, ds, sub, interpret):
    shapes = x.shape, k.shape, cum.shape
    x, k, cum, visible = _flat(x, k, cum, visible)
    ds = ds.reshape((-1,) + ds.shape[-4:])
    _, heads, rows, chunk, width = x.shape
    grid, wide, narrow, mask, scores = _specs(x, k)
    tile = pltpu.VMEM((width, LANES), _F32)
    tiles_r = pltpu.VMEM((rows, width, LANES), _F32)
    natural = pltpu.VMEM((LANES, width), _F32)
    dx, dk, dcum = pl.pallas_call(
        functools.partial(_scores_backward_kernel, sub=sub),
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(cum.shape, _F32),
        ),
        grid=grid, in_specs=[wide, narrow, narrow, mask, scores],
        out_specs=(wide, narrow, narrow),
        scratch_shapes=[
            tiles_r, tile, tile, pltpu.VMEM((rows, chunk, LANES), _F32),
            tiles_r, tile, tile,
            pltpu.VMEM((rows, LANES, width), _F32), natural, natural,
        ],
        compiler_params=_PARALLEL, interpret=interpret,
        name="kda_pair_scores_backward",
    )(x, k, cum, visible, ds)
    return tuple(t.reshape(s) for t, s in zip((dx, dk, dcum), shapes))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def pair_scores(x, k, cum, visible, sub, interpret=False):
    """float32 scores [.., H, R, C, C] of x [.., H, R, C, K], k and cum [..,
    H, C, K], visible [.., 1, C, C] (bool): the contract above, for operands
    that `tiles` accepts. `interpret` is for tests off the chip."""
    return _forward(x, k, cum, visible, sub, interpret)


def _pair_scores_fwd(x, k, cum, visible, sub, interpret):
    return _forward(x, k, cum, visible, sub, interpret), (x, k, cum, visible)


def _pair_scores_bwd(sub, interpret, residuals, ds):
    x, k, cum, visible = residuals
    return _backward(x, k, cum, visible, ds, sub, interpret) + (None,)


pair_scores.defvjp(_pair_scores_fwd, _pair_scores_bwd)
