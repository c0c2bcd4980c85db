"""The delta rule's walk over a head's chunks as Pallas TPU kernels, forward
and backward: the state between chunks stays in VMEM for the whole walk, and
everything that reads a chunk's entering state is formed in that chunk's grid
step, so neither the 256 states nor a second `fresh` go through HBM.

The contract is `layers/kda._chunk_outputs_scan`'s (which stays the form every
other platform, dtype and shape runs, and the definition these kernels are
tested against). Per head, with S = 0 entering chunk 0, for n = 0 .. N - 1:

    S~     = S rounded to the compute dtype
    fresh  = u[n] - (w[n] @ S~) rounded to the compute dtype
    out[n] = q_start[n] @ S~ + b_scores[n] @ fresh          (float32 sums)
    S      = kept[n][:, None] * S + k_end[n]^T @ fresh      (float32)

w, k_end, q_start [B, N, H, C, K] and u [B, N, H, C, V] in the compute dtype,
kept [B, N, H, K] float32; b_scores is row 0 of scores [B, N, H, R, C, C]
float32, the pair scores as their kernel stacks them (rounded to the compute
dtype for its product, as the XLA form rounds it; the other rows are not
read); out [B, N, H, C, V] in the compute dtype. Nothing is transposed to
chunk-major, sliced or converted on the way in: the blocks are cut straight
out of those arrays (a slice of the stacked scores costs two passes over them
in HBM, a float32 `out` a third more of the kernel's writes and a pass to
round it).

Grid (B, H / hb, N): a step takes `hb` heads' blocks of one chunk, the chunk
axis last and sequential. The state lives in scratch as its transpose, S^T
[V, K] float32 a head, so that `kept` (a row of K) scales it along the lanes
and `w @ S~` is the `a @ b.T` form the MXU takes as it is. The `hb` heads of
a step are independent and their loop is unrolled: one head's dependent chain
(`w @ S~` before `k_end^T @ fresh`) hides behind another's.

The backward kernel (`jax.custom_vjp`) walks the chunks in reverse with the
gradient of the state, dS^T, in scratch. Residuals are the inputs and the
entering states S^T [B, N, H, V, K] float32, which the forward rule's kernel
writes beside `out` (the primal call writes `out` alone); `fresh` is formed
again in the step. Under `jax.checkpoint` the forward pass proper asks for no
residuals and runs the primal kernel (`optimize_remat`). With dO = d out[n]
and dS' the gradient of the state leaving chunk n, gradient operands of
products rounded to the compute dtype as the einsums' transposes round them
on the chip (the other rows of d scores are zeros, padded on outside the
kernel):

    dfresh = b_scores^T dO + k_end dS'       du = dfresh     dw = -dfresh S~^T
    db_scores = dO fresh^T     dq_start = dO S~^T     dk_end = fresh dS'^T
    dkept = rowsum(dS' * S)    dS = kept[:, None] * dS' + q_start^T dO - w^T dfresh
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Heads a grid step takes (`kept`'s block is [hb, K] float32: a multiple of 8).
HEADS_A_STEP = 8

_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))    # a @ b.T
_TN = (((0,), (0,)), ((), ()))    # a.T @ b


def tiles(w: jax.Array, u: jax.Array, heads_a_step: int = HEADS_A_STEP) -> bool:
    """Whether the kernels can take these operands as they are: static, from
    shape and dtype alone (w [.., H, C, K], u [.., H, C, V])."""
    heads, chunk, width = w.shape[-3:]
    return (
        w.dtype == u.dtype == jnp.bfloat16
        and width % 128 == 0
        and u.shape[-1] % 128 == 0
        and chunk % 16 == 0
        and heads % heads_a_step == 0
    )


def _dot(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=_F32)
    return lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _fresh(w, u, entering):
    """u - w @ S~ of one head's chunk, both roundings the XLA form's."""
    dtype = u.dtype
    erased = _dot(w, entering, _NT).astype(dtype)
    return (u.astype(_F32) - erased.astype(_F32)).astype(dtype)


def _forward_kernel(w_ref, u_ref, k_end_ref, kept_ref, q_ref, b_ref, out_ref,
                    *rest):
    *states_ref, state_ref = rest       # the forward rule's kernel keeps the states
    dtype = w_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    for head in range(w_ref.shape[2]):
        state = state_ref[head]                             # S^T [V, K]
        if states_ref:
            states_ref[0][0, 0, head] = state
        entering = state.astype(dtype)
        fresh = _fresh(w_ref[0, 0, head], u_ref[0, 0, head], entering)
        out_ref[0, 0, head] = (_dot(q_ref[0, 0, head], entering, _NT) + _dot(
            b_ref[0, 0, head, 0].astype(dtype), fresh)).astype(dtype)
        state_ref[head] = kept_ref[0, 0, head:head + 1, :] * state + _dot(
            fresh, k_end_ref[0, 0, head], _TN)


def _backward_kernel(w_ref, u_ref, k_end_ref, kept_ref, q_ref, b_ref,
                     states_ref, dout_ref,
                     dw_ref, du_ref, dk_end_ref, dkept_ref, dq_ref, db_ref,
                     dstate_ref):
    dtype = w_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    for head in range(w_ref.shape[2]):
        state = states_ref[0, 0, head]                      # S^T [V, K] entering
        entering = state.astype(dtype)
        leaving = dstate_ref[head]                          # dS'^T [V, K]
        d_leaving = leaving.astype(dtype)
        w, q, k_end = w_ref[0, 0, head], q_ref[0, 0, head], k_end_ref[0, 0, head]
        fresh = _fresh(w, u_ref[0, 0, head], entering)      # [C, V]
        d_out = dout_ref[0, 0, head]                        # [C, V]
        d_fresh = (
            _dot(b_ref[0, 0, head, 0].astype(dtype), d_out, _TN)
            + _dot(k_end, d_leaving, _NT)
        ).astype(dtype)
        du_ref[0, 0, head] = d_fresh
        dw_ref[0, 0, head] = (-_dot(d_fresh, entering)).astype(dtype)
        dq_ref[0, 0, head] = _dot(d_out, entering).astype(dtype)
        db_ref[0, 0, head, 0] = _dot(d_out, fresh, _NT)
        dk_end_ref[0, 0, head] = _dot(fresh, d_leaving).astype(dtype)
        dkept_ref[0, 0, head:head + 1, :] = jnp.sum(
            leaving * state, axis=0, keepdims=True)
        dstate_ref[head] = (
            kept_ref[0, 0, head:head + 1, :] * leaving
            + _dot(d_out, q, _TN) - _dot(d_fresh, w, _TN)
        )


def _specs(w, u, heads_a_step, reverse):
    """(grid, block specs of [.., C, K], [.., C, V], kept, row 0 of the
    scores, the states), the chunks walked from the last where `reverse`."""
    batch, chunks, heads, chunk, width = w.shape
    values = u.shape[-1]
    at = (lambda n: chunks - 1 - n) if reverse else (lambda n: n)
    block = lambda *minor: pl.BlockSpec(
        (1, 1, heads_a_step) + minor,
        lambda b, h, n: (b, at(n), h) + (0,) * len(minor),
    )
    grid = (batch, heads // heads_a_step, chunks)
    return (grid, block(chunk, width), block(chunk, values), block(width),
            block(1, chunk, chunk), block(values, width))


_SEQUENTIAL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _forward(w, u, k_end, kept, q_start, scores, heads_a_step, interpret,
             keep_states):
    batch, chunks, heads, chunk, width = w.shape
    values = u.shape[-1]
    grid, keys, vals, row, pairs, state = _specs(w, u, heads_a_step, False)
    out = jax.ShapeDtypeStruct(u.shape, u.dtype)
    states = jax.ShapeDtypeStruct((batch, chunks, heads, values, width), _F32)
    return pl.pallas_call(
        _forward_kernel,
        out_shape=(out, states) if keep_states else out,
        grid=grid, in_specs=[keys, vals, keys, row, keys, pairs],
        out_specs=(vals, state) if keep_states else vals,
        scratch_shapes=[pltpu.VMEM((heads_a_step, values, width), _F32)],
        compiler_params=_SEQUENTIAL, interpret=interpret,
        name="kda_carry",
    )(w, u, k_end, kept, q_start, scores)


def _backward(w, u, k_end, kept, q_start, scores, states, d_out,
              heads_a_step, interpret):
    values = u.shape[-1]
    grid, keys, vals, row, pairs, state = _specs(w, u, heads_a_step, True)
    like = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype)
    read = jax.ShapeDtypeStruct(scores.shape[:3] + (1,) + scores.shape[4:], _F32)
    *gradients, d_read = pl.pallas_call(
        _backward_kernel,
        out_shape=(like(w), like(u), like(k_end), like(kept), like(q_start),
                   read),
        grid=grid,
        in_specs=[keys, vals, keys, row, keys, pairs, state, vals],
        out_specs=(keys, vals, keys, row, keys, pairs),
        scratch_shapes=[pltpu.VMEM((heads_a_step, values, w.shape[-1]), _F32)],
        compiler_params=_SEQUENTIAL, interpret=interpret,
        name="kda_carry_backward",
    )(w, u, k_end, kept, q_start, scores, states, d_out)
    # Zeros for the rows the walk does not read: a pad XLA fuses into what
    # sums the scores' gradients, where rows of zeros from the kernel would
    # be 67 MB more of HBM a group, written and alive.
    unread = [(0, 0)] * 3 + [(0, scores.shape[3] - 1)] + [(0, 0)] * 2
    return (*gradients, jnp.pad(d_read, unread))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def chunk_outputs(w, u, k_end, kept, q_start, scores,
                  heads_a_step=HEADS_A_STEP, interpret=False):
    """out [B, N, H, C, V]: the contract above, for operands that `tiles`
    accepts. `interpret` is for tests off the chip."""
    return _forward(
        w, u, k_end, kept, q_start, scores, heads_a_step, interpret, False)


def _chunk_outputs_fwd(w, u, k_end, kept, q_start, scores, heads_a_step,
                       interpret):
    out, states = _forward(
        w, u, k_end, kept, q_start, scores, heads_a_step, interpret, True)
    return out, (w, u, k_end, kept, q_start, scores, states)


def _chunk_outputs_bwd(heads_a_step, interpret, residuals, d_out):
    return _backward(*residuals, d_out, heads_a_step, interpret)


chunk_outputs.defvjp(_chunk_outputs_fwd, _chunk_outputs_bwd, optimize_remat=True)
