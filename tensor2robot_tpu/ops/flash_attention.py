"""Pallas TPU flash attention: the per-device attention hot op.

The online-softmax (flash) recurrence computed in a single Pallas kernel:
Q stays resident in VMEM per grid step while K/V are consumed block by
block with running (output, row-sum, row-max) accumulators — the S×S logit
matrix never exists in HBM, so HBM traffic is O(S·D) instead of O(S²)
(the usual bandwidth bound for attention on TPU). Used standalone and as
the per-hop tile kernel of parallel/ring_attention.py, which adds the
sequence-parallel ring on top.

Positions are GLOBAL: q_offset/k_offset shift the causal mask so a kernel
invocation can compute one (q-shard × k-shard) tile of a longer sequence
(exactly what each ring hop needs).

Dispatch: the Pallas path runs on TPU (or anywhere with interpret=True,
which tests use); other backends and non-divisible block shapes fall back
to the einsum reference. A sequence whose whole-K/V blocks cannot fit the
chip's VMEM is refused with an error naming the shape (`_vmem_kwargs`),
never quietly rerouted. Gradients: jax.custom_vjp with a FLASH backward —
two Pallas kernels (dq; dk+dv) recompute attention probabilities tile by
tile from the forward's saved row statistics L = m + log(l) and
D = rowsum(dO*O), so the backward is also O(S·D) HBM (the
FlashAttention-2 scheme); the S×S logit matrix never materializes in
either direction.

Packed documents go another way: `segment_attention` (causal and same
document, grouped-query) runs the upstream splash kernel where it is
lowered for a TPU and its tiles divide the sequence, and a blocked einsum
everywhere else. The kernels above have no document mask; with one added
they lost that race (tools/race_segment_attention.py, PERF.md PR 29).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_NEG_INF = -1e30

# -- contraction override (low-precision serving hook) -------------------------
# The einsum path's two contractions (QK^T logits, PV mix) are the only
# attention FLOPs a serving export can re-lower onto int8/fp8 operands
# (export/serve_quant.py attention lowering). Rather than have the
# serving layer re-implement attention (masking, windows, offsets), the
# reference path exposes exactly those two ops as an override point:
# inside `attention_contraction_override(impl)`, logits come from
# `impl.qk(q, k, scale)` and the mixed output from `impl.pv(probs, v)`;
# everything else (mask construction, softmax, dtypes) is unchanged.
# The flash/ring/ulysses kernels never consult the hook — their tiled
# recurrences have no materialized contraction to swap — which is why
# attention-head eligibility is einsum-path-only.
_CONTRACTION_OVERRIDE: contextvars.ContextVar = contextvars.ContextVar(
    "t2r_attention_contraction_override", default=None
)


@contextlib.contextmanager
def attention_contraction_override(impl):
    """Installs `impl` (with .qk(q, k, scale) and .pv(probs, v)) as the
    reference path's contraction implementation for the context."""
    token = _CONTRACTION_OVERRIDE.set(impl)
    try:
        yield
    finally:
        _CONTRACTION_OVERRIDE.reset(token)

# Row statistics (l, m, lse, delta) cross the pallas_call boundary stored
# with a trailing broadcast dim of _STATS_LANES so their blocks satisfy
# Mosaic's (8, 128) tile constraint; a [block_q]-shaped block would need a
# sublane dim divisible by 8, which a per-row vector cannot provide. This
# mirrors the upstream jax.experimental.pallas TPU flash kernel's own l/m
# layout. It costs 128x HBM on the stat tensors (still O(S) vs the O(S^2)
# logits the kernel avoids); a [bh, 1, s_q] stats-in-lanes layout would be
# 128x slimmer but constrains partial q-blocks to multiples of 128 and
# needs an in-kernel sublane->lane transpose — worth exploring only after
# this layout is validated on hardware.
_STATS_LANES = 128

# Every kernel keeps some operands WHOLE in VMEM per grid step: the
# forward and dq kernels a (1, S_k, D) K and V block, the dk/dv kernel a
# (1, S_q, ...) Q, dO and two row-stat blocks. Measured on TPU v5 lite
# (libtpu 0.0.34, 128 MiB of VMEM): under the compiler's default scoped
# limit the forward stops compiling once K+V pass ~14 MiB (bf16 D=128:
# S=28672 compiles, S=32768 is RESOURCE_EXHAUSTED in vmem; f32: 12288 vs
# 16384); with `vmem_limit_bytes` raised, a kernel compiles while the sum
# of its whole-sequence blocks (lane-padded) stays under the limit minus
# a few MiB (at a 100 MiB limit: 96 MiB compiles, 128 MiB does not, for
# forward and backward alike). So small shapes keep the default, larger
# ones ask for 25/32 of the chip's VMEM, and a shape beyond that is
# refused HERE, by name — the alternative is an XLA allocation failure
# deep inside whatever program contains the call. Blocking K/V through
# the grid instead (no whole-sequence operand) is ROADMAP A4.
_VMEM_DEFAULT_BUDGET = 12 << 20
_VMEM_MARGIN = 4 << 20


def _vmem_kwargs(kernel: str, whole_blocks, interpret: bool, shapes: str):
    """pallas_call kwargs sizing `kernel`'s scoped VMEM for its
    whole-sequence blocks [(rows, cols, dtype), ...]; raises ValueError
    naming the shapes when they cannot fit the chip."""
    if interpret:
        return {}  # the interpreter has no VMEM
    need = sum(
        rows * -(-cols // 128) * 128 * jnp.dtype(dtype).itemsize
        for rows, cols, dtype in whole_blocks
    )
    if need <= _VMEM_DEFAULT_BUDGET:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    limit = pltpu.get_tpu_info().vmem_capacity_bytes * 25 // 32
    if need > limit - _VMEM_MARGIN:
        raise ValueError(
            f"{kernel} keeps whole-sequence blocks of {need >> 20} MiB in "
            f"VMEM for {shapes}, over the {(limit - _VMEM_MARGIN) >> 20} MiB "
            "this chip can give one kernel; shard the sequence "
            "(parallel/ring_attention.py) or shorten it"
        )
    return {"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=limit)}


# Auto-dispatch crossover shared by every attention entry point
# (layers/transformer.py single-device, parallel/ring_attention.py per-hop
# local length, parallel/ulysses_attention.py full length): below this
# per-device attended length the XLA einsum path wins on measured speed
# (BENCH_FLASH_r03); at/above it the einsum path's O(S^2) logits OOM
# where the flash kernel's O(S) tiles still fit (the r4 A/B's expected
# einsum OOM at S=4096). Re-evaluated by each BENCH_FLASH capture.
FLASH_AUTO_SEQ = 4096


def _check_window(window: Optional[int], causal: bool) -> None:
    """Shared entry-point validation: a window needs causal semantics, and
    window < 1 would mask EVERYTHING — in the reference path the finite
    _NEG_INF cap then normalizes to uniform attention over all positions
    (a silent future-information leak), so it must be rejected, not
    computed."""
    if window is None:
        return
    if not causal:
        raise ValueError("window requires causal=True (causal sliding window)")
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")


def _k_block_bounds(q0, block_q, block_k, num_kb, k_off, causal, window):
    """[j_lo, j_hi) over k blocks visible to the q block starting at GLOBAL
    position q0. A k block j covers global [k_off + j*bk, k_off + (j+1)*bk).
    Causal keeps blocks whose min k <= the block's max q; the window keeps
    blocks whose max k > q0 - W — both exact (floor division on possibly
    negative numerators). Shared by the forward recurrence and the dq
    backward so their visibility can never desynchronize."""
    j_lo = 0
    j_hi = num_kb
    if causal:
        j_hi = jnp.maximum(
            0,
            jnp.minimum(num_kb, (q0 + block_q - 1 - k_off) // block_k + 1),
        )
    if window is not None:
        j_lo = jnp.maximum(0, (q0 - window + 1 - k_off) // block_k)
    return j_lo, j_hi


def _dot_precision(dtype) -> Optional[lax.Precision]:
    """Matmul precision for kernel dots computing in f32 from `dtype` inputs.

    The TPU MXU natively multiplies bf16; at DEFAULT precision an f32
    matmul is decomposed into a single bf16 pass (~2^-8 relative error).
    For f32 inputs that silently downgrades the kernel below f32 accuracy,
    so request HIGHEST (the multi-pass bf16 decomposition, true-f32
    accurate). For bf16 inputs the operands are exactly representable and
    DEFAULT is both exact-enough and the fast path.
    """
    return lax.Precision.HIGHEST if dtype == jnp.float32 else None


def reference_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset=0,
    k_offset=0,
    precision: Optional[lax.Precision] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Materialized-logits attention over [B, S, H, D] — numerics oracle
    and non-TPU fallback. Offsets shift global positions for tiled use.
    window=W restricts each query to the last W keys (q-W < k <= q, the
    causal sliding window); requires causal=True."""
    _check_window(window, causal)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    override = _CONTRACTION_OVERRIDE.get()
    if override is not None:
        logits = override.qk(q, k, scale)
    else:
        logits = (
            jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=precision) * scale
        )
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = k_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    # Fully-masked rows normalize against the -inf cap instead of NaN-ing.
    probs = jax.nn.softmax(logits, axis=-1)
    if override is not None:
        return override.pv(probs, v).astype(q.dtype)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", probs, v, precision=precision
    ).astype(q.dtype)


#: Queries a block of `segment_attention`'s einsum path takes at once.
SEGMENT_BLOCK_Q = 1024

#: Tiles of `segment_attention`'s kernel path (`BlockSizes` of the upstream
#: splash kernel): queries a grid step, keys fetched a grid step and keys a
#: product, for the forward kernel and for the fused backward kernel. From
#: the sweep of `tools/race_segment_attention.py` over 256 to 2048 on a
#: TPU v5e at q [1, 8192, 32, 64], k, v [1, 8192, 8, 64] (PERF.md, PR 29):
#: within half a percent of the sweep's fastest, which took tiles of 2048;
#: with none over 1024 every multiple of 1024 positions tiles, and 2048
#: queries with 2048 keys no longer fit the kernel's VMEM.
SEGMENT_KERNEL_BLOCKS = dict(
    block_q=1024, block_kv=1024, block_kv_compute=512,
    block_q_dkv=1024, block_kv_dkv=1024, block_kv_dkv_compute=1024,
)

#: The fused backward kernel writes one partial dq a block of keys, [heads,
#: S / block_kv_dkv, S, D] in all, and sums them afterwards: 268 MB at
#: granite's [32 x 8,192 x 64], 4 GB at latent attention's [32 x 16,384 x
#: 192]. Over this many bytes the backward takes a dq kernel of its own
#: (`SEGMENT_DQ_BLOCKS`), which holds no partials; the race of PR 29 read it
#: a fifth slower as the layer runs it.
SEGMENT_FUSED_BACKWARD_BYTES = 1 << 30
SEGMENT_DQ_BLOCKS = dict(block_q_dq=1024, block_kv_dq=1024)


def segment_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: jax.Array,
    scale: Optional[float] = None,
) -> jax.Array:
    """Causal attention over packed documents: query i sees key j iff
    j <= i and segment_ids[j] == segment_ids[i].

    q [B, S, H, D]; k [B, S, KVH, D] and v [B, S, KVH, DV] with H a
    multiple of KVH (the group's keys are never repeated) and DV any width
    (latent attention's values are narrower than its keys); the output is
    [B, S, H, DV]; segment_ids [B, S]. Softmax
    statistics and every accumulation in float32, the probabilities
    rounded to the values' dtype for the second product only. Two
    implementations of that one contract, chosen by what the call can see:

    * `_segment_kernel`, a tiled online-softmax Pallas kernel whose scores
      never leave VMEM, where the program is lowered for a TPU
      (`lax.platform_dependent`), the operands are bfloat16 and S is a
      multiple of every tile of `SEGMENT_KERNEL_BLOCKS`;
    * `_segment_einsum`, an einsum blocked over `SEGMENT_BLOCK_Q` queries,
      everywhere else: other platforms, float32 (the kernel's products
      would round it to bfloat16), short or ragged sequences.
    """
    heads, kv_heads = q.shape[2], k.shape[2]
    if heads % kv_heads:
        raise ValueError(f"{heads} query heads over {kv_heads} key heads")
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if _segment_kernel_tiles(q):
        return lax.platform_dependent(
            q, k, v, segment_ids,
            tpu=functools.partial(_segment_kernel, scale=scale),
            default=functools.partial(_segment_einsum, scale=scale),
        )
    return _segment_einsum(q, k, v, segment_ids, scale=scale)


def _segment_kernel_tiles(q: jax.Array) -> bool:
    """Whether the kernel path can take `q` as it is: static, from shape
    and dtype alone."""
    return q.dtype == jnp.bfloat16 and all(
        q.shape[1] % tile == 0 for tile in SEGMENT_KERNEL_BLOCKS.values()
    )


def _segment_einsum(q, k, v, segment_ids, scale):
    """Block n of `SEGMENT_BLOCK_Q` queries meets keys
    0 .. (n+1) * SEGMENT_BLOCK_Q only, so the blocks above the diagonal are
    never computed and the largest logits alive are
    [B, H, SEGMENT_BLOCK_Q, S], float32 in HBM. Each block is recomputed in
    the backward pass (`jax.checkpoint`), so its probabilities do not
    outlive it."""
    batch, seq, heads, dim = q.shape
    kv_heads = k.shape[2]
    precision = _dot_precision(q.dtype)
    block_q = min(SEGMENT_BLOCK_Q, seq)
    q = q.reshape(batch, seq, kv_heads, heads // kv_heads, dim)

    @jax.checkpoint
    def block(q_blk, k_ctx, v_ctx, seg_q, seg_k, start):
        logits = jnp.einsum(
            "bqgrd,bkgd->bgrqk", q_blk, k_ctx, precision=precision,
            preferred_element_type=jnp.float32,
        ) * scale
        q_pos = start + jnp.arange(q_blk.shape[1])
        mask = (q_pos[:, None] >= jnp.arange(k_ctx.shape[1])[None, :])[None] & (
            seg_q[:, :, None] == seg_k[:, None, :]
        )
        probs = jax.nn.softmax(
            jnp.where(mask[:, None, None], logits, _NEG_INF), axis=-1
        )
        return jnp.einsum(
            "bgrqk,bkgd->bqgrd", probs.astype(v_ctx.dtype), v_ctx,
            precision=precision,
        )

    out = []
    for start in range(0, seq, block_q):
        stop = min(start + block_q, seq)
        out.append(block(
            q[:, start:stop], k[:, :stop], v[:, :stop],
            segment_ids[:, start:stop], segment_ids[:, :stop], start,
        ))
    return jnp.concatenate(out, axis=1).reshape(
        batch, seq, heads, v.shape[-1]
    )


def _segment_kernel(q, k, v, segment_ids, scale, interpret=False):
    """The upstream splash kernel (`jax.experimental.pallas.ops.tpu.
    splash_attention`), one multi-query call a key head and sequence: a
    forward kernel with running row maxima and sums that emits the output
    and the log-sum-exp, and a backward kernel that rebuilds each tile of
    probabilities from them. A tile's mask is made in the kernel from
    positions and the two id vectors; tiles above the diagonal are skipped.
    The kernel takes no scale, so the queries carry it: exact for a power
    of two (granite's 1/64, 64 ** -0.5), else one more rounding of the
    scaled query to its dtype, from float32."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
        splash_attention_mask as splash_mask,
    )

    batch, seq, heads, dim = q.shape
    kv_heads = k.shape[2]
    group = heads // kv_heads
    partials = (
        heads * (seq // SEGMENT_KERNEL_BLOCKS["block_kv_dkv"]) * seq * dim
        * q.dtype.itemsize
    )
    if partials <= SEGMENT_FUSED_BACKWARD_BYTES:
        # dq, dk and dv from one backward kernel: it won the race too.
        backward = {"use_fused_bwd_kernel": True}
    else:
        backward = SEGMENT_DQ_BLOCKS
    kernel = splash.make_splash_mqa_single_device(
        splash_mask.MultiHeadMask([splash_mask.CausalMask((seq, seq))] * group),
        block_sizes=splash.BlockSizes(**backward, **SEGMENT_KERNEL_BLOCKS),
        interpret=interpret,
    )
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    # [B, S, KVH * G, D] -> [B, KVH, G, S, D]; keys [B, KVH, S, D].
    q = q.reshape(batch, seq, kv_heads, group, dim).transpose(0, 2, 3, 1, 4)
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)

    def one_sequence(q, k, v, ids):
        ids = splash.SegmentIds(ids, ids)
        return jax.vmap(kernel, in_axes=(0, 0, 0, None))(q, k, v, ids)

    out = jax.vmap(one_sequence)(q, k, v, segment_ids.astype(jnp.int32))
    return out.transpose(0, 3, 1, 2, 4).reshape(
        batch, seq, heads, v.shape[-1]
    )


def _flash_body(
    offsets_ref, q_ref, k_ref, v_ref, block_k, scale, causal, precision,
    window=None,
):
    """The shared online-softmax recurrence over k blocks; returns the raw
    accumulator triple (o_unnormalized, row_sum, row_max).

    window=W (causal sliding window, q-W < k <= q) masks per element AND
    tightens the k-block loop bounds, so compute is O(S*W) instead of
    O(S^2) — the whole point of local attention at long context.
    """
    qi = pl.program_id(1)
    block_q = q_ref.shape[1]
    dim = q_ref.shape[2]
    s_k = k_ref.shape[1]
    num_kb = s_k // block_k

    q = q_ref[0].astype(jnp.float32) * scale
    q_pos = (
        offsets_ref[0]
        + qi * block_q
        + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    )

    q0 = offsets_ref[0] + qi * block_q
    j_lo, j_hi = _k_block_bounds(
        q0, block_q, block_k, num_kb, offsets_ref[1], causal, window
    )

    def body(j, carry):
        o_acc, l_acc, m_acc = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q,
            k_blk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )  # [block_q, block_k]
        if causal:
            k_pos = (
                offsets_ref[1]
                + j * block_k
                + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            )
            visible = q_pos >= k_pos
            if window is not None:
                visible = visible & (q_pos - k_pos < window)
            s = jnp.where(visible, s, _NEG_INF)
        # Row stats stay [block_q, 1] (keepdims) — 2D shapes lower cleanly
        # on Mosaic where 1D per-row vectors may not.
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_acc, m_blk)
        alpha = jnp.exp(m_acc - m_new)
        p = jnp.exp(s - m_new)
        # Fully-masked tiles contribute nothing (not exp(0)=1 garbage).
        p = jnp.where(m_new == _NEG_INF, 0.0, p)
        l_new = l_acc * alpha + jnp.sum(p, axis=-1, keepdims=True)
        o_new = o_acc * alpha + jax.lax.dot_general(
            p,
            v_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )
        return o_new, l_new, m_new

    o_acc = jnp.zeros((block_q, dim), jnp.float32)
    l_acc = jnp.zeros((block_q, 1), jnp.float32)
    m_acc = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    return lax.fori_loop(j_lo, j_hi, body, (o_acc, l_acc, m_acc))


def _flash_kernel(
    offsets_ref,  # SMEM [2] int32: (q_offset, k_offset) global shifts
    q_ref,  # VMEM [1, block_q, D]
    k_ref,  # VMEM [1, S_k, D]
    v_ref,  # VMEM [1, S_k, D]
    o_ref,  # VMEM [1, block_q, D]
    *,
    block_k: int,
    scale: float,
    causal: bool,
    precision: Optional[lax.Precision] = None,
    window: Optional[int] = None,
):
    o_acc, l_acc, _ = _flash_body(
        offsets_ref, q_ref, k_ref, v_ref, block_k, scale, causal, precision,
        window,
    )
    l_acc = jnp.maximum(l_acc, 1e-30)
    o_ref[0] = (o_acc / l_acc).astype(o_ref.dtype)


def _flash_tile_kernel(
    offsets_ref, q_ref, k_ref, v_ref, o_ref, l_ref, m_ref,
    *, block_k, scale, causal, precision=None, window=None,
):
    """Like _flash_kernel but emits the UNNORMALIZED accumulator triple
    (o_partial, row_sum, row_max) — the online-softmax residuals a ring hop
    merges across devices (parallel/ring_attention.py). l/m blocks are
    [1, block_q, _STATS_LANES] with the stat broadcast along the lane dim."""
    o_acc, l_acc, m_acc = _flash_body(
        offsets_ref, q_ref, k_ref, v_ref, block_k, scale, causal, precision,
        window,
    )
    o_ref[0] = o_acc
    l_ref[0] = jnp.broadcast_to(l_acc, l_ref.shape[1:])
    m_ref[0] = jnp.broadcast_to(m_acc, m_ref.shape[1:])


def flash_attention_tile(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset=0,
    k_offset=0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    vma=None,
    window: Optional[int] = None,
):
    """One (q-shard × k-shard) flash tile over [B, S, H, D].

    Returns (o_partial [B,Sq,H,D] f32 unnormalized, l [B,H,Sq], m [B,H,Sq])
    — the same contract as ring_attention's reference _block_attend, so a
    ring hop can merge tiles across devices without renormalizing twice.

    vma: mesh axis names the outputs vary over — required when called
    inside shard_map (the ring passes its sequence axis).
    window: causal sliding window W (q-W < k <= q) in GLOBAL positions.
    """
    _check_window(window, causal)
    if not interpret and jax.default_backend() != "tpu":
        raise ValueError(
            "flash_attention_tile compiles only on TPU; pass interpret=True "
            "to run the kernel in interpreter mode on this backend."
        )
    from jax.experimental.pallas import tpu as pltpu  # noqa: F401

    batch, s_q, heads, dim = q.shape
    s_k = k.shape[1]
    bh = batch * heads
    scale = scale if scale is not None else dim ** -0.5
    bq = _pick_block(s_q, block_q)
    bk = _pick_block(s_k, block_k)
    if bq is None or bk is None:
        raise ValueError(
            f"No MXU-viable block divides shard lengths (q={s_q}, k={s_k}); "
            "use the reference path (ring_attention use_flash=False) for "
            "these shapes."
        )
    offsets = jnp.stack(
        [jnp.asarray(q_offset, jnp.int32), jnp.asarray(k_offset, jnp.int32)]
    )

    def out_struct(shape):
        if vma is not None:
            return jax.ShapeDtypeStruct(shape, jnp.float32, vma=frozenset(vma))
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    def fold(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(bh, x.shape[1], dim)

    o, l, m = pl.pallas_call(
        functools.partial(
            _flash_tile_kernel, block_k=bk, scale=scale, causal=causal,
            precision=_dot_precision(q.dtype), window=window,
        ),
        out_shape=(
            out_struct((bh, s_q, dim)),
            out_struct((bh, s_q, _STATS_LANES)),
            out_struct((bh, s_q, _STATS_LANES)),
        ),
        grid=(bh, s_q // bq),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s_k, dim), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, s_k, dim), lambda b, i: (b, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, bq, dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, _STATS_LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, _STATS_LANES), lambda b, i: (b, i, 0)),
        ),
        interpret=interpret,
        **_vmem_kwargs(
            "flash_attention_tile",
            [(s_k, dim, k.dtype), (s_k, dim, v.dtype)],
            interpret, f"k/v {k.shape} {k.dtype}",
        ),
    )(offsets, fold(q), fold(k), fold(v))
    o = jnp.transpose(o.reshape(batch, heads, s_q, dim), (0, 2, 1, 3))
    l = l[..., 0].reshape(batch, heads, s_q)
    m = m[..., 0].reshape(batch, heads, s_q)
    return o, l, m


def _pick_block(size: int, preferred: int) -> Optional[int]:
    """Usable kernel block size for a sequence dim: the whole dim when it
    fits one block, else the largest divisor <= preferred that is still
    MXU/VPU-viable. A partial block must be a multiple of 8 (Mosaic's
    sublane tile — checked at lowering on real TPU, not by the CPU
    interpreter); the full dim is always legal regardless of size. None ->
    no viable blocking (prime-ish lengths); callers fall back to the
    einsum reference rather than run a degenerate (1, D)-block grid."""
    if size <= 0:
        return None
    if size <= preferred:
        return size
    for block in range(preferred - preferred % 8, 7, -8):
        if size % block == 0:
            return block
    return None


def _flash_attention_fwd_impl(
    q, k, v, offsets, causal, scale, block_q, block_k, interpret,
    window=None,
):
    from jax.experimental.pallas import tpu as pltpu

    batch, s_q, heads, dim = q.shape
    s_k = k.shape[1]
    bh = batch * heads

    # [B, S, H, D] -> [B*H, S, D]
    def fold(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(bh, x.shape[1], dim)

    qf, kf, vf = fold(q), fold(k), fold(v)
    grid = (bh, s_q // block_q)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, block_k=block_k, scale=scale, causal=causal,
            precision=_dot_precision(q.dtype), window=window,
        ),
        out_shape=jax.ShapeDtypeStruct((bh, s_q, dim), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s_k, dim), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, s_k, dim), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dim), lambda b, i: (b, i, 0)),
        interpret=interpret,
        **_vmem_kwargs(
            "flash_attention",
            [(s_k, dim, k.dtype), (s_k, dim, v.dtype)],
            interpret, f"k/v {k.shape} {k.dtype}",
        ),
    )(offsets, qf, kf, vf)
    return jnp.transpose(out.reshape(batch, heads, s_q, dim), (0, 2, 1, 3))


def _bwd_tile(q_scaled, k_blk, v_blk, do_blk, lse, delta, q_pos, k_pos,
              causal, precision=None, window=None):
    """Shared backward-tile recompute: probabilities and dS for one
    (q-tile x k-tile) pair, from the saved row stats.

    q_scaled must already carry the softmax scale (s = q_scaled @ k^T), so
    ds @ k (for dQ) and ds^T @ q_scaled (for dK) each carry exactly one
    factor of scale — dQ multiplies its own factor afterwards.
    lse/delta are [block_q, 1] columns. Returns (p, ds), both
    [block_q, block_k] f32.
    """
    s = jax.lax.dot_general(
        q_scaled, k_blk,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision,
    )
    p = jnp.exp(s - lse)
    if causal:
        visible = q_pos >= k_pos
        if window is not None:
            visible = visible & (q_pos - k_pos < window)
        p = jnp.where(visible, p, 0.0)
    dp = jax.lax.dot_general(
        do_blk, v_blk,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision,
    )
    ds = p * (dp - delta)
    return p, ds


def _flash_bwd_dq_kernel(
    offsets_ref,  # SMEM [2] int32
    q_ref,  # VMEM [1, block_q, D]
    k_ref,  # VMEM [1, S_k, D]
    v_ref,  # VMEM [1, S_k, D]
    do_ref,  # VMEM [1, block_q, D]
    lse_ref,  # VMEM [1, block_q, _STATS_LANES]  L = m + log(l), lane-bcast
    delta_ref,  # VMEM [1, block_q, _STATS_LANES]  D = rowsum(dO*O), bcast
    dq_ref,  # VMEM [1, block_q, D]
    *,
    block_k: int,
    scale: float,
    causal: bool,
    precision: Optional[lax.Precision] = None,
    window: Optional[int] = None,
):
    """dQ_i = scale * sum_j dS_ij K_j, with P recomputed per k-tile from
    the saved row stats (FlashAttention-2 backward, query-parallel half)."""
    qi = pl.program_id(1)
    block_q = q_ref.shape[1]
    dim = q_ref.shape[2]
    s_k = k_ref.shape[1]
    num_kb = s_k // block_k

    q = q_ref[0].astype(jnp.float32) * scale
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, 0:1]
    delta = delta_ref[0][:, 0:1]
    q_pos = (
        offsets_ref[0]
        + qi * block_q
        + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    )

    # Same k-block visibility bounds as the forward (shared helper).
    q0 = offsets_ref[0] + qi * block_q
    j_lo, j_hi = _k_block_bounds(
        q0, block_q, block_k, num_kb, offsets_ref[1], causal, window
    )

    def body(j, acc):
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        k_pos = (
            offsets_ref[1]
            + j * block_k
            + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        )
        _, ds = _bwd_tile(q, k_blk, v_blk, do, lse, delta, q_pos, k_pos,
                          causal, precision, window)
        return acc + jax.lax.dot_general(
            ds, k_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )

    acc = lax.fori_loop(
        j_lo, j_hi, body, jnp.zeros((block_q, dim), jnp.float32)
    )
    dq_ref[0] = (acc * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    offsets_ref,  # SMEM [2] int32
    q_ref,  # VMEM [1, S_q, D]
    k_ref,  # VMEM [1, block_k, D]
    v_ref,  # VMEM [1, block_k, D]
    do_ref,  # VMEM [1, S_q, D]
    lse_ref,  # VMEM [1, S_q, _STATS_LANES]
    delta_ref,  # VMEM [1, S_q, _STATS_LANES]
    dk_ref,  # VMEM [1, block_k, D]
    dv_ref,  # VMEM [1, block_k, D]
    *,
    block_q: int,
    scale: float,
    causal: bool,
    precision: Optional[lax.Precision] = None,
    window: Optional[int] = None,
):
    """dK_j = scale * sum_i dS_ij^T Q_i; dV_j = sum_i P_ij^T dO_i (the
    key-parallel half: each grid step owns one k-tile, loops q-tiles)."""
    ki = pl.program_id(1)
    block_k = k_ref.shape[1]
    dim = k_ref.shape[2]
    s_q = q_ref.shape[1]
    num_qb = s_q // block_q

    k_blk = k_ref[0].astype(jnp.float32)
    v_blk = v_ref[0].astype(jnp.float32)
    k_pos = (
        offsets_ref[1]
        + ki * block_k
        + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    )

    # q-block visibility bounds for this k block (the forward's relation
    # transposed): causal keeps q blocks whose max q >= the block's min k;
    # the window keeps q blocks whose min q <= max k + W - 1.
    k0 = offsets_ref[1] + ki * block_k
    i_lo = 0
    i_hi = num_qb
    if causal:
        i_lo = jnp.maximum(0, (k0 - offsets_ref[0]) // block_q)
    if window is not None:
        i_hi = jnp.maximum(
            0,
            jnp.minimum(
                num_qb,
                (k0 + block_k - 1 + window - 1 - offsets_ref[0]) // block_q
                + 1,
            ),
        )

    def body(i, carry):
        dk_acc, dv_acc = carry
        q_blk = (
            q_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
            * scale
        )
        do_blk = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * block_q, block_q), :][:, 0:1]
        delta = delta_ref[0, pl.ds(i * block_q, block_q), :][:, 0:1]
        q_pos = (
            offsets_ref[0]
            + i * block_q
            + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        )
        p, ds = _bwd_tile(q_blk, k_blk, v_blk, do_blk, lse, delta, q_pos,
                          k_pos, causal, precision, window)
        dv_acc = dv_acc + jax.lax.dot_general(
            p, do_blk,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )
        dk_acc = dk_acc + jax.lax.dot_general(
            ds, q_blk,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )
        return dk_acc, dv_acc

    dk_acc, dv_acc = lax.fori_loop(
        i_lo,
        i_hi,
        body,
        (
            jnp.zeros((block_k, dim), jnp.float32),
            jnp.zeros((block_k, dim), jnp.float32),
        ),
    )
    # q was pre-scaled, so ds @ q already carries one factor of scale; dk
    # needs exactly one (dS/dK_j = scale * q_i), which it therefore has.
    dk_ref[0] = dk_acc.astype(dk_ref.dtype)
    dv_ref[0] = dv_acc.astype(dv_ref.dtype)


def flash_attention_bwd_delta(dout: jax.Array, out: jax.Array) -> jax.Array:
    """delta = rowsum(dO * O) in [B, H, Sq] layout — the O(S*D) precompute
    both backward entry points (single-device _bwd, ring hop) feed to the
    backward kernels."""
    return jnp.transpose(
        jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1),
        (0, 2, 1),
    )


def flash_attention_bwd_tile(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    do: jax.Array,
    lse: jax.Array,
    delta: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset=0,
    k_offset=0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    vma=None,
    window: Optional[int] = None,
):
    """Backward of one (q-shard x k-shard) tile: (dq, dk, dv).

    The ring-hop counterpart of flash_attention_tile: given the GLOBAL row
    stats lse = m + log(l) and delta = rowsum(dO*O) (both [B, H, Sq]),
    recomputes this tile's probabilities in the two backward kernels and
    returns its additive contributions — a ring hop accumulates dq locally
    and sends dk/dv around with the k/v blocks. All outputs f32.

    vma: mesh axis names the outputs vary over (shard_map callers).
    window: causal sliding window W in GLOBAL positions.
    """
    _check_window(window, causal)
    if not interpret and jax.default_backend() != "tpu":
        raise ValueError(
            "flash_attention_bwd_tile compiles only on TPU; pass "
            "interpret=True to run in interpreter mode on this backend."
        )
    from jax.experimental.pallas import tpu as pltpu

    batch, s_q, heads, dim = q.shape
    s_k = k.shape[1]
    bh = batch * heads
    scale = scale if scale is not None else dim ** -0.5
    bq = _pick_block(s_q, block_q)
    bk = _pick_block(s_k, block_k)
    if bq is None or bk is None:
        raise ValueError(
            f"No MXU-viable block divides shard lengths (q={s_q}, k={s_k})."
        )
    offsets = jnp.stack(
        [jnp.asarray(q_offset, jnp.int32), jnp.asarray(k_offset, jnp.int32)]
    )

    def fold(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(bh, x.shape[1], dim)

    def out_struct(shape, dtype=jnp.float32):
        if vma is not None:
            return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
        return jax.ShapeDtypeStruct(shape, dtype)

    qf, kf, vf, dof = fold(q), fold(k), fold(v), fold(do)
    # Row stats enter the kernels lane-broadcast (see _STATS_LANES).
    lsef = jnp.broadcast_to(
        lse.reshape(bh, s_q)[..., None], (bh, s_q, _STATS_LANES)
    )
    deltaf = jnp.broadcast_to(
        delta.reshape(bh, s_q)[..., None], (bh, s_q, _STATS_LANES)
    )

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, block_k=bk, scale=scale, causal=causal,
            precision=_dot_precision(q.dtype), window=window,
        ),
        out_shape=out_struct((bh, s_q, dim)),
        grid=(bh, s_q // bq),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s_k, dim), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, s_k, dim), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, bq, dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, _STATS_LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, _STATS_LANES), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dim), lambda b, i: (b, i, 0)),
        interpret=interpret,
        **_vmem_kwargs(
            "flash_attention_bwd_tile (dq)",
            [(s_k, dim, k.dtype), (s_k, dim, v.dtype)],
            interpret, f"k/v {k.shape} {k.dtype}",
        ),
    )(offsets, qf, kf, vf, dof, lsef, deltaf)

    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, block_q=bq, scale=scale, causal=causal,
            precision=_dot_precision(q.dtype), window=window,
        ),
        out_shape=(
            out_struct((bh, s_k, dim)),
            out_struct((bh, s_k, dim)),
        ),
        grid=(bh, s_k // bk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, s_q, dim), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bk, dim), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dim), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, s_q, dim), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, s_q, _STATS_LANES), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, s_q, _STATS_LANES), lambda b, j: (b, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, bk, dim), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dim), lambda b, j: (b, j, 0)),
        ),
        interpret=interpret,
        **_vmem_kwargs(
            "flash_attention_bwd_tile (dk/dv)",
            [
                (s_q, dim, q.dtype), (s_q, dim, do.dtype),
                (s_q, _STATS_LANES, jnp.float32),
                (s_q, _STATS_LANES, jnp.float32),
            ],
            interpret, f"q/do {q.shape} {q.dtype}",
        ),
    )(offsets, qf, kf, vf, dof, lsef, deltaf)

    def unfold(x, s):
        return jnp.transpose(x.reshape(batch, heads, s, dim), (0, 2, 1, 3))

    return unfold(dq, s_q), unfold(dk, s_k), unfold(dv, s_k)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10)
)
def _flash_attention(
    q, k, v, q_offset, k_offset, causal, scale, block_q, block_k, interpret,
    window,
):
    offsets = jnp.stack(
        [jnp.asarray(q_offset, jnp.int32), jnp.asarray(k_offset, jnp.int32)]
    )
    return _flash_attention_fwd_impl(
        q, k, v, offsets, causal, scale, block_q, block_k, interpret, window
    )


def _fwd(
    q, k, v, q_offset, k_offset, causal, scale, block_q, block_k, interpret,
    window,
):
    # Forward via the tile kernel so the row stats (l, m) come out as
    # residuals; normalization happens here (one O(S*D) elementwise pass).
    o, l, m = flash_attention_tile(
        q, k, v, causal=causal, scale=scale,
        q_offset=q_offset, k_offset=k_offset,
        block_q=block_q, block_k=block_k, interpret=interpret,
        window=window,
    )
    l_safe = jnp.maximum(l, 1e-30)
    out = (o / jnp.transpose(l_safe, (0, 2, 1))[..., None]).astype(q.dtype)
    lse = m + jnp.log(l_safe)  # [B, H, Sq]
    return out, (q, k, v, out, lse, q_offset, k_offset)


def _bwd(causal, scale, block_q, block_k, interpret, window, residuals, g):
    q, k, v, out, lse, q_offset, k_offset = residuals
    dq, dk, dv = flash_attention_bwd_tile(
        q, k, v, g,
        lse,
        flash_attention_bwd_delta(g, out),
        causal=causal, scale=scale,
        q_offset=q_offset, k_offset=k_offset,
        block_q=block_q, block_k=block_k, interpret=interpret,
        window=window,
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), None, None


_flash_attention.defvjp(_fwd, _bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset=0,
    k_offset=0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Attention over [B, S, H, D] with the flash recurrence on TPU.

    Falls back to reference_attention off-TPU (unless interpret=True, the
    test path) and for sequence lengths with no usable block divisor.
    q_offset/k_offset shift the global positions of the q/k shards for the
    causal mask (ring-attention tiles).

    window=W restricts each query to the last W keys (causal sliding
    window, q-W < k <= q): the kernel skips k blocks wholly outside the
    window, so long-context compute drops from O(S^2) to O(S*W).
    """
    if q.ndim != 4:
        raise ValueError(f"Expected [B, S, H, D], got {q.shape}")
    _check_window(window, causal)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if interpret is None:
        interpret = False
    # Pallas compiles natively only on TPU; elsewhere the kernel runs in
    # interpreter mode (tests) or falls back to the reference — including
    # when a caller explicitly passes interpret=False off-TPU.
    # Both fallbacks SUPPRESS the serving contraction override: a
    # flash-configured head must compute what the Pallas kernel would
    # (f32), not silently pick up quantized contractions — otherwise
    # the exported program's attention numerics would depend on the
    # export HOST (off-TPU trace = reference fallback) or on the
    # sequence's block divisibility, while T2R_SERVE_NATIVE_ATTN
    # promises flash heads never lower.
    if jax.default_backend() != "tpu" and not interpret:
        with attention_contraction_override(None):
            return reference_attention(
                q, k, v, causal=causal, scale=scale,
                q_offset=q_offset, k_offset=k_offset, window=window,
            )
    bq = _pick_block(q.shape[1], block_q)
    bk = _pick_block(k.shape[1], block_k)
    if bq is None or bk is None:
        with attention_contraction_override(None):
            return reference_attention(
                q, k, v, causal=causal, scale=scale,
                q_offset=q_offset, k_offset=k_offset, window=window,
            )
    return _flash_attention(
        q, k, v, q_offset, k_offset, causal, scale, bq, bk, interpret, window
    )
