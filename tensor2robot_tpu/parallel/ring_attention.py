"""Ring attention: sequence/context parallelism over the device mesh.

Long-context support beyond anything in the reference (SURVEY §5 notes the
reference's sequences are ~40 steps with no CP): Q/K/V are sharded along
the sequence axis of the mesh; each device keeps its Q shard resident while
K/V shards rotate around the ring via `ppermute` over ICI neighbors, and
attention accumulates with the online-softmax (flash) recurrence — memory
per device stays O(seq/devices), and the K/V transfer for step i+1 overlaps
the compute of step i (XLA schedules the ppermute DMA concurrently with the
einsums). Causal masking is block-structured: whole blocks strictly in the
future are skipped analytically via masking (Liu et al., arXiv:2310.01889).

Layout: [batch, seq, heads, dim], seq sharded over the mesh's 'sequence'
axis. With a single-device sequence axis this degrades to plain (flash)
attention — the sequence length lives in the specs, so a CP mesh axis
slots in without touching model code (SURVEY §5 long-context row).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tensor2robot_tpu.parallel import collectives

from tensor2robot_tpu.ops.flash_attention import reference_attention
from tensor2robot_tpu.parallel.mesh import SEQUENCE_AXIS

_NEG_INF = -1e30


def _mark_varying(tree, axis_name):
    """Marks device-local accumulators varying over the ring axis for
    shard_map's vma tracking."""
    return jax.tree_util.tree_map(
        lambda leaf: lax.pcast(leaf, (axis_name,), to="varying"), tree
    )


def _ring_hops(axis_size: int, block: int, causal: bool,
               window: Optional[int]) -> int:
    """Compute hops the ring actually needs. Visibility of the block
    arriving at hop i depends only on i (src = me - i uniformly), so with
    a causal window W over per-device shards of length B, every hop past
    floor((W + B - 2) / B) delivers a fully-masked tile on EVERY device —
    the ring truncates to that many hops, device-uniformly."""
    if not causal or window is None:
        return axis_size
    return min(axis_size, (window + block - 2) // block + 1)


def _block_attend(q, k_blk, v_blk, q_offset, k_offset, scale, causal,
                  window=None):
    """One (q-shard x k-block) tile: returns (o_partial, row_sum, row_max)
    in the online-softmax decomposition."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk) * scale
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = k_offset + jnp.arange(k_blk.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1)  # [B, H, Sq]
    p = jnp.exp(s - m[..., None])
    # Fully-masked tiles: zero contribution, not exp(0)=1 garbage.
    p = jnp.where((m == _NEG_INF)[..., None], 0.0, p)
    l = jnp.sum(p, axis=-1)  # [B, H, Sq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk)
    return o, l, m


def _ring_shard_fn(
    q, k, v, *, axis_name: str, causal: bool, scale: float,
    axis_size: int, use_flash: bool = False, interpret: bool = False,
    return_lse: bool = False, window: Optional[int] = None,
):
    """Per-device body: q is resident; k/v circulate the ring.

    axis_size is static (the mesh is known at trace time), so the ring is
    unrolled: XLA schedules each hop's ppermute DMA against the next hop's
    compute without a loop counter in the way.
    """
    my_index = lax.axis_index(axis_name)
    block = q.shape[1]
    q_offset = my_index * block

    batch, _, heads, dim = q.shape
    o_acc = jnp.zeros(q.shape, jnp.float32)
    l_acc = jnp.zeros((batch, heads, block), jnp.float32)
    m_acc = jnp.full((batch, heads, block), _NEG_INF, jnp.float32)
    # Mark the device-local accumulators as varying over the ring axis:
    # shard_map's vma tracking (when check_vma is on, the reference path)
    # requires them to match the axis-index-dependent tile updates they
    # accumulate.
    o_acc, l_acc, m_acc = _mark_varying((o_acc, l_acc, m_acc), axis_name)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def body(i, carry):
        o_acc, l_acc, m_acc, k_blk, v_blk = carry
        # Block i arrived from the device i hops ring-upstream.
        src_index = lax.rem(my_index - i + axis_size, axis_size)
        if use_flash:
            # Pallas flash tile: the per-hop hot op, no [Sq, Sk] logits in
            # HBM (ops/flash_attention.py).
            from tensor2robot_tpu.ops.flash_attention import flash_attention_tile

            o_blk, l_blk, m_blk = flash_attention_tile(
                q, k_blk, v_blk, causal=causal, scale=scale,
                q_offset=q_offset, k_offset=src_index * block,
                interpret=interpret, vma=(axis_name,), window=window,
            )
        else:
            o_blk, l_blk, m_blk = _block_attend(
                q, k_blk, v_blk, q_offset, src_index * block, scale, causal,
                window,
            )
        # Online-softmax merge of the new tile into the running state.
        m_new = jnp.maximum(m_acc, m_blk)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m_blk - m_new)
        l_new = l_acc * alpha + l_blk * beta
        o_new = (
            o_acc * jnp.transpose(alpha, (0, 2, 1))[..., None]
            + o_blk.astype(jnp.float32)
            * jnp.transpose(beta, (0, 2, 1))[..., None]
        )
        # Rotate K/V to the next device; XLA overlaps this DMA with the
        # next iteration's einsums.
        k_next = collectives.ppermute(k_blk, axis_name, perm)
        v_next = collectives.ppermute(v_blk, axis_name, perm)
        return o_new, l_new, m_new, k_next, v_next

    carry = (o_acc, l_acc, m_acc, k, v)
    # Static unroll — axis_size is mesh shape; a causal window truncates
    # the rotation to the hops whose tiles are not fully masked.
    for i in range(_ring_hops(axis_size, block, causal, window)):
        carry = body(i, carry)
    o_acc, l_acc, m_acc, _, _ = carry
    l_acc = jnp.maximum(l_acc, 1e-30)
    out = o_acc / jnp.transpose(l_acc, (0, 2, 1))[..., None]
    if return_lse:
        # Global log-sum-exp per row: the backward ring's residual.
        return out.astype(q.dtype), m_acc + jnp.log(l_acc)
    return out.astype(q.dtype)


def ring_attention_manual(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = SEQUENCE_AXIS,
    axis_size: int,
    causal: bool = False,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Ring attention INSIDE an enclosing shard_map (manual mode).

    `ring_attention` below builds its own shard_map; a caller already
    running under one — the pipelined encoder's per-device program, where
    the pipe axis owns the outer shard_map and the sequence axis is also
    manual — cannot nest another. This entry point runs the same
    per-device ring body directly on the LOCAL shards: q/k/v are
    [batch_local, seq/axis_size, heads, dim], the rotation rides
    collectives.ppermute over `axis_name`, and causal masking uses global
    positions derived from lax.axis_index. It is the piece that makes
    DP x SP x PP composable (parallel/planner.py's 3D plans); the XLA
    einsum tile is used per hop (the flash-kernel path stays on the
    shard_map-owning entry points).
    """
    if q.ndim != 4:
        raise ValueError(f"Expected [B, S_local, H, D], got {q.shape}")
    from tensor2robot_tpu.ops.flash_attention import _check_window

    _check_window(window, causal)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    return _ring_shard_fn(
        q, k, v, axis_name=axis_name, causal=causal, scale=scale,
        axis_size=axis_size, use_flash=False, interpret=False,
        window=window,
    )


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = SEQUENCE_AXIS,
    causal: bool = False,
    scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Sequence-parallel attention over `mesh`'s `axis_name`.

    Args:
      q, k, v: [batch, seq, heads, dim]; seq must divide evenly by the
        sequence-axis size.
      mesh: the device mesh (axes from parallel.mesh.make_mesh).
      axis_name: mesh axis carrying the sequence shards.
      causal: apply causal masking over *global* positions.
      scale: logit scale; defaults to dim ** -0.5.
      use_flash: per-hop tiles via the Pallas flash kernel
        (ops/flash_attention.py). Default (None): the XLA einsum path,
        matching the single-device dispatch policy (BENCH_FLASH_r03
        measured the Pallas kernel at 0.7% of peak vs the XLA path's
        win on-chip), auto-switching to flash when the per-hop LOCAL
        length S/N reaches ops.flash_attention.FLASH_AUTO_SEQ — past
        that the [S/N, S/N] logit shards are the O(S^2) memory hazard
        flash's O(S) tiles avoid. Pass True/False to force either path
        (tools/validate_flash_tpu.py re-evaluates the default).
      interpret: run the Pallas kernel in interpreter mode (tests on CPU).
      window: causal sliding window W in GLOBAL positions. Besides the
        per-tile masking, the ring itself truncates: only
        ceil((W + B - 2) / B) + 1-ish hops of the rotation carry visible
        tiles (B = per-device shard), so a bounded window makes ring cost
        independent of the TOTAL context length.

    Returns:
      [batch, seq, heads, dim] attention output, sequence-sharded like q.
    """
    if q.ndim != 4:
        raise ValueError(f"Expected [B, S, H, D], got {q.shape}")
    from tensor2robot_tpu.ops.flash_attention import _check_window

    _check_window(window, causal)
    axis_size = mesh.shape[axis_name]
    if q.shape[1] % axis_size != 0:
        raise ValueError(
            f"Sequence length {q.shape[1]} must be divisible by the "
            f"{axis_name!r} axis size {axis_size}."
        )
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if use_flash is None:
        # One dispatch policy everywhere: the XLA
        # einsum path by default exactly as in single-device attention
        # (layers/transformer.py), on the same r3 on-chip evidence —
        # switching to flash tiles when the per-hop LOCAL length crosses
        # FLASH_AUTO_SEQ, where the einsum path's [S/N, S/N] logit
        # shards become the same O(S^2) memory hazard the single-device
        # threshold guards. interpret=True still selects the
        # (interpreted) kernel so CPU tests exercise what an opt-in TPU
        # run compiles.
        from tensor2robot_tpu.ops.flash_attention import FLASH_AUTO_SEQ

        local_seq = q.shape[1] // axis_size
        use_flash = interpret or local_seq >= FLASH_AUTO_SEQ
        if use_flash:
            # Per-device shard lengths must admit a viable kernel block;
            # otherwise quietly keep the einsum path (an explicit
            # use_flash=True with bad shapes raises in the tile instead).
            from tensor2robot_tpu.ops.flash_attention import _pick_block

            local = q.shape[1] // axis_size
            if _pick_block(local, 128) is None:
                use_flash = False
    if use_flash:
        return _ring_flash(
            q, k, v, mesh, axis_name, causal, scale, interpret, window
        )
    return _ring_call(
        q, k, v, mesh, axis_name, causal, scale, False, False, window=window
    )


def _ring_call(q, k, v, mesh, axis_name, causal, scale, use_flash, interpret,
               return_lse=False, window=None):
    axis_size = mesh.shape[axis_name]
    spec = P(None, axis_name, None, None)
    extra = {}
    if use_flash:
        # Pallas kernels inside shard_map trip the varying-manual-axes
        # checker (jax recommends check_vma=False as the workaround); the
        # reference path keeps full checking.
        extra["check_vma"] = False
    fn = collectives.shard_map(
        functools.partial(
            _ring_shard_fn, axis_name=axis_name, causal=causal, scale=scale,
            axis_size=axis_size, use_flash=use_flash, interpret=interpret,
            return_lse=return_lse, window=window,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, P(None, None, axis_name)) if return_lse else spec,
        **extra,
    )
    return fn(q, k, v)


def _ring_bwd_shard_fn(
    q, k, v, dout, out, lse, *, axis_name: str, causal: bool, scale: float,
    axis_size: int, interpret: bool, window: Optional[int] = None,
):
    """Backward ring: dq accumulates on the q-owner; dk/dv contributions
    RIDE THE RING with their k/v blocks, so after the full rotation each
    block arrives home carrying every device's contribution (the ring
    formulation of the FlashAttention-2 backward; per hop, the two Pallas
    backward kernels recompute this tile's probabilities from the global
    row stats)."""
    from tensor2robot_tpu.ops.flash_attention import (
        flash_attention_bwd_delta,
        flash_attention_bwd_tile,
    )

    my_index = lax.axis_index(axis_name)
    block = q.shape[1]
    q_offset = my_index * block
    delta = flash_attention_bwd_delta(dout, out)  # [B, H, Sq_local]

    dq_acc = jnp.zeros(q.shape, jnp.float32)
    dk_travel = jnp.zeros(k.shape, jnp.float32)
    dv_travel = jnp.zeros(v.shape, jnp.float32)
    dq_acc, dk_travel, dv_travel = _mark_varying(
        (dq_acc, dk_travel, dv_travel), axis_name
    )
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    hops = _ring_hops(axis_size, block, causal, window)
    carry = (dq_acc, dk_travel, dv_travel, k, v)
    for i in range(hops):  # static unroll, as in the forward ring
        dq_acc, dk_travel, dv_travel, k_blk, v_blk = carry
        src_index = lax.rem(my_index - i + axis_size, axis_size)
        dq_t, dk_t, dv_t = flash_attention_bwd_tile(
            q, k_blk, v_blk, dout, lse, delta,
            causal=causal, scale=scale,
            q_offset=q_offset, k_offset=src_index * block,
            interpret=interpret, vma=(axis_name,), window=window,
        )
        dq_acc = dq_acc + dq_t
        dk_travel = dk_travel + dk_t
        dv_travel = dv_travel + dv_t
        # Rotate the block AND its accumulated gradient together; the
        # final rotation delivers them back to the block's owner.
        k_blk, v_blk, dk_travel, dv_travel = (
            collectives.ppermute(t, axis_name, perm)
            for t in (k_blk, v_blk, dk_travel, dv_travel)
        )
        carry = (dq_acc, dk_travel, dv_travel, k_blk, v_blk)
    dq_acc, dk_travel, dv_travel, _, _ = carry
    if hops < axis_size:
        # A truncated rotation leaves each traveling gradient `hops` shifts
        # from home; one ppermute with the remaining shift delivers it.
        home = [(j, (j + axis_size - hops) % axis_size)
                for j in range(axis_size)]
        dk_travel = collectives.ppermute(dk_travel, axis_name, home)
        dv_travel = collectives.ppermute(dv_travel, axis_name, home)
    return (
        dq_acc.astype(q.dtype),
        dk_travel.astype(k.dtype),
        dv_travel.astype(v.dtype),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_flash(q, k, v, mesh, axis_name, causal, scale, interpret, window):
    """Flash-tile ring forward with a flash ring BACKWARD: pallas_call has
    no autodiff rule, so the custom vjp runs a second ring whose hops are
    the FlashAttention-2 backward kernels (flash_attention_bwd_tile) —
    O(seq/devices * dim) memory in both directions."""
    return _ring_call(
        q, k, v, mesh, axis_name, causal, scale, True, interpret,
        window=window,
    )


def _ring_flash_fwd(q, k, v, mesh, axis_name, causal, scale, interpret,
                    window):
    out, lse = _ring_call(
        q, k, v, mesh, axis_name, causal, scale, True, interpret,
        return_lse=True, window=window,
    )
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(mesh, axis_name, causal, scale, interpret, window,
                    residuals, g):
    q, k, v, out, lse = residuals
    axis_size = mesh.shape[axis_name]
    spec = P(None, axis_name, None, None)
    lse_spec = P(None, None, axis_name)
    fn = collectives.shard_map(
        functools.partial(
            _ring_bwd_shard_fn, axis_name=axis_name, causal=causal,
            scale=scale, axis_size=axis_size, interpret=interpret,
            window=window,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec, lse_spec),
        out_specs=(spec, spec, spec),
        check_vma=False,
    )
    return fn(q, k, v, g, out, lse)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)
