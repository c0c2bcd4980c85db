"""Ulysses-style all-to-all sequence parallelism (DeepSpeed-Ulysses).

The second context-parallel strategy next to parallel/ring_attention.py:
instead of rotating K/V around a ring (N hops, compute overlapped with
ppermute DMAs), TWO all_to_all collectives re-shard the problem so each
device computes FULL attention for a subset of heads:

    [B, S/N, H, D]  --all_to_all-->  [B, S, H/N, D]
    full (flash) attention per local head group
    [B, S, H/N, D]  --all_to_all-->  [B, S/N, H, D]

Trade-off vs the ring: one collective round instead of N hops (better
when the per-hop compute is too small to hide a ppermute), but it
requires heads % N == 0 and moves Q as well as K/V. Per-device memory is
O(S * H/N * D) — linear in global sequence length over the head shard,
vs the ring's O(S/N * H * D); both avoid S^2 logits via the flash
kernel. Gradients flow through all_to_all natively (its transpose is the
inverse all_to_all), so no custom vjp is needed — including through the
flash kernel path, whose custom vjp runs the Pallas backward per head
group.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tensor2robot_tpu.parallel import collectives

from tensor2robot_tpu.ops.flash_attention import (
    flash_attention,
    reference_attention,
)
from tensor2robot_tpu.parallel.mesh import SEQUENCE_AXIS


def _ulysses_shard_fn(
    q, k, v, *, axis_name: str, causal: bool, scale: float,
    use_flash: bool, interpret: bool, window=None,
):
    """Per-device body: seq-sharded in, seq-sharded out.

    all_to_all splits the heads axis across devices and concatenates the
    sequence axis, giving each device the FULL sequence for H/N heads;
    attention is then entirely local (no masking subtleties — global
    positions are contiguous here, unlike ring hops).
    """
    # [B, S/N, H, D] -> [B, S, H/N, D]: scatter heads (axis 2), gather
    # sequence (axis 1).
    def scatter_heads(x):
        return collectives.all_to_all(
            x, axis_name, 2, 1, tiled=True
        )

    def gather_heads(x):
        return collectives.all_to_all(
            x, axis_name, 1, 2, tiled=True
        )

    q_local = scatter_heads(q)
    k_local = scatter_heads(k)
    v_local = scatter_heads(v)
    if use_flash:
        out = flash_attention(
            q_local, k_local, v_local, causal=causal, scale=scale,
            interpret=interpret, window=window,
        )
    else:
        # Sequence-parallel heads are never eligible for the serving
        # contraction override (export/serve_quant.py) — suppress it so
        # the local attention computes the exact reference contraction
        # regardless of any ambient lowering context.
        from tensor2robot_tpu.ops.flash_attention import (
            attention_contraction_override,
        )

        with attention_contraction_override(None):
            out = reference_attention(
                q_local, k_local, v_local, causal=causal, scale=scale,
                window=window,
            )
    return gather_heads(out)


def ulysses_attention_manual(
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
    """Ulysses attention INSIDE an enclosing shard_map (manual mode).

    `ulysses_attention` below builds its own shard_map; a caller already
    running under one — the pipelined encoder's per-device program, where
    the pipe axis owns the outer shard_map and the sequence axis is also
    manual — cannot nest another. This entry point runs the same
    per-device head-scatter body directly on the LOCAL shards: q/k/v are
    [batch_local, seq/axis_size, heads, dim], the two all_to_all rounds
    ride collectives.all_to_all over `axis_name`, and local attention is
    the exact reference contraction over the full gathered sequence.
    The ring twin is ring_attention.ring_attention_manual — together
    they make BOTH context-parallel strategies composable with pipeline
    parallelism (parallel/planner.py's widened factorization space); the
    XLA einsum tile is used locally (the flash-kernel path stays on the
    shard_map-owning entry points).
    """
    if q.ndim != 4:
        raise ValueError(f"Expected [B, S_local, H, D], got {q.shape}")
    from tensor2robot_tpu.ops.flash_attention import _check_window

    _check_window(window, causal)
    heads = q.shape[2]
    if heads % axis_size != 0:
        raise ValueError(
            f"Ulysses all-to-all needs heads ({heads}) divisible by the "
            f"{axis_name!r} axis size ({axis_size}); use "
            "ring_attention_manual for head counts that do not split."
        )
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    return _ulysses_shard_fn(
        q, k, v, axis_name=axis_name, causal=causal, scale=scale,
        use_flash=False, interpret=False, window=window,
    )


def ulysses_attention(
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
    """Sequence-parallel attention via head-scatter all_to_all.

    Same contract as ring_attention: q/k/v are [batch, seq, heads, dim]
    with seq sharded over `axis_name`; returns the seq-sharded output.
    Requires seq % axis_size == 0 AND heads % axis_size == 0 (each device
    owns whole heads after the scatter).
    """
    if q.ndim != 4:
        raise ValueError(f"Expected [B, S, H, D], got {q.shape}")
    from tensor2robot_tpu.ops.flash_attention import _check_window

    _check_window(window, causal)
    axis_size = mesh.shape[axis_name]
    _, seq, heads, _ = q.shape
    if seq % axis_size != 0:
        raise ValueError(
            f"Sequence length {seq} must be divisible by the "
            f"{axis_name!r} axis size {axis_size}."
        )
    if heads % axis_size != 0:
        raise ValueError(
            f"Ulysses all-to-all needs heads ({heads}) divisible by the "
            f"{axis_name!r} axis size ({axis_size}); use ring_attention "
            "for head counts that do not split."
        )
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if use_flash is None:
        # Same dispatch policy as ring_attention and single-device
        # attention: XLA einsum path by default on
        # the r3 on-chip evidence, flash above the auto threshold.
        # Ulysses' local attention runs over the FULL sequence (heads
        # are what the all_to_all splits), so the threshold compares
        # the full length; interpret=True keeps the kernel exercised
        # in CPU tests; True opts back in.
        from tensor2robot_tpu.ops.flash_attention import FLASH_AUTO_SEQ

        use_flash = interpret or seq >= FLASH_AUTO_SEQ
    spec = P(None, axis_name, None, None)
    extra = {}
    if use_flash:
        # Pallas kernels inside shard_map trip the varying-manual-axes
        # checker; the einsum path keeps full checking (as in
        # ring_attention._ring_call).
        extra["check_vma"] = False
    fn = collectives.shard_map(
        functools.partial(
            _ulysses_shard_fn, axis_name=axis_name, causal=causal,
            scale=scale, use_flash=use_flash, interpret=interpret,
            window=window,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        **extra,
    )
    return fn(q, k, v)
