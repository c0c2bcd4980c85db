"""Transformer blocks over the attention hot op.

Beyond the reference layer library (its temporal models top out at
SNAIL/TCN scale, layers/snail.py; SURVEY §5 long-context row): a standard
pre-norm transformer whose attention routes through ops/flash_attention —
single-device attention on the XLA einsum path below _FLASH_AUTO_SEQ
and the Pallas flash kernel above it (O(S^2) logits vs O(S) tiles; see
MultiHeadAttention.use_flash for the measured rationale), and
sequence-parallel attention when constructed with a mesh whose
`sequence` axis is >1 — the ring (parallel/ring_attention.py) by
default, or Ulysses all-to-all (parallel/ulysses_attention.py) via
`sequence_parallel_mode="ulysses"`; the mesh paths share the same
einsum-first dispatch policy (flash opt-in). Sequence length lives in
the specs, so the same model trains short episodes on one chip and long
contexts on a CP mesh without code changes.
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from tensor2robot_tpu.ops import flash_attention as flash_lib
from tensor2robot_tpu.parallel import mesh as mesh_lib

# Single-device auto-dispatch crossover: below this sequence length the
# XLA einsum path wins on measured speed; at/above it the einsum path's
# [S, S] logits (b8/h8 f32 at S=4096: ~4 GiB) OOM territory where the
# flash kernel's O(S) tiles still fit. The constant is shared with the
# sequence-parallel paths (same policy on the per-device attended
# length): ops/flash_attention.FLASH_AUTO_SEQ.
_FLASH_AUTO_SEQ = flash_lib.FLASH_AUTO_SEQ


class MultiHeadAttention(nn.Module):
    """Self-attention over [batch, seq, features].

    mesh: when given with a sequence axis > 1, attention runs
    sequence-parallel (the ring by default; `sequence_parallel_mode=
    "ulysses"` selects the all-to-all strategy); otherwise single-device
    attention via plain XLA (default) or the Pallas flash kernel
    (use_flash=True).
    """

    num_heads: int
    head_dim: int
    causal: bool = True
    mesh: Optional[object] = None
    # Attention kernel policy, tri-state:
    #   None (default) — auto. Single-device attention takes the XLA
    #     einsum path below _FLASH_AUTO_SEQ, measured FASTER than the
    #     Pallas flash kernel on the available chip (BENCH_FLASH_r03
    #     microbench: flash fwd 1.33 TFLOPS at b4/s2048/h8/d128 bf16,
    #     ~0.7% of peak; docs/PERFORMANCE.md); at seq >=
    #     _FLASH_AUTO_SEQ it switches to the flash kernel because the
    #     einsum path's [S, S] logits are O(S^2) HBM and OOM where
    #     flash's O(S) tiles still fit (the r4 A/B's expected einsum
    #     OOM at S=4096). Sequence-parallel (mesh) attention defaults
    #     to the einsum path too (ring/ulysses follow the same r3
    #     evidence; per-hop logits there are [S/N, S/N] shards, so the
    #     memory pressure is divided by the mesh).
    #   True — force the flash kernel everywhere (the O(S)-memory lever
    #     at any length).
    #   False — force the einsum path everywhere (long S may OOM).
    # The on-chip A/B (tools/validate_flash_tpu.py -> BENCH_FLASH_r05)
    # re-evaluates this default each capture.
    use_flash: Optional[bool] = None
    interpret: bool = False
    # Causal sliding window W (each query attends to its last W steps).
    # Works on every path: single-device flash tightens its k-block loop,
    # the ring truncates its rotation to the hops carrying visible tiles,
    # and ulysses passes W to its full-sequence local attention.
    window: Optional[int] = None
    # Context-parallel strategy when the mesh's sequence axis is >1:
    # "ring" (K/V rotate, O(seq/N) memory/device) or "ulysses" (head-
    # scatter all_to_all, one collective round, needs heads % N == 0).
    sequence_parallel_mode: str = "ring"
    # Incremental decoding: calls carry ONE new step ([B, 1, F]) which is
    # appended to a K/V cache ("cache" variable collection, capacity
    # decode_max_len) and attended against the cached prefix — the
    # streaming-serving mode (O(cache) per step; O(window) when a window
    # caps it). Requires causal=True and no sequence-parallel mesh.
    decode: bool = False
    decode_max_len: int = 2048
    # Grouped-query attention: num_kv_heads < num_heads shares each K/V
    # head across a GROUP of query heads (GQA, arXiv:2305.13245). The
    # projection and — the point for robots — the decode-mode K/V cache
    # shrink by the group factor; K/V are broadcast back to num_heads
    # only at attend time. None = num_heads (standard MHA).
    num_kv_heads: Optional[int] = None
    # Manual sequence parallelism: >1 means this attention already runs
    # INSIDE a shard_map whose manual axes include the sequence axis (the
    # pipelined encoder's per-device program) and its input is the LOCAL
    # sequence shard. Attention then rides the manual entry point of the
    # selected strategy — ring_attention.ring_attention_manual or
    # ulysses_attention.ulysses_attention_manual — over that axis instead
    # of opening its own shard_map (which cannot nest). The piece that
    # composes SP with PP (parallel/planner.py 3D plans).
    manual_sequence_size: int = 1
    # Score multiplier; None is the ops' default of head_dim ** -0.5.
    # Single-device full-forward paths only.
    scale: Optional[float] = None
    # Compute dtype of the projections (None follows input and params).
    dtype: Optional[jnp.dtype] = None
    kernel_init: Callable = nn.linear.default_kernel_init

    def _kv_heads(self) -> int:
        kv = self.num_kv_heads if self.num_kv_heads is not None else self.num_heads
        if self.num_heads % kv != 0:
            raise ValueError(
                f"num_heads={self.num_heads} must be divisible by "
                f"num_kv_heads={kv}"
            )
        return kv

    def _expand_kv(self, t: jax.Array) -> jax.Array:
        """[B, S, KVH, D] -> [B, S, H, D] by repeating each kv head over
        its query group (no-op for standard MHA)."""
        groups = self.num_heads // t.shape[2]
        if groups == 1:
            return t
        return jnp.repeat(t, groups, axis=2)

    @nn.compact
    def __call__(
        self, x: jax.Array, segment_ids: Optional[jax.Array] = None
    ) -> jax.Array:
        """segment_ids [B, S]: packed documents; a query then sees the
        earlier keys of its own document only (causal, single device)."""
        batch, seq, _ = x.shape
        features = self.num_heads * self.head_dim
        kv_heads = self._kv_heads()
        kv_features = kv_heads * self.head_dim

        def project(width, name):
            return nn.Dense(
                width, use_bias=False, dtype=self.dtype,
                kernel_init=self.kernel_init, name=name,
            )

        with jax.named_scope("attention_proj"):
            qkv = project(features + 2 * kv_features, "qkv")(x)
        q, k, v = jnp.split(
            qkv, [features, features + kv_features], axis=-1
        )
        q = q.reshape(batch, seq, self.num_heads, self.head_dim)
        k = k.reshape(batch, seq, kv_heads, self.head_dim)
        v = v.reshape(batch, seq, kv_heads, self.head_dim)
        distributed = (
            self.decode or self.mesh is not None
            or self.manual_sequence_size > 1
        )
        if self.scale is not None and distributed:
            raise ValueError(
                "scale is taken by the single-device full forward only"
            )
        if segment_ids is not None and (
            distributed or self.window is not None or not self.causal
        ):
            raise ValueError(
                "segment_ids belong to the causal single-device full "
                "forward: no decode, mesh or window"
            )
        if segment_ids is not None:
            with jax.named_scope("attention"):
                out = flash_lib.segment_attention(
                    q, k, v, segment_ids, scale=self.scale
                )
            with jax.named_scope("attention_proj"):
                return project(x.shape[-1], "out")(
                    out.reshape(batch, seq, features)
                )
        if self.decode:
            # The cache stores kv_heads only (the GQA memory win); the
            # group broadcast happens on the read inside _decode_step.
            out = self._decode_step(q, k, v)
            out = out.reshape(batch, seq, features)
            return project(x.shape[-1], "out")(out)
        # Training/full-forward paths attend at full head count: the
        # flash/ring/ulysses kernels take equal q/k head dims.
        k, v = self._expand_kv(k), self._expand_kv(v)
        if self.sequence_parallel_mode not in ("ring", "ulysses"):
            # Validate eagerly — a typo must fail on the laptop run, not
            # only once the config reaches a multi-device CP mesh.
            raise ValueError(
                "sequence_parallel_mode must be 'ring' or 'ulysses', "
                f"got {self.sequence_parallel_mode!r}"
            )
        if self.manual_sequence_size > 1:
            if self.sequence_parallel_mode == "ulysses":
                from tensor2robot_tpu.parallel.ulysses_attention import (
                    ulysses_attention_manual,
                )

                out = ulysses_attention_manual(
                    q, k, v,
                    axis_name=mesh_lib.SEQUENCE_AXIS,
                    axis_size=self.manual_sequence_size,
                    causal=self.causal,
                    window=self.window,
                )
            else:
                from tensor2robot_tpu.parallel.ring_attention import (
                    ring_attention_manual,
                )

                out = ring_attention_manual(
                    q, k, v,
                    axis_name=mesh_lib.SEQUENCE_AXIS,
                    axis_size=self.manual_sequence_size,
                    causal=self.causal,
                    window=self.window,
                )
            out = out.reshape(batch, seq, features)
            return project(x.shape[-1], "out")(out)
        sequence_axis = (
            dict(self.mesh.shape).get(mesh_lib.SEQUENCE_AXIS, 1)
            if self.mesh is not None
            else 1
        )
        if sequence_axis > 1 and self.sequence_parallel_mode == "ulysses":
            from tensor2robot_tpu.parallel.ulysses_attention import (
                ulysses_attention,
            )

            # The sequence-parallel paths KEEP their own None=auto flash
            # default (ring_attention.py:204): per-hop tiles materialize
            # S_local^2 logits on the einsum path, so flash there is a
            # memory lever first and the kernels' shape-fallback applies.
            out = ulysses_attention(
                q, k, v, mesh=self.mesh, causal=self.causal,
                use_flash=self.use_flash, interpret=self.interpret,
                window=self.window,
            )
        elif sequence_axis > 1:
            from tensor2robot_tpu.parallel.ring_attention import ring_attention

            out = ring_attention(
                q, k, v, mesh=self.mesh, causal=self.causal,
                use_flash=self.use_flash, interpret=self.interpret,
                window=self.window,
            )
        else:
            use_flash = self.use_flash
            if use_flash is None:
                # Auto: einsum wins on measured speed at moderate S, but
                # its [S, S] logits are O(S^2) HBM — above the threshold
                # only flash's O(S) tiles fit (use_flash docstring).
                use_flash = seq >= _FLASH_AUTO_SEQ
            if use_flash:
                out = flash_lib.flash_attention(
                    q, k, v, causal=self.causal, scale=self.scale,
                    interpret=self.interpret, window=self.window,
                )
            else:
                # Plain-XLA attention, measured faster on-chip than the
                # Pallas kernel at these sizes (use_flash docstring).
                out = flash_lib.reference_attention(
                    q, k, v, causal=self.causal, scale=self.scale,
                    window=self.window,
                )
        out = out.reshape(batch, seq, features)
        return project(x.shape[-1], "out")(out)

    def _decode_step(self, q, k, v):
        """Appends this step's k/v to the cache and attends q against the
        cached prefix. One step per call ([B, 1, H, D]); with a window,
        attention reads only the last `window` cache slots (dynamic_slice
        with clamped start), so per-step cost is O(window) not O(max_len).

        Cache lifecycle: `init` RUNS the module, so the cache it returns
        has already consumed the init step — zero it before the first real
        step (`jax.tree_util.tree_map(jnp.zeros_like, variables["cache"])`)
        and thread the mutated collection between calls
        (`apply(..., mutable=["cache"])`).
        """
        if not self.causal:
            raise ValueError("decode mode requires causal=True")
        if self.mesh is not None and (
            dict(self.mesh.shape).get(mesh_lib.SEQUENCE_AXIS, 1) > 1
        ):
            raise ValueError(
                "decode mode is single-device (serving); drop the "
                "sequence-parallel mesh"
            )
        batch, seq, _, dim = q.shape
        kv_heads = k.shape[2]
        if seq != 1:
            raise ValueError(
                f"decode mode consumes ONE step per call, got seq={seq}; "
                "run the full-sequence forward for teacher forcing"
            )
        cached_k = self.variable(
            "cache", "cached_key",
            jnp.zeros, (batch, self.decode_max_len, kv_heads, dim), k.dtype,
        )
        cached_v = self.variable(
            "cache", "cached_value",
            jnp.zeros, (batch, self.decode_max_len, kv_heads, dim), v.dtype,
        )
        index = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        i = index.value
        cached_k.value = lax.dynamic_update_slice(
            cached_k.value, k, (0, i, 0, 0)
        )
        cached_v.value = lax.dynamic_update_slice(
            cached_v.value, v, (0, i, 0, 0)
        )
        index.value = i + 1

        if self.window is not None:
            span = min(self.window, self.decode_max_len)
            # Last `span` slots ending at i (clamped at the left edge; the
            # global-position mask inside reference_attention hides any
            # pre-history the clamp drags in at the start of the episode).
            start = jnp.clip(i - span + 1, 0, self.decode_max_len - span)
            k_ctx = lax.dynamic_slice(
                cached_k.value, (0, start, 0, 0),
                (batch, span, kv_heads, dim),
            )
            v_ctx = lax.dynamic_slice(
                cached_v.value, (0, start, 0, 0),
                (batch, span, kv_heads, dim),
            )
        else:
            start = 0
            k_ctx, v_ctx = cached_k.value, cached_v.value
        # GQA: broadcast the cached kv heads to the query head count only
        # here, at attend time — the cache itself stays kv_heads wide.
        k_ctx, v_ctx = self._expand_kv(k_ctx), self._expand_kv(v_ctx)
        # The numerics oracle already speaks tiled global positions: the
        # single query sits at position i, the cache slice at `start`.
        return flash_lib.reference_attention(
            q.astype(jnp.float32),
            k_ctx.astype(jnp.float32),
            v_ctx.astype(jnp.float32),
            causal=True,
            q_offset=i,
            k_offset=start,
            window=self.window,
        ).astype(q.dtype)


class TransformerBlock(nn.Module):
    """Pre-norm block: x + MHA(LN(x)); x + FFN(LN(x)).

    The feed-forward is dense by default; `num_experts > 1` swaps in the
    expert-parallel MoE (layers/moe.py, experts sharded over the mesh's
    `expert` axis), whose router aux loss is accumulated into the
    "moe_aux_loss" collection for the caller's loss term.
    """

    num_heads: int
    head_dim: int
    mlp_ratio: int = 4
    causal: bool = True
    mesh: Optional[object] = None
    use_flash: Optional[bool] = None
    interpret: bool = False
    num_experts: int = 1
    num_selected_experts: int = 2
    sequence_parallel_mode: str = "ring"
    window: Optional[int] = None
    decode: bool = False
    decode_max_len: int = 2048
    num_kv_heads: Optional[int] = None
    manual_sequence_size: int = 1

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = x + MultiHeadAttention(
            num_heads=self.num_heads,
            head_dim=self.head_dim,
            causal=self.causal,
            mesh=self.mesh,
            use_flash=self.use_flash,
            interpret=self.interpret,
            sequence_parallel_mode=self.sequence_parallel_mode,
            window=self.window,
            decode=self.decode,
            decode_max_len=self.decode_max_len,
            num_kv_heads=self.num_kv_heads,
            manual_sequence_size=self.manual_sequence_size,
            name="attention",
        )(nn.LayerNorm(name="ln_attn")(x))
        h = nn.LayerNorm(name="ln_mlp")(x)
        if self.num_experts > 1:
            from tensor2robot_tpu.layers.moe import MoEBlock

            h, aux_loss = MoEBlock(
                num_experts=self.num_experts,
                hidden_dim=self.mlp_ratio * x.shape[-1],
                num_selected=self.num_selected_experts,
                mesh=self.mesh,
                name="moe",
            )(h)
            self.sow("moe_aux_loss", "aux_loss", aux_loss)
        else:
            h = nn.Dense(self.mlp_ratio * x.shape[-1], name="mlp_in")(h)
            h = nn.gelu(h)
            h = nn.Dense(x.shape[-1], name="mlp_out")(h)
        return x + h


class LatentAttention(nn.Module):
    """Multi-head latent attention without positions (MLA, `mla_use_nope`),
    over packed documents, in its expanded (training) form:

        q = W_q x                       heads of qk_nope_dim + qk_rope_dim
        [c, k_pe] = W_kva x             a latent of kv_rank, qk_rope_dim shared
        [k_nope, v] = W_kvb RMSNorm(c)  heads of qk_nope_dim + v_dim
        k = [k_nope, k_pe]              k_pe the same for every head, never rotated

    scores `q k^T (qk_nope_dim + qk_rope_dim)^-1/2`, causal and within
    `segment_ids`' documents (`ops/flash_attention.segment_attention`, whose
    values may be narrower than its keys), then `W_o`. The latent is what a
    decode cache would hold; there is none here.
    """

    num_heads: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    kv_rank: int
    epsilon: float = 1e-5
    dtype: Optional[jnp.dtype] = None
    kernel_init: Callable = nn.linear.default_kernel_init

    @nn.compact
    def __call__(self, x: jax.Array, segment_ids: jax.Array) -> jax.Array:
        batch, seq, _ = x.shape
        heads, nope, rope = self.num_heads, self.qk_nope_dim, self.qk_rope_dim

        def project(width, name):
            return nn.Dense(
                width, use_bias=False, dtype=self.dtype,
                kernel_init=self.kernel_init, name=name,
            )

        with jax.named_scope("mla/q_proj"):
            q = checkpoint_name(
                project(heads * (nope + rope), "q_proj")(x), "mla_q_proj"
            ).reshape(batch, seq, heads, nope + rope)
        with jax.named_scope("mla/kv_down"):
            latent, k_pe = jnp.split(
                project(self.kv_rank + rope, "kv_a")(x), [self.kv_rank], axis=-1
            )
            latent = RMSNorm(self.epsilon, name="kv_norm")(latent)
        with jax.named_scope("mla/kv_up"):
            kv = project(heads * (nope + self.v_dim), "kv_b")(latent).reshape(
                batch, seq, heads, nope + self.v_dim
            )
            k = jnp.concatenate([
                kv[..., :nope],
                jnp.broadcast_to(k_pe[:, :, None], (batch, seq, heads, rope)),
            ], axis=-1)
            v = kv[..., nope:]
        with jax.named_scope("attention"):
            out = flash_lib.segment_attention(q, k, v, segment_ids)
        with jax.named_scope("attention_proj"):
            return project(x.shape[-1], "o_proj")(
                out.reshape(batch, seq, heads * self.v_dim)
            )


class RMSNorm(nn.Module):
    """x / rms(x) * scale over the last axis, statistics in float32."""

    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        variance = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return (x32 * lax.rsqrt(variance + self.epsilon) * scale).astype(x.dtype)


class SwiGLU(nn.Module):
    """W_down (silu(W_gate x) * (W_up x)), no bias.

    The two wide products carry `checkpoint_name`s ("mlp_gate", "mlp_up"):
    a `jax.checkpoint` with a names policy may keep them; anywhere else the
    names lower to nothing.
    """

    hidden_dim: int
    dtype: Optional[jnp.dtype] = None
    kernel_init: Callable = nn.linear.default_kernel_init
    # The `jax.named_scope` its device time is billed to.
    scope_name: str = "mlp"

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        def dense(width, name):
            return nn.Dense(
                width, use_bias=False, dtype=self.dtype,
                kernel_init=self.kernel_init, name=name,
            )

        with jax.named_scope(self.scope_name):
            gate = checkpoint_name(dense(self.hidden_dim, "gate")(x), "mlp_gate")
            up = checkpoint_name(dense(self.hidden_dim, "up")(x), "mlp_up")
            return dense(x.shape[-1], "down")(nn.silu(gate) * up)


class HybridBlock(nn.Module):
    """Pre-RMSNorm block whose mixer is chosen by `layer_type`:

        u = h + r * Mixer(RMSNorm(h));  h_next = u + r * SwiGLU(RMSNorm(u))

    "mamba" is the Mamba-2 mixer (layers/mamba2.py); "attention" is
    grouped-query attention with no positional encoding and a fixed score
    multiplier. Both read `segment_ids` [B, S]: packed documents do not
    see each other.
    """

    layer_type: str
    num_heads: int
    num_kv_heads: int
    head_dim: int
    attention_multiplier: float
    mlp_dim: int
    mamba_heads: int
    mamba_head_dim: int
    mamba_state: int
    mamba_groups: int = 1
    mamba_conv: int = 4
    mamba_chunk: int = 256
    residual_multiplier: float = 1.0
    epsilon: float = 1e-5
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, h: jax.Array, segment_ids: jax.Array) -> jax.Array:
        init = nn.initializers.normal(0.02)
        normed = RMSNorm(self.epsilon, name="norm_mixer")(h)
        if self.layer_type == "mamba":
            from tensor2robot_tpu.layers.mamba2 import Mamba2Mixer

            mixed = Mamba2Mixer(
                num_heads=self.mamba_heads, head_dim=self.mamba_head_dim,
                state_size=self.mamba_state, num_groups=self.mamba_groups,
                conv_width=self.mamba_conv, chunk_size=self.mamba_chunk,
                epsilon=self.epsilon, dtype=self.dtype, name="mixer",
            )(normed, segment_ids)
        elif self.layer_type == "attention":
            mixed = MultiHeadAttention(
                num_heads=self.num_heads, head_dim=self.head_dim,
                num_kv_heads=self.num_kv_heads, causal=True,
                scale=self.attention_multiplier, dtype=self.dtype,
                kernel_init=init, name="mixer",
            )(normed, segment_ids)
        else:
            raise ValueError(f"no mixer for layer type {self.layer_type!r}")
        u = h + self.residual_multiplier * mixed
        mlp = SwiGLU(self.mlp_dim, dtype=self.dtype, kernel_init=init, name="mlp")(
            RMSNorm(self.epsilon, name="norm_mlp")(u)
        )
        return u + self.residual_multiplier * mlp


class PipelineStage(nn.Module):
    """The repeating unit of the pipelined encoder: a run of pre-norm
    blocks. Stage-internal attention is single-device by default; a
    sequence_axis_size > 1 (the DP x SP x PP composition) runs each
    block's attention as a MANUAL context-parallel strategy — ring K/V
    rotation or ulysses head-scatter, per sequence_parallel_mode — over
    the sequence axis, legal because the stage executes inside
    pipeline_apply's shard_map, where the sequence axis is manual
    alongside pipe."""

    num_blocks: int
    num_heads: int
    head_dim: int
    mlp_ratio: int = 4
    causal: bool = True
    use_flash: Optional[bool] = None
    interpret: bool = False
    window: Optional[int] = None
    num_kv_heads: Optional[int] = None
    sequence_axis_size: int = 1
    sequence_parallel_mode: str = "ring"

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        for i in range(self.num_blocks):
            x = TransformerBlock(
                num_heads=self.num_heads,
                head_dim=self.head_dim,
                mlp_ratio=self.mlp_ratio,
                causal=self.causal,
                mesh=None,
                use_flash=self.use_flash,
                interpret=self.interpret,
                window=self.window,
                num_kv_heads=self.num_kv_heads,
                manual_sequence_size=self.sequence_axis_size,
                sequence_parallel_mode=self.sequence_parallel_mode,
                name=f"block_{i}",
            )(x)
        return x


class TransformerEncoder(nn.Module):
    """N pre-norm blocks with learned positional embeddings over
    [batch, seq, features]; final LayerNorm.

    pipeline_stages > 1 runs the block stack as a GPipe pipeline over the
    mesh's `pipe` axis (parallel/pipeline.py): the blocks split into
    equal stages whose stacked parameters live under the `pipe_stages`
    param key (sharded dim-0 over `pipe` by the trainer's sharding
    rules), and the batch streams through in `pipeline_microbatches`
    microbatches. Composes with the data axis and with sequence
    parallelism (ring or ulysses, run manually inside the pipeline's
    shard_map); mutually exclusive with MoE inside the pipelined stack.
    """

    num_layers: int
    num_heads: int
    head_dim: int
    max_seq_len: int = 2048
    mlp_ratio: int = 4
    causal: bool = True
    mesh: Optional[object] = None
    use_flash: Optional[bool] = None
    interpret: bool = False
    num_experts: int = 1
    num_selected_experts: int = 2
    sequence_parallel_mode: str = "ring"
    pipeline_stages: int = 1
    pipeline_microbatches: Optional[int] = None
    window: Optional[int] = None
    decode: bool = False
    num_kv_heads: Optional[int] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        batch, seq, features = x.shape
        if seq > self.max_seq_len:
            raise ValueError(
                f"sequence length {seq} exceeds max_seq_len={self.max_seq_len}"
            )
        positions = self.param(
            "pos_embedding",
            nn.initializers.normal(0.02),
            (self.max_seq_len, features),
        )
        if self.decode:
            return self._decode_step(x, positions)
        x = x + positions[None, :seq, :]
        if self.pipeline_stages > 1:
            x = self._pipelined_blocks(x)
        else:
            for i in range(self.num_layers):
                x = self._block(i)(x)
        return nn.LayerNorm(name="ln_final")(x)

    def _block(self, i: int, decode: bool = False) -> "TransformerBlock":
        """One stack block; the decode twin differs only in cache mode
        (identical param naming, so trained variables slot straight in)."""
        return TransformerBlock(
            num_heads=self.num_heads,
            head_dim=self.head_dim,
            mlp_ratio=self.mlp_ratio,
            causal=self.causal,
            mesh=self.mesh,
            use_flash=self.use_flash,
            interpret=self.interpret,
            num_experts=self.num_experts,
            num_selected_experts=self.num_selected_experts,
            sequence_parallel_mode=self.sequence_parallel_mode,
            window=self.window,
            decode=decode,
            decode_max_len=self.max_seq_len,
            num_kv_heads=self.num_kv_heads,
            name=f"block_{i}",
        )

    def _decode_step(self, x: jax.Array, positions: jax.Array) -> jax.Array:
        """One incremental step: positional embedding at the episode
        position (own cache counter), then the block stack in decode mode
        (each attention appends to its K/V cache). Mutate the "cache"
        collection across calls: `module.apply(..., mutable=["cache"])`.
        """
        if self.pipeline_stages > 1:
            raise ValueError("decode mode does not compose with pipelining")
        pos = self.variable(
            "cache", "position", lambda: jnp.zeros((), jnp.int32)
        )
        step = lax.dynamic_slice(
            positions, (pos.value, 0), (1, positions.shape[1])
        )
        pos.value = pos.value + 1
        x = x + step[None]
        for i in range(self.num_layers):
            x = self._block(i, decode=True)(x)
        return nn.LayerNorm(name="ln_final")(x)

    def _pipelined_blocks(self, x: jax.Array) -> jax.Array:
        """Blocks as a GPipe schedule over the mesh's pipe axis."""
        from tensor2robot_tpu.parallel import mesh as mesh_mod
        from tensor2robot_tpu.parallel import pipeline

        stages = self.pipeline_stages
        if self.num_layers % stages != 0:
            raise ValueError(
                f"num_layers={self.num_layers} not divisible by "
                f"pipeline_stages={stages}"
            )
        if self.num_experts > 1:
            raise ValueError(
                "pipeline_stages > 1 does not compose with MoE feed-"
                "forwards (the router aux-loss channel does not cross the "
                "pipeline schedule)"
            )
        if self.mesh is None:
            raise ValueError("pipeline_stages > 1 requires a mesh")
        mesh_axes = dict(self.mesh.shape)
        if mesh_axes.get(mesh_mod.PIPE_AXIS, 1) != stages:
            raise ValueError(
                f"mesh pipe axis {mesh_axes.get(mesh_mod.PIPE_AXIS, 1)} "
                f"!= pipeline_stages={stages}"
            )
        seq_size = mesh_axes.get(mesh_mod.SEQUENCE_AXIS, 1)
        if seq_size > 1 and self.sequence_parallel_mode not in (
            "ring", "ulysses"
        ):
            raise ValueError(
                "pipeline_stages > 1 composes with sequence parallelism "
                "in ring or ulysses mode (the in-shard_map manual "
                "strategies); got "
                f"sequence_parallel_mode={self.sequence_parallel_mode!r}"
            )
        if (
            seq_size > 1
            and self.sequence_parallel_mode == "ulysses"
            and self.num_heads % seq_size != 0
        ):
            raise ValueError(
                f"ulysses inside the pipeline needs num_heads="
                f"{self.num_heads} divisible by the sequence axis size "
                f"{seq_size} (each device owns whole heads after the "
                "all_to_all scatter); use ring mode otherwise"
            )
        if seq_size > 1 and x.shape[1] % seq_size != 0:
            raise ValueError(
                f"sequence length {x.shape[1]} not divisible by the "
                f"sequence axis size {seq_size}"
            )

        def make_stage(sequence_axis_size: int) -> PipelineStage:
            return PipelineStage(
                num_blocks=self.num_layers // stages,
                num_heads=self.num_heads,
                head_dim=self.head_dim,
                mlp_ratio=self.mlp_ratio,
                causal=self.causal,
                use_flash=self.use_flash,
                interpret=self.interpret,
                window=self.window,
                num_kv_heads=self.num_kv_heads,
                sequence_axis_size=sequence_axis_size,
                sequence_parallel_mode=self.sequence_parallel_mode,
            )

        # The applied stage runs the manual context-parallel strategy
        # (ring or ulysses) when the mesh shards the sequence; init runs
        # OUTSIDE pipeline_apply's shard_map (no
        # manual axes yet), so it uses a single-device twin — attention
        # strategy does not change the parameter structure.
        stage = make_stage(seq_size)
        init_stage = make_stage(1)
        batch = x.shape[0]
        data_size = mesh_axes.get(mesh_mod.DATA_AXIS, 1)
        if self.pipeline_microbatches is not None:
            micro = self.pipeline_microbatches
            if batch % micro != 0:
                raise ValueError(
                    f"batch {batch} not divisible by pipeline_microbatches="
                    f"{micro}"
                )
        else:
            # Default: the largest valid microbatch count up to 2*S (~33%
            # bubble). Valid = divides the batch AND leaves each
            # microbatch's example dim divisible by the data axis
            # (pipeline_apply shards it there under dp x pp).
            if batch % data_size != 0:
                raise ValueError(
                    f"batch {batch} not divisible by data axis {data_size}"
                )
            limit = batch // data_size
            micro = max(
                d
                for d in range(1, min(limit, 2 * stages) + 1)
                if limit % d == 0
            )

        def init_stacked(rng):
            dummy = jnp.zeros((1,) + x.shape[1:], x.dtype)
            rngs = jax.random.split(rng, stages)
            return pipeline.stack_stage_params(
                [init_stage.init(r, dummy)["params"] for r in rngs]
            )

        stacked = self.param(mesh_mod.PIPE_STAGES_KEY, init_stacked)
        return pipeline.pipeline_apply(
            lambda p, h: stage.apply({"params": p}, h),
            stacked,
            x,
            mesh=self.mesh,
            num_microbatches=micro,
            batch_axis=mesh_mod.DATA_AXIS if data_size > 1 else None,
            sequence_axis=(
                mesh_mod.SEQUENCE_AXIS if seq_size > 1 else None
            ),
        )
