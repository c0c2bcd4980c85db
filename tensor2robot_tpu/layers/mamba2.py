"""Mamba-2 mixer: the selective state-space layer in its chunked (dual) form.

Per head h of size P with a state of N channels (arXiv:2405.21060):

    S_t = a_t S_{t-1} + dt_t x_t B_t^T        a_t = exp(-exp(A_log) dt_t)
    y_t = S_t C_t + D x_t

around it a fused input projection `[z, xBC, dt] = W_in u`, a causal
depthwise convolution with SiLU over `xBC = [x, B, C]`, `dt =
softplus(dt + dt_bias)`, the gated norm `RMSNorm(y * silu(z))` over all
`d_inner` channels and the output projection.

The recurrence is never stepped: a sequence is cut into chunks of
`chunk_size`. Inside a chunk the output is a masked matrix product
(quadratic in the chunk), each chunk leaves one state, the states of the
chunks are carried forward by a small matrix over chunks, and the state
that enters a chunk adds its part to every position of it. The decay
between two positions is a difference of cumulative sums of `log a`, kept
in float32; everything that meets the matrix units is in the layer's
compute dtype. The backward is autodiff through these products: with the
block under `nn.remat` nothing of it outlives one layer but what the
remat's policy keeps by name (the input projection's output carries the
`checkpoint_name` "mamba2_in_proj"; the name lowers to nothing elsewhere).

Packed documents: where `segment_ids` change, a new document starts. Its
first token takes `a_t = 0` (no state carries over), and the convolution's
taps that reach into the previous document read zero. In the chunked form
a reset cannot be `log a = -inf` (the differences turn into `inf - inf`),
so every decay between positions of different documents is masked to
zero: inside a chunk, from a position to its chunk's end, between chunk
states, and from a chunk's entering state to its positions.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from tensor2robot_tpu.layers.transformer import RMSNorm


def document_index(segment_ids: jax.Array) -> jax.Array:
    """[B, S] ids -> [B, S] int32 that grows by one wherever the id
    changes: two positions share a document iff their indices are equal,
    also where a later document reuses an earlier id."""
    changed = segment_ids[:, 1:] != segment_ids[:, :-1]
    return jnp.cumsum(
        jnp.pad(changed, ((0, 0), (1, 0))).astype(jnp.int32), axis=1
    )


def causal_conv(x: jax.Array, kernel: jax.Array, bias: jax.Array,
                doc: jax.Array) -> jax.Array:
    """Depthwise causal convolution over [B, S, C] with `kernel` [W, C]:
    out[t] = bias + sum_k kernel[k] x[t - (W-1) + k], a tap reading zero
    before the sequence and across a document boundary."""
    width = kernel.shape[0]
    seq = x.shape[1]
    out = jnp.broadcast_to(bias.astype(x.dtype), x.shape)
    for k in range(width):
        shift = width - 1 - k
        if shift >= seq:
            continue
        tap = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, :seq]
        same = jnp.pad(
            doc, ((0, 0), (shift, 0)), constant_values=-1
        )[:, :seq] == doc
        out = out + jnp.where(same[..., None], tap, 0) * kernel[k].astype(x.dtype)
    return out


def masked_exp(log_decay: jax.Array, mask: jax.Array) -> jax.Array:
    """exp(log_decay) where mask, else 0, with no overflow (and no NaN in
    the gradient) where the masked-out difference is positive."""
    return jnp.where(mask, jnp.exp(jnp.where(mask, log_decay, 0.0)), 0.0)


def ssd_chunked(x: jax.Array, dt: jax.Array, log_a: jax.Array,
                b: jax.Array, c: jax.Array, doc: jax.Array,
                chunk: int) -> jax.Array:
    """y_t = C_t . S_t of the recurrence above, for all t at once.

    x [B, S, H, P] and b, c [B, S, G, N] in the compute dtype; dt and
    log_a = log a_t [B, S, H] in float32; doc [B, S] from
    `document_index`. S must be a multiple of `chunk`. Heads h = g * (H/G)
    + r share group g's B and C.
    """
    batch, seq, heads, head_dim = x.shape
    groups, state = b.shape[2:]
    per_group = heads // groups
    if seq % chunk:
        raise ValueError(
            f"sequence length {seq} is not a multiple of the chunk {chunk}"
        )
    chunks = seq // chunk
    dtype = x.dtype
    f32 = jnp.float32

    xd = (x.astype(f32) * dt[..., None]).astype(dtype).reshape(
        batch, chunks, chunk, groups, per_group, head_dim
    )
    b = b.reshape(batch, chunks, chunk, groups, state)
    c = c.reshape(batch, chunks, chunk, groups, state)
    doc = doc.reshape(batch, chunks, chunk)
    # [B, C, H, L]: log of the decay from the chunk's start through l.
    cum = jnp.cumsum(
        log_a.reshape(batch, chunks, chunk, heads).transpose(0, 1, 3, 2), axis=-1
    )

    # 1. Inside a chunk: (decay * C B^T) x, lower triangle, same document.
    visible = (doc[..., :, None] == doc[..., None, :]) & jnp.tril(
        jnp.ones((chunk, chunk), bool)
    )
    decay = masked_exp(
        cum[..., :, None] - cum[..., None, :], visible[:, :, None]
    )
    scores = jnp.einsum(
        "bclgn,bcsgn->bcgls", c, b, preferred_element_type=f32
    )
    mixing = (
        decay.reshape(batch, chunks, groups, per_group, chunk, chunk)
        * scores[:, :, :, None]
    ).astype(dtype)
    y = jnp.einsum(
        "bcgrls,bcsgrp->bclgrp", mixing, xd, preferred_element_type=f32
    )

    # 2. What each chunk's own tokens leave in the state at its end.
    last_doc = doc[..., -1]
    to_end = masked_exp(
        cum[..., -1:] - cum, (doc == last_doc[..., None])[:, :, None]
    ).transpose(0, 1, 3, 2).reshape(batch, chunks, chunk, groups, per_group)
    left = jnp.einsum(
        "bcsgn,bcsgrp->bcgrpn", b,
        (xd.astype(f32) * to_end[..., None]).astype(dtype),
        preferred_element_type=f32,
    )

    # 3. The state entering chunk z: every earlier chunk's, decayed over
    # the whole chunks between, while the document is still the same.
    through = jnp.cumsum(cum[..., -1], axis=1)            # [B, C, H]
    before = jnp.pad(through, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    doc_before = jnp.pad(last_doc, ((0, 0), (1, 0)), constant_values=-1)[:, :-1]
    earlier = jnp.tril(jnp.ones((chunks, chunks), bool), k=-1)
    carried = masked_exp(
        before[:, :, None] - through[:, None, :],         # [B, z, c, H]
        ((doc_before[:, :, None] == last_doc[:, None, :]) & earlier)[..., None],
    )
    entering = jnp.einsum(
        "bzch,bchpn->bzhpn", carried,
        left.reshape(batch, chunks, heads, head_dim, state),
    ).reshape(batch, chunks, groups, per_group, head_dim, state)

    # 4. What the entering state adds at each position of its document.
    from_start = masked_exp(
        cum, (doc == doc_before[..., None])[:, :, None]
    ).transpose(0, 1, 3, 2).reshape(batch, chunks, chunk, groups, per_group)
    y = y + jnp.einsum(
        "bclgn,bcgrpn->bclgrp", c, entering.astype(dtype),
        preferred_element_type=f32,
    ) * from_start[..., None]
    return y.reshape(batch, seq, heads, head_dim).astype(dtype)


def uniform_log(low: float, high: float):
    def init(key, shape, dtype=jnp.float32):
        return jnp.log(jax.random.uniform(key, shape, dtype, low, high))

    return init


def inverse_softplus_log_uniform(low: float, high: float):
    """dt_bias such that softplus(dt_bias) is log-uniform in [low, high]."""

    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(
            key, shape, dtype, jnp.log(low), jnp.log(high)
        ))
        return dt + jnp.log(-jnp.expm1(-dt))

    return init


class Mamba2Mixer(nn.Module):
    """[B, S, D] -> [B, S, D]; `segment_ids` [B, S] mark packed documents.

    Initialisation follows the family's convention: A uniform in [1, 16],
    dt log-uniform in [1e-3, 1e-1], D = 1, matrices normal(0.02).
    """

    num_heads: int
    head_dim: int
    state_size: int
    num_groups: int = 1
    conv_width: int = 4
    chunk_size: int = 256
    epsilon: float = 1e-5
    # Compute dtype of the projections (None follows input and params).
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, u: jax.Array, segment_ids: jax.Array) -> jax.Array:
        batch, seq, features = u.shape
        heads, head_dim = self.num_heads, self.head_dim
        inner = heads * head_dim
        bc_width = self.num_groups * self.state_size
        conv_width = inner + 2 * bc_width
        dense_init = nn.initializers.normal(0.02)
        doc = document_index(segment_ids)

        with jax.named_scope("mamba2/in_proj"):
            projected = checkpoint_name(nn.Dense(
                inner + conv_width + heads, use_bias=False, dtype=self.dtype,
                kernel_init=dense_init, name="in_proj",
            )(u), "mamba2_in_proj")
            z, xbc, dt = jnp.split(projected, [inner, inner + conv_width], axis=-1)
        with jax.named_scope("mamba2/conv"):
            kernel = self.param(
                "conv_kernel", dense_init, (self.conv_width, conv_width)
            )
            bias = self.param("conv_bias", nn.initializers.zeros, (conv_width,))
            xbc = nn.silu(causal_conv(xbc, kernel, bias, doc))
            x, b, c = jnp.split(xbc, [inner, inner + bc_width], axis=-1)
        with jax.named_scope("mamba2/ssd"):
            a_log = self.param("A_log", uniform_log(1.0, 16.0), (heads,))
            d_skip = self.param("D", nn.initializers.ones, (heads,))
            dt_bias = self.param(
                "dt_bias", inverse_softplus_log_uniform(1e-3, 1e-1), (heads,)
            )
            dt = nn.softplus(dt.astype(jnp.float32) + dt_bias)
            x = x.reshape(batch, seq, heads, head_dim)
            y = ssd_chunked(
                x, dt, -jnp.exp(a_log) * dt,
                b.reshape(batch, seq, self.num_groups, self.state_size),
                c.reshape(batch, seq, self.num_groups, self.state_size),
                doc, self.chunk_size,
            )
            y = y + x * d_skip[:, None].astype(x.dtype)
        with jax.named_scope("mamba2/gate_norm"):
            # RMSNorm(y * silu(z)) over all `inner` channels, in float32.
            gated = y.reshape(batch, seq, inner).astype(jnp.float32) * nn.silu(
                z.astype(jnp.float32)
            )
            y = RMSNorm(self.epsilon, name="norm")(gated).astype(y.dtype)
        with jax.named_scope("mamba2/out_proj"):
            return nn.Dense(
                features, use_bias=False, dtype=self.dtype,
                kernel_init=dense_init, name="out_proj",
            )(y)
