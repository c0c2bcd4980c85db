"""Kimi Delta Attention (KDA) mixer: the gated delta rule with a decay of its
own for every channel, computed in chunks.

Per head with keys of K channels and values of V, a state S in R^{K x V}:

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                     a_t = exp(g_t) in (0, 1]^K

around it `q^ = silu(conv(W_q x))` (k and v alike, a causal depthwise
convolution each), `q = q^ / |q^| * K^-1/2`, `k = k^ / |k^|`, the decay
`g_t = -exp(A_log) * softplus(W_f2 (W_f1 x_t) + dt_bias)`, `beta_t =
sigmoid(W_b x_t)`, and the output `W_o [RMSNorm_head(o_t) * sigmoid(W_g2
(W_g1 x_t) + b_g)]`.

Unlike `layers/mamba2.py`'s scan the update is not diagonal, so the
recurrence is cut into chunks of C positions that are solved, not summed.
With `G_i` the sum of `g` from the chunk's start through i:

    A[i, j] = beta_i sum_d k_id k_jd exp(G_id - G_jd)        j < i
    T = (I + A)^-1
    W = T (beta * k * exp(G)),  U = T (beta * v)
    V' = U - W S                          S the state entering the chunk
    o  = (q * exp(G)) S + B V'            B[i, j] as A with q_i, j <= i, no beta
    S' = Diag(exp(G_C)) S + sum_j (k_j * exp(G_C - G_j))^T V'_j

Every exponent that is formed is <= 0. `exp(G_i - G_j)` does not factor
into `exp(G_i) exp(-G_j)` (the second overflows under a strong decay), and
written out it is a [C, C, K] array for every chunk and head. So a chunk is
cut once more, into sub-blocks of `SUB_BLOCK` positions: between two
sub-blocks the decay is taken through the later one's first position
(`exp(G_i - G_ref) exp(G_ref - G_j)`, both factors <= 1) and the scores are
a matrix product; inside a sub-block the [c, c, K] differences are formed
and summed at once. Where they live depends on the platform the program is
lowered for (`_pair_scores`): on a TPU, with bfloat16 operands and K a
multiple of 128, in VMEM, a grid step of `ops/kda_pair_scores`' two Pallas
kernels (forward, and a hand-written backward that forms them again) at a
time, never in HBM; everywhere else in HBM as float32 arrays of XLA's, a
slab of chunks at a time, each slab recomputed in the backward pass
(`_pair_scores_slabs`). `(I + A)^-1` is forward substitution inside a
sub-block, all sub-blocks at once, and the block-triangular formula above
it. W and U are one einsum over all chunks. The walk over the chunks (the
state from chunk to chunk, and `V'` and `o`, which read the state that enters
a chunk) has two forms too, chosen the same way (`_chunk_outputs`): on a TPU,
with bfloat16 operands, K and V multiples of 128 and a group's heads a
multiple of eight, `ops/kda_carry`'s Pallas kernels: the state stays in VMEM
as float32 for the whole walk, a grid step a chunk of eight heads forms `V'`,
`o` and the next state where it is, and only the backward's residual, the
entering states in float32, is written to HBM (by the forward that the
backward pass runs; the backward kernel walks the chunks in reverse);
everywhere else a `lax.scan` over two products a chunk that stacks the
entering states in HBM, and three einsums over all chunks that read them.
The backward is autodiff (but for the kernels' own),
a group of heads at a time, each group recomputed from q, k, v, g and beta
(`kda_chunked`); the output carries the `checkpoint_name` "kda_out" so
that a block under `nn.remat` can keep it and not run the rule a third
time.

The rule is one jitted function, `kda_chunked`, shared by every layer that
calls it at the same shapes: a step program of several KDA layers traces
it once (jax's trace cache answers the other layers, and with it the JVP,
the partial evaluation and the transpose of that one jaxpr) and lowers a
forward and a backward program once each, which every layer calls and XLA
inlines; the kernels' bodies are lowered for Mosaic once a program, not
once a layer and pass. Its arguments are all the arrays it reads; what it
reads of this module's globals is read when it is first traced.

Packed documents: at a document's first token `a_t` is 0. In the chunked
form every decay whose span crosses a boundary is masked to zero (from
`document_index`, never a `-inf` in a cumulative sum): inside a chunk, from
the entering state to a position, from a position to the chunk's end and
from one chunk's end to the next. The convolutions' taps that reach into
the previous document read zero.
"""

from __future__ import annotations

import functools
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from tensor2robot_tpu.layers.mamba2 import (
    inverse_softplus_log_uniform,
    masked_exp,
    uniform_log,
    causal_conv,
    document_index,
)
from tensor2robot_tpu.layers.transformer import RMSNorm
from tensor2robot_tpu.ops import kda_carry, kda_pair_scores

#: Positions of a sub-block: the [c, c, K] decay differences are formed
#: inside one only, and forward substitution runs over its c rows.
SUB_BLOCK = 16


def _highest(dtype):
    """float32 operands keep float32 products (the TPU's default rounds
    them to bfloat16); narrower operands take the default."""
    return lax.Precision.HIGHEST if dtype == jnp.float32 else None


#: Elements of the [c, c, K] decay differences alive at once in the XLA form:
#: the pair scores are taken this many chunks' worth at a time (256 MB in
#: float32). The kernels' grid takes its place on a TPU.
PAIR_SLAB_ELEMENTS = 1 << 26


def _pair_scores(x, k, cum, visible):
    """scores[.., r, i, j] = sum_d x[.., r, i, d] k[.., j, d] exp(cum[.., i,
    d] - cum[.., j, d]) for j <= i of one chunk, 0 elsewhere and where not
    `visible`.

    x [B, N, H, R, C, K] (R stacked row operands), k [B, N, H, C, K] in the
    compute dtype, cum [B, N, H, C, K] float32 and non-increasing along C,
    visible [B, N, 1, C, C]. Two implementations of that one contract,
    chosen by what the call can see:

    * `ops/kda_pair_scores.pair_scores`, Pallas kernels (forward and a
      hand-written backward) that form a sub-block's decay differences in
      VMEM, where the program is lowered for a TPU
      (`lax.platform_dependent`) and the operands tile
      (`kda_pair_scores.tiles`: bfloat16, K a multiple of 128, whole chunks
      of whole heads on 128 lanes);
    * `_pair_scores_slabs`, the XLA form, everywhere else: other platforms,
      float32 (the kernel's products would round it), narrow heads.
    """
    sub = min(SUB_BLOCK, k.shape[-2])
    if kda_pair_scores.tiles(x, k, sub):
        return lax.platform_dependent(
            x, k, cum, visible,
            tpu=functools.partial(kda_pair_scores.pair_scores, sub=sub),
            default=_pair_scores_slabs,
        )
    return _pair_scores_slabs(x, k, cum, visible)


def _pair_scores_slabs(x, k, cum, visible):
    """`_pair_scores` a slab of chunks at a time, each slab recomputed in
    the backward pass: the decay differences of one slab ([c, c, K] a
    sub-block, float32 in HBM) are all that is ever alive, forward and
    backward."""
    batch, chunks, heads, chunk, width = k.shape
    sub = min(SUB_BLOCK, chunk)
    per_chunk = batch * heads * chunk * sub * width
    per_slab = max(
        n for n in range(1, chunks + 1)
        if chunks % n == 0 and (n == 1 or n * per_chunk <= PAIR_SLAB_ELEMENTS)
    )

    def slabs_first(t):   # [B, N, ..] -> [N / per_slab, B, per_slab, ..]
        return jnp.moveaxis(
            t.reshape((batch, chunks // per_slab, per_slab) + t.shape[2:]), 1, 0
        )

    scores = lax.map(
        lambda slab: _slab_pair_scores(*slab),
        (slabs_first(x), slabs_first(k), slabs_first(cum), slabs_first(visible)),
    )
    return jnp.moveaxis(scores, 0, 1).reshape(
        (batch, chunks, heads) + scores.shape[-3:]
    )


@jax.checkpoint
def _slab_pair_scores(x, k, cum, visible):
    """`_pair_scores` of the chunks it is given ([.., R, C, K], [.., C, K],
    [.., C, K], [.., C, C] with any leading axes)."""
    chunk, width = k.shape[-2:]
    sub = min(SUB_BLOCK, chunk)
    blocks = chunk // sub
    dtype = x.dtype
    lead = k.shape[:-2]
    rows = x.shape[-3]
    cum_b = cum.reshape(lead + (blocks, sub, width))
    ref = cum_b[..., :1, :]                      # a sub-block's first position
    # Sub-block I against every earlier position of the chunk, through
    # ref_I: i decays from ref_I (<= 0), j decays up to ref_I (<= 0 for the
    # earlier sub-blocks, clipped for the others, which the mask drops).
    x_b = x.reshape(lead + (rows, blocks, sub, width))
    x_in = (x_b * jnp.exp(cum_b - ref)[..., None, :, :, :]).astype(dtype)
    k_out = (
        k[..., None, :, :]
        * jnp.exp(jnp.minimum(ref - cum[..., None, :, :], 0.0))
    ).astype(dtype)                                # [.., blocks, C, K]
    between = jnp.einsum(
        "...rbik,...bjk->...rbij", x_in, k_out,
        precision=_highest(dtype), preferred_element_type=jnp.float32,
    ).reshape(lead + (rows, chunk, chunk))
    # Inside a sub-block: the differences themselves, lower triangle.
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    decay = masked_exp(
        cum_b[..., :, None, :] - cum_b[..., None, :, :], lower[..., None]
    )                                              # [.., blocks, c, c, K]
    k_b = k.reshape(lead + (blocks, sub, width)).astype(jnp.float32)
    inside = jnp.sum(
        x_b.astype(jnp.float32)[..., :, None, :]
        * (k_b[..., None, :, :] * decay)[..., None, :, :, :, :],
        axis=-1,
    )                                              # [.., R, blocks, c, c]
    block_of = jnp.arange(chunk) // sub
    earlier = block_of[:, None] > block_of[None, :]
    diagonal = (
        inside[..., :, :, None, :] * jnp.eye(blocks, dtype=inside.dtype)[:, None, :, None]
    ).reshape(lead + (rows, chunk, chunk))
    scores = jnp.where(earlier, between, diagonal)
    return jnp.where(visible[..., None, :, :], scores, 0.0)


def _substitute(a):
    """(I + a)^-1 for a [.., c, c] strictly lower triangular, by forward
    substitution over the c rows: N = (I + a)^-1 - I satisfies N[i] = -a[i]
    - sum_{j<i} a[i, j] N[j]. Rows and columns lead and the batch is minor,
    so that a row's update is elementwise work over the batch."""
    size = a.shape[-1]
    lead = a.shape[:-2]
    a = jnp.moveaxis(a.reshape((-1, size, size)), 0, -1)   # [i, j, batch]
    rows = [-a[0]]
    for i in range(1, size):
        solved = jnp.stack(rows)                            # [i, j, batch]
        rows.append(-a[i] - jnp.sum(a[i, :i, None] * solved, axis=0))
    inverse = jnp.stack(rows) + jnp.eye(size, dtype=a.dtype)[..., None]
    return jnp.moveaxis(inverse, -1, 0).reshape(lead + (size, size))


def unit_lower_inverse(a):
    """(I + a)^-1 for a [.., n, n] strictly lower triangular, float32: the
    diagonal sub-blocks by `_substitute`, then [[P, 0], [R, Q]]^-1 = [[P^-1,
    0], [-Q^-1 R P^-1, Q^-1]] up to n."""
    size = a.shape[-1]
    if size <= SUB_BLOCK:
        return _substitute(a)
    half = size // 2
    top = unit_lower_inverse(a[..., :half, :half])
    bottom = unit_lower_inverse(a[..., half:, half:])
    corner = -jnp.matmul(
        bottom,
        jnp.matmul(a[..., half:, :half], top, precision=lax.Precision.HIGHEST),
        precision=lax.Precision.HIGHEST,
    )
    return jnp.concatenate([
        jnp.concatenate([top, jnp.zeros_like(corner).swapaxes(-1, -2)], axis=-1),
        jnp.concatenate([corner, bottom], axis=-1),
    ], axis=-2)


#: Elements of q (positions x heads x channels) a group of heads may have:
#: the delta rule runs a group of heads after another, each recomputed in
#: the backward pass, so that one group's intermediates are alive at once
#: (two groups at 16,384 positions x 32 heads x 128).
HEAD_GROUP_ELEMENTS = 1 << 25


def head_groups(batch: int, seq: int, heads: int, width: int) -> int:
    """The fewest groups of heads of at most `HEAD_GROUP_ELEMENTS` each."""
    return min(
        n for n in range(1, heads + 1)
        if heads % n == 0
        and (n == heads or batch * seq * (heads // n) * width <= HEAD_GROUP_ELEMENTS)
    )


def _chunk_outputs(w, u, k_end, kept, q_start, scores):
    """The walk over the chunks: out [B, N, H, C, V] in the compute dtype
    where, per head and with S = 0 [K, V] float32 entering chunk 0, for n =
    0 .. N - 1

        S~     = S rounded to the compute dtype
        fresh  = u[n] - (w[n] @ S~) rounded to the compute dtype
        out[n] = q_start[n] @ S~ + b_scores[n] @ fresh      (float32 sums)
        S      = kept[n][:, None] * S + k_end[n]^T @ fresh

    w, k_end, q_start [B, N, H, C, K] and u [B, N, H, C, V] in the compute
    dtype, kept [B, N, H, K] float32, b_scores row 0 of scores [B, N, H, R,
    C, C] float32 (`_pair_scores`' stacked rows, handed on whole: the kernels
    cut the row out themselves, where XLA would write the slice to HBM); the
    document masks are inside kept, k_end, q_start and the scores already. Two
    implementations of that one contract, chosen as `_pair_scores` chooses:

    * `ops/kda_carry.chunk_outputs`, Pallas kernels (forward, and a backward
      that walks the chunks in reverse) that keep S in VMEM for the whole
      walk and form `fresh` and `out` where the state is, where the program
      is lowered for a TPU (`lax.platform_dependent`) and the operands tile
      (`kda_carry.tiles`: bfloat16, K and V multiples of 128, C of 16, the
      heads a multiple of the heads a grid step takes);
    * `_chunk_outputs_scan`, the XLA form and the definition, everywhere
      else: other platforms, float32, narrow heads, odd head counts.
    """
    if kda_carry.tiles(w, u):
        return lax.platform_dependent(
            w, u, k_end, kept, q_start, scores,
            tpu=kda_carry.chunk_outputs, default=_chunk_outputs_scan,
        )
    return _chunk_outputs_scan(w, u, k_end, kept, q_start, scores)


def _chunk_outputs_scan(w, u, k_end, kept, q_start, scores):
    """`_chunk_outputs` in XLA: the carry a `lax.scan` over two products a
    chunk that stacks every chunk's entering state ([B, N, H, K, V] in the
    compute dtype, in HBM), then one einsum each over all chunks for `fresh`
    (a second time) and the output's two products. Backward by autodiff."""
    batch, _, heads, _, width = w.shape
    dtype = w.dtype
    f32 = jnp.float32
    precision = _highest(dtype)

    def carry(state, inputs):
        """state [B, H, K, V] float32 enters the chunk; the next leaves."""
        w_n, u_n, k_end_n, kept_n = inputs
        entering = state.astype(dtype)
        fresh = u_n - jnp.einsum(
            "bhck,bhkv->bhcv", w_n, entering, precision=precision,
            preferred_element_type=f32,
        ).astype(dtype)
        left = kept_n[..., None] * state + jnp.einsum(
            "bhck,bhcv->bhkv", k_end_n, fresh, precision=precision,
            preferred_element_type=f32,
        )
        return left, entering

    chunk_major = lambda t: jnp.moveaxis(t, 1, 0)
    _, states = lax.scan(
        carry, jnp.zeros((batch, heads, width, u.shape[-1]), f32),
        (chunk_major(w), chunk_major(u), chunk_major(k_end), chunk_major(kept)),
    )
    states = jnp.moveaxis(states, 0, 1)                     # [B, N, H, K, V]
    fresh = u - jnp.einsum(
        "bnhck,bnhkv->bnhcv", w, states, precision=precision,
        preferred_element_type=f32,
    ).astype(dtype)
    out = jnp.einsum(
        "bnhck,bnhkv->bnhcv", q_start, states, precision=precision,
        preferred_element_type=f32,
    ) + jnp.einsum(
        "bnhij,bnhjv->bnhiv", scores[..., 0, :, :].astype(dtype), fresh,
        precision=precision, preferred_element_type=f32,
    )
    return out.astype(dtype)


@functools.partial(jax.jit, static_argnames=("chunk",))
def kda_chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, doc: jax.Array, chunk: int = 64) -> jax.Array:
    """o_t = S_t^T q_t of the recurrence above, for all t at once.

    q, k [B, S, H, K] and v [B, S, H, V] in the compute dtype (q already
    scaled, both normalised by the caller); g = log a_t [B, S, H, K] <= 0
    and beta [B, S, H] in float32; doc [B, S] from `document_index`. S must
    be a multiple of `chunk`, and `chunk` of `SUB_BLOCK` or under it.

    The heads are independent: they go in the fewest groups of at most
    `HEAD_GROUP_ELEMENTS`, each under `jax.checkpoint`. A caller that keeps
    the output across its own recomputation (`checkpoint_name` "kda_out")
    then runs the rule twice a step, forward and once more for the
    backward, and holds one group's intermediates.

    Jitted here and not by its caller, so that a model's layers share one
    trace and one lowering of it (the module's docstring).
    """
    batch, seq, heads, width = q.shape
    if seq % chunk or (chunk > SUB_BLOCK and chunk % SUB_BLOCK):
        raise ValueError(
            f"sequence length {seq} is not a multiple of the chunk {chunk}, "
            f"or the chunk not of {SUB_BLOCK}"
        )
    groups = head_groups(batch, seq, heads, width)
    rule = jax.checkpoint(functools.partial(_kda_heads, doc=doc, chunk=chunk))
    if groups == 1:
        return rule(q, k, v, g, beta)

    def grouped(t):   # [B, S, H, ..] -> [groups, B, S, H / groups, ..]
        return jnp.moveaxis(
            t.reshape((batch, seq, groups, heads // groups) + t.shape[3:]), 2, 0
        )

    out = lax.map(
        lambda group: rule(*group),
        (grouped(q), grouped(k), grouped(v), grouped(g), grouped(beta)),
    )
    return jnp.moveaxis(out, 0, 2).reshape(batch, seq, heads, -1)


def _kda_heads(q, k, v, g, beta, *, doc, chunk):
    """`kda_chunked` of the heads it is given."""
    batch, seq, heads, width = q.shape
    chunks = seq // chunk
    dtype = q.dtype
    f32 = jnp.float32
    precision = _highest(dtype)

    def split(t):   # [B, S, H, ..] -> [B, N, H, C, ..]
        return jnp.moveaxis(
            t.reshape((batch, chunks, chunk) + t.shape[2:]), 3, 2
        )

    q, k, v, g = split(q), split(k), split(v), split(g)
    beta = split(beta[..., None])                           # [B, N, H, C, 1]
    doc = doc.reshape(batch, chunks, chunk)
    last_doc = doc[..., -1]
    doc_before = jnp.pad(last_doc, ((0, 0), (1, 0)), constant_values=-1)[:, :-1]
    same = (doc[..., :, None] == doc[..., None, :])[:, :, None]     # [B, N, 1, C, C]
    entered = (doc == doc_before[..., None])[:, :, None, :, None]   # [B, N, 1, C, 1]
    to_end = (doc == last_doc[..., None])[:, :, None, :, None]
    through = (last_doc == doc_before)[:, :, None, None]            # [B, N, 1, 1]

    cum = jnp.cumsum(g, axis=-2)                            # [B, N, H, C, K]
    with jax.named_scope("kda/pair_scores"):
        scores = _pair_scores(jnp.stack([q, k], axis=3), k, cum, same)
    a_scores = beta * jnp.tril(scores[..., 1, :, :], k=-1)
    solve = unit_lower_inverse(a_scores).astype(dtype)      # T [B, N, H, C, C]

    from_start = jnp.where(entered, jnp.exp(cum), 0.0)
    rhs = jnp.concatenate(
        [(beta * k.astype(f32) * from_start).astype(dtype),
         (beta * v.astype(f32)).astype(dtype)], axis=-1,
    )
    solved = jnp.einsum(
        "bnhij,bnhjd->bnhid", solve, rhs, precision=precision,
        preferred_element_type=f32,
    ).astype(dtype)
    w, u = solved[..., :width], solved[..., width:]
    q_start = (q.astype(f32) * from_start).astype(dtype)
    k_end = (k.astype(f32) * jnp.where(
        to_end, jnp.exp(cum[..., -1:, :] - cum), 0.0)).astype(dtype)
    kept = jnp.where(through, jnp.exp(cum[..., -1, :]), 0.0)        # [B, N, H, K]

    with jax.named_scope("kda/carry"):
        out = _chunk_outputs(w, u, k_end, kept, q_start, scores)
    return jnp.moveaxis(out, 2, 3).reshape(batch, seq, heads, -1)


def _unit_norm(x: jax.Array) -> jax.Array:
    """x / |x| over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    return x32 * lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True) + 1e-6)


class KDAMixer(nn.Module):
    """[B, S, D] -> [B, S, D]; `segment_ids` [B, S] mark packed documents.

    Initialisation follows the public implementation's convention: A
    uniform in [1, 16] (one a head), softplus(dt_bias) log-uniform in
    [1e-3, 1e-1] (one a channel), matrices normal(0.02), no convolution
    bias.
    """

    num_heads: int
    head_dim: int
    conv_width: int = 4
    gate_rank: Optional[int] = None      # None: head_dim
    chunk_size: int = 64
    epsilon: float = 1e-5
    # Compute dtype of the projections (None follows input and params).
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x: jax.Array, segment_ids: jax.Array) -> jax.Array:
        batch, seq, features = x.shape
        heads, dim = self.num_heads, self.head_dim
        inner = heads * dim
        rank = self.gate_rank or dim
        init = nn.initializers.normal(0.02)
        doc = document_index(segment_ids)

        def dense(width, name, use_bias=False):
            return nn.Dense(
                width, use_bias=use_bias, dtype=self.dtype, kernel_init=init,
                name=name,
            )

        with jax.named_scope("kda/qkv_proj"):
            qkv = dense(3 * inner, "qkv_proj")(x)
        with jax.named_scope("kda/conv"):
            kernel = self.param("conv_kernel", init, (self.conv_width, 3 * inner))
            qkv = nn.silu(causal_conv(
                qkv, kernel, jnp.zeros((3 * inner,), qkv.dtype), doc
            )).reshape(batch, seq, 3, heads, dim)
            q = (_unit_norm(qkv[:, :, 0]) * dim ** -0.5).astype(qkv.dtype)
            k = _unit_norm(qkv[:, :, 1]).astype(qkv.dtype)
            v = qkv[:, :, 2]
        with jax.named_scope("kda/gates"):
            a_log = self.param("A_log", uniform_log(1.0, 16.0), (heads,))
            dt_bias = self.param(
                "dt_bias", inverse_softplus_log_uniform(1e-3, 1e-1), (inner,)
            )
            decay = dense(inner, "f_b")(dense(rank, "f_a")(x))
            g = -jnp.exp(a_log)[:, None] * nn.softplus(
                decay.astype(jnp.float32) + dt_bias
            ).reshape(batch, seq, heads, dim)
            beta = nn.sigmoid(dense(heads, "b_proj")(x).astype(jnp.float32))
            gate = dense(inner, "g_b", use_bias=True)(dense(rank, "g_a")(x))
        with jax.named_scope("kda/delta_rule"):
            o = checkpoint_name(
                kda_chunked(q, k, v, g, beta, doc, self.chunk_size), "kda_out"
            )
        with jax.named_scope("kda/gate_norm"):
            # The norm over each head's channels, one learned scale of dim.
            o = RMSNorm(self.epsilon, name="o_norm")(o.astype(jnp.float32))
            o = (o.reshape(batch, seq, inner) * nn.sigmoid(
                gate.astype(jnp.float32))).astype(qkv.dtype)
        with jax.named_scope("kda/out_proj"):
            return dense(features, "o_proj")(o)
