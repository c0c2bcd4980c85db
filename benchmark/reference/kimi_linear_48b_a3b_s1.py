"""Plain reference of one chip's share of Kimi-Linear-48B-A3B: the train
step of a KDA (gated delta rule) / latent-attention model with
sigmoid-routed experts on packed documents.

Written from the model's public `config.json` (`model_type: kimi_linear`)
and the gated-delta-rule recurrence, in float32 `jax.numpy` at `highest`
matmul precision. It imports nothing of the program and takes nothing the
program made. RMSNorm with a learned scale, no bias but the output gate's:

  h0 = E[token];  u = h + Mixer_l(RMSNorm(h));  h' = u + FFN_l(RMSNorm(u))
  logits = RMSNorm(h_L) W_head^T                        (untied head)
  loss = mean cross-entropy over positions with loss_mask 1

  KDA (layers in linear_attn_config.kda_layers, 1-indexed), H heads of K:
    q^, k^, v = silu(causal depthwise conv(W x)) each; q = q^/|q^| K^-1/2,
    k = k^/|k^|; g_t = -exp(A_log_h) softplus(W_f2 W_f1 x_t + dt_bias) per
    channel; beta_t = sigmoid(W_b x_t) per head;
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,
    o_t = S_t^T q_t;  out = W_o [RMSNorm_head(o_t) sigmoid(W_g2 W_g1 x_t + b_g)]
  latent attention (full_attn_layers): q = W_q x in heads of nope + rope;
    [c, k_pe] = W_kva x; [k_nope, v] = W_kvb RMSNorm(c); k = [k_nope, k_pe]
    (k_pe shared by the heads, nothing rotated: mla_use_nope); scores q k^T
    (nope + rope)^-1/2 masked to j <= i within the document; W_o.
  FFN: the first first_k_dense_replace layers W_d (silu(W_g x) * W_u x);
    the others s = sigmoid(W_r x) over all router_experts, the
    num_experts_per_token largest s + b, w_e = routed_scaling_factor s_e /
    (sum of the chosen s), y = sum_{chosen e held here} w_e E_e(x) +
    E_shared(x). What the experts held elsewhere would add is left out.

Packed documents: at a document's first token exp(g_t) is taken as 0, the
convolutions' taps that reach into the previous document read 0, attention
is masked by segment.

Departures, each noted: (a) parameters are keyed by the path the program's
checkpoints use, q, k and v the columns of one matrix, so that both sides
start from the same seeded weights; (b) the delta rule is computed in
chunks of `kda_chunk_size` positions written plainly (`kda_chunk`: the
[C, C, K] decay differences are formed, the unit-triangular system is
solved by `solve_triangular`), one chunk after another under `lax.scan`,
because the stepped recurrence (`kda_recurrence`, tests only) would keep
34 GB of states for a 16k backward; benchmark/tests holds the two against
each other; (c) attention is blocked over queries by a Python loop and a
block's keys stop at its last query, so `flops.py` counts the causal
blocks; (d) the routed experts sort the (token, choice) pairs by expert
and use `lax.ragged_dot`, over all tokens x k rows (ROW_BLOCK at a time,
by a Python loop) so that none can be dropped; `flops.py` does not count that equation, nor more than one trip
of the scan's body: `train_step.mfu` leaves out the routed experts and
all but one chunk of the delta rule (2.6% and 1.6% of the step at the
cell's size) and so reads low, never high; (e) |x| has 1e-6 under the
root, as the public implementation has; (f) values the config lacks are
under `assumed` in the configuration file.

`quant`, where given, rounds every operand of every matrix product (not
the router's, which the program keeps in float32 too, and not the
triangular solve) to a lower precision: that is the control of `correct`.

At the cell's own size the step does not fit one chip beside its float32
state, so `streaming_step` follows the same equations layer by layer, as
granite_4_0_h_micro_p1's does.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 512
ROW_BLOCK = 16384
LOSS_BLOCK = 2048
NEG = -1e30


def _settings(config):
    m = dict(config["model"])
    linear = m["linear_attn_config"]
    kinds = []
    for number in range(1, m["num_hidden_layers"] + 1):
        mixer = "kda" if number in linear["kda_layers"] else "mla"
        if mixer == "mla" and number not in linear["full_attn_layers"]:
            raise ValueError(f"no mixer named for layer {number}")
        kinds.append(
            (mixer, "dense" if number <= m["first_k_dense_replace"] else "moe"))
    return dict(
        m, kinds=kinds, kda_heads=linear["num_heads"],
        kda_dim=linear["head_dim"], kda_conv=linear["short_conv_kernel_size"],
        kda_chunk=m.get("kda_chunk_size", 64),
        router_experts=m.get("router_experts") or m["num_experts"],
        first_expert=m.get("first_expert", 0),
    )


def optimizer(config):
    return dict(config["optimizer"])


# -- parameters ----------------------------------------------------------------


def layer_shapes(s, kind):
    """{leaf name inside `layer_<i>/`: shape} of one layer."""
    mixer, ffn = kind
    d = s["hidden_size"]
    shapes = {"norm_mixer/scale": (d,), "norm_mlp/scale": (d,)}
    if mixer == "kda":
        h, k, rank = s["kda_heads"], s["kda_dim"], s["kda_dim"]
        shapes.update({
            "mixer/qkv_proj/kernel": (d, 3 * h * k),
            "mixer/conv_kernel": (s["kda_conv"], 3 * h * k),
            "mixer/A_log": (h,), "mixer/dt_bias": (h * k,),
            "mixer/f_a/kernel": (d, rank), "mixer/f_b/kernel": (rank, h * k),
            "mixer/b_proj/kernel": (d, h),
            "mixer/g_a/kernel": (d, rank), "mixer/g_b/kernel": (rank, h * k),
            "mixer/g_b/bias": (h * k,),
            "mixer/o_norm/scale": (k,), "mixer/o_proj/kernel": (h * k, d),
        })
    else:
        h = s["num_attention_heads"]
        nope, rope, v = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
        shapes.update({
            "mixer/q_proj/kernel": (d, h * (nope + rope)),
            "mixer/kv_a/kernel": (d, s["kv_lora_rank"] + rope),
            "mixer/kv_norm/scale": (s["kv_lora_rank"],),
            "mixer/kv_b/kernel": (s["kv_lora_rank"], h * (nope + v)),
            "mixer/o_proj/kernel": (h * v, d),
        })
    if ffn == "dense":
        width = s["intermediate_size"]
        shapes.update({
            "mlp/gate/kernel": (d, width), "mlp/up/kernel": (d, width),
            "mlp/down/kernel": (width, d),
        })
    else:
        e, f = s["num_experts"], s["moe_intermediate_size"]
        shared = s["num_shared_experts"] * f
        shapes.update({
            "moe/router": (d, s["router_experts"]),
            "moe/selection_bias": (s["router_experts"],),
            "moe/gate": (e, d, f), "moe/up": (e, d, f), "moe/down": (e, f, d),
            "moe/shared/gate/kernel": (d, shared),
            "moe/shared/up/kernel": (d, shared),
            "moe/shared/down/kernel": (shared, d),
        })
    return shapes


def init_params(key, config):
    s = _settings(config)
    shapes = {"embedding": (s["vocab_size"], s["hidden_size"]),
              "lm_head": (s["vocab_size"], s["hidden_size"]),
              "final_norm/scale": (s["hidden_size"],)}
    for index, kind in enumerate(s["kinds"]):
        for name, shape in layer_shapes(s, kind).items():
            shapes[f"layer_{index}/{name}"] = shape
    params = {}
    for number, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, number)
        if name.endswith("/scale"):
            value = jnp.ones(shape, jnp.float32)
        elif name.endswith("/bias") or name.endswith("selection_bias"):
            value = jnp.zeros(shape, jnp.float32)
        elif name.endswith("A_log"):
            value = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name.endswith("dt_bias"):
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            value = dt + jnp.log(-jnp.expm1(-dt))   # softplus^-1(dt)
        else:
            value = 0.02 * jax.random.normal(k, shape, jnp.float32)
        params[name] = value
    return params


# -- the equations ---------------------------------------------------------------


def _q(quant, x):
    return x if quant is None else quant(x)


def matmul(spec, a, b, quant=None):
    return jnp.einsum(spec, _q(quant, a), _q(quant, b), precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def swiglu(x, gate, up, down, quant=None):
    hidden = jax.nn.silu(matmul("bsd,de->bse", x, gate, quant)) * matmul(
        "bsd,de->bse", x, up, quant)
    return matmul("bse,ed->bsd", hidden, down, quant)


def documents(segment_ids):
    """[B, S] -> the number of the document each position is in."""
    first = jnp.concatenate(
        [jnp.zeros_like(segment_ids[:, :1]),
         (segment_ids[:, 1:] != segment_ids[:, :-1]).astype(segment_ids.dtype)],
        axis=1,
    )
    return jnp.cumsum(first, axis=1)


def conv_causal_depthwise(x, kernel, doc):
    """out[t] = sum_k kernel[k] * x[t - (W - 1) + k], a tap outside the
    sequence or in another document reading 0."""
    width, seq = kernel.shape[0], x.shape[1]
    out = jnp.zeros_like(x)
    for k in range(width):
        back = width - 1 - k
        if back >= seq:
            continue
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :seq - back]], axis=1)
        shifted_doc = jnp.concatenate(
            [jnp.full_like(doc[:, :back], -1), doc[:, :seq - back]], axis=1)
        out = out + jnp.where((shifted_doc == doc)[..., None], shifted, 0.0) * kernel[k]
    return out


def kda_recurrence(q, k, v, g, beta, doc):
    """The delta rule stepped token by token (tests only): q, k [B, S, H, K],
    v [B, S, H, V], g [B, S, H, K], beta [B, S, H], doc [B, S]."""
    first = jnp.concatenate(
        [jnp.ones_like(doc[:, :1], bool), doc[:, 1:] != doc[:, :-1]], axis=1)
    a = jnp.where(first[..., None, None], 0.0, jnp.exp(g))

    def step(state, inputs):
        q_t, k_t, v_t, a_t, beta_t = inputs
        state = a_t[..., None] * state
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, state, precision=HIGHEST)
        state = state + (beta_t[..., None] * k_t)[..., None] * (v_t - seen)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=HIGHEST)

    batch, _, heads, width = q.shape
    swap = lambda t: jnp.swapaxes(t, 0, 1)
    _, out = lax.scan(
        step, jnp.zeros((batch, heads, width, v.shape[-1]), jnp.float32),
        (swap(q), swap(k), swap(v), swap(a), swap(beta)))
    return swap(out)


def kda_chunk(state, q, k, v, g, beta, doc, doc_before, quant=None):
    """One chunk of the delta rule in its chunked form, written plainly.
    state [B, H, K, V] enters; q, k [B, C, H, K], v [B, C, H, V], g [B, C,
    H, K], beta [B, C, H], doc [B, C], doc_before [B] (the document the
    previous chunk ended in). Returns (the state that leaves, o [B, C, H, V])."""
    size = q.shape[1]
    cum = jnp.cumsum(g, axis=1)                                     # G
    same = doc[:, :, None] == doc[:, None, :]                       # [B, i, j]
    lower = jnp.tril(jnp.ones((size, size), bool))
    seen = (same & lower)[..., None, None]
    # exp(G_i - G_j) for j <= i of one document, else 0: [B, i, j, H, K].
    decay = jnp.where(
        seen, jnp.exp(jnp.where(seen, cum[:, :, None] - cum[:, None, :], 0.0)), 0.0)
    k_decayed = k[:, None] * decay
    strict = jnp.tril(jnp.ones((size, size), bool), k=-1)
    a = jnp.where(strict, matmul("bihd,bijhd->bhij", k, k_decayed, quant), 0.0)
    a = a * jnp.moveaxis(beta, 1, 2)[..., None]                     # beta_i
    b = matmul("bihd,bijhd->bhij", q, k_decayed, quant)
    from_start = jnp.where(
        (doc == doc_before[:, None])[..., None, None], jnp.exp(cum), 0.0)
    rhs = jnp.concatenate(
        [beta[..., None] * k * from_start, beta[..., None] * v], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(size), jnp.moveaxis(rhs, 1, 2), lower=True,
        unit_diagonal=True)                                         # [B, H, C, K + V]
    w, u = solved[..., :k.shape[-1]], solved[..., k.shape[-1]:]
    fresh = u - matmul("bhck,bhkv->bhcv", w, state, quant)          # V'
    out = matmul("bchk,bhkv->bchv", q * from_start, state, quant) + matmul(
        "bhij,bhjv->bihv", b, fresh, quant)
    last = doc[:, -1]
    to_end = jnp.where(
        (doc == last[:, None])[..., None, None], jnp.exp(cum[:, -1:] - cum), 0.0)
    kept = jnp.where((last == doc_before)[:, None, None], jnp.exp(cum[:, -1]), 0.0)
    state = kept[..., None] * state + matmul(
        "bchk,bhcv->bhkv", k * to_end, fresh, quant)
    return state, out


def kda_scan(q, k, v, g, beta, doc, chunk, quant=None):
    """`kda_chunk` over the chunks of a sequence, one after another."""
    batch, seq, heads, width = q.shape
    chunks = seq // chunk
    split = lambda t: jnp.swapaxes(
        t.reshape((batch, chunks, chunk) + t.shape[2:]), 0, 1)
    doc_c = split(doc)
    before = jnp.concatenate(
        [jnp.full_like(doc_c[:1, :, -1], -1), doc_c[:-1, :, -1]], axis=0)

    @jax.checkpoint   # a chunk's decay differences are not kept for the backward
    def body(state, inputs):
        return kda_chunk(state, *inputs, quant=quant)

    _, out = lax.scan(
        body, jnp.zeros((batch, heads, width, v.shape[-1]), jnp.float32),
        (split(q), split(k), split(v), split(g), split(beta), doc_c, before))
    return jnp.swapaxes(out, 0, 1).reshape(batch, seq, heads, -1)


def unit(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_mixer(p, x, doc, s, quant=None):
    batch, seq, _ = x.shape
    heads, dim = s["kda_heads"], s["kda_dim"]
    qkv = matmul("bsd,de->bse", x, p["mixer/qkv_proj/kernel"], quant)
    qkv = jax.nn.silu(conv_causal_depthwise(qkv, p["mixer/conv_kernel"], doc))
    q, k, v = (t.reshape(batch, seq, heads, dim) for t in jnp.split(qkv, 3, axis=-1))
    q, k = unit(q) * dim ** -0.5, unit(k)
    low = matmul("bsd,dr->bsr", x, p["mixer/f_a/kernel"], quant)
    g = -jnp.exp(p["mixer/A_log"])[:, None] * jax.nn.softplus(
        matmul("bsr,re->bse", low, p["mixer/f_b/kernel"], quant)
        + p["mixer/dt_bias"]).reshape(batch, seq, heads, dim)
    beta = jax.nn.sigmoid(matmul("bsd,dh->bsh", x, p["mixer/b_proj/kernel"], quant))
    out = kda_scan(q, k, v, g, beta, doc, min(s["kda_chunk"], seq), quant)
    out = rms_norm(out, p["mixer/o_norm/scale"], s["rms_norm_eps"])
    low = matmul("bsd,dr->bsr", x, p["mixer/g_a/kernel"], quant)
    gate = jax.nn.sigmoid(
        matmul("bsr,re->bse", low, p["mixer/g_b/kernel"], quant) + p["mixer/g_b/bias"])
    return matmul("bse,ed->bsd", out.reshape(batch, seq, heads * dim) * gate,
                  p["mixer/o_proj/kernel"], quant)


def attention_core(q, k, v, segment_ids, scale, quant=None, scanned=False):
    """q, k [B, S, H, D], v [B, S, H, DV]. One block of QUERY_BLOCK queries
    at a time. As `loss_fn` writes it (and `flops.py` counts it) a Python
    loop whose blocks stop at their last query. `scanned`: the same blocks
    under `lax.scan`, each against all the keys, masked: the layer-by-layer
    step's form, because the backward of the loop holds one padded gradient
    of k and of v a block (13 GB at 16,384 positions) where the scan adds
    them up in place."""
    seq = q.shape[1]

    def block(start, stop, q_blk, k, v, segment_ids, seg_q):
        k_ctx, v_ctx, seg_k = k[:, :stop], v[:, :stop], segment_ids[:, :stop]
        scores = matmul("bqhd,bkhd->bhqk", q_blk, k_ctx, quant) * scale
        visible = (
            (start + jnp.arange(q_blk.shape[1]))[:, None] >= jnp.arange(stop)[None, :]
        )[None] & (seg_q[:, :, None] == seg_k[:, None, :])
        probs = jax.nn.softmax(jnp.where(visible[:, None], scores, NEG), axis=-1)
        return matmul("bhqk,bkhd->bqhd", probs, v_ctx, quant)

    size = min(QUERY_BLOCK, seq)
    if scanned and seq % size == 0:
        blocks = seq // size
        split = lambda t: jnp.swapaxes(
            t.reshape((t.shape[0], blocks, size) + t.shape[2:]), 0, 1)
        body = jax.checkpoint(
            lambda start, q_blk, seg_q: block(start, seq, q_blk, k, v, segment_ids, seg_q))
        _, out = lax.scan(
            lambda _, xs: (None, body(*xs)), None,
            (jnp.arange(blocks) * size, split(q), split(segment_ids)))
        return jnp.swapaxes(out, 0, 1).reshape(q.shape[:3] + v.shape[3:])
    out = []
    for start in range(0, seq, size):
        stop = min(start + size, seq)
        # Recomputed in the backward pass: a block's probabilities are not kept.
        out.append(jax.checkpoint(functools.partial(block, start, stop))(
            q[:, start:stop], k, v, segment_ids, segment_ids[:, start:stop]))
    return jnp.concatenate(out, axis=1)


def mla_mixer(p, x, segment_ids, s, quant=None):
    batch, seq, _ = x.shape
    heads = s["num_attention_heads"]
    nope, rope, vdim = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    q = matmul("bsd,de->bse", x, p["mixer/q_proj/kernel"], quant).reshape(
        batch, seq, heads, nope + rope)
    down = matmul("bsd,de->bse", x, p["mixer/kv_a/kernel"], quant)
    latent = rms_norm(
        down[..., :s["kv_lora_rank"]], p["mixer/kv_norm/scale"], s["rms_norm_eps"])
    k_pe = down[..., s["kv_lora_rank"]:]
    kv = matmul("bsr,re->bse", latent, p["mixer/kv_b/kernel"], quant).reshape(
        batch, seq, heads, nope + vdim)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, :, None], (batch, seq, heads, rope))],
        axis=-1)
    out = attention_core(
        q, k, kv[..., nope:], segment_ids, (nope + rope) ** -0.5, quant,
        scanned=s.get("streamed", False))
    return matmul("bse,ed->bsd", out.reshape(batch, seq, heads * vdim),
                  p["mixer/o_proj/kernel"], quant)


def route(p, x, s):
    """(ids [T, k] of the chosen experts, weights [T, k]) over all
    router_experts, in float32 whatever `quant`."""
    scores = jax.nn.sigmoid(jnp.einsum("td,de->te", x, p["moe/router"], precision=HIGHEST))
    _, ids = lax.top_k(scores + p["moe/selection_bias"], s["num_experts_per_token"])
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, s["routed_scaling_factor"] * chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def routed_share(p, x, s, quant=None):
    """sum over the chosen experts held here of w_e E_e(x), for x [T, D]:
    every (token, choice) pair sorted by expert, the pairs of experts held
    elsewhere behind the others and weighted 0."""
    tokens = x.shape[0]
    k, count, first = s["num_experts_per_token"], s["num_experts"], s["first_expert"]
    ids, weights = route(p, x, s)
    local = ids.reshape(-1) - first
    held = (local >= 0) & (local < count)
    order = jnp.argsort(jnp.where(held, local, count), stable=True)
    sizes = jnp.sum(
        (local[:, None] == jnp.arange(count)[None, :]) & held[:, None], axis=0
    ).astype(jnp.int32)
    token = order // k
    starts = jnp.cumsum(sizes) - sizes
    weight = jnp.where(held[order], weights.reshape(-1)[order], 0.0)

    @jax.checkpoint   # a block's rows are not kept for the backward
    def block(first, token, weight):
        """Rows [first, first + len(token)) of the sorted pairs."""
        rows = token.shape[0]
        group = jnp.clip(starts + sizes - first, 0, rows) - jnp.clip(
            starts - first, 0, rows)
        grouped = lambda a, w: lax.ragged_dot(
            _q(quant, a), _q(quant, w), group, precision=HIGHEST)
        # Rows of experts held elsewhere lie behind every group and have
        # weight 0; a grouped product leaves them undefined, forward and
        # backward, so they are zeroed on the way in and on the way out.
        live = (weight != 0.0)[:, None]
        taken = jnp.where(live, x[token], 0.0)
        hidden = jax.nn.silu(grouped(taken, p["moe/gate"])) * grouped(taken, p["moe/up"])
        out = grouped(hidden, p["moe/down"])
        return jnp.where(live, out * weight[:, None], 0.0)

    y = jnp.zeros((tokens, x.shape[1]), jnp.float32)
    for first in range(0, tokens * k, ROW_BLOCK):
        part = slice(first, first + ROW_BLOCK)
        y = y.at[token[part]].add(block(first, token[part], weight[part]))
    return y


def moe_ffn(p, x, s, quant=None):
    batch, seq, width = x.shape
    routed = routed_share(p, x.reshape(batch * seq, width), s, quant)
    return routed.reshape(batch, seq, width) + swiglu(
        x, p["moe/shared/gate/kernel"], p["moe/shared/up/kernel"],
        p["moe/shared/down/kernel"], quant)


def mixer_half(p, h, segment_ids, mixer, s, quant=None):
    """u = h + Mixer(RMSNorm(h)); `p` holds the layer's leaves without the
    `layer_<i>/`."""
    x = rms_norm(h, p["norm_mixer/scale"], s["rms_norm_eps"])
    if mixer == "kda":
        return h + kda_mixer(p, x, documents(segment_ids), s, quant)
    return h + mla_mixer(p, x, segment_ids, s, quant)


def ffn_half(p, u, ffn, s, quant=None):
    """h' = u + FFN(RMSNorm(u))."""
    x = rms_norm(u, p["norm_mlp/scale"], s["rms_norm_eps"])
    if ffn == "dense":
        return u + swiglu(x, p["mlp/gate/kernel"], p["mlp/up/kernel"],
                          p["mlp/down/kernel"], quant)
    return u + moe_ffn(p, x, s, quant)


def layer(p, h, segment_ids, kind, s, quant=None):
    """One block."""
    mixer, ffn = kind
    return ffn_half(p, mixer_half(p, h, segment_ids, mixer, s, quant), ffn, s, quant)


def head_loss(head, norm_scale, h, targets, loss_mask, s, quant=None):
    """Mean masked cross-entropy of the untied head, LOSS_BLOCK positions at
    a time (a Python loop: the logits of one block are alive at once)."""
    h = rms_norm(h, norm_scale, s["rms_norm_eps"])
    mask = loss_mask.astype(jnp.float32)
    total = 0.0
    for start in range(0, h.shape[1], LOSS_BLOCK):
        part = slice(start, start + LOSS_BLOCK)
        logits = matmul("bsd,vd->bsv", h[:, part], head, quant)
        picked = jnp.take_along_axis(logits, targets[:, part, None], axis=-1)[..., 0]
        total = total + jnp.sum(
            (jax.nn.logsumexp(logits, axis=-1) - picked) * mask[:, part])
    return total / jnp.maximum(jnp.sum(mask), 1.0)


def layer_params(params, index):
    prefix = f"layer_{index}/"
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def loss_fn(params, batch, key, config, quant=None):
    """The whole step's loss as one function (tiny sizes, and what
    `flops.py` counts). `key` is unused: the model draws nothing."""
    del key
    s = _settings(config)
    features, labels = batch["features"], batch["labels"]
    h = params["embedding"][features["tokens"]]
    for index, kind in enumerate(s["kinds"]):
        h = layer(layer_params(params, index), h, features["segment_ids"], kind, s, quant)
    return head_loss(params["lm_head"], params["final_norm/scale"], h,
                     labels["targets"], labels["loss_mask"], s, quant)


# -- operations and bytes of the new kernels ------------------------------------------


def kernel_costs(config, batch, seq, bytes_per_element):
    """{"kda": .., "mla": ..}: the train step's model FLOPs and the bytes the
    products cannot avoid moving, of the delta rules and of latent
    attention's score and value products alone, all layers of each kind.

    FLOPs: `flops.count` over this file's `kda_chunk` at one chunk's shapes
    times the chunks (it counts a scanned body once; the triangular solve is
    no matrix product and is left out: low, not high) and over
    `attention_core` (the causal blocks). Bytes, once a pass and three
    passes a step: q, k, v, g, beta and o of the delta rule, and q, k, v, o
    with the log-sum-exp of attention: not the decay differences, the
    chunks' states or the logits, which a kernel need not move.
    """
    import flops

    s = _settings(config)
    f32 = jnp.float32
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, f32)
    heads, dim, chunk = s["kda_heads"], s["kda_dim"], min(s["kda_chunk"], seq)
    chunks = seq // chunk
    one_chunk = flops.count(
        lambda p, doc: kda_chunk(
            p["state"], p["q"], p["k"], p["v"], p["g"], p["beta"], doc, doc[:, 0])[1],
        {"state": shape(batch, heads, dim, dim),
         "q": shape(batch, chunk, heads, dim), "k": shape(batch, chunk, heads, dim),
         "v": shape(batch, chunk, heads, dim), "g": shape(batch, chunk, heads, dim),
         "beta": shape(batch, chunk, heads)},
        jax.ShapeDtypeStruct((batch, chunk), jnp.int32),
        bytes_per_element=bytes_per_element,
    )
    kda_layers = sum(mixer == "kda" for mixer, _ in s["kinds"])
    kda_elements = batch * seq * heads * (5 * dim + 1)
    attention_heads = s["num_attention_heads"]
    qk, vdim = s["qk_nope_head_dim"] + s["qk_rope_head_dim"], s["v_head_dim"]
    attention = flops.count(
        lambda p, seg: attention_core(p["q"], p["k"], p["v"], seg, 1.0),
        {"q": shape(batch, seq, attention_heads, qk),
         "k": shape(batch, seq, attention_heads, qk),
         "v": shape(batch, seq, attention_heads, vdim)},
        jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        bytes_per_element=bytes_per_element,
    )
    mla_layers = sum(mixer == "mla" for mixer, _ in s["kinds"])
    mla_elements = batch * seq * attention_heads * (2 * qk + 2 * vdim + 1)
    return {
        "kda": {
            "forward_flops": one_chunk["forward_flops"] * chunks * kda_layers,
            "step_flops": one_chunk["step_flops"] * chunks * kda_layers,
            "step_bytes": 3 * kda_elements * bytes_per_element * kda_layers,
        },
        "mla": {
            "forward_flops": attention["forward_flops"] * mla_layers,
            "step_flops": attention["step_flops"] * mla_layers,
            "step_bytes": 3 * mla_elements * bytes_per_element * mla_layers,
        },
    }


def moe_costs(config, routed_rows, bytes_per_element):
    """FLOPs and bytes a step of the routed experts' grouped products, from
    the rows the program counted as routed to the experts held (summed over
    the routed layers): rows x 3 matrices x 2 x hidden x width x 3 passes;
    the held experts' matrices once a pass and a layer, and each row's
    operands and results (x, gate, up, the product into down, y)."""
    s = _settings(config)
    d, f = s["hidden_size"], s["moe_intermediate_size"]
    layers = sum(ffn == "moe" for _, ffn in s["kinds"])
    weights = layers * s["num_experts"] * 3 * d * f
    return {
        "step_flops": routed_rows * 3 * 2 * d * f * 3,
        "step_bytes": 3 * (weights + routed_rows * (2 * d + 3 * f)) * bytes_per_element,
    }


#: The leaves that are one plain projection each: a weight matrix times the
#: positions (the experts' grouped products are `moe_costs`', the small
#: low-rank gates and the router sit among elementwise work and are left out).
PROJECTIONS = (
    "mixer/qkv_proj/kernel", "mixer/q_proj/kernel", "mixer/kv_a/kernel",
    "mixer/kv_b/kernel", "mixer/o_proj/kernel",
    "mlp/gate/kernel", "mlp/up/kernel", "mlp/down/kernel",
    "moe/shared/gate/kernel", "moe/shared/up/kernel", "moe/shared/down/kernel",
)


def projection_costs(config, batch, seq, bytes_per_element):
    """FLOPs and bytes a step of the plain projections, all layers and the
    head: positions x 2 x the matrix's elements, three passes; each matrix
    once a pass with its operand and result rows."""
    s = _settings(config)
    matrices = [(s["hidden_size"], s["vocab_size"])]        # the head
    for kind in s["kinds"]:
        shapes = layer_shapes(s, kind)
        matrices += [shapes[name] for name in PROJECTIONS if name in shapes]
    positions = batch * seq
    return {
        "step_flops": 3 * positions * 2 * sum(a * b for a, b in matrices),
        "step_bytes": 3 * bytes_per_element * sum(
            a * b + positions * (a + b) for a, b in matrices),
    }


# -- the same step, layer by layer, for a chip that cannot hold it whole ------------


class StreamingStep:
    """`step(params, opt, batch, base_key, count)` with `compare.py`'s
    contract (new params, new optimizer state, loss, norms of the
    gradient's leaves), for a model whose float32 state is most of a chip.

    Parameters and Adam moments are taken and returned as host arrays; one
    half layer's share (mixer, feed-forward) is put on the device at a time.
    Forward: each half's input is kept. Backward: each half is recomputed
    under `jax.vjp`, its gradient's norms are read, its Adam update is
    applied and sent home.
    """

    def __init__(self, config, quant=None):
        self._s = s = dict(_settings(config), streamed=True)
        self._spec = optimizer(config)
        if self._spec["kind"] != "adam":
            raise ValueError("the streaming step is written for Adam")

        def programs(half):
            """(forward, backward) of `half(p, x, seg)`, jitted."""
            def backward(p, x, seg, g):
                _, vjp = jax.vjp(lambda p_, x_: half(p_, x_, seg), p, x)
                return vjp(g)

            return jax.jit(half), jax.jit(backward)

        # A layer in two programs, mixer and feed-forward: a whole layer's
        # backward in float32 does not fit beside what the chip still holds.
        self._mixer = {
            mixer: programs(
                lambda p, h, seg, mixer=mixer: mixer_half(p, h, seg, mixer, s, quant))
            for mixer in sorted({m for m, _ in s["kinds"]})
        }
        self._ffn = {
            ffn: programs(lambda p, u, seg, ffn=ffn: ffn_half(p, u, ffn, s, quant))
            for ffn in sorted({f for _, f in s["kinds"]})
        }
        self._embed = jax.jit(lambda e, tokens: e[tokens])
        self._head = jax.jit(jax.value_and_grad(
            lambda head, scale, h, y, m: head_loss(head, scale, h, y, m, s, quant),
            argnums=(0, 1, 2),
        ))
        self._lookup_grad = jax.jit(
            lambda e, tokens, g_h: jnp.zeros_like(e).at[tokens].add(g_h))
        self._norm = jax.jit(lambda g: jnp.sqrt(jnp.sum(jnp.square(g))))
        self._adam = jax.jit(self._adam_leaf)

    def _adam_leaf(self, p, g, mu, nu, t):
        spec = self._spec
        b1, b2 = spec["b1"], spec["b2"]
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * jnp.square(g)
        new = p - spec["learning_rate"] * (mu / (1 - b1 ** t)) / (
            jnp.sqrt(nu / (1 - b2 ** t)) + spec["eps"])
        return new, mu, nu

    def _update(self, names, params, opt, grads, count, out):
        """Adam on the leaves `names`, results to the host, into `out`."""
        new_params, new_opt, norms = out
        t = jnp.asarray(count + 1, jnp.float32)
        for name in names:
            g = grads[name]
            norms[name] = self._norm(g)
            new, mu, nu = self._adam(
                jnp.asarray(params[name]), g,
                jnp.asarray(opt["mu"][name]), jnp.asarray(opt["nu"][name]), t,
            )
            new_params[name] = np.asarray(new)
            new_opt["mu"][name] = np.asarray(mu)
            new_opt["nu"][name] = np.asarray(nu)

    def __call__(self, params, opt, batch, base_key, count):
        del base_key   # the model draws nothing
        kinds = self._s["kinds"]
        count = int(count)
        features, labels = batch["features"], batch["labels"]
        tokens, seg = features["tokens"], features["segment_ids"]

        def on_device(index):
            return {k: jnp.asarray(v) for k, v in layer_params(params, index).items()}

        def halves(index):
            p = {k: jnp.asarray(v) for k, v in layer_params(params, index).items()}
            mixer, ffn = kinds[index]
            return ({k: v for k, v in p.items() if k.startswith(("mixer/", "norm_mixer"))},
                    {k: v for k, v in p.items() if not k.startswith(("mixer/", "norm_mixer"))},
                    self._mixer[mixer], self._ffn[ffn])

        inputs = [self._embed(jnp.asarray(params["embedding"]), tokens)]
        for index in range(len(kinds)):
            p_mixer, p_ffn, mixer, ffn = halves(index)
            inputs.append(mixer[0](p_mixer, inputs[-1], seg))
            inputs.append(ffn[0](p_ffn, inputs[-1], seg))
        loss, (g_head, g_scale, g_h) = self._head(
            jnp.asarray(params["lm_head"]), jnp.asarray(params["final_norm/scale"]),
            inputs.pop(), labels["targets"], labels["loss_mask"],
        )
        out = ({}, {"mu": {}, "nu": {}}, {})
        self._update(["final_norm/scale", "lm_head"], params, opt,
                     {"final_norm/scale": g_scale, "lm_head": g_head}, count, out)
        del g_head
        for index in reversed(range(len(kinds))):
            p_mixer, p_ffn, mixer, ffn = halves(index)
            for p_half, program in ((p_ffn, ffn), (p_mixer, mixer)):
                g_p, g_h = program[1](p_half, inputs.pop(), seg, g_h)
                grads = {f"layer_{index}/{k}": v for k, v in g_p.items()}
                del g_p
                self._update(sorted(grads), params, opt, grads, count, out)
        g_embedding = self._lookup_grad(jnp.asarray(params["embedding"]), tokens, g_h)
        self._update(["embedding"], params, opt, {"embedding": g_embedding}, count, out)
        new_params, new_opt, norms = out
        return new_params, new_opt, loss, norms


def streaming_step(config, quant=None):
    return StreamingStep(config, quant)
