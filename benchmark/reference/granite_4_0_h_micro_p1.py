"""Plain reference of the first period of granite-4.0-h-micro: the train
step of a Mamba-2 / attention hybrid on packed documents.

Written from the model's public `config.json` (`model_type:
granitemoehybrid`, no experts) and the Mamba-2 paper (arXiv:2405.21060,
the minimal chunked listing), in float32 `jax.numpy` at `highest` matmul
precision. It imports nothing of the program and takes nothing the program
made. With r = residual_multiplier and RMSNorm of a learned scale:

  h0 = embedding_multiplier * E[token]
  u  = h + r * Mixer_l(RMSNorm(h));  h' = u + r * MLP(RMSNorm(u))
  MLP(x) = W_down (silu(W_gate x) * (W_up x))
  logits = RMSNorm(h_L) E^T / logits_scaling          (tied embedding)
  loss = mean cross-entropy over positions with loss_mask 1

  attention (layer_types[l] == "attention"): 32 query heads over 8 key
    heads of 64, no positional encoding, scores q k^T *
    attention_multiplier, masked to j <= i and segment[j] == segment[i].
  Mamba-2 (elsewhere): [z, xBC, dt] = W_in x; xBC = silu(causal depthwise
    conv(xBC) + b); dt = softplus(dt + dt_bias); a_t = exp(-exp(A_log) dt_t);
    S_t = a_t S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t + D x_t;
    y = RMSNorm(y * silu(z)) over all d_inner channels; W_out y.

Packed documents: at a document's first token a_t is 0, the convolution's
taps that reach into the previous document read 0, attention is masked by
segment. In the chunked listing the decay between j < i is exp of a
difference of cumulative sums of log a; a reset is written by zeroing
every decay whose span crosses a document boundary (a `-inf` in the sums
would turn the differences into inf - inf).

Departures, each noted: (a) parameters are keyed by the path the program's
checkpoints use (`layer_0/mixer/in_proj/kernel`), so that both sides start
from the same seeded weights; q, k and v are the columns of one `qkv`
matrix, as the program stores them; (b) attention is blocked over queries
by a Python loop, and a block's keys stop at its last query: the blocks
above the diagonal, fully masked, are skipped, so `flops.py` counts the
causal half (plus the masked half of each diagonal block); (c) nothing
here puts `lax.scan` or `lax.map` around a matrix product, because
`flops.py` counts a sub-program's equations once; each attention block
is under `jax.checkpoint`, so that the backward holds one block's
probabilities at a time; (d) values the config
lacks follow the family's convention (`assumed` in the configuration
file): A uniform in [1, 16], dt log-uniform in [1e-3, 1e-1], D = 1,
matrices normal(0.02), norms' scales 1, convolution bias 0.

`quant`, where given, rounds every operand of every matrix product to a
lower precision: that is the control of `correct`.

At the cell's own size the whole step does not fit one chip beside its
float32 state (12.4 GB of weights, gradient and Adam moments), so
`streaming_step` follows the same equations layer by layer: the weights
and the moments live on the host, one layer's share is on the device at a
time, each layer is recomputed in the backward pass. `loss_fn` and
`streaming_step` are built from the same per-layer functions;
benchmark/tests holds them against each other.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 1024
LOSS_BLOCK = 2048
NEG = -1e30


def _settings(config):
    m = config["model"]
    depth = m["num_hidden_layers"]
    return dict(
        m,
        layer_types=list(m["layer_types"][:depth]),
        head_dim=m["hidden_size"] // m["num_attention_heads"],
        d_inner=m["mamba_expand"] * m["hidden_size"],
    )


def optimizer(config):
    return dict(config["optimizer"])


# -- parameters ----------------------------------------------------------------


def layer_shapes(s, layer_type):
    """{leaf name inside `layer_<i>/`: shape} of one layer."""
    d, mlp = s["hidden_size"], s["shared_intermediate_size"]
    shapes = {
        "norm_mixer/scale": (d,), "norm_mlp/scale": (d,),
        "mlp/gate/kernel": (d, mlp), "mlp/up/kernel": (d, mlp),
        "mlp/down/kernel": (mlp, d),
    }
    if layer_type == "attention":
        heads, kv, hd = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"]
        shapes["mixer/qkv/kernel"] = (d, (heads + 2 * kv) * hd)
        shapes["mixer/out/kernel"] = (heads * hd, d)
    else:
        inner, h = s["d_inner"], s["mamba_n_heads"]
        conv = inner + 2 * s["mamba_n_groups"] * s["mamba_d_state"]
        shapes.update({
            "mixer/in_proj/kernel": (d, inner + conv + h),
            "mixer/conv_kernel": (s["mamba_d_conv"], conv),
            "mixer/conv_bias": (conv,),
            "mixer/A_log": (h,), "mixer/D": (h,), "mixer/dt_bias": (h,),
            "mixer/norm/scale": (inner,),
            "mixer/out_proj/kernel": (inner, d),
        })
    return shapes


def init_params(key, config):
    s = _settings(config)
    shapes = {"embedding": (s["vocab_size"], s["hidden_size"]),
              "final_norm/scale": (s["hidden_size"],)}
    for index, layer_type in enumerate(s["layer_types"]):
        for name, shape in layer_shapes(s, layer_type).items():
            shapes[f"layer_{index}/{name}"] = shape
    params = {}
    for number, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, number)
        if name.endswith("/scale") or name.endswith("/D"):
            value = jnp.ones(shape, jnp.float32)
        elif name.endswith("conv_bias"):
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


def documents(segment_ids):
    """[B, S] -> the number of the document each position is in."""
    first = jnp.concatenate(
        [jnp.zeros_like(segment_ids[:, :1]),
         (segment_ids[:, 1:] != segment_ids[:, :-1]).astype(segment_ids.dtype)],
        axis=1,
    )
    return jnp.cumsum(first, axis=1)


def conv_causal_depthwise(x, kernel, bias, doc):
    """out[t] = bias + sum_k kernel[k] * x[t - (W - 1) + k], a tap outside
    the sequence or in another document reading 0."""
    width, seq = kernel.shape[0], x.shape[1]
    out = jnp.broadcast_to(bias, x.shape)
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


def ssd_recurrence(x, dt, a_log, b, c, doc):
    """The recurrence stepped token by token (tests only): x [B, S, H, P],
    dt [B, S, H], a_log [H], b and c [B, S, N]. S_t = a_t S_{t-1} + dt_t
    x_t B_t^T with a_t = 0 at a document's first token; y_t = S_t C_t."""
    first = jnp.concatenate(
        [jnp.ones_like(doc[:, :1], bool), doc[:, 1:] != doc[:, :-1]], axis=1)
    a = jnp.where(first[..., None], 0.0, jnp.exp(-jnp.exp(a_log) * dt))

    def step(state, inputs):
        a_t, dt_t, x_t, b_t, c_t = inputs
        state = a_t[..., None, None] * state + (
            (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return state, jnp.sum(state * c_t[:, None, None, :], axis=-1)

    batch, _, heads, dim = x.shape
    state0 = jnp.zeros((batch, heads, dim, b.shape[-1]), jnp.float32)
    swap = lambda t: jnp.swapaxes(t, 0, 1)
    _, y = lax.scan(step, state0, (swap(a), swap(dt), swap(x), swap(b), swap(c)))
    return swap(y)


def _segsum(x, same):
    """exp-ready differences x_cum[i] - x_cum[j] for j <= i along the last
    axis of x [..., T], -inf elsewhere and wherever `same` [..., T, T]
    (broadcast) says i and j are not of one document."""
    total = x.shape[-1]
    cum = jnp.cumsum(x, axis=-1)
    diff = cum[..., :, None] - cum[..., None, :]
    keep = jnp.tril(jnp.ones((total, total), bool)) & same
    return jnp.where(keep, diff, -jnp.inf)


def ssd_scan(x, dt, a_log, b, c, doc, chunk, quant=None):
    """The Mamba-2 paper's minimal chunked listing (ssd_minimal_discrete),
    one group of B and C, with document resets. x [B, S, H, P], dt
    [B, S, H], a_log [H], b and c [B, S, N], doc [B, S]. Returns y [B, S,
    H, P] with y_t = S_t C_t."""
    batch, seq, heads, dim = x.shape
    chunks = seq // chunk
    split = lambda t: t.reshape((batch, chunks, chunk) + t.shape[2:])
    xd = split(x * dt[..., None])                       # b c l h p
    a = split(-jnp.exp(a_log) * dt).transpose(0, 3, 1, 2)   # b h c l
    b, c, doc = split(b), split(c), split(doc)          # b c l n; b c l
    a_cum = jnp.cumsum(a, axis=-1)

    # 1. Inside each chunk (the diagonal blocks).
    same = (doc[..., :, None] == doc[..., None, :])[:, None]     # b 1 c l s
    decay = jnp.exp(_segsum(a, same))                             # b h c l s
    scores = matmul("bcln,bcsn->bcls", c, b, quant)
    y_diag = matmul(
        "bhcls,bcshp->bclhp", decay * scores[:, None], xd, quant)

    # 2. The state each chunk's own tokens leave at its end.
    last = doc[..., -1]                                           # b c
    to_end = jnp.where(
        (doc == last[..., None])[:, None], jnp.exp(a_cum[..., -1:] - a_cum), 0.0)
    states = matmul(
        "bcln,bclhp->bchpn", b, xd * to_end.transpose(0, 2, 3, 1)[..., None], quant)

    # 3. The recurrence over chunk states: the state entering chunk z.
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    ends = jnp.concatenate([jnp.full_like(last[:, :1], -1), last], axis=1)
    # State z (z >= 1) is of the document chunk z-1 ends in; it reaches the
    # start of chunk z' > z iff chunk z'-1 still ends in that document.
    same_end = (ends[:, :, None] == ends[:, None, :])[:, None]   # b 1 z z
    chunk_decay = jnp.exp(_segsum(
        jnp.pad(a_cum[..., -1], ((0, 0), (0, 0), (1, 0))), same_end))
    entering = matmul("bhzc,bchpn->bzhpn", chunk_decay, states)[:, :-1]

    # 4. What the entering state adds at each position of its document.
    from_start = jnp.where(
        (doc == ends[:, :-1, None])[:, None], jnp.exp(a_cum), 0.0)   # b h c l
    y_off = matmul("bcln,bchpn->bclhp", c, entering, quant) * (
        from_start.transpose(0, 2, 3, 1)[..., None])
    return (y_diag + y_off).reshape(batch, seq, heads, dim)


def mamba_mixer(p, x, doc, s, quant=None):
    batch, seq, _ = x.shape
    inner, heads, dim = s["d_inner"], s["mamba_n_heads"], s["mamba_d_head"]
    state = s["mamba_n_groups"] * s["mamba_d_state"]
    if s["mamba_n_groups"] != 1:
        raise ValueError("the reference is written for one group of B and C")
    proj = matmul("bsd,de->bse", x, p["mixer/in_proj/kernel"], quant)
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * state], axis=-1)
    xbc = jax.nn.silu(conv_causal_depthwise(
        xbc, p["mixer/conv_kernel"], p["mixer/conv_bias"], doc))
    xs, b, c = jnp.split(xbc, [inner, inner + state], axis=-1)
    xs = xs.reshape(batch, seq, heads, dim)
    dt = jax.nn.softplus(dt + p["mixer/dt_bias"])
    y = ssd_scan(xs, dt, p["mixer/A_log"], b, c, doc, s["mamba_chunk_size"], quant)
    y = (y + p["mixer/D"][:, None] * xs).reshape(batch, seq, inner)
    y = rms_norm(y * jax.nn.silu(z), p["mixer/norm/scale"], s["rms_norm_eps"])
    return matmul("bse,ed->bsd", y, p["mixer/out_proj/kernel"], quant)


def attention_core(q, k, v, segment_ids, scale, quant=None):
    """q [B, S, KV, R, D], k and v [B, S, KV, D]. One block of QUERY_BLOCK
    queries at a time, against the keys up to the block's last query."""
    seq = q.shape[1]

    @jax.checkpoint   # a block's probabilities are not kept for the backward
    def block(q_blk, k_ctx, v_ctx, seg_q, seg_k, positions):
        scores = matmul("bqgrd,bkgd->bgrqk", q_blk, k_ctx, quant) * scale
        visible = (
            positions[:, None] >= jnp.arange(k_ctx.shape[1])[None, :]
        )[None] & (seg_q[:, :, None] == seg_k[:, None, :])
        probs = jax.nn.softmax(jnp.where(visible[:, None, None], scores, NEG), axis=-1)
        return matmul("bgrqk,bkgd->bqgrd", probs, v_ctx, quant)

    out = []
    for start in range(0, seq, QUERY_BLOCK):
        stop = min(start + QUERY_BLOCK, seq)
        out.append(block(
            q[:, start:stop], k[:, :stop], v[:, :stop],
            segment_ids[:, start:stop], segment_ids[:, :stop],
            jnp.arange(start, stop),
        ))
    return jnp.concatenate(out, axis=1)


def attention_mixer(p, x, segment_ids, s, quant=None):
    batch, seq, _ = x.shape
    heads, kv, dim = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"]
    qkv = matmul("bsd,de->bse", x, p["mixer/qkv/kernel"], quant)
    q, k, v = jnp.split(qkv, [heads * dim, (heads + kv) * dim], axis=-1)
    out = attention_core(
        q.reshape(batch, seq, kv, heads // kv, dim),
        k.reshape(batch, seq, kv, dim), v.reshape(batch, seq, kv, dim),
        segment_ids, s["attention_multiplier"], quant,
    )
    return matmul("bse,ed->bsd", out.reshape(batch, seq, heads * dim),
                  p["mixer/out/kernel"], quant)


def layer(p, h, segment_ids, layer_type, s, quant=None):
    """One block; `p` holds the layer's leaves without the `layer_<i>/`."""
    r, eps = s["residual_multiplier"], s["rms_norm_eps"]
    x = rms_norm(h, p["norm_mixer/scale"], eps)
    if layer_type == "attention":
        mixed = attention_mixer(p, x, segment_ids, s, quant)
    else:
        mixed = mamba_mixer(p, x, documents(segment_ids), s, quant)
    u = h + r * mixed
    x = rms_norm(u, p["norm_mlp/scale"], eps)
    gate = matmul("bsd,de->bse", x, p["mlp/gate/kernel"], quant)
    up = matmul("bsd,de->bse", x, p["mlp/up/kernel"], quant)
    return u + r * matmul("bse,ed->bsd", jax.nn.silu(gate) * up,
                          p["mlp/down/kernel"], quant)


def embed(embedding, tokens, s):
    return s["embedding_multiplier"] * embedding[tokens]


def head_loss(embedding, norm_scale, h, targets, loss_mask, s, quant=None):
    """Mean masked cross-entropy of the tied head, LOSS_BLOCK positions at a
    time (a Python loop: the logits of one block are alive at once)."""
    h = rms_norm(h, norm_scale, s["rms_norm_eps"])
    mask = loss_mask.astype(jnp.float32)
    total = 0.0
    for start in range(0, h.shape[1], LOSS_BLOCK):
        part = slice(start, start + LOSS_BLOCK)
        logits = matmul("bsd,vd->bsv", h[:, part], embedding, quant) / s["logits_scaling"]
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
    h = embed(params["embedding"], features["tokens"], s)
    for index, layer_type in enumerate(s["layer_types"]):
        h = layer(layer_params(params, index), h, features["segment_ids"],
                  layer_type, s, quant)
    return head_loss(params["embedding"], params["final_norm/scale"], h,
                     labels["targets"], labels["loss_mask"], s, quant)


# -- operations and bytes of the two new kernels ------------------------------------


def kernel_costs(config, batch, seq, bytes_per_element):
    """{"ssd": .., "attention": ..}: `flops.count` over this file's scan and
    attention core at the cell's shapes, times the layers of each kind: the
    train step's model FLOPs of those products alone, whatever the program
    computes them with, and the bytes a pass has to move: for the scan the
    products' operands and results, for attention its inputs and outputs."""
    import flops

    s = _settings(config)
    f32 = jnp.float32
    heads, dim, n = s["mamba_n_heads"], s["mamba_d_head"], s["mamba_d_state"]
    kv, hd = s["num_key_value_heads"], s["head_dim"]
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, f32)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    ssd = flops.count(
        lambda p, doc: ssd_scan(p["x"], p["dt"], p["a"], p["b"], p["c"], doc,
                                s["mamba_chunk_size"]),
        {"x": shape(batch, seq, heads, dim), "dt": shape(batch, seq, heads),
         "a": shape(heads), "b": shape(batch, seq, n), "c": shape(batch, seq, n)},
        ids, bytes_per_element=bytes_per_element,
    )
    attention = flops.count(
        lambda p, seg: attention_core(p["q"], p["k"], p["v"], seg,
                                      s["attention_multiplier"]),
        {"q": shape(batch, seq, kv, s["num_attention_heads"] // kv, hd),
         "k": shape(batch, seq, kv, hd), "v": shape(batch, seq, kv, hd)},
        ids, bytes_per_element=bytes_per_element,
    )
    # Attention's bytes are what a tiled kernel has to move: q, k, v, the
    # output and a log-sum-exp a query head, once a pass (forward, and the
    # two passes of their gradients), not the [heads, seq, seq] logits and
    # probabilities of the products above, which such a kernel keeps on the
    # chip (as `kernel_costs["mla"]` of kimi_linear_48b_a3b_s1.py counts).
    q_heads = s["num_attention_heads"]
    attention["step_bytes"] = 3 * bytes_per_element * batch * seq * (
        2 * q_heads * hd + 2 * kv * hd + q_heads
    )
    layers = s["layer_types"]
    scale = lambda counted, times: {
        k: v * times for k, v in counted.items() if k != "equations"}
    return {
        "ssd": scale(ssd, sum(t != "attention" for t in layers)),
        "attention": scale(attention, sum(t == "attention" for t in layers)),
    }


# -- the same step, layer by layer, for a chip that cannot hold it whole ------------


class StreamingStep:
    """`step(params, opt, batch, base_key, count)` with `compare.py`'s
    contract (new params, new optimizer state, loss, norms of the
    gradient's leaves), for a model whose float32 state is most of a chip.

    Parameters and Adam moments are taken and returned as host arrays; one
    layer's share is put on the device at a time. Forward: each layer's
    input is kept. Backward: each layer is recomputed under `jax.vjp`, its
    gradient's norms are read, its Adam update is applied and sent home.
    The embedding's gradient is the head's part plus the lookup's.
    """

    def __init__(self, config, quant=None):
        self._s = s = _settings(config)
        self._spec = optimizer(config)
        if self._spec["kind"] != "adam":
            raise ValueError("the streaming step is written for Adam")

        def forward(layer_type):
            return jax.jit(lambda p, h, seg: layer(p, h, seg, layer_type, s, quant))

        def backward(layer_type):
            def run(p, h, seg, g):
                _, vjp = jax.vjp(lambda p_, h_: layer(p_, h_, seg, layer_type, s, quant), p, h)
                return vjp(g)

            return jax.jit(run)

        kinds = sorted(set(s["layer_types"]))
        self._forward = {kind: forward(kind) for kind in kinds}
        self._backward = {kind: backward(kind) for kind in kinds}
        self._embed = jax.jit(lambda e, tokens: embed(e, tokens, s))
        self._head = jax.jit(jax.value_and_grad(
            lambda e, scale, h, y, m: head_loss(e, scale, h, y, m, s, quant),
            argnums=(0, 1, 2),
        ))
        self._lookup_grad = jax.jit(
            lambda g_e, tokens, g_h: g_e.at[tokens].add(s["embedding_multiplier"] * g_h))
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
        s = self._s
        count = int(count)
        features, labels = batch["features"], batch["labels"]
        tokens, seg = features["tokens"], features["segment_ids"]
        types = s["layer_types"]

        def on_device(index):
            return {k: jnp.asarray(v) for k, v in layer_params(params, index).items()}

        inputs = [self._embed(jnp.asarray(params["embedding"]), tokens)]
        for index, layer_type in enumerate(types):
            inputs.append(self._forward[layer_type](on_device(index), inputs[-1], seg))
        embedding = jnp.asarray(params["embedding"])
        loss, (g_embedding, g_scale, g_h) = self._head(
            embedding, jnp.asarray(params["final_norm/scale"]), inputs.pop(),
            labels["targets"], labels["loss_mask"],
        )
        out = ({}, {"mu": {}, "nu": {}}, {})
        self._update(["final_norm/scale"], params, opt,
                     {"final_norm/scale": g_scale}, count, out)
        for index in reversed(range(len(types))):
            g_p, g_h = self._backward[types[index]](
                on_device(index), inputs.pop(), seg, g_h)
            grads = {f"layer_{index}/{k}": v for k, v in g_p.items()}
            del g_p
            self._update(sorted(grads), params, opt, grads, count, out)
        g_embedding = self._lookup_grad(g_embedding, tokens, g_h)
        del embedding
        self._update(["embedding"], params, opt, {"embedding": g_embedding}, count, out)
        new_params, new_opt, norms = out
        return new_params, new_opt, loss, norms


def streaming_step(config, quant=None):
    return StreamingStep(config, quant)
