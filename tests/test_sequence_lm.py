"""The layers of the hybrid state-space / attention sequence model (ISSUE
28): the Mamba-2 mixer's chunked scan against the stepped recurrence,
packed documents against the same documents run alone, segment-masked
attention, RMSNorm, SwiGLU and the chunked loss. CPU, tiny sizes, float32.
The model and the trainer: test_sequence_lm_model.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.layers import mamba2
from tensor2robot_tpu.layers.transformer import (
    HybridBlock,
    MultiHeadAttention,
    RMSNorm,
    SwiGLU,
)
from tensor2robot_tpu.models import sequence_lm_models
from tensor2robot_tpu.ops import flash_attention as flash_lib
from tensor2robot_tpu.specs import TensorSpecStruct
from tests.sequence_lm_fixtures import (
    LENGTHS,
    SEQ,
    batch as _batch,
    model as _model,
    one_segment as _one_segment,
    segments as _segments,
    spans as _spans,
)


# -- the scan -------------------------------------------------------------------


def _stepped(x, dt, log_a, b, c, doc):
    """S_t = a_t S_{t-1} + dt_t x_t B_t^T, a_t = 0 at a document's first
    token; y_t = S_t C_t. Heads of group g share b[:, :, g], c[:, :, g]."""
    batch, seq, heads, dim = x.shape
    groups = b.shape[2]
    per_group = heads // groups
    state = np.zeros((batch, heads, dim, b.shape[-1]))
    out = np.zeros(x.shape)
    for t in range(seq):
        first = (doc[:, t] != doc[:, t - 1]) if t else np.ones(batch, bool)
        a = np.where(first[:, None], 0.0, np.exp(log_a[:, t]))
        b_t = np.repeat(b[:, t], per_group, axis=1)
        c_t = np.repeat(c[:, t], per_group, axis=1)
        state = a[..., None, None] * state + (
            (dt[:, t, :, None] * x[:, t])[..., None] * b_t[:, :, None, :]
        )
        out[:, t] = np.einsum("bhpn,bhn->bhp", state, c_t)
    return out


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("resets", [False, True])
def test_chunked_scan_is_the_stepped_recurrence(resets, groups):
    rng = np.random.RandomState(3)
    heads, dim, state = 4, 8, 16
    x = rng.randn(2, SEQ, heads, dim)
    dt = np.log1p(np.exp(rng.randn(2, SEQ, heads)))
    log_a = -np.exp(rng.uniform(0, 2, heads)) * dt
    b = rng.randn(2, SEQ, groups, state)
    c = rng.randn(2, SEQ, groups, state)
    segments = _segments() if resets else np.ones((2, SEQ), np.int32)
    doc = np.asarray(mamba2.document_index(jnp.asarray(segments)))
    with jax.default_matmul_precision("highest"):
        got = mamba2.ssd_chunked(
            *(jnp.asarray(v, jnp.float32) for v in (x, dt, log_a, b, c)),
            jnp.asarray(doc), chunk=16,
        )
    want = _stepped(x, dt, log_a, b, c, doc)
    assert np.max(np.abs(np.asarray(got) - want)) < 1e-5 * max(1.0, np.abs(want).max())


def test_scan_gradient_is_finite_across_resets():
    rng = np.random.RandomState(4)
    args = [jnp.asarray(v, jnp.float32) for v in (
        rng.randn(1, 32, 2, 4), np.log1p(np.exp(rng.randn(1, 32, 2))),
    )]
    b = jnp.asarray(rng.randn(1, 32, 1, 8), jnp.float32)
    doc = mamba2.document_index(jnp.asarray([[1] * 10 + [2] * 15 + [0] * 7]))

    def loss(x, dt):
        return jnp.sum(mamba2.ssd_chunked(x, dt, -8.0 * dt, b, b, doc, 8) ** 2)

    grads = jax.grad(loss, argnums=(0, 1))(*args)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


def test_scan_refuses_a_ragged_sequence():
    z = jnp.zeros
    with pytest.raises(ValueError, match="multiple of the chunk"):
        mamba2.ssd_chunked(
            z((1, 10, 2, 4)), z((1, 10, 2)), z((1, 10, 2)), z((1, 10, 1, 8)),
            z((1, 10, 1, 8)), z((1, 10), jnp.int32), 8,
        )


def test_document_index_counts_boundaries_and_reused_ids():
    ids = jnp.asarray([[5, 5, 2, 2, 5, 0, 0]])
    assert mamba2.document_index(ids).tolist() == [[0, 0, 1, 1, 2, 3, 3]]


def test_causal_conv_stops_at_a_document_boundary():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, 12, 3), jnp.float32)
    kernel = jnp.asarray(rng.randn(4, 3), jnp.float32)
    bias = jnp.asarray(rng.randn(3), jnp.float32)
    doc = jnp.asarray([[0] * 5 + [1] * 7])
    got = np.asarray(mamba2.causal_conv(x, kernel, bias, doc))
    for start, stop in ((0, 5), (5, 12)):
        padded = np.pad(np.asarray(x)[0, start:stop], ((3, 0), (0, 0)))
        want = np.asarray(bias) + sum(
            padded[k:k + stop - start] * np.asarray(kernel)[k] for k in range(4)
        )
        np.testing.assert_allclose(got[0, start:stop], want, rtol=1e-5, atol=1e-6)


# -- packed documents against the same documents alone ----------------------------


def _alone(fn, packed_in, row, pad_to):
    """fn on each document of `row` alone (right-padded to `pad_to` with a
    padding segment), put back where the document sits in the packing."""
    out = None
    for start, stop in _spans(row):
        length = stop - start
        piece = np.zeros((1, pad_to) + packed_in.shape[2:], packed_in.dtype)
        piece[0, :length] = packed_in[row, start:stop]
        seg = np.zeros((1, pad_to), np.int32)
        seg[0, :length] = 1
        result = np.asarray(fn(jnp.asarray(piece), jnp.asarray(seg)))
        if out is None:
            out = np.zeros((SEQ,) + result.shape[2:], result.dtype)
        out[start:stop] = result[0, :length]
    return out, sum(LENGTHS[row])


@pytest.mark.parametrize("mixer", ["mamba", "attention", "block"])
def test_packed_mixer_gives_each_document_its_own_output(mixer):
    rng = np.random.RandomState(1)
    x = rng.randn(2, SEQ, 32).astype(np.float32)
    if mixer == "mamba":
        module = mamba2.Mamba2Mixer(
            num_heads=4, head_dim=16, state_size=8, chunk_size=16
        )
    elif mixer == "attention":
        module = MultiHeadAttention(
            num_heads=4, head_dim=8, num_kv_heads=2, scale=0.05
        )
    else:
        module = HybridBlock(
            layer_type="mamba", num_heads=4, num_kv_heads=2, head_dim=8,
            attention_multiplier=0.1, mlp_dim=48, mamba_heads=4,
            mamba_head_dim=16, mamba_state=8, mamba_chunk=16,
            residual_multiplier=0.22,
        )
    segments = jnp.asarray(_segments())
    with jax.default_matmul_precision("highest"):
        variables = module.init(jax.random.PRNGKey(0), jnp.asarray(x), segments)
        packed = np.asarray(module.apply(variables, jnp.asarray(x), segments))
        for row in range(2):
            alone, used = _alone(
                lambda piece, seg: module.apply(variables, piece, seg), x, row, SEQ
            )
            np.testing.assert_allclose(
                packed[row, :used], alone[:used], rtol=2e-4, atol=2e-5
            )


def test_packed_model_gives_each_document_its_own_logits():
    model = _model()
    features, _ = _batch()
    with jax.default_matmul_precision("highest"):
        variables = model.init_variables(jax.random.PRNGKey(0), features)
        packed, _ = model.inference_network_fn(variables, features, "predict")
        packed = np.asarray(packed["logits"])
        assert packed.shape == (2, SEQ, 96)

        def logits(tokens, seg):
            out, _ = model.inference_network_fn(
                variables,
                TensorSpecStruct({"tokens": tokens, "segment_ids": seg}), "predict",
            )
            return out["logits"]

        for row in range(2):
            alone, used = _alone(logits, np.asarray(features["tokens"]), row, SEQ)
            np.testing.assert_allclose(
                packed[row, :used], alone[:used], rtol=2e-4, atol=2e-4
            )


def test_segment_attention_is_masked_attention(monkeypatch):
    monkeypatch.setattr(flash_lib, "SEGMENT_BLOCK_Q", 16)  # four blocks of queries
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(2, SEQ, 4, 8), jnp.float32)
    k = jnp.asarray(rng.randn(2, SEQ, 2, 8), jnp.float32)
    v = jnp.asarray(rng.randn(2, SEQ, 2, 8), jnp.float32)
    segments = jnp.asarray(_segments())
    got = flash_lib.segment_attention(q, k, v, segments, scale=0.2)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, axis=2),
        precision=jax.lax.Precision.HIGHEST,
    ) * 0.2
    mask = jnp.tril(jnp.ones((SEQ, SEQ), bool))[None] & (
        segments[:, :, None] == segments[:, None, :]
    )
    probs = jax.nn.softmax(jnp.where(mask[:, None], logits, -1e30), axis=-1)
    want = jnp.einsum(
        "bhqk,bkhd->bqhd", probs, jnp.repeat(v, 2, axis=2),
        precision=jax.lax.Precision.HIGHEST,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_one_segment_is_plain_causal_attention_at_the_given_scale():
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(2, 16, 32), jnp.float32)
    module = MultiHeadAttention(num_heads=4, head_dim=8, num_kv_heads=2, scale=0.3)
    variables = module.init(jax.random.PRNGKey(0), x)
    np.testing.assert_allclose(
        np.asarray(module.apply(variables, x, _one_segment(2, 16))),
        np.asarray(module.apply(variables, x)), rtol=1e-5, atol=1e-6,
    )
    default = MultiHeadAttention(num_heads=4, head_dim=8, num_kv_heads=2)
    assert not np.allclose(
        np.asarray(default.apply(variables, x)), np.asarray(module.apply(variables, x))
    )
    explicit = MultiHeadAttention(
        num_heads=4, head_dim=8, num_kv_heads=2, scale=8 ** -0.5
    )
    np.testing.assert_allclose(
        np.asarray(default.apply(variables, x)),
        np.asarray(explicit.apply(variables, x)), rtol=1e-6, atol=1e-7,
    )


@pytest.mark.parametrize("kwargs", [
    {"decode": True}, {"window": 4}, {"causal": False},
])
def test_segment_ids_are_refused_off_the_plain_causal_path(kwargs):
    x = jnp.zeros((1, 1 if kwargs.get("decode") else 8, 16))
    module = MultiHeadAttention(num_heads=2, head_dim=8, **kwargs)
    with pytest.raises(ValueError, match="segment_ids"):
        module.init(jax.random.PRNGKey(0), x, jnp.ones(x.shape[:2], jnp.int32))


def test_scale_is_refused_in_decode_mode():
    module = MultiHeadAttention(num_heads=2, head_dim=8, decode=True, scale=0.1)
    with pytest.raises(ValueError, match="scale"):
        module.init(jax.random.PRNGKey(0), jnp.zeros((1, 1, 16)))


# -- small layers -----------------------------------------------------------------


def test_rms_norm_and_swiglu_are_their_formulas():
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(3, 5, 16), jnp.float32)
    norm = RMSNorm(1e-5)
    variables = norm.init(jax.random.PRNGKey(0), x)
    want = np.asarray(x) / np.sqrt(np.mean(np.asarray(x) ** 2, -1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(np.asarray(norm.apply(variables, x)), want, rtol=1e-5)
    mlp = SwiGLU(24)
    variables = mlp.init(jax.random.PRNGKey(1), x)
    p = variables["params"]
    assert set(p) == {"gate", "up", "down"} and "bias" not in p["gate"]
    gate = np.asarray(x) @ np.asarray(p["gate"]["kernel"])
    up = np.asarray(x) @ np.asarray(p["up"]["kernel"])
    want = (gate / (1 + np.exp(-gate)) * up) @ np.asarray(p["down"]["kernel"])
    np.testing.assert_allclose(np.asarray(mlp.apply(variables, x)), want, rtol=1e-4, atol=1e-5)


def test_hybrid_block_refuses_an_unknown_layer_type():
    block = HybridBlock(
        layer_type="conv", num_heads=2, num_kv_heads=2, head_dim=8,
        attention_multiplier=0.1, mlp_dim=16, mamba_heads=2, mamba_head_dim=16,
        mamba_state=8,
    )
    with pytest.raises(ValueError, match="no mixer"):
        block.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, 16)), _one_segment(1, 16))


def test_chunked_cross_entropy_is_the_whole_cross_entropy(monkeypatch):
    monkeypatch.setattr(sequence_lm_models, "LOSS_CHUNK", 8)  # four pieces
    rng = np.random.RandomState(7)
    hidden = jnp.asarray(rng.randn(2, 32, 16), jnp.float32)
    table = jnp.asarray(rng.randn(40, 16), jnp.float32)
    targets = jnp.asarray(rng.randint(0, 40, (2, 32)))
    mask = jnp.asarray(rng.rand(2, 32) < 0.7, jnp.float32)
    total, count = sequence_lm_models.chunked_cross_entropy(
        hidden, table, targets, mask, logits_scaling=4.0
    )
    logits = hidden @ table.T / 4.0
    want = jnp.sum(
        -jnp.take_along_axis(jax.nn.log_softmax(logits), targets[..., None], -1)[..., 0]
        * mask
    )
    assert float(count) == float(mask.sum())
    np.testing.assert_allclose(float(total), float(want), rtol=1e-5)
