"""The layers of the hybrid state-space / attention sequence model (ISSUE
28): the Mamba-2 mixer's chunked scan against the stepped recurrence,
packed documents against the same documents run alone, segment-masked
attention, RMSNorm, SwiGLU and the chunked loss. CPU, tiny sizes, float32.
The model and the trainer: test_sequence_lm_model.py."""

import re

import flax.linen as nn
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


def _masked_attention(q, k, v, segments, scale):
    """Materialised logits, keys repeated over their group: the plain form."""
    group = q.shape[2] // k.shape[2]
    highest = jax.lax.Precision.HIGHEST
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, jnp.repeat(k, group, axis=2), precision=highest
    ) * scale
    seq = q.shape[1]
    mask = jnp.tril(jnp.ones((seq, seq), bool))[None] & (
        segments[:, :, None] == segments[:, None, :]
    )
    probs = jax.nn.softmax(jnp.where(mask[:, None], logits, -1e30), axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", probs, jnp.repeat(v, group, axis=2), precision=highest
    )


def test_segment_attention_is_masked_attention(monkeypatch):
    monkeypatch.setattr(flash_lib, "SEGMENT_BLOCK_Q", 16)  # four blocks of queries
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(2, SEQ, 4, 8), jnp.float32)
    k = jnp.asarray(rng.randn(2, SEQ, 2, 8), jnp.float32)
    v = jnp.asarray(rng.randn(2, SEQ, 2, 8), jnp.float32)
    segments = jnp.asarray(_segments())
    got = flash_lib.segment_attention(q, k, v, segments, scale=0.2)
    want = _masked_attention(q, k, v, segments, 0.2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# The kernel path of `segment_attention` (ISSUE 29), in Pallas interpret mode:
# tiles of 128 over 256 positions, so every case has four tiles a head, one
# of them above the diagonal.
KERNEL_SEQ = 256
PACKINGS = {
    "one_document": ((256,), (256,)),
    "several_documents": ((40, 60, 28, 100, 28), (128, 64, 50)),
    "boundary_inside_a_tile": ((100, 156), (190, 66)),
    "first_token_only_document": ((1, 255), (1, 1, 126, 1, 127)),
}
HEAD_COUNTS = {"grouped": (8, 2), "equal": (4, 4)}


@pytest.fixture
def kernel_tiles(monkeypatch):
    monkeypatch.setattr(
        flash_lib, "SEGMENT_KERNEL_BLOCKS",
        dict.fromkeys(flash_lib.SEGMENT_KERNEL_BLOCKS, 128),
    )
    monkeypatch.setattr(flash_lib, "SEGMENT_BLOCK_Q", 64)


def _packing(name):
    """segment_ids [2, KERNEL_SEQ]; what a row's documents leave is padding (0)."""
    return jnp.asarray(_segments(PACKINGS[name], KERNEL_SEQ))


def _qkv(heads, kv_heads, dtype=jnp.float32, dim=16):
    rng = np.random.RandomState(heads)
    return tuple(
        jnp.asarray(rng.randn(2, KERNEL_SEQ, n, dim), dtype)
        for n in (heads, kv_heads, kv_heads)
    )


@pytest.mark.parametrize("heads", HEAD_COUNTS)
@pytest.mark.parametrize("packing", PACKINGS)
def test_segment_kernel_forward_is_masked_attention(kernel_tiles, packing, heads):
    q, k, v = _qkv(*HEAD_COUNTS[heads])
    segments = _packing(packing)
    got = flash_lib._segment_kernel(q, k, v, segments, 0.25, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_masked_attention(q, k, v, segments, 0.25)),
        rtol=1e-5, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(flash_lib._segment_einsum(q, k, v, segments, 0.25)),
        rtol=1e-5, atol=1e-5,
    )


@pytest.mark.parametrize("heads", HEAD_COUNTS)
@pytest.mark.parametrize("packing", PACKINGS)
def test_segment_kernel_gradients_are_masked_attentions(kernel_tiles, packing, heads):
    q, k, v = _qkv(*HEAD_COUNTS[heads])
    segments = _packing(packing)
    weight = jnp.asarray(np.random.RandomState(1).randn(*q.shape), jnp.float32)

    def gradients(attend):
        return jax.grad(
            lambda q, k, v: jnp.sum(attend(q, k, v, segments, 0.25) * weight),
            argnums=(0, 1, 2),
        )(q, k, v)

    got = gradients(lambda *a: flash_lib._segment_kernel(*a, interpret=True))
    for name, g, plain, einsum in zip(
        "qkv", got, gradients(_masked_attention),
        gradients(flash_lib._segment_einsum),
    ):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(plain), rtol=1e-4, atol=1e-4, err_msg=name
        )
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(einsum), rtol=1e-4, atol=1e-4, err_msg=name
        )


@pytest.mark.parametrize("scale", [2 ** -6, 0.3])
def test_segment_kernel_in_bfloat16_follows_the_einsum(kernel_tiles, scale):
    """At the dtype the kernel path is chosen for. A scale that is no power
    of two costs the scaled query one more rounding."""
    q, k, v = _qkv(8, 2, jnp.bfloat16)
    segments = _packing("several_documents")
    weight = jnp.asarray(np.random.RandomState(1).randn(*q.shape), jnp.float32)

    def both(attend):
        def loss(q, k, v):
            out = attend(q, k, v, segments, scale)
            return jnp.sum(out.astype(jnp.float32) * weight), out
        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            q, k, v
        )
        return (out,) + grads

    got = both(lambda *a: flash_lib._segment_kernel(*a, interpret=True))
    want = both(lambda q, k, v, s, scale: _masked_attention(
        *(x.astype(jnp.float32) for x in (q, k, v)), s, scale
    ))
    for name, g, w, e in zip(
        ("out", "dq", "dk", "dv"), got, want, both(flash_lib._segment_einsum)
    ):
        assert g.dtype == jnp.bfloat16, name
        g, w, e = (np.asarray(x, np.float32) for x in (g, w, e))
        size = np.abs(w).max()
        assert np.abs(g - w).max() < 0.02 * size, name
        # No farther from the plain float32 form than the einsum path is.
        assert np.abs(g - w).max() < 2 * np.abs(e - w).max() + 1e-3 * size, name


def test_segment_attention_takes_the_kernel_on_a_tpu_only_and_only_where_it_tiles(
    kernel_tiles, monkeypatch
):
    segments = _packing("several_documents")
    q, k, v = _qkv(8, 2, jnp.bfloat16)

    def attend(q, k, v, segments=segments):
        return flash_lib.segment_attention(q, k, v, segments, scale=0.25)

    # Tiled and bfloat16: the platform chooses, and this one is no TPU. The
    # kernel was traced without `interpret`, so lowering it here would raise.
    assert "platform_index" in str(jax.make_jaxpr(attend)(q, k, v))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(attend)(q, k, v), np.float32),
        np.asarray(flash_lib._segment_einsum(q, k, v, segments, 0.25), np.float32),
    )
    # Lowered for a TPU, the kernels carry it: no logits in the program.
    lowered = jax.jit(attend).trace(q, k, v).lower(lowering_platforms=("tpu",))
    assert lowered.as_text().count("tpu_custom_call") == 1
    assert f"{KERNEL_SEQ}x{KERNEL_SEQ}" not in lowered.as_text()
    both = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2),
    )).trace(q, k, v).lower(lowering_platforms=("tpu",)).as_text()
    assert both.count("tpu_custom_call") == 2  # forward; dq, dk and dv fused

    # Ragged, shorter than a tile, or float32: the einsum, whatever the platform.
    def kernel_refused(*args, **kwargs):
        raise AssertionError("the kernel path was traced")

    monkeypatch.setattr(flash_lib, "_segment_kernel", kernel_refused)
    for cut, dtype in ((200, jnp.bfloat16), (64, jnp.bfloat16), (256, jnp.float32)):
        args = [x[:, :cut].astype(dtype) for x in (q, k, v)]
        jaxpr = str(jax.make_jaxpr(attend)(*args, segments[:, :cut]))
        assert "platform_index" not in jaxpr, (cut, dtype)


# -- the kernel's residuals across a block's recomputation ----------------------------


def _rematted_attention_block(kind, keep):
    """(block, its arguments) of one attention layer at a size the kernel
    path tiles (bfloat16, 1,024 positions), recomputed in the backward pass
    as its model does ("latent": a Kimi block with the dense feed-forward,
    "grouped": the hybrid model's attention block), or under the same
    policy without the kernel's residual name."""
    if kind == "latent":
        names = (sequence_lm_models.KIMI_KEPT_RESIDUALS["mla"]
                 + sequence_lm_models.KIMI_KEPT_RESIDUALS["dense"])
        block = sequence_lm_models._remat_kimi_block("mla", "dense")
        cls = sequence_lm_models.KimiLinearBlock
        fields = dict(
            mixer="mla", ffn="dense", heads=4, qk_nope_dim=32, qk_rope_dim=16,
            v_dim=32, kv_rank=32, mlp_dim=64, experts={}, rope_theta=1e4,
        )
    else:
        names = sequence_lm_models.KEPT_RESIDUALS
        block = sequence_lm_models._RematBlock
        cls = HybridBlock
        fields = dict(
            layer_type="attention", num_heads=4, num_kv_heads=2, head_dim=16,
            attention_multiplier=0.25, mlp_dim=64, mamba_heads=2,
            mamba_head_dim=16, mamba_state=8,
        )
    if not keep:
        block = nn.remat(cls, policy=jax.checkpoint_policies.save_only_these_names(
            *(n for n in names if n != flash_lib.ATTENTION_RESIDUALS)))
    seq = max(flash_lib.SEGMENT_KERNEL_BLOCKS.values())
    x = jnp.asarray(np.random.RandomState(2).randn(2, seq, 64), jnp.float32)
    segments = jnp.asarray(_segments(((300, 500, 224), (1000,)), seq))
    return block(dtype=jnp.bfloat16, **fields), x, segments


@pytest.mark.parametrize("keep", [True, False], ids=["kept", "name_left_out"])
@pytest.mark.parametrize("kind", ["latent", "grouped"])
def test_a_rematted_attention_block_runs_the_forward_kernel_once(kind, keep):
    """Lowered for a TPU: with the kernel's output and log-sum-exp kept, the
    backward pass reads them and has no forward kernel of its own; under a
    policy that leaves the name out it runs the forward again."""
    assert flash_lib.ATTENTION_RESIDUALS in sequence_lm_models.KEPT_RESIDUALS
    assert flash_lib.ATTENTION_RESIDUALS in sequence_lm_models.KIMI_KEPT_RESIDUALS["mla"]
    block, x, segments = _rematted_attention_block(kind, keep)
    params = block.init(jax.random.PRNGKey(0), x, segments)

    def loss(params, x):
        out = block.apply(params, x, segments)
        out = out[0] if kind == "latent" else out
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).trace(
        params, x).lower(lowering_platforms=("tpu",)).as_text()
    kernels = re.findall(r'kernel_name = "(splash_\w+)"', text)
    forwards = [name for name in kernels if name.startswith("splash_mqa_fwd")]
    assert len(forwards) == (1 if keep else 2), kernels
    # One backward kernel either way (dq, dk and dv fused at this size).
    assert len(kernels) - len(forwards) == 1
    assert text.count("tpu_custom_call") == len(kernels)


def test_the_kept_residuals_give_the_gradients_of_the_recomputed_ones(kernel_tiles):
    """The interpreted kernel under a policy that keeps its named residuals
    and under one that rebuilds them: the same q, k and v gradients."""
    q, k, v = _qkv(8, 2, jnp.bfloat16)
    segments = _packing("several_documents")
    weight = jnp.asarray(np.random.RandomState(4).randn(*q.shape), jnp.float32)

    def grads(names):
        attend = jax.checkpoint(
            lambda q, k, v: flash_lib._segment_kernel(
                q, k, v, segments, 0.25, interpret=True),
            policy=jax.checkpoint_policies.save_only_these_names(*names),
        )
        return jax.grad(
            lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32) * weight),
            argnums=(0, 1, 2),
        )(q, k, v)

    kept = grads((flash_lib.ATTENTION_RESIDUALS,))
    rebuilt = grads(())
    for name, a, b in zip(("dq", "dk", "dv"), kept, rebuilt):
        assert a.dtype == jnp.bfloat16, name
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32), err_msg=name)


def test_attention_layer_gives_the_same_through_kernel_and_einsum(
    kernel_tiles, monkeypatch
):
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(2, KERNEL_SEQ, 32), jnp.float32)
    segments = _packing("several_documents")
    module = MultiHeadAttention(
        num_heads=4, head_dim=16, num_kv_heads=2, scale=0.125, dtype=jnp.bfloat16
    )
    variables = module.init(jax.random.PRNGKey(0), x, segments)
    through_einsum = module.apply(variables, x, segments)

    calls = []

    def interpreted(*args, **kwargs):
        calls.append(args[0].shape)
        return flash_lib._segment_kernel(*args, interpret=True, **kwargs)

    # The platform's choice falls on the default branch here: put the kernel there.
    monkeypatch.setattr(flash_lib, "_segment_einsum", interpreted)
    through_kernel = module.apply(variables, x, segments)
    assert calls == [(2, KERNEL_SEQ, 4, 16)]
    assert through_kernel.dtype == through_einsum.dtype
    np.testing.assert_allclose(
        np.asarray(through_kernel, np.float32),
        np.asarray(through_einsum, np.float32), rtol=0.02, atol=0.02,
    )


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
