"""The layers `KimiLinearLMModel` brought (ISSUE 32): the chunked delta rule
against the stepped recurrence, values and gradients, under a decay that
would overflow a factored form and across document resets; the pair scores'
Pallas kernels (ISSUE 33, 34) and the kernels of the walk over the chunks
(ISSUE 37: the carry and the products that read the chunks' states) in
interpret mode against the XLA forms, the choice between them, and the rule
as one jitted function that every KDA layer of a step program shares; latent attention's widths through
`segment_attention`, kernel and einsum; routed experts that drop no token
whatever the imbalance, whose shares add up to the uncut layer and whose
program holds no scatter. The model itself is in tests/test_kimi_linear.py.
CPU, tiny sizes, float32 but for the kernels' operands and the experts'
bfloat16 case."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from tensor2robot_tpu.layers import kda
from tensor2robot_tpu.layers.moe import RoutedExperts
from tensor2robot_tpu.ops import kda_carry
from tensor2robot_tpu.ops import kda_pair_scores
from tensor2robot_tpu.ops import flash_attention as flash_lib
from tensor2robot_tpu.ops import moe as moe_ops
from tensor2robot_tpu.train import train_eval
from tests.sequence_lm_fixtures import (
    KIMI_LINEAR,
    SEQ,
    batch as _batch,
    kimi_model as _model,
    kimi_reference as _reference,
    loss_fn as _loss_fn,
    segments as _segments,
)


# -- the delta rule ----------------------------------------------------------------


def _recurrence(q, k, v, g, beta, doc):
    """S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T, o_t = S_t^T q_t,
    stepped; exp(g) is 0 at a document's first token."""
    first = jnp.concatenate(
        [jnp.ones_like(doc[:, :1], bool), doc[:, 1:] != doc[:, :-1]], axis=1)
    a = jnp.where(first[..., None, None], 0.0, jnp.exp(g))
    highest = lax.Precision.HIGHEST

    def step(state, inputs):
        q_t, k_t, v_t, a_t, b_t = inputs
        state = a_t[..., None] * state
        erased = jnp.einsum("bhk,bhkv->bhv", k_t, state, precision=highest)
        state = state + (b_t[..., None] * k_t)[..., None] * (v_t - erased)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=highest)

    swap = lambda t: jnp.swapaxes(t, 0, 1)
    batch, _, heads, width = q.shape
    _, out = lax.scan(
        step, jnp.zeros((batch, heads, width, v.shape[-1])),
        (swap(q), swap(k), swap(v), swap(a), swap(beta)))
    return swap(out)


@pytest.fixture
def patch_kda(monkeypatch):
    """Sets a global that `kda.kda_chunked` reads (of layers/kda.py, or of
    `module`) for one test. The rule is jitted and jax's trace cache does
    not see globals, so what was traced before the change, and after it, is
    dropped."""
    def patch(name, value, module=kda):
        monkeypatch.setattr(module, name, value)
        kda.kda_chunked.clear_cache()

    yield patch
    kda.kda_chunked.clear_cache()


def _delta_inputs(strength, resets, seq=128, batch=2, heads=3, width=8):
    rng = np.random.RandomState(3)
    draw = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    q, k, v = (draw(batch, seq, heads, width) for _ in range(3))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * width ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -strength * jnp.asarray(rng.rand(batch, seq, heads, width), jnp.float32)
    beta = jnp.asarray(rng.rand(batch, seq, heads), jnp.float32)
    ids = np.ones((batch, seq), np.int32)
    if resets:
        ids[0, 37:] = 2      # inside a sub-block
        ids[0, 64:] = 3      # at a chunk's first position
        ids[0, 100:] = 0     # padding
        ids[-1, 5:] = 2
    return q, k, v, g, beta, kda.document_index(jnp.asarray(ids))


# A decay of 8 a step and channel is exp(-1,000) over a chunk of 128
# channels' worth: `exp(-G)` of a factored form overflows float32 at 89.
@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("strength", [0.1, 8.0])
@pytest.mark.parametrize("resets", [False, True])
def test_chunked_delta_rule_is_the_stepped_recurrence(resets, strength, chunk):
    q, k, v, g, beta, doc = _delta_inputs(strength, resets)
    with jax.default_matmul_precision("highest"):
        want = _recurrence(q, k, v, g, beta, doc)
        got = kda.kda_chunked(q, k, v, g, beta, doc, chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("strength", [0.1, 8.0])
@pytest.mark.parametrize("resets", [False, True])
def test_chunked_delta_rule_gradients_are_the_recurrences(resets, strength):
    *inputs, doc = _delta_inputs(strength, resets)
    weight = jnp.asarray(np.random.RandomState(5).randn(2, 128, 3, 8), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(
            lambda *a: jnp.sum(_recurrence(*a, doc) * weight), argnums=range(5)
        )(*inputs)
        got = jax.grad(
            lambda *a: jnp.sum(kda.kda_chunked(*a, doc, 64) * weight),
            argnums=range(5),
        )(*inputs)
    for name, g, w in zip(("q", "k", "v", "g", "beta"), got, want):
        assert bool(jnp.all(jnp.isfinite(g))), name
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=1e-4, err_msg=name
        )


def test_delta_rule_refuses_a_ragged_sequence():
    q, k, v, g, beta, doc = _delta_inputs(0.1, False, seq=48)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        kda.kda_chunked(q, k, v, g, beta, doc, 32)


@pytest.mark.parametrize("size", [8, 16, 64])
def test_unit_lower_inverse_inverts(size):
    rng = np.random.RandomState(size)
    a = jnp.asarray(
        np.tril(rng.randn(3, 2, size, size), k=-1) * 2.0 / size, jnp.float32)
    got = kda.unit_lower_inverse(a)
    eye = np.eye(size, dtype=np.float32)
    product = np.einsum("...ij,...jk->...ik", np.asarray(a) + eye, np.asarray(got))
    np.testing.assert_allclose(product, np.broadcast_to(eye, product.shape), atol=1e-5)


def test_pair_scores_go_a_slab_of_chunks_at_a_time(patch_kda):
    q, k, v, g, beta, doc = _delta_inputs(0.5, True)
    with jax.default_matmul_precision("highest"):
        whole = kda.kda_chunked(q, k, v, g, beta, doc, 32)
        patch_kda("PAIR_SLAB_ELEMENTS", 1)                   # one chunk a slab
        jaxpr = jax.make_jaxpr(
            lambda *a: kda.kda_chunked(*a, doc, 32))(q, k, v, g, beta)
        slabbed = kda.kda_chunked(q, k, v, g, beta, doc, 32)
    np.testing.assert_allclose(np.asarray(slabbed), np.asarray(whole), atol=1e-6)
    assert "length=4" in str(jaxpr)                          # 128 / 32 slabs


def test_heads_go_a_group_at_a_time(patch_kda):
    q, k, v, g, beta, doc = _delta_inputs(0.5, True)
    weight = jnp.asarray(np.random.RandomState(5).randn(2, 128, 3, 8), jnp.float32)
    run = lambda: jax.value_and_grad(
        lambda q: jnp.sum(kda.kda_chunked(q, k, v, g, beta, doc, 32) * weight))(q)
    with jax.default_matmul_precision("highest"):
        whole, whole_grad = run()
        patch_kda("HEAD_GROUP_ELEMENTS", 2 * 128 * 8)        # one head
        grouped, grouped_grad = run()
    assert float(whole) == pytest.approx(float(grouped), rel=1e-6)
    np.testing.assert_allclose(
        np.asarray(grouped_grad), np.asarray(whole_grad), atol=1e-6)


# -- the pair scores' kernels (interpret mode) against the XLA form ----------------


def _pair_inputs(strength, resets, chunk, heads, chunks=2):
    """Operands of `_pair_scores` at K = 128, bfloat16: x [1, N, H, 2, C, K]."""
    rng = np.random.RandomState(11)
    width = 128
    draw = lambda *shape: jnp.asarray(
        rng.randn(*shape) / np.sqrt(width), jnp.bfloat16)
    x = draw(1, chunks, heads, 2, chunk, width)
    k = draw(1, chunks, heads, chunk, width)
    cum = jnp.cumsum(-strength * jnp.asarray(
        rng.rand(1, chunks, heads, chunk, width), jnp.float32), axis=-2)
    ids = np.ones((1, chunks * chunk), np.int32)
    if resets:
        ids[0, 5:] = 2                   # a boundary inside a sub-block
        ids[0, 6:] = 3                   # a document of one token
        ids[0, 7:] = 4                   # and another
        ids[0, chunk:] = 5               # at a chunk's first position
        ids[0, chunk + chunk // 2 + 3:] = 0   # padding
    doc = kda.document_index(jnp.asarray(ids)).reshape(1, chunks, chunk)
    visible = (doc[..., :, None] == doc[..., None, :])[:, :, None]
    return x, k, cum, visible


def _kernel_scores(x, k, cum, visible):
    return kda_pair_scores.pair_scores(
        x, k, cum, visible, min(kda.SUB_BLOCK, k.shape[-2]), interpret=True)


@pytest.mark.parametrize("chunk,heads", [(16, 8), (64, 2)])
@pytest.mark.parametrize("strength", [0.1, 8.0])
@pytest.mark.parametrize("resets", [False, True])
def test_pair_scores_kernel_is_the_xla_form(resets, strength, chunk, heads):
    x, k, cum, visible = _pair_inputs(strength, resets, chunk, heads)
    assert x.shape[3] == 2 and kda_pair_scores.tiles(x, k, min(16, chunk))
    want = kda._slab_pair_scores(x, k, cum, visible)
    got = _kernel_scores(x, k, cum, visible)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    assert bool(jnp.all(jnp.isfinite(got)))
    size = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5 * size)
    hidden = np.broadcast_to(~np.asarray(visible)[:, :, :, None], got.shape)
    assert not np.asarray(got)[hidden].any()
    if strength == 8.0:
        # A decay of up to 8 a step and channel: eight positions apart all
        # but the slowest channels have underflowed, and none to `inf` or `nan`.
        far = np.tril(np.ones((chunk, chunk), bool), k=-8)
        assert np.abs(np.asarray(got)[..., far]).max() < 1e-4 * size


@pytest.mark.parametrize("chunk,heads", [(16, 8), (64, 2)])
@pytest.mark.parametrize("strength", [0.1, 8.0])
@pytest.mark.parametrize("resets", [False, True])
def test_pair_scores_kernel_gradients_are_the_xla_forms(resets, strength, chunk, heads):
    x, k, cum, visible = _pair_inputs(strength, resets, chunk, heads)
    weight = jnp.asarray(
        np.random.RandomState(5).randn(1, 2, heads, 2, chunk, chunk), jnp.float32)
    both = lambda scores: jax.grad(
        lambda x, k, cum: jnp.sum(scores(x, k, cum, visible) * weight),
        argnums=(0, 1, 2),
    )(x, k, cum)
    got, want = both(_kernel_scores), both(kda._slab_pair_scores)
    # Autodiff rounds the gradients of the two between-sub-block operands to
    # bfloat16 on their way back (2^-9 of each term); the kernel keeps them
    # in float32 and rounds the scores' gradient for those products instead.
    # Inside a sub-block (all there is at chunk 16) both are float32.
    for name, g, w in zip(("x", "k", "cum"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all(), name
        tolerance = 0.01 if chunk > kda.SUB_BLOCK or name != "cum" else 1e-5
        assert np.abs(g - w).max() <= tolerance * np.abs(w).max(), name


# -- the walk over the chunks: its kernels (interpret mode) against the XLA form ---


def _packed_ids(seq):
    """One row of `seq` >= 256 positions: a document that ends inside a chunk
    (and inside a sub-block), one that ends at a chunk's edge, one that spans
    several chunks, a short one and padding."""
    ids = np.ones((1, seq), np.int32)
    ids[0, 37:] = 2
    ids[0, 64:] = 3
    ids[0, 200:] = 4
    ids[0, 230:] = 0
    return ids


@functools.lru_cache(maxsize=None)     # eager, op by op: seconds a call
def _walk_inputs(strength, resets, chunk, heads, seq=256):
    """Operands of `_chunk_outputs` as `_kda_heads` makes them at K = V = 128
    from bfloat16 q, k, v: (w, u, k_end, kept, q_start, scores)."""
    q, k, v, g, beta, doc = _delta_inputs(
        strength, False, seq=seq, batch=1, heads=heads, width=128)
    if resets:
        doc = kda.document_index(jnp.asarray(_packed_ids(seq)))
    held = []

    def capture(*operands):
        held.extend(operands)
        return kda._chunk_outputs_scan(*operands)

    walk, kda._chunk_outputs = kda._chunk_outputs, capture
    try:
        rounded = lambda t: t.astype(jnp.bfloat16)
        kda._kda_heads(rounded(q), rounded(k), rounded(v), g, beta, doc=doc, chunk=chunk)
    finally:
        kda._chunk_outputs = walk
    return tuple(held)


def _kernel_walk(*operands, heads_a_step=8):
    return kda_carry.chunk_outputs(*operands, heads_a_step, True)


WALK_SHAPES = [(16, 8, 8), (64, 16, 16)]       # (chunk, heads, heads a grid step)
WALK_OPERANDS = ("w", "u", "k_end", "kept", "q_start", "scores")


@pytest.mark.parametrize("chunk,heads,hb", WALK_SHAPES)
@pytest.mark.parametrize("strength", [0.1, 8.0])
@pytest.mark.parametrize("resets", [False, True])
def test_carry_kernel_is_the_xla_form(resets, strength, chunk, heads, hb):
    operands = _walk_inputs(strength, resets, chunk, heads)
    assert kda_carry.tiles(operands[0], operands[1], hb)
    want = kda._chunk_outputs_scan(*operands)
    got = _kernel_walk(*operands, heads_a_step=hb)
    assert got.dtype == want.dtype == jnp.bfloat16 and got.shape == want.shape
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    assert bool(jnp.all(jnp.isfinite(got)))
    # The same roundings at the same points; the sums inside a product run in
    # another order, and a state that rounds the other way moves what follows
    # (under a weak decay for many chunks): a bfloat16 step of the outputs.
    size = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= 2 ** -7 * size


@pytest.mark.parametrize("chunk,heads,hb", WALK_SHAPES)
@pytest.mark.parametrize("strength", [0.1, 8.0])
@pytest.mark.parametrize("resets", [False, True])
def test_carry_kernel_gradients_are_the_xla_forms(resets, strength, chunk, heads, hb):
    operands = _walk_inputs(strength, resets, chunk, heads)
    weight = jnp.asarray(
        np.random.RandomState(5).randn(*operands[1].shape), jnp.float32)

    def both(walk):
        return jax.grad(
            lambda *a: jnp.sum(walk(*a).astype(jnp.float32) * weight),
            argnums=range(6))(*operands)

    got = both(lambda *a: _kernel_walk(*a, heads_a_step=hb))
    want = both(kda._chunk_outputs_scan)
    # Autodiff rounds every product's gradient to bfloat16 and sums the
    # rounded parts; the kernel sums in float32 and rounds once. The rows of
    # the scores that the walk does not read get zeros from both.
    assert not np.asarray(got[5][:, :, :, 1:]).any()
    for name, g, w in zip(WALK_OPERANDS, got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all(), name
        assert np.abs(g - w).max() <= 0.02 * np.abs(w).max(), name


def _parents_walk(w, u, k_end, kept, q_start, scores):
    """The carry and the three products of `_kda_heads` as they stood before
    ISSUE 37, line for line, from its slice of the scores to its rounding
    of the output (which it did after a transpose)."""
    b_scores = scores[..., 0, :, :]
    batch, _, heads, _, width = w.shape
    dtype = w.dtype
    f32 = jnp.float32
    precision = kda._highest(dtype)

    def carry(state, inputs):
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
    states = jnp.moveaxis(states, 0, 1)
    fresh = u - jnp.einsum(
        "bnhck,bnhkv->bnhcv", w, states, precision=precision,
        preferred_element_type=f32,
    ).astype(dtype)
    out = jnp.einsum(
        "bnhck,bnhkv->bnhcv", q_start, states, precision=precision,
        preferred_element_type=f32,
    ) + jnp.einsum(
        "bnhij,bnhjv->bnhiv", b_scores.astype(dtype), fresh,
        precision=precision, preferred_element_type=f32,
    )
    return out.astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_xla_form_of_the_walk_changes_no_bit(dtype):
    operands = _walk_inputs(0.5, True, 64, 8)
    cast = lambda t: t if t.dtype == jnp.float32 else t.astype(dtype)
    operands = tuple(cast(t) for t in operands)
    weight = jnp.asarray(
        np.random.RandomState(5).randn(*operands[1].shape), jnp.float32)
    both = lambda walk: jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(walk(*a).astype(jnp.float32) * weight),
        argnums=range(6)))(*operands)
    # On this platform the choice is the XLA form whatever the operands.
    (got, got_grads), (want, want_grads) = both(kda._chunk_outputs), both(_parents_walk)
    assert float(got) == float(want)
    for name, g, w in zip(WALK_OPERANDS, got_grads, want_grads):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(kda._chunk_outputs)(*operands)),
        np.asarray(jax.jit(_parents_walk)(*operands)))



@pytest.fixture
def kernel_pair_scores(patch_kda):
    """`kda_chunked` through the kernels, interpreted, where the operands tile."""
    taken = []

    def scores(x, k, cum, visible):
        taken.append(x.shape)
        return _kernel_scores(x, k, cum, visible)

    patch_kda("_pair_scores", scores)
    return taken


def _wide_delta_inputs(strength, resets):
    """`_delta_inputs` at K = 128, two heads, q, k, v rounded to bfloat16."""
    q, k, v, g, beta, doc = _delta_inputs(
        strength, resets, batch=1, heads=2, width=128)
    rounded = lambda t: t.astype(jnp.bfloat16)
    return rounded(q), rounded(k), rounded(v), g, beta, doc


def _assert_the_rule_is_the_recurrence(q, k, v, g, beta, doc):
    """`kda_chunked` of bfloat16 q, k, v at chunk 64 against the stepped
    recurrence in float32, values and five gradients, to bfloat16's step."""
    weight = jnp.asarray(np.random.RandomState(5).randn(*q.shape), jnp.float32)
    f32 = lambda t: t.astype(jnp.float32)

    def both(rule, *operands):
        """(the rule's output, its gradients under `weight`)."""
        def loss(*a):
            out = f32(rule(*a))
            return jnp.sum(out * weight), out

        (_, out), grads = jax.value_and_grad(
            loss, argnums=range(5), has_aux=True)(*operands)
        return out, grads

    with jax.default_matmul_precision("highest"):
        want_out, want = both(
            lambda *a: _recurrence(*a, doc), f32(q), f32(k), f32(v), g, beta)
    got_out, got = both(lambda *a: kda.kda_chunked(*a, doc, 64), q, k, v, g, beta)
    size = float(jnp.abs(want_out).max())
    assert np.abs(np.asarray(f32(got_out)) - np.asarray(want_out)).max() < 0.03 * size
    for name, g_, w in zip(("q", "k", "v", "g", "beta"), got, want):
        g_, w = np.asarray(g_, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g_).all(), name
        assert np.abs(g_ - w).max() < 0.05 * np.abs(w).max(), name


@pytest.mark.parametrize("strength", [0.1, 8.0])
@pytest.mark.parametrize("resets", [False, True])
def test_delta_rule_through_the_kernels_is_the_stepped_recurrence(
    kernel_pair_scores, resets, strength
):
    _assert_the_rule_is_the_recurrence(*_wide_delta_inputs(strength, resets))
    assert kernel_pair_scores and kernel_pair_scores[0] == (1, 2, 2, 2, 64, 128)


def test_pair_scores_take_the_kernels_on_a_tpu_only_and_only_where_they_tile(patch_kda):
    x, k, cum, visible = _pair_inputs(0.1, True, 64, 2)
    scores = lambda x, k, cum: kda._pair_scores(x, k, cum, visible)
    # Tiled and bfloat16: the platform chooses, and this one is no TPU. The
    # kernels were traced without `interpret`, so lowering them here would raise.
    assert "platform_index" in str(jax.make_jaxpr(scores)(x, k, cum))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(scores)(x, k, cum)),
        np.asarray(kda._pair_scores_slabs(x, k, cum, visible)),
    )
    # Lowered for a TPU, one kernel forward and one more with the backward: no
    # [.., 16, 16, 128] differences in the program.
    lowered = jax.jit(scores).trace(x, k, cum).lower(lowering_platforms=("tpu",))
    assert lowered.as_text().count("tpu_custom_call") == 1
    assert "16x16x128" not in lowered.as_text()
    both = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(scores(*a)), argnums=(0, 1, 2),
    )).trace(x, k, cum).lower(lowering_platforms=("tpu",)).as_text()
    assert both.count("tpu_custom_call") == 2    # forward; dx, dk and dcum fused
    assert "16x16x128" not in both

    # float32, narrow heads, or an odd number of them: the XLA form alone.
    def kernel_refused(*args, **kwargs):
        raise AssertionError("the kernel path was traced")

    patch_kda("pair_scores", kernel_refused, module=kda_pair_scores)
    f32 = lambda t: t.astype(jnp.float32)
    for operands in (
        (f32(x), f32(k), cum),
        (x[..., :64], k[..., :64], cum[..., :64]),
        (x[:, :, :1], k[:, :, :1], cum[:, :, :1]),
    ):
        assert "platform_index" not in str(jax.make_jaxpr(scores)(*operands))
    q, k4, v, g, beta, doc = _delta_inputs(0.5, True)          # float32, K = 8
    assert "platform_index" not in str(jax.make_jaxpr(
        lambda *a: kda.kda_chunked(*a, doc, 64))(q, k4, v, g, beta))


@pytest.fixture
def kernel_walk(kernel_pair_scores, patch_kda):
    """`kda_chunked` through both kernel pairs, interpreted."""
    taken = []

    def walk(*operands):
        taken.append(operands[0].shape)
        return _kernel_walk(*operands)

    patch_kda("_chunk_outputs", walk)
    return taken


@pytest.mark.parametrize("strength", [0.1, 8.0])
@pytest.mark.parametrize("resets", [False, True])
def test_delta_rule_through_both_kernel_pairs_is_the_stepped_recurrence(
    kernel_walk, kernel_pair_scores, resets, strength
):
    seq, heads = 256, 8
    q, k, v, g, beta, doc = _delta_inputs(
        strength, False, seq=seq, batch=1, heads=heads, width=128)
    if resets:   # ends inside a chunk, at a chunk's edge, spans several chunks
        doc = kda.document_index(jnp.asarray(_packed_ids(seq)))
    rounded = lambda t: t.astype(jnp.bfloat16)
    _assert_the_rule_is_the_recurrence(
        rounded(q), rounded(k), rounded(v), g, beta, doc)
    assert kernel_pair_scores and kernel_pair_scores[0] == (1, 4, 8, 2, 64, 128)
    assert kernel_walk and kernel_walk[0] == (1, 4, 8, 64, 128)


def test_the_walk_takes_the_kernels_on_a_tpu_only_and_only_where_they_tile(patch_kda):
    operands = _walk_inputs(0.1, True, 64, 8)
    walk = kda._chunk_outputs
    # Tiled and bfloat16: the platform chooses, and this one is no TPU. The
    # kernels were traced without `interpret`, so lowering them here would raise.
    assert "platform_index" in str(jax.make_jaxpr(walk)(*operands))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(walk)(*operands)),
        np.asarray(kda._chunk_outputs_scan(*operands)),
    )
    # Lowered for a TPU, one kernel forward, and with the backward the forward
    # that keeps the states and the reverse walk: no loop over the chunks and
    # no product outside the kernels.
    lowered = jax.jit(walk).trace(*operands).lower(lowering_platforms=("tpu",)).as_text()
    assert lowered.count("tpu_custom_call") == 1
    assert "stablehlo.while" not in lowered and "dot_general" not in lowered
    both = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(walk(*a).astype(jnp.float32)), argnums=range(6),
    )).trace(*operands).lower(lowering_platforms=("tpu",)).as_text()
    assert both.count("tpu_custom_call") == 2
    assert "stablehlo.while" not in both and "dot_general" not in both
    assert "4x8x128x128xf32" in both               # the states the backward reads

    # float32, narrow heads, or a number of them the grid step does not divide:
    # the XLA form alone.
    def kernel_refused(*args, **kwargs):
        raise AssertionError("the kernel path was traced")

    patch_kda("chunk_outputs", kernel_refused, module=kda_carry)
    f32 = lambda t: t.astype(jnp.float32)
    w, u, k_end, kept, q_start, scores = operands
    for refused in (
        tuple(f32(t) for t in operands),
        (w[..., :64], u, k_end[..., :64], kept[..., :64], q_start[..., :64], scores),
        (w, u[..., :64], k_end, kept, q_start, scores),
        tuple(t[:, :, :6] for t in operands),
    ):
        assert "platform_index" not in str(jax.make_jaxpr(walk)(*refused))
        assert "scan[" in str(jax.make_jaxpr(walk)(*refused))



# -- one jitted rule, shared by the KDA layers of a step program -------------------


def _kda_model_loss(layers, heads=2):
    """(loss(params) of a bfloat16 model of `layers` KDA layers whose heads
    tile (`heads` x 128 channels, one chunk of 64: the pair scores take two
    heads a grid step, the walk over the chunks eight), its parameters)."""
    linear = {**KIMI_LINEAR, "kda_layers": list(range(1, layers + 1)),
              "full_attn_layers": [], "num_heads": heads, "head_dim": 128}
    model = _model(num_hidden_layers=layers, linear_attn_config=linear,
                   kda_chunk_size=64, device_type="tpu")
    features, labels = _batch()
    params = model.init_variables(jax.random.PRNGKey(0), features)["params"]
    return _loss_fn(model, features, labels), params


def _lowered_for_a_tpu(layers):
    loss, params = _kda_model_loss(layers, heads=8)
    return jax.jit(jax.value_and_grad(loss)).trace(params).lower(
        lowering_platforms=("tpu",)).as_text()


def test_a_step_program_holds_the_kernels_once_however_many_layers_call_them():
    import re

    two, three = _lowered_for_a_tpu(2), _lowered_for_a_tpu(3)
    bodies = lambda text, kernel: len(re.findall(f'kernel_name = "{kernel}"', text))
    calls = lambda text: len(re.findall(r"call @kda_chunked", text))
    programs = lambda text: len(re.findall(r"func\.func private @kda_chunked", text))
    for text in (two, three):
        # The rule's forward program and its backward program, which holds
        # the forward kernel once more: the rule recomputes itself there.
        assert programs(text) == 2
        assert bodies(text, "kda_pair_scores") == 2
        assert bodies(text, "kda_pair_scores_backward") == 1
        assert bodies(text, "kda_carry") == 2
        assert bodies(text, "kda_carry_backward") == 1
        assert "16x16x128" not in text
    # A call of each program a layer.
    assert (calls(two), calls(three)) == (4, 6)


def test_the_shared_jit_changes_no_bit(monkeypatch):
    loss, params = _kda_model_loss(2)
    # A new function each: jax caches a trace on the function it is given.
    assert "kda_chunked" in str(jax.make_jaxpr(lambda p: loss(p))(params))
    shared = jax.jit(jax.value_and_grad(loss))(params)
    monkeypatch.setattr(kda, "kda_chunked", kda.kda_chunked.__wrapped__)
    inlined = jax.jit(jax.value_and_grad(loss))(params)
    assert "kda_chunked" not in str(jax.make_jaxpr(lambda p: loss(p))(params))
    assert float(shared[0]) == float(inlined[0])
    equal = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)), shared[1], inlined[1])
    assert all(jax.tree_util.tree_leaves(equal)), equal


@pytest.fixture(scope="module")
def one_described_chip():
    """A v5e that is described and not attached: the chip's own compiler
    takes the kernels at the cell's shapes (no time, no result)."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topology = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as error:
        pytest.skip(f"no v5e:2x2 topology can be described here: {error}")
    return SingleDeviceSharding(topology.devices[0])


@pytest.mark.parametrize("kernels", ["pair_scores", "walk"])
def test_the_chips_compiler_takes_the_kernels_at_the_cells_shapes(
    one_described_chip, kernels
):
    # A group of 16 heads of one layer of kimi_linear_48b_a3b_s1 at 16,384.
    shape = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_described_chip)
    wide = shape((1, 256, 16, 64, 128), jnp.bfloat16)
    if kernels == "pair_scores":
        operands = (
            shape((1, 256, 16, 2, 64, 128), jnp.bfloat16), wide,
            shape((1, 256, 16, 64, 128), jnp.float32),
            shape((1, 256, 1, 64, 64), jnp.bool_),
        )
        loss = lambda *a: jnp.sum(kda._pair_scores(*a))
        differentiated = (0, 1, 2)
    else:
        operands = (
            wide, wide, wide, shape((1, 256, 16, 128), jnp.float32), wide,
            shape((1, 256, 16, 2, 64, 64), jnp.float32),
        )
        loss = lambda *a: jnp.sum(kda._chunk_outputs(*a).astype(jnp.float32))
        differentiated = range(6)
    compiled = jax.jit(jax.value_and_grad(
        loss, argnums=differentiated)).lower(*operands).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2


# -- latent attention's widths through segment_attention ---------------------------


def _masked_attention(q, k, v, segments, scale):
    highest = lax.Precision.HIGHEST
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=highest) * scale
    seq = q.shape[1]
    mask = jnp.tril(jnp.ones((seq, seq), bool))[None] & (
        segments[:, :, None] == segments[:, None, :]
    )
    probs = jax.nn.softmax(jnp.where(mask[:, None], logits, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=highest)


KERNEL_SEQ = 256


def _wide_qkv(dtype=jnp.float32):
    rng = np.random.RandomState(7)
    draw = lambda dim: jnp.asarray(rng.randn(2, KERNEL_SEQ, 2, dim), dtype)
    segments = jnp.asarray(_segments(((100, 156), (190, 66)), KERNEL_SEQ))
    return draw(192), draw(192), draw(128), segments


@pytest.fixture
def kernel_tiles(monkeypatch):
    monkeypatch.setattr(
        flash_lib, "SEGMENT_KERNEL_BLOCKS",
        dict.fromkeys(flash_lib.SEGMENT_KERNEL_BLOCKS, 128),
    )
    monkeypatch.setattr(
        flash_lib, "SEGMENT_DQ_BLOCKS", dict.fromkeys(flash_lib.SEGMENT_DQ_BLOCKS, 128)
    )
    monkeypatch.setattr(flash_lib, "SEGMENT_BLOCK_Q", 64)


@pytest.mark.parametrize("fused", [True, False])
def test_segment_attention_takes_values_narrower_than_keys(
    kernel_tiles, monkeypatch, fused
):
    if not fused:   # the dq kernel of its own, as at 16,384 x 192
        monkeypatch.setattr(flash_lib, "SEGMENT_FUSED_BACKWARD_BYTES", 0)
    q, k, v, segments = _wide_qkv()
    scale = 192 ** -0.5
    weight = jnp.asarray(np.random.RandomState(1).randn(2, KERNEL_SEQ, 2, 128), jnp.float32)

    def both(attend):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(attend(q, k, v, segments, scale) * weight),
            argnums=(0, 1, 2),
        )(q, k, v)

    want_out = _masked_attention(q, k, v, segments, scale)
    assert want_out.shape == (2, KERNEL_SEQ, 2, 128)
    for attend in (
        flash_lib._segment_einsum,
        lambda *a: flash_lib._segment_kernel(*a, interpret=True),
    ):
        np.testing.assert_allclose(
            np.asarray(attend(q, k, v, segments, scale)), np.asarray(want_out),
            rtol=1e-5, atol=1e-5,
        )
        (_, got), (_, want) = both(attend), both(_masked_attention)
        for name, g, w in zip("qkv", got, want):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-4, err_msg=name
            )


def test_the_fused_backward_goes_where_its_partials_outgrow_a_gigabyte(monkeypatch):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
    )

    seen = []
    real = splash.BlockSizes

    def spy(**kwargs):
        seen.append(kwargs)
        return real(**kwargs)

    monkeypatch.setattr(splash, "BlockSizes", spy)
    shape = lambda seq, heads, dim: jax.ShapeDtypeStruct((1, seq, heads, dim), jnp.bfloat16)
    ids = lambda seq: jax.ShapeDtypeStruct((1, seq), jnp.int32)
    for seq, kv_heads, dim, dim_v in ((8192, 8, 64, 64), (16384, 32, 192, 128)):
        jax.eval_shape(
            lambda q, k, v, s: flash_lib._segment_kernel(q, k, v, s, 0.1),
            shape(seq, 32, dim), shape(seq, kv_heads, dim), shape(seq, kv_heads, dim_v),
            ids(seq),
        )
    granite, latent = seen
    assert granite == {"use_fused_bwd_kernel": True, **flash_lib.SEGMENT_KERNEL_BLOCKS}
    assert latent == {**flash_lib.SEGMENT_DQ_BLOCKS, **flash_lib.SEGMENT_KERNEL_BLOCKS}


# -- routed experts ------------------------------------------------------------------


def _experts(seed=0, tokens=64, features=16, hidden=8, held=4, scored=16):
    rng = np.random.RandomState(seed)
    draw = lambda *shape: jnp.asarray(rng.randn(*shape) * 0.3, jnp.float32)
    return dict(
        x=jnp.asarray(rng.randn(tokens, features), jnp.float32),
        router=draw(features, scored),
        gate=draw(scored, features, hidden), up=draw(scored, features, hidden),
        down=draw(scored, hidden, features),
    )


def _dense_share(p, bias, first, count, k, scaling):
    """Every held expert on every token, masked by the routing: the plain
    form of one share."""
    scores = jax.nn.sigmoid(p["x"] @ p["router"])
    _, ids = lax.top_k(scores + bias, k)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = scaling * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    y = jnp.zeros_like(p["x"])
    for e in range(first, first + count):
        w = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        y = y + w[:, None] * (
            (jax.nn.silu(p["x"] @ p["gate"][e]) * (p["x"] @ p["up"][e])) @ p["down"][e]
        )
    return y


def _share(p, bias, first, count, k=4, scaling=2.446, row_buffer=None):
    held = slice(first, first + count)
    return moe_ops.routed_experts(
        p["x"], p["router"], bias, p["gate"][held], p["up"][held], p["down"][held],
        held=(first, count), num_selected=k, scaling=scaling, row_buffer=row_buffer,
    )


@pytest.mark.parametrize("row_buffer,dtype", [
    *(pytest.param(b, jnp.float32, id=str(b)) for b in (None, 16, 7)),
    *(pytest.param(b, jnp.bfloat16, id=f"bfloat16-{b}") for b in (None, 16, 7)),
])
def test_routed_experts_are_the_masked_dense_share(row_buffer, dtype):
    """Float32 tokens to float32 rounding; bfloat16 tokens (the experts'
    products then run in bfloat16, the combine and the tokens' gradient sum
    in float32) to a few roundings of bfloat16 of the largest value, against
    the dense share in float32 of the same bfloat16 tokens: the routing is
    the same, since the router reads the tokens in float32."""
    p, bias = _experts(), jnp.zeros((16,))
    p["x"] = p["x"].astype(dtype)
    with jax.default_matmul_precision("highest"):
        y, counts = _share(p, bias, 4, 4, row_buffer=row_buffer)
        want = _dense_share(p, bias, 4, 4, 4, 2.446)
        got = jax.grad(
            lambda p: jnp.sum(jnp.sin(_share(p, bias, 4, 4, row_buffer=row_buffer)[0])))(p)
        wanted = jax.grad(
            lambda p: jnp.sum(jnp.sin(_dense_share(p, bias, 4, 4, 4, 2.446))))(p)
    assert y.dtype == jnp.float32 and got["x"].dtype == dtype
    close = lambda a, b, atol, **kw: np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32), atol=atol, **kw)
    if dtype == jnp.float32:
        close(y, want, 2e-6)
        tolerance = dict.fromkeys(p, 2e-5)
    else:
        eps = float(jnp.finfo(jnp.bfloat16).eps)
        close(y, want, 4 * eps * float(jnp.max(jnp.abs(want))))
        tolerance = {name: 4 * eps * float(jnp.max(jnp.abs(wanted[name].astype(jnp.float32))))
                     for name in p}
    assert 0 < int(counts["routed_rows"]) < 64 * 4
    for name in p:
        close(got[name], wanted[name], tolerance[name], err_msg=name)


@pytest.mark.parametrize("row_buffer", [None, 16, 7])
def test_the_routed_layer_holds_no_scatter(row_buffer):
    """The layer's forward and backward, the bias rule's counts among them,
    lowered: rows go to the experts through the sorted order and come back
    through its inverse as gathers, and no op of the program is a scatter.
    The scatter-add form before held seven here: the experts' sizes, the
    combine and its recomputation in the backward, the pair weights'
    gradient, the tokens' gradient (summed in the tokens' dtype), the
    router's chosen scores' gradient, and the counts."""
    p, bias = _experts(), jnp.zeros((16,))

    def loss(p):
        held = slice(4, 8)
        y, counts = moe_ops.routed_experts(
            p["x"], p["router"], bias, p["gate"][held], p["up"][held], p["down"][held],
            held=(4, 4), num_selected=4, scaling=2.446, row_buffer=row_buffer,
            expert_counts=True,
        )
        return jnp.sum(jnp.sin(y)), counts

    text = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(p).as_text()
    assert "stablehlo.sort" in text and "stablehlo.gather" in text
    assert "scatter" not in text


@pytest.mark.parametrize("block", [1, 5, 16])
@pytest.mark.parametrize("first,selection", [(4, 0.0), (0, 10.0)])
def test_tokens_come_back_a_block_of_sorted_tokens_at_a_time(monkeypatch, block, first, selection):
    """Blocks that split a level, that leave a ragged last block of the 64
    tokens, and of one token; with the routing as the router has it and with
    every choice of every token held (a level for each of the four)."""
    monkeypatch.setattr(moe_ops, "TOKEN_BLOCK", block)
    p = _experts(seed=1)
    bias = jnp.where(jnp.arange(16) < 4, selection, 0.0)
    with jax.default_matmul_precision("highest"):
        y, _ = _share(p, bias, first, 4, row_buffer=16)
        want = _dense_share(p, bias, first, 4, 4, 2.446)
        got = jax.grad(
            lambda p: jnp.sum(jnp.sin(_share(p, bias, first, 4, row_buffer=16)[0])))(p)
        wanted = jax.grad(
            lambda p: jnp.sum(jnp.sin(_dense_share(p, bias, first, 4, 4, 2.446))))(p)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-6)
    for name in p:
        np.testing.assert_allclose(
            np.asarray(got[name]), np.asarray(wanted[name]), atol=2e-5, err_msg=name)


@pytest.mark.parametrize("seed,tokens,k,buckets", [
    (0, 64, 4, 5), (1, 37, 8, 17), (2, 5, 6, 3), (3, 128, 2, 2),
])
def test_positions_are_the_inverse_of_the_stable_sort(seed, tokens, k, buckets):
    """A token's held buckets differ (the router's choices do) and the last
    bucket, the pairs held elsewhere, repeats within a token."""
    rng = np.random.RandomState(seed)
    ids = np.stack([rng.permutation(max(k, buckets + 3))[:k] for _ in range(tokens)])
    key = jnp.asarray(np.minimum(ids, buckets - 1), jnp.int32)
    pos, sizes = moe_ops._sorted_positions(key, buckets)
    order = np.argsort(np.asarray(key).reshape(-1), kind="stable")
    assert pos.dtype == jnp.int32 and pos.shape == (tokens, k)
    np.testing.assert_array_equal(order[np.asarray(pos).reshape(-1)], np.arange(tokens * k))
    np.testing.assert_array_equal(
        np.asarray(sizes), np.bincount(np.asarray(key).reshape(-1), minlength=buckets))


@pytest.mark.parametrize("row_buffer", [None, 16, 7])
def test_rows_a_grouped_product_leaves_undefined_reach_no_gradient(
    monkeypatch, row_buffer
):
    """On the chip a grouped product leaves the rows behind its last group
    undefined, and they may hold NaN: here they do. Output and gradients
    are those of a product that zeroes them, as the CPU's does."""
    p, bias = _experts(seed=3), jnp.zeros((16,))

    def both():
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda p: jnp.sum(jnp.sin(_share(p, bias, 4, 4, row_buffer=row_buffer)[0]))
            )(p)

    want = both()
    ragged_dot = jax.lax.ragged_dot

    def undefined_behind(lhs, rhs, group_sizes, **kwargs):
        out = ragged_dot(lhs, rhs, group_sizes, **kwargs)
        behind = jnp.arange(lhs.shape[0]) >= jnp.sum(group_sizes)
        return jnp.where(behind[:, None], jnp.nan, out)

    monkeypatch.setattr(jax.lax, "ragged_dot", undefined_behind)
    got = both()
    assert float(got[0]) == float(want[0])
    for name in p:
        assert bool(jnp.all(jnp.isfinite(got[1][name]))), name
        np.testing.assert_array_equal(
            np.asarray(got[1][name]), np.asarray(want[1][name]), err_msg=name)


@pytest.mark.parametrize("row_buffer", [None, 32, 5])
def test_no_token_is_dropped_when_every_token_goes_to_held_experts(row_buffer):
    """A selection bias that sends all four choices of every token to the
    four experts held: 256 rows where an even router sends 64."""
    p = _experts(seed=1)
    bias = jnp.where(jnp.arange(16) < 4, 10.0, 0.0)
    ref, config = _reference()
    s = dict(
        ref._settings(config), num_experts=4, first_expert=0, router_experts=16,
        num_experts_per_token=4, routed_scaling_factor=2.446,
    )
    with jax.default_matmul_precision("highest"):
        y, counts = _share(p, bias, 0, 4, row_buffer=row_buffer)
        want = ref.routed_share(
            {"moe/router": p["router"], "moe/selection_bias": bias,
             "moe/gate": p["gate"][:4], "moe/up": p["up"][:4],
             "moe/down": p["down"][:4]}, p["x"], s)
    assert int(counts["routed_rows"]) == 64 * 4
    assert int(counts["max_expert_rows"]) == 64
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(_dense_share(p, bias, 0, 4, 4, 2.446)), atol=2e-6
    )


def test_selection_bias_moves_the_choice_and_not_the_weights():
    p = _experts(seed=2)
    bias = jnp.zeros((16,)).at[9].set(10.0)       # expert 9 into every choice
    plain_ids, _ = moe_ops.sigmoid_top_k(p["x"], p["router"], jnp.zeros((16,)), 4, 2.446)
    ids, weights = moe_ops.sigmoid_top_k(p["x"], p["router"], bias, 4, 2.446)
    assert bool(jnp.all(jnp.any(ids == 9, axis=-1)))
    assert not bool(jnp.all(jnp.any(plain_ids == 9, axis=-1)))
    scores = jax.nn.sigmoid(p["x"] @ p["router"])     # no bias in them
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    np.testing.assert_allclose(
        np.asarray(weights),
        np.asarray(2.446 * chosen / chosen.sum(-1, keepdims=True)), rtol=1e-5,
    )
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 2.446, rtol=1e-5)
    # And no gradient reaches it.
    grad = jax.grad(lambda b: jnp.sum(_share(p, b, 8, 4)[0]))(bias)
    assert not np.any(np.asarray(grad))


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """16 experts over 4 chips of 4: the four shares' routed parts, with the
    shared expert and the residual counted once, are what the uncut
    reference layer gives."""
    ref, config = _reference()
    s = ref._settings(config)
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(2, SEQ, 64), jnp.float32)
    uncut = {**s, "num_experts": 16, "router_experts": 16, "first_expert": 0}
    shapes = {k: v for k, v in ref.layer_shapes(uncut, ("kda", "moe")).items()
              if k.startswith("moe/")}
    whole = {
        name: jnp.asarray(rng.randn(*shape) * 0.1, jnp.float32)
        for name, shape in sorted(shapes.items())
    }
    with jax.default_matmul_precision("highest"):
        want = x + ref.moe_ffn(whole, x, uncut)
        routed = jnp.zeros_like(x)
        shared = None
        for first in range(0, 16, 4):
            layer = RoutedExperts(
                num_experts=4, router_experts=16, first_expert=first,
                hidden_dim=32, num_selected=4, scaling=2.446,
            )
            held = slice(first, first + 4)
            variables = {"params": {
                "router": whole["moe/router"],
                "selection_bias": whole["moe/selection_bias"],
                "gate": whole["moe/gate"][held], "up": whole["moe/up"][held],
                "down": whole["moe/down"][held],
                "shared": {n: {"kernel": whole[f"moe/shared/{n}/kernel"]}
                           for n in ("gate", "up", "down")},
            }}
            y, counts = layer.apply(variables, x)
            without = layer.clone(shared_experts=0).apply(
                {"params": {k: v for k, v in variables["params"].items() if k != "shared"}},
                x,
            )[0]
            routed = routed + without
            shared = y - without
            assert float(counts[3]) == 2 * SEQ
    np.testing.assert_allclose(
        np.asarray(x + routed + shared), np.asarray(want), atol=2e-5
    )


def test_a_share_reports_its_rows():
    layer = RoutedExperts(
        num_experts=4, router_experts=16, hidden_dim=8, num_selected=4)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 32, 16), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(0), x)
    _, counts = layer.apply(variables, x)
    routed, fullest, peak, positions = (float(c) for c in counts)
    assert RoutedExperts.COUNT_NAMES == (
        "moe_routed_rows", "moe_max_expert_rows", "moe_peak_rows", "moe_positions")
    assert train_eval._MOE_KEYS == RoutedExperts.COUNT_NAMES   # the trainer's copy
    assert positions == 64 and peak == 4 * fullest
    assert 0 < fullest <= routed <= peak
    with pytest.raises(ValueError, match="expert matrices"):
        moe_ops.routed_experts(
            x[0], jnp.zeros((16, 16)), jnp.zeros((16,)), jnp.zeros((3, 16, 8)),
            jnp.zeros((3, 16, 8)), jnp.zeros((3, 8, 16)), held=(0, 4), num_selected=4,
        )
