"""`DeepseekV3LMModel` and the rotary positions of `LatentAttention`: the
rotation against the published form, positions per document, float32
angles at 16k, the model against the benchmark's plain reference of the
Kanana-2-30B-A3B share, packed documents against the same documents alone,
the keys it refuses, the shares of an expert layer against the uncut layer,
training through `train_eval_model`, and the Kimi-Linear model it shares its
block with where it was. CPU, tiny sizes, float32."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.layers.moe import RoutedExperts
from tensor2robot_tpu.layers.transformer import (
    LatentAttention,
    document_positions,
    rotary_interleaved,
)
from tensor2robot_tpu.specs import TensorSpecStruct
from tests.sequence_lm_fixtures import (
    LENGTHS,
    SEQ,
    batch as _batch,
    deepseek_model as _model,
    kanana_reference as _reference,
    kimi_model as _kimi_model,
    loss_fn as _loss_fn,
    segments as _segments,
    spans as _spans,
)


def _nest(flat):
    tree = {}
    for path, value in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for name in parents:
            node = node.setdefault(name, {})
        node[leaf] = value
    return tree


# -- the rotation ---------------------------------------------------------------------


def _published(x, positions, theta):
    """The published rope_interleave form in float64: channels de-interleaved
    (evens, then odds), then x cos + rotate_half(x) sin, with cos and sin of
    the angles repeated over the two halves."""
    x = np.asarray(x, np.float64)
    width = x.shape[-1]
    inverse = 1.0 / theta ** (np.arange(0, width, 2) / width)
    angles = np.asarray(positions, np.float64)[..., None] * inverse
    emb = np.concatenate([angles, angles], axis=-1)
    emb = emb.reshape(emb.shape[:2] + (1,) * (x.ndim - 3) + emb.shape[2:])
    x = np.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    half = width // 2
    rotated = np.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * np.cos(emb) + rotated * np.sin(emb)


def test_pairs_turned_in_place_give_the_published_forms_scores():
    rng = np.random.RandomState(0)
    q = rng.randn(2, 40, 3, 16).astype(np.float32)
    k = rng.randn(2, 40, 16).astype(np.float32)        # one key for all heads
    positions = rng.randint(0, 5000, (2, 40))
    ours_q = np.asarray(rotary_interleaved(jnp.asarray(q), jnp.asarray(positions), 1e6))
    ours_k = np.asarray(rotary_interleaved(jnp.asarray(k), jnp.asarray(positions), 1e6))
    want_q, want_k = _published(q, positions, 1e6), _published(k, positions, 1e6)
    scores = np.einsum("bihd,bjd->bhij", ours_q, ours_k)
    want = np.einsum("bihd,bjd->bhij", want_q, want_k)
    np.testing.assert_allclose(scores, want, rtol=1e-4, atol=2e-4)
    # In place, not de-interleaved: the same channels, another order.
    order = np.concatenate([np.arange(0, 16, 2), np.arange(1, 16, 2)])
    np.testing.assert_allclose(ours_q[..., order], want_q, rtol=1e-4, atol=2e-4)
    assert ours_q.dtype == np.float32
    half = rotary_interleaved(jnp.asarray(q, jnp.bfloat16), jnp.asarray(positions), 1e6)
    assert half.dtype == jnp.bfloat16


def test_positions_restart_at_every_document():
    ids = _segments()
    positions = np.asarray(document_positions(jnp.asarray(ids)))
    for row in range(2):
        want = np.concatenate(
            [np.arange(n) for n in LENGTHS[row]]
            + [np.arange(SEQ - sum(LENGTHS[row]))]     # the padding counts on alone
        )
        np.testing.assert_array_equal(positions[row], want)
    # A document that starts at position 0, and ids that come back after 0.
    np.testing.assert_array_equal(
        np.asarray(document_positions(jnp.asarray([[3, 3, 0, 1, 1, 1]]))),
        [[0, 1, 0, 0, 1, 2]])


def test_within_a_document_a_score_depends_only_on_the_distance():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(16), jnp.float32)
    k = jnp.asarray(rng.randn(16), jnp.float32)

    def score(i, j):
        at = lambda x, p: rotary_interleaved(
            x[None, None], jnp.asarray([[p]]), 1e6)[0, 0]
        return float(jnp.dot(at(q, i), at(k, j)))

    for distance in (0, 3, 250):
        scores = [score(start + distance, start) for start in (0, 17, 4000, 16000)]
        np.testing.assert_allclose(scores, scores[0], rtol=2e-3, atol=2e-3)
    assert abs(score(300, 0) - score(0, 0)) > 1e-2


def test_float32_angles_hold_at_the_last_position_where_bfloat16_ones_do_not():
    rng = np.random.RandomState(2)
    x = rng.randn(1, 4, 64).astype(np.float32)
    positions = np.array([[16380, 16381, 16382, 16383]])
    want = _published(x, positions, 1e6)
    order = np.concatenate([np.arange(0, 64, 2), np.arange(1, 64, 2)])
    ours = np.asarray(rotary_interleaved(jnp.asarray(x), jnp.asarray(positions), 1e6))
    np.testing.assert_allclose(ours[..., order], want, atol=5e-3)
    # The same rotation with its angles rounded to bfloat16 turns the first
    # pairs by whole radians the wrong way.
    inverse = 1e6 ** (-np.arange(0, 64, 2) / 64)
    angles = (positions[..., None] * inverse).astype(np.float32)
    rough = np.asarray(jnp.asarray(angles, jnp.bfloat16).astype(jnp.float32), np.float64)
    assert np.max(np.abs(rough - positions[..., None] * inverse)) > 8.0
    pairs = x.astype(np.float64).reshape(1, 4, 32, 2)
    turned = np.stack([
        pairs[..., 0] * np.cos(rough) - pairs[..., 1] * np.sin(rough),
        pairs[..., 1] * np.cos(rough) + pairs[..., 0] * np.sin(rough),
    ], axis=-1).reshape(1, 4, 64)
    assert np.max(np.abs(turned[..., order] - want)) > 0.5


def test_latent_attention_without_a_theta_turns_nothing():
    """`rope_theta` None is the Kimi cell's layer: the parameters and the
    output are what they were, and no op sits under `mla/rope`."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, SEQ, 32), jnp.float32)
    seg = jnp.asarray(_segments())
    plain = LatentAttention(num_heads=4, qk_nope_dim=8, qk_rope_dim=4, v_dim=8, kv_rank=16)
    turned = plain.clone(rope_theta=1e6)
    variables = plain.init(jax.random.PRNGKey(0), x, seg)
    assert jax.tree_util.tree_structure(variables) == jax.tree_util.tree_structure(
        turned.init(jax.random.PRNGKey(0), x, seg))
    text = lambda module: jax.jit(lambda v: module.apply(v, x, seg)).lower(
        variables).as_text(debug_info=True)
    assert "mla/rope" not in text(plain) and "mla/rope" in text(turned)
    assert not np.allclose(
        np.asarray(plain.apply(variables, x, seg)), np.asarray(turned.apply(variables, x, seg)))


# -- the model --------------------------------------------------------------------------


def test_layer_kinds_follow_first_k_dense_replace_and_unsupported_keys_are_refused():
    model = _model()
    features, _ = _batch()
    params = model.init_variables(jax.random.PRNGKey(0), features)["params"]
    kinds = [("kv_b" in params[f"layer_{i}"]["mixer"], "moe" in params[f"layer_{i}"])
             for i in range(5)]
    assert kinds == [(True, False)] + 4 * [(True, True)]
    assert params["layer_0"]["mlp"]["gate"]["kernel"].shape == (64, 128)
    assert params["layer_1"]["moe"]["router"].shape == (64, 16)
    assert params["layer_1"]["moe"]["gate"].shape == (4, 64, 32)
    assert params["layer_1"]["moe"]["shared"]["gate"]["kernel"].shape == (64, 2 * 32)
    assert params["layer_1"]["mixer"]["q_proj"]["kernel"].shape == (64, 4 * 24)
    assert params["lm_head"].shape == params["embedding"].shape == (96, 64)
    params = _model(first_k_dense_replace=3).init_variables(
        jax.random.PRNGKey(0), features)["params"]
    assert ["moe" in params[f"layer_{i}"] for i in range(5)] == [False] * 3 + [True] * 2
    for bad in (
        dict(q_lora_rank=16), dict(rope_scaling={"type": "yarn", "factor": 4.0}),
        dict(rope_interleave=False), dict(scoring_func="softmax"),
        dict(topk_method="greedy"), dict(norm_topk_prob=False), dict(n_group=8),
        dict(topk_group=2), dict(moe_layer_freq=2), dict(hidden_act="gelu"),
        dict(attention_bias=True), dict(tie_word_embeddings=True),
    ):
        with pytest.raises(ValueError, match="not supported"):
            _model(**bad)
    with pytest.raises(ValueError, match="qk_head_dim"):
        _model(qk_head_dim=64)
    with pytest.raises(ValueError, match="held of"):
        _model(first_expert=14)


@pytest.mark.parametrize("seed", [0, 1])
def test_loss_and_gradients_follow_the_plain_reference(seed):
    ref, config = _reference()
    model = _model()
    features, labels = _batch(seed)
    flat = ref.init_params(jax.random.PRNGKey(seed), config)
    raw = {"features": dict(features.items()), "labels": dict(labels.items())}
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda p: ref.loss_fn(p, raw, None, config)))(flat)
        got_loss, got = jax.jit(
            jax.value_and_grad(_loss_fn(model, features, labels)))(_nest(flat))
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    got = {"/".join(str(k.key) for k in path): leaf
           for path, leaf in jax.tree_util.tree_leaves_with_path(got)}
    assert set(got) == set(want)
    scale = float(np.median([np.linalg.norm(np.asarray(v)) for v in want.values()]))
    for name, value in want.items():
        gap = np.linalg.norm(np.asarray(got[name]) - np.asarray(value))
        assert gap <= 1e-4 * max(np.linalg.norm(np.asarray(value)), scale), name


def test_reference_positions_are_the_programs():
    ref, _ = _reference()
    ids = jnp.asarray(_segments())
    np.testing.assert_array_equal(
        np.asarray(ref.positions_in_document(ids)), np.asarray(document_positions(ids)))


def test_packed_model_gives_each_document_its_own_logits():
    """Positions restart at each document: a document's logits in the
    packing are those it gets alone, from position 0."""
    model = _model()
    features, _ = _batch()
    with jax.default_matmul_precision("highest"):
        variables = model.init_variables(jax.random.PRNGKey(0), features)

        @jax.jit
        def logits(tokens, seg):
            out, _ = model.inference_network_fn(
                variables,
                TensorSpecStruct({"tokens": tokens, "segment_ids": seg}), "predict",
            )
            return out["logits"]

        tokens = np.asarray(features["tokens"])
        packed = np.asarray(logits(features["tokens"], features["segment_ids"]))
        for row in range(2):
            for start, stop in _spans(row):
                piece = np.zeros((1, SEQ), np.int32)
                piece[0, :stop - start] = tokens[row, start:stop]
                seg = np.zeros((1, SEQ), np.int32)
                seg[0, :stop - start] = 1
                alone = np.asarray(logits(jnp.asarray(piece), jnp.asarray(seg)))
                np.testing.assert_allclose(
                    packed[row, start:stop], alone[0, :stop - start], rtol=2e-4, atol=2e-4)
        # One segment over the whole row: positions no longer restart, and
        # every document but the first reads other logits.
        joined = np.asarray(logits(features["tokens"], jnp.minimum(features["segment_ids"], 1)))
        start, stop = _spans(0)[1]
        assert not np.allclose(joined[0, start:stop], packed[0, start:stop], atol=1e-3)


def test_eight_shares_of_a_16_of_128_expert_layer_add_up_to_the_uncut_layer():
    """128 experts over 8 chips of 16, 6 a token, two shared: the eight
    shares' routed parts, with the shared experts counted once, are what
    the uncut reference layer gives."""
    ref, config = _reference()
    s = ref._settings(config)
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(2, SEQ, 64), jnp.float32)
    uncut = {**s, "n_routed_experts": 128, "router_experts": 128, "first_expert": 0,
             "num_experts_per_tok": 6}
    shapes = {k: v for k, v in ref.layer_shapes(uncut, ("mla", "moe")).items()
              if k.startswith("moe/")}
    whole = {name: jnp.asarray(rng.randn(*shape) * 0.1, jnp.float32)
             for name, shape in sorted(shapes.items())}
    whole["moe/selection_bias"] = jnp.asarray(rng.randn(128) * 0.05, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.moe_ffn(whole, x, uncut)
        routed, shared = jnp.zeros_like(x), None
        for first in range(0, 128, 16):
            layer = RoutedExperts(
                num_experts=16, router_experts=128, first_expert=first,
                hidden_dim=32, num_selected=6, shared_experts=2, scaling=2.448,
            )
            held = slice(first, first + 16)
            variables = {"params": {
                "router": whole["moe/router"],
                "selection_bias": whole["moe/selection_bias"],
                "gate": whole["moe/gate"][held], "up": whole["moe/up"][held],
                "down": whole["moe/down"][held],
                "shared": {n: {"kernel": whole[f"moe/shared/{n}/kernel"]}
                           for n in ("gate", "up", "down")},
            }}
            y, _ = layer.apply(variables, x)
            without = layer.clone(shared_experts=0).apply(
                {"params": {k: v for k, v in variables["params"].items() if k != "shared"}},
                x,
            )[0]
            routed, shared = routed + without, y - without
    np.testing.assert_allclose(np.asarray(routed + shared), np.asarray(want), atol=3e-5)


def test_trains_from_packed_records_through_train_eval_model(tmp_path):
    from tensor2robot_tpu.data import tfrecord
    from tensor2robot_tpu.data.encoder import encode_example
    from tensor2robot_tpu.data.input_generators import DefaultRecordInputGenerator
    from tensor2robot_tpu.train.train_eval import train_eval_model

    model = _model(num_hidden_layers=2, learning_rate=1e-2)
    spec = {
        **dict(model.preprocessor.get_in_feature_specification("train")),
        **dict(model.preprocessor.get_in_label_specification("train")),
    }
    records = []
    for seed in range(4):
        features, labels = _batch(seed)
        for row in range(2):
            records.append(encode_example(spec, {
                **{k: np.asarray(v[row]) for k, v in features.items()},
                **{k: np.asarray(v[row]) for k, v in labels.items()},
            }))
    path = str(tmp_path / "packed.tfrecord")
    tfrecord.write_tfrecords(path, records)
    train_eval_model(
        model,
        input_generator_train=DefaultRecordInputGenerator(
            file_patterns=path, batch_size=4, seed=0
        ),
        model_dir=str(tmp_path / "run"), max_train_steps=12, eval_steps=None,
        save_checkpoints_steps=100, log_every_steps=1,
    )
    with open(tmp_path / "run" / "train" / "metrics.jsonl") as f:
        log = [json.loads(line) for line in f if line.strip()]
    losses = [record["loss"] for record in log if "loss" in record]
    assert len(losses) >= 10, losses
    assert np.mean(losses[-3:]) < np.mean(losses[:3]) - 0.1, losses
    last = log[-1]
    assert last["tokens_per_s"] > 0 and 0 < last["pad_share"] < 0.5
    assert 0.4 < last["moe_rows_per_token"] < 2.5 and last["moe_imbalance"] >= 1.0


def test_t2r_check_flows_the_target():
    from tensor2robot_tpu.analysis.specflow import check_targets

    assert dict(check_targets())["deepseek-v3-lm"] == []


# -- the model that shares its block is where it was --------------------------------------


def test_kimi_linear_model_outputs_are_unchanged():
    """Outputs of the parent commit (d8f449b) on the same seeds, CPU float32:
    `KimiLinearBlock` and `LatentAttention` changed under it."""
    model = _kimi_model()
    features, labels = _batch(0)
    variables = model.init_variables(jax.random.PRNGKey(0), features)
    out = model.network.apply(variables, features, "train", labels)
    assert float(out["loss"]) == pytest.approx(4.578944206237793, rel=1e-6)
    assert (float(out["moe_routed_rows"]), float(out["moe_max_expert_rows"])) == (562.0, 200.0)
    logits = np.asarray(model.network.apply(variables, features, "eval")["logits"])
    np.testing.assert_allclose(
        logits[0, 5, :3],
        [0.033893194049596786, -0.1319984495639801, -0.01751089096069336],
        rtol=1e-5, atol=1e-7,
    )
    assert float(np.abs(logits).sum()) == pytest.approx(1529.04150390625, rel=1e-5)
