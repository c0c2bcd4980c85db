"""`KimiLinearLMModel` (ISSUE 32): packed documents against the same
documents alone, the program against the benchmark's plain reference,
per-block recomputation, training through `train_eval_model` and
`CompiledModel.train_step`, the counters, its `t2r-check` target, and the
models that share its code where they were. Its layers are in
tests/test_kimi_layers.py. CPU, tiny sizes, float32."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.layers import kda
from tensor2robot_tpu.layers.transformer import LatentAttention
from tensor2robot_tpu.models import sequence_lm_models
from tensor2robot_tpu.models.sequence_lm_models import KimiLinearBlock
from tensor2robot_tpu.specs import TensorSpecStruct
from tensor2robot_tpu.utils import tracing
from tests.sequence_lm_fixtures import (
    KIMI_LINEAR,
    LENGTHS,
    SEQ,
    batch as _batch,
    kimi_model as _model,
    kimi_reference as _reference,
    loss_fn as _loss_fn,
    model as _hybrid_model,
    segments as _segments,
    spans as _spans,
)


def _two_layer_model(**overrides):
    """Every kind of mixer and feed-forward in two layers: latent attention
    with the dense SwiGLU, then KDA with routed experts."""
    linear = {**KIMI_LINEAR, "kda_layers": [2], "full_attn_layers": [1]}
    return _model(num_hidden_layers=2, linear_attn_config=linear, **overrides)


def _nest(flat):
    tree = {}
    for path, value in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for name in parents:
            node = node.setdefault(name, {})
        node[leaf] = value
    return tree


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


@pytest.mark.parametrize("mixer", ["kda", "mla"])
def test_packed_mixer_gives_each_document_its_own_output(mixer):
    rng = np.random.RandomState(1)
    x = rng.randn(2, SEQ, 32).astype(np.float32)
    if mixer == "kda":
        module = kda.KDAMixer(num_heads=4, head_dim=8, chunk_size=16)
    else:
        module = LatentAttention(
            num_heads=4, qk_nope_dim=8, qk_rope_dim=4, v_dim=8, kv_rank=16
        )
    segments = jnp.asarray(_segments())
    with jax.default_matmul_precision("highest"):
        variables = module.init(jax.random.PRNGKey(0), jnp.asarray(x), segments)
        apply = jax.jit(module.apply)
        packed = np.asarray(apply(variables, jnp.asarray(x), segments))
        for row in range(2):
            alone, used = _alone(
                lambda piece, seg: apply(variables, piece, seg), x, row, SEQ
            )
            np.testing.assert_allclose(
                packed[row, :used], alone[:used], rtol=2e-4, atol=2e-5
            )


def test_packed_model_gives_each_document_its_own_logits():
    model = _two_layer_model()
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

        packed = np.asarray(logits(features["tokens"], features["segment_ids"]))
        assert packed.shape == (2, SEQ, 96)

        for row in range(2):
            alone, used = _alone(logits, np.asarray(features["tokens"]), row, SEQ)
            np.testing.assert_allclose(
                packed[row, :used], alone[:used], rtol=2e-4, atol=2e-4
            )


# -- the model -------------------------------------------------------------------------


def test_layer_kinds_follow_the_config_and_unsupported_keys_are_refused():
    model = _model()
    features, _ = _batch()
    params = model.init_variables(jax.random.PRNGKey(0), features)["params"]
    kinds = [
        ("qkv_proj" in params[f"layer_{i}"]["mixer"], "moe" in params[f"layer_{i}"])
        for i in range(5)
    ]
    assert kinds == [(True, False), (True, True), (True, True), (False, True), (True, True)]
    assert "kv_b" in params["layer_3"]["mixer"]
    assert params["layer_1"]["moe"]["router"].shape == (64, 16)
    assert params["layer_1"]["moe"]["gate"].shape == (4, 64, 32)
    assert params["lm_head"].shape == params["embedding"].shape == (96, 64)
    for bad in (
        dict(q_lora_rank=16), dict(mla_use_nope=False), dict(moe_renormalize=False),
        dict(moe_router_activation_func="softmax"), dict(num_expert_group=2),
        dict(tie_word_embeddings=True),
    ):
        with pytest.raises(ValueError, match="not supported"):
            _model(**bad)
    with pytest.raises(ValueError, match="names no mixer"):
        _model(num_hidden_layers=6)
    with pytest.raises(ValueError, match="held of"):
        _model(first_expert=14)


def test_specs_are_the_hybrid_models():
    ours, theirs = _model(), _hybrid_model()
    for mode in ("train", "predict"):
        assert dict(ours.get_feature_specification(mode)) == dict(
            theirs.get_feature_specification(mode))
    assert dict(ours.get_label_specification("train")) == dict(
        theirs.get_label_specification("train"))


@pytest.mark.parametrize("seed", [0, 1])
def test_gradients_follow_the_plain_reference(seed):
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


def test_per_block_recomputation_changes_no_bit(monkeypatch):
    features, labels = _batch()
    rematted = _two_layer_model()
    variables = rematted.init_variables(jax.random.PRNGKey(0), features)
    # Op by op, as the hybrid model's twin of this test.
    loss_b, grads_b = jax.value_and_grad(_loss_fn(rematted, features, labels))(
        variables["params"])
    monkeypatch.setattr(
        sequence_lm_models, "_remat_kimi_block", lambda mixer, ffn: KimiLinearBlock
    )
    loss_a, grads_a = jax.value_and_grad(
        _loss_fn(_two_layer_model(), features, labels))(variables["params"])
    assert float(loss_a) == float(loss_b)
    equal = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)), grads_a, grads_b
    )
    assert all(jax.tree_util.tree_leaves(equal)), equal


def test_a_block_keeps_what_its_kind_names_and_every_kept_name_is_emitted():
    import re

    features, labels = _batch()
    model = _two_layer_model()
    variables = model.init_variables(jax.random.PRNGKey(0), features)
    text = str(jax.make_jaxpr(jax.grad(_loss_fn(model, features, labels)))(
        variables["params"]))
    emitted = set(re.findall(r"name\[name=(\w+)\]", text))
    kept = {n for names in sequence_lm_models.KIMI_KEPT_RESIDUALS.values() for n in names}
    assert emitted == kept
    assert "kda_out" in kept
    assert set(sequence_lm_models.KIMI_KEPT_RESIDUALS) == {"kda", "mla", "dense", "moe"}


def test_step_metrics_carry_tokens_and_routed_rows():
    from tensor2robot_tpu.train.train_eval import CompiledModel

    model = _two_layer_model(learning_rate=3e-3)
    features, labels = _batch()
    compiled = CompiledModel(model, donate_state=True)
    batch = compiled.shard_batch({"features": features, "labels": labels})
    state = compiled.init_state(jax.random.PRNGKey(0), batch)
    losses = []
    for _ in range(5):
        state, metrics = compiled.train_step(state, batch, jax.random.PRNGKey(1))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    ids = _segments()
    assert float(metrics["pad_tokens"]) == float((ids == 0).sum())
    assert float(metrics["moe_positions"]) == 2 * SEQ               # one routed layer
    routed = float(metrics["moe_routed_rows"])
    assert 0.4 < routed / (2 * SEQ) < 2.5                            # 4 x 4 / 16 = 1 if even
    assert float(metrics["moe_peak_rows"]) == 4 * float(metrics["moe_max_expert_rows"])


def test_trains_from_packed_records_through_train_eval_model(tmp_path):
    from tensor2robot_tpu.data import tfrecord
    from tensor2robot_tpu.data.encoder import encode_example
    from tensor2robot_tpu.data.input_generators import DefaultRecordInputGenerator
    from tensor2robot_tpu.train.train_eval import train_eval_model

    model = _two_layer_model(learning_rate=1e-2)
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
    before = tracing.counters()
    train_eval_model(
        model,
        # Seeded, as the granite twin: an order that ends on four records
        # of the row that packs full would log a pad_share of 0.
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
    after = tracing.counters()
    grown = lambda name: after[name] - before.get(name, 0)
    assert grown("moe.routed_rows") == sum(
        record["moe_routed_rows"] for record in log if "moe_routed_rows" in record)
    assert 0 < grown("moe.max_expert_rows") < grown("moe.routed_rows")


def test_routed_rows_sum_on_the_device_and_token_models_have_none():
    from tensor2robot_tpu.train.train_eval import add_token_counts, token_log_record

    plain = {"tokens": jnp.asarray(40.0), "pad_tokens": jnp.asarray(5.0)}
    assert set(add_token_counts(None, plain)) == {"tokens", "pad_tokens"}
    step = {**plain, "moe_routed_rows": jnp.asarray(100.0),
            "moe_max_expert_rows": jnp.asarray(40.0),
            "moe_peak_rows": jnp.asarray(160.0), "moe_positions": jnp.asarray(360.0)}
    sums = add_token_counts(add_token_counts(None, step), step)
    assert sums["moe_routed_rows"].dtype == jnp.int32
    before = tracing.counters()
    record = token_log_record(jax.device_get(sums), 1.0)
    after = tracing.counters()
    assert record["moe_rows_per_token"] == pytest.approx(200 / 720)
    assert record["moe_imbalance"] == pytest.approx(1.6)
    assert after["moe.routed_rows"] - before.get("moe.routed_rows", 0) == 200
    assert after["moe.max_expert_rows"] - before.get("moe.max_expert_rows", 0) == 80
    assert "moe_imbalance" not in token_log_record(
        jax.device_get(add_token_counts(None, plain)), 1.0)


def test_t2r_check_flows_the_target():
    from tensor2robot_tpu.analysis.specflow import check_targets

    results = dict(check_targets())
    assert results["kimi-linear-lm"] == []


# -- the models that share its code are where they were ----------------------------------


def test_hybrid_model_outputs_are_unchanged():
    """Outputs of the parent commit (682089c) on the same seeds, CPU float32:
    `segment_attention`, `SwiGLU` and the model's base class changed under it."""
    model = _hybrid_model()
    features, labels = _batch(0)
    variables = model.init_variables(jax.random.PRNGKey(0), features)
    loss = model.network.apply(variables, features, "train", labels)["loss"]
    assert float(loss) == pytest.approx(4.559289932250977, rel=1e-6)
    logits = np.asarray(model.network.apply(variables, features, "eval")["logits"])
    np.testing.assert_allclose(
        logits[0, 5, :3],
        [0.0035132388584315777, 0.017541300505399704, 0.033839888870716095],
        rtol=1e-5, atol=1e-7,
    )
    assert float(np.abs(logits).sum()) == pytest.approx(215.22125244140625, rel=1e-5)


def test_transformer_bc_expert_outputs_are_unchanged():
    """`TransformerBCModel` with four experts through `MoEBlock`'s capacity
    routing, which stays beside the new one: the parent's outputs."""
    from tensor2robot_tpu.models.transformer_models import TransformerBCModel

    model = TransformerBCModel(
        action_size=3, pose_size=4, episode_length=8, image_size=(16, 16),
        use_flash=False, device_type="cpu", num_experts=4,
    )
    rng = np.random.RandomState(0)
    features = {
        "image": jnp.asarray(rng.rand(2, 8, 16, 16, 3), jnp.float32),
        "gripper_pose": jnp.asarray(rng.randn(2, 8, 4), jnp.float32),
    }
    variables = model.init_variables(jax.random.PRNGKey(0), features)
    out, _ = model.inference_network_fn(variables, features, "eval")
    action = np.asarray(out["action"])
    np.testing.assert_allclose(
        action[0, 0], [-1.1238480806350708, 0.46207261085510254, 0.5631028413772583],
        rtol=1e-5, atol=1e-6,
    )
    assert float(np.abs(action).sum()) == pytest.approx(48.648319244384766, rel=1e-5)
