"""`HybridSequenceLMModel` (ISSUE 28): specs, the layer pattern, the
program against the benchmark's plain reference, per-layer recomputation
and what a block keeps across it (ISSUE 31), training from packed records
through `train_eval_model` and through `CompiledModel.train_step`, the
token counters, its `t2r-check` target, and the transformer family's
outputs unchanged. CPU, tiny sizes, float32."""

import ast
import collections
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.utils import tracing
from tests.sequence_lm_fixtures import (
    LENGTHS,
    REPO as _REPO,
    SEQ,
    batch as _batch,
    loss_fn as _loss_fn,
    model as _model,
    segments as _segments,
)


# -- the model ----------------------------------------------------------------------


def test_specs_hold_tokens_and_segments_and_no_positions():
    model = _model()
    features = model.get_feature_specification("train")
    labels = model.get_label_specification("train")
    assert sorted(features.keys()) == ["segment_ids", "tokens"]
    assert sorted(labels.keys()) == ["loss_mask", "targets"]
    assert features["tokens"].dtype == np.int32 and features["tokens"].shape == (SEQ,)
    assert labels["loss_mask"].dtype == np.float32


def test_layer_pattern_is_read_up_to_num_hidden_layers():
    pattern = ["mamba"] * 5 + ["attention"] + ["mamba"] * 9 + ["attention"]
    model = _model(layer_types=pattern, num_hidden_layers=7)
    features, _ = _batch()
    params = model.init_variables(jax.random.PRNGKey(0), features)["params"]
    layers = sorted(k for k in params if k.startswith("layer_"))
    assert len(layers) == 7
    assert "qkv" in params["layer_5"]["mixer"] and "in_proj" in params["layer_6"]["mixer"]
    with pytest.raises(ValueError, match="num_hidden_layers"):
        _model(layer_types=("mamba",), num_hidden_layers=2)
    with pytest.raises(ValueError, match="mamba_expand"):
        _model(mamba_d_head=16)


def _loss_and_grads(model, variables, features, labels):
    return jax.value_and_grad(_loss_fn(model, features, labels))(variables["params"])


def test_per_layer_recomputation_changes_no_bit(monkeypatch):
    from tensor2robot_tpu.layers.transformer import HybridBlock
    from tensor2robot_tpu.models import sequence_lm_models

    features, labels = _batch()
    rematted = _model()
    variables = rematted.init_variables(jax.random.PRNGKey(0), features)
    # Op by op: under jit XLA fuses the recomputed forward its own way and
    # the last bits move; the equations are the same ones in the same order.
    loss_b, grads_b = _loss_and_grads(rematted, variables, features, labels)
    monkeypatch.setattr(sequence_lm_models, "_RematBlock", HybridBlock)
    loss_a, grads_a = _loss_and_grads(_model(), variables, features, labels)
    assert float(loss_a) == float(loss_b)
    equal = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)), grads_a, grads_b
    )
    assert all(jax.tree_util.tree_leaves(equal)), equal


# -- what a block keeps across its recomputation (ISSUE 31) -------------------------


@pytest.fixture(scope="module")
def block_variants():
    """{variant: (loss, gradients, jaxpr of the gradient)} of the tiny
    model (mamba, attention, mamba) on one batch and one set of weights:
    the model's block (named residuals kept), the whole block recomputed,
    and no recomputation."""
    import flax.linen as nn

    from tensor2robot_tpu.layers.transformer import HybridBlock
    from tensor2robot_tpu.models import sequence_lm_models

    features, labels = _batch()
    variables = _model().init_variables(jax.random.PRNGKey(0), features)
    blocks = {
        "kept": sequence_lm_models._RematBlock,
        "whole": nn.remat(HybridBlock),
        "plain": HybridBlock,
    }
    out = {}
    with pytest.MonkeyPatch.context() as patch:
        for name, block in blocks.items():
            patch.setattr(sequence_lm_models, "_RematBlock", block)
            loss = _loss_fn(_model(), features, labels)
            value, grads = jax.jit(jax.value_and_grad(loss))(variables["params"])
            out[name] = (
                value, grads, jax.make_jaxpr(jax.grad(loss))(variables["params"])
            )
    return out


@pytest.mark.parametrize("one,other", [
    ("kept", "whole"), ("kept", "plain"), ("whole", "plain"),
])
def test_kept_residuals_change_no_gradient(block_variants, one, other):
    loss_a, grads_a, _ = block_variants[one]
    loss_b, grads_b, _ = block_variants[other]
    assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-6)
    leaves_a = jax.tree_util.tree_leaves_with_path(grads_a)
    leaves_b = [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(grads_b)]
    assert len(leaves_a) == len(leaves_b)
    # A leaf's gap over the larger of its norm and the median leaf's: the
    # gradients of `dt_bias` are sums that all but cancel (1e-12).
    scale = float(np.median([np.linalg.norm(leaf) for leaf in leaves_b]))
    for (path, a), b in zip(leaves_a, leaves_b):
        gap = np.linalg.norm(np.asarray(a) - b)
        assert gap <= 1e-6 * max(np.linalg.norm(b), scale), jax.tree_util.keystr(path)


_SCOPES = (
    "mamba2/in_proj", "mamba2/ssd", "mamba2/out_proj", "attention",
    "attention_proj", "mlp", "lm_head",
)


def _equations(jaxpr, stack=""):
    """(equation, its whole name stack) of a jaxpr and of every jaxpr
    inside its equations' parameters."""
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        yield eqn, here
        for value in eqn.params.values():
            for item in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner, here)


def _products_by_scope(closed):
    """{scope: number of `dot_general`s} of a jaxpr (the scopes do not
    nest; None counts the products outside all of them)."""
    counts = collections.Counter()
    for eqn, stack in _equations(closed.jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        counts[next((s for s in _SCOPES if f"/{s}/" in f"{stack}/"), None)] += 1
    return counts


def test_the_policy_saves_three_products_a_block_and_nothing_else(block_variants):
    kept, whole, plain = (
        _products_by_scope(block_variants[name][2])
        for name in ("kept", "whole", "plain")
    )
    blocks, mamba_blocks = 3, 2
    assert whole["mlp"] - kept["mlp"] == 2 * blocks
    assert whole["mamba2/in_proj"] - kept["mamba2/in_proj"] == mamba_blocks
    assert sum(whole.values()) - sum(kept.values()) == 2 * blocks + mamba_blocks
    # The products the policy keeps are not computed twice any more.
    assert kept["mamba2/in_proj"] == plain["mamba2/in_proj"]
    # The scans and the attention layer's scores and values still are.
    for scope in ("mamba2/ssd", "attention"):
        assert kept[scope] == whole[scope] > plain[scope], scope
    for scope in set(whole) - {"mlp", "mamba2/in_proj"}:
        assert kept[scope] == whole[scope], scope


def _checkpoint_names_in_source():
    from tensor2robot_tpu.layers import kda, mamba2, transformer

    names = []
    for module in (mamba2, transformer, kda):
        with open(module.__file__) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            called = getattr(node.func, "id", getattr(node.func, "attr", None))
            if called == "checkpoint_name":
                assert isinstance(node.args[1], ast.Constant), ast.dump(node)
                names.append(node.args[1].value)
    return names


def test_every_kept_name_is_emitted_and_every_emitted_name_is_kept(block_variants):
    from tensor2robot_tpu.models.sequence_lm_models import (
        KEPT_RESIDUALS,
        KIMI_KEPT_RESIDUALS,
    )

    assert len(set(KEPT_RESIDUALS)) == len(KEPT_RESIDUALS)
    # The layer files name what either model keeps, and nothing else.
    kimi = {name for names in KIMI_KEPT_RESIDUALS.values() for name in names}
    assert sorted(_checkpoint_names_in_source()) == sorted(
        set(KEPT_RESIDUALS) | kimi)
    # And the tiny model's step really passes through each of them.
    emitted = {
        eqn.params["name"] for eqn, _ in _equations(block_variants["plain"][2].jaxpr)
        if eqn.primitive.name == "name"
    }
    assert emitted == set(KEPT_RESIDUALS)


def _nameless(monkeypatch):
    """The two layer files as they were before their outputs had names."""
    from tensor2robot_tpu.layers import mamba2, transformer

    for module in (mamba2, transformer):
        monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)


def _lowered_text(jitted, *args):
    """StableHLO of a jitted call; jax numbers its private functions
    (`@_where_189`) by a counter of the process, which is left out."""
    return re.sub(r"(@\w+?)_\d+\b", r"\1", jitted.lower(*args).as_text())


def _layer_case(layer):
    from tensor2robot_tpu.layers.mamba2 import Mamba2Mixer
    from tensor2robot_tpu.layers.transformer import SwiGLU

    x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64), jnp.float32)
    if layer == "swiglu":
        return SwiGLU(128), (x,)
    return (
        Mamba2Mixer(num_heads=4, head_dim=32, state_size=16, chunk_size=16),
        (x, jnp.asarray(_segments())),
    )


@pytest.mark.parametrize("layer", ["swiglu", "mamba2"])
def test_a_named_layer_computes_what_its_nameless_twin_does(monkeypatch, layer):
    module, inputs = _layer_case(layer)
    variables = module.init(jax.random.PRNGKey(5), *inputs)

    def apply():
        # A function of its own each time: no trace is shared with the twin.
        return jax.jit(lambda *args: module.apply(*args))

    named = apply()(variables, *inputs)
    named_text = _lowered_text(apply(), variables, *inputs)
    assert "name[name=" in str(jax.make_jaxpr(apply())(variables, *inputs))
    _nameless(monkeypatch)
    assert "name[name=" not in str(jax.make_jaxpr(apply())(variables, *inputs))
    assert bool(jnp.array_equal(named, apply()(variables, *inputs)))
    assert bool(jnp.all(jnp.isfinite(named))) and float(jnp.abs(named).sum()) > 0
    # Outside a policy the name is no operation: the lowering is the twin's.
    assert named_text == _lowered_text(apply(), variables, *inputs)


def test_the_predict_path_lowers_without_remat_or_names(monkeypatch):
    features, _ = _batch()
    model = _model()
    variables = model.init_variables(jax.random.PRNGKey(0), features)

    def lowered():
        predict = jax.jit(
            lambda v, f: _model().inference_network_fn(v, f, "predict")[0]["logits"]
        )
        return _lowered_text(predict, variables, features)

    text = lowered()
    assert "optimization_barrier" not in text
    _nameless(monkeypatch)
    assert text == lowered()


def test_step_metrics_carry_tokens_and_pad_tokens():
    model = _model()
    features, labels = _batch()
    variables = model.init_variables(jax.random.PRNGKey(0), features)
    outputs, _ = model.inference_network_fn(variables, features, "train", labels=labels)
    loss, metrics = model.model_train_fn(features, labels, outputs, "train")
    assert np.isfinite(float(loss))
    assert float(metrics["tokens"]) == float(np.asarray(labels["loss_mask"]).sum())
    assert float(metrics["pad_tokens"]) == 2 * SEQ - sum(map(sum, LENGTHS))


def _reference():
    path = os.path.join(_REPO, "benchmark", "reference", "granite_4_0_h_micro_p1.py")
    spec = importlib.util.spec_from_file_location("granite_reference_for_tests", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    with open(os.path.join(_REPO, "benchmark", "configs", "granite_4_0_h_micro_p1.json")) as f:
        config = json.load(f)
    config["model"] = dict(
        config["model"], hidden_size=64, shared_intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=4,
        mamba_d_head=32, mamba_d_state=16, mamba_chunk_size=16, vocab_size=96,
        num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"],
    )
    return module, config


def _nest(flat):
    tree = {}
    for path, value in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for name in parents:
            node = node.setdefault(name, {})
        node[leaf] = value
    return tree


@pytest.mark.parametrize("seed", [0, 1])
def test_gradients_follow_the_plain_reference(seed):
    ref, config = _reference()
    m = config["model"]
    model = _model(
        attention_multiplier=m["attention_multiplier"],
        embedding_multiplier=m["embedding_multiplier"],
        residual_multiplier=m["residual_multiplier"],
        logits_scaling=m["logits_scaling"],
    )
    features, labels = _batch(seed)
    flat = ref.init_params(jax.random.PRNGKey(seed), config)
    raw = {"features": dict(features.items()), "labels": dict(labels.items())}
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(
            lambda p: ref.loss_fn(p, raw, None, config))(flat)
        got_loss, got = _loss_and_grads(
            model, {"params": _nest(flat)}, features, labels
        )
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    got = {"/".join(str(k.key) for k in path): leaf
           for path, leaf in jax.tree_util.tree_leaves_with_path(got)}
    assert set(got) == set(want)
    scale = float(np.median([np.linalg.norm(np.asarray(v)) for v in want.values()]))
    for name, value in want.items():
        gap = np.linalg.norm(np.asarray(got[name]) - np.asarray(value))
        assert gap <= 1e-4 * max(np.linalg.norm(np.asarray(value)), scale), name


def test_reference_scan_is_the_stepped_recurrence():
    ref, _ = _reference()
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(2, SEQ, 4, 8), jnp.float32)
    dt = jnp.asarray(np.log1p(np.exp(rng.randn(2, SEQ, 4))), jnp.float32)
    a_log = jnp.asarray(rng.uniform(0, 2, 4), jnp.float32)
    b = jnp.asarray(rng.randn(2, SEQ, 16), jnp.float32)
    c = jnp.asarray(rng.randn(2, SEQ, 16), jnp.float32)
    doc = ref.documents(jnp.asarray(_segments()))
    chunked = ref.ssd_scan(x, dt, a_log, b, c, doc, 16)
    stepped = ref.ssd_recurrence(x, dt, a_log, b, c, doc)
    bound = 1e-5 * max(1.0, float(jnp.max(jnp.abs(stepped))))
    assert float(jnp.max(jnp.abs(chunked - stepped))) < bound


# -- the trainer ----------------------------------------------------------------------


@pytest.mark.parametrize("log_every_steps,iterations_per_loop", [(1, 1), (5, 1), (6, 3)])
def test_trains_from_packed_records_through_train_eval_model(
    tmp_path, log_every_steps, iterations_per_loop
):
    from tensor2robot_tpu.data import tfrecord
    from tensor2robot_tpu.data.encoder import encode_example
    from tensor2robot_tpu.data.input_generators import DefaultRecordInputGenerator
    from tensor2robot_tpu.train.train_eval import train_eval_model

    model = _model(learning_rate=1e-2)
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
        # Seeded: one unseeded order in seventy ends on a batch of four
        # records of the row that packs full, whose pad_share is 0.
        input_generator_train=DefaultRecordInputGenerator(
            file_patterns=path, batch_size=4, seed=0
        ),
        model_dir=str(tmp_path / "run"), max_train_steps=12, eval_steps=None,
        save_checkpoints_steps=100, log_every_steps=log_every_steps,
        iterations_per_loop=iterations_per_loop,
    )
    with open(tmp_path / "run" / "train" / "metrics.jsonl") as f:
        log = [json.loads(line) for line in f if line.strip()]
    losses = [record["loss"] for record in log if "loss" in record]
    if log_every_steps == 1:
        assert len(losses) >= 10, losses
        assert np.mean(losses[-3:]) < np.mean(losses[:3]) - 0.1, losses
    assert losses[-1] < losses[0]
    last = log[-1]
    assert last["tokens_per_s"] > 0 and 0 < last["pad_share"] < 0.5
    # The counters are counts: every step of the run, logged or not. The 12
    # batches hold 48 records, each one of the two rows' packings, in the
    # order the shuffle gave them.
    after = tracing.counters()
    tokens = after["train.tokens"] - before.get("train.tokens", 0)
    pad = after["train.pad_tokens"] - before.get("train.pad_tokens", 0)
    row_tokens = [sum(lengths) - len(lengths) for lengths in LENGTHS]
    second_rows, rest = divmod(pad, SEQ - sum(LENGTHS[1]))
    assert rest == 0 and 0 < second_rows < 48
    assert tokens == (48 - second_rows) * row_tokens[0] + second_rows * row_tokens[1]
    if log_every_steps == 1:
        assert tokens == sum(record["tokens"] for record in log if "tokens" in record)


def test_trains_through_compiled_model_train_step():
    from tensor2robot_tpu.train.train_eval import CompiledModel

    model = _model(learning_rate=3e-3)
    features, labels = _batch()
    compiled = CompiledModel(model, donate_state=True)
    batch = compiled.shard_batch({"features": features, "labels": labels})
    state = compiled.init_state(jax.random.PRNGKey(0), batch)
    losses = []
    for _ in range(5):
        state, metrics = compiled.train_step(state, batch, jax.random.PRNGKey(1))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] and float(metrics["tokens"]) > 0


def test_token_counts_sum_on_the_device_and_other_models_have_none():
    from tensor2robot_tpu.train.train_eval import add_token_counts, token_log_record

    assert add_token_counts(None, {"loss": jnp.ones(())}) is None
    assert token_log_record(None, 2.0) == {}
    step = {"tokens": jnp.asarray(40.0), "pad_tokens": jnp.asarray(5.0)}
    chunk = {"tokens": jnp.asarray([30.0, 20.0]), "pad_tokens": jnp.asarray([2.0, 3.0])}
    sums = add_token_counts(add_token_counts(None, step), chunk)
    assert sums["tokens"].dtype == jnp.int32
    before = tracing.counters()
    record = token_log_record(jax.device_get(sums), 0.5)
    assert record == {"tokens_per_s": 180.0, "pad_share": 0.1}
    after = tracing.counters()
    assert after["train.tokens"] - before.get("train.tokens", 0) == 90
    assert after["train.pad_tokens"] - before.get("train.pad_tokens", 0) == 10


def test_t2r_check_flows_the_target():
    from tensor2robot_tpu.analysis.specflow import check_targets

    results = dict(check_targets())
    assert results["hybrid-sequence-lm"] == []


# -- the transformer family is where it was ---------------------------------------------

# Outputs of the parent commit (914e25a) on the same seeds, CPU float32.
_BC_GOLDEN = {
    "default": ({}, [-1.6007338762283325, -0.020983200520277023, 0.7772454023361206],
                37.308021545410156),
    "gqa": ({"num_kv_heads": 2},
            [-1.1355141401290894, -0.12641729414463043, 0.08570169657468796],
            35.29999542236328),
    "window": ({"attention_window": 4},
               [-1.6007338762283325, -0.020983200520277023, 0.7772454023361206],
               38.478355407714844),
}


@pytest.mark.parametrize("case", sorted(_BC_GOLDEN))
def test_transformer_bc_outputs_are_unchanged(case):
    from tensor2robot_tpu.models.transformer_models import TransformerBCModel

    kwargs, first, total = _BC_GOLDEN[case]
    model = TransformerBCModel(
        action_size=3, pose_size=4, episode_length=8, image_size=(16, 16),
        use_flash=False, device_type="cpu", **kwargs,
    )
    rng = np.random.RandomState(0)
    features = {
        "image": jnp.asarray(rng.rand(2, 8, 16, 16, 3), jnp.float32),
        "gripper_pose": jnp.asarray(rng.randn(2, 8, 4), jnp.float32),
    }
    variables = model.init_variables(jax.random.PRNGKey(0), features)
    out, _ = model.inference_network_fn(variables, features, "eval")
    action = np.asarray(out["action"])
    np.testing.assert_allclose(action[0, 0], first, rtol=1e-5, atol=1e-6)
    assert float(np.abs(action).sum()) == pytest.approx(total, rel=1e-5)
