"""The Kimi-Linear share (configuration kimi_linear_48b_a3b_s1) at a tiny
preset on the CPU (presets/kimi_linear_48b_a3b_s1.json): the plain
reference against the program in float32, the reference's chunked delta
rule against its stepped recurrence, its layer-by-layer step against its
whole step, the driver through `run.run_cell`, the control and each planted
fault coming out not correct, the kernels' counts against hand counts and
the new readers on a hand-made run.

The preset keeps the real cell and configuration files and changes sizes
only (5 layers: KDA + dense, KDA, KDA, latent attention, KDA, the last
four with 4 of 16 experts held, 4 a token). It runs the bare float32 model,
so its stated precision is float32 and the control is bfloat16. Limits
from CPU readings at these sizes on three seeds (PERF.md section 2's
rules): sound runs read 2.2e-6 or less on every number; the bfloat16
control reads 1.1e-3 or more on the gradient and on the change; half the
loss left out reads 0.6 on the gradient, the state unchanged 1.0 on the
change, the resets left out 0.2 on the gradient."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import compare
import flops
import manifest
import program_side
import report
import run as bench_run
import scope_sums
import tiny

CELL = "kimi_linear_48b_a3b_s1.train_packed_16k"
CONFIG = "kimi_linear_48b_a3b_s1"
TINY_MODEL = tiny.TINY_MODEL[CONFIG]["model"]
TINY_LIMITS = tiny.TINY_LIMITS[CONFIG]
SEQ = tiny.TINY_MODEL[CONFIG]["arguments"]["sequence_length"]
DRIVER = manifest.driver("train_resident_tokens_moe")


def tiny_config():
    return tiny.tiny_config(CONFIG)


def tiny_cell(rows=2):
    return tiny.tiny_cell(
        CELL, batch=rows, median_tokens=12, sigma=1.0, min_tokens=4
    )


def _raw(seed, rows=2):
    packed, documents = DRIVER.packed_documents(
        seed, rows, SEQ, TINY_MODEL["vocab_size"], tiny_cell()["traffic"]
    )
    assert documents >= 3
    return {
        "features": {k: jnp.asarray(packed[k]) for k in ("tokens", "segment_ids")},
        "labels": {k: jnp.asarray(packed[k]) for k in ("targets", "loss_mask")},
    }


def _program_readings(config, weights, raw, key):
    from tensor2robot_tpu.train.train_eval import CompiledModel

    model = program_side.build_model(
        dict(config, arguments=DRIVER.constructor_arguments(config)), weights
    )
    compiled = CompiledModel(model, donate_state=False)
    batch = compiled.shard_batch(program_side.as_program_batch(raw))
    state = compiled.init_state(jax.random.PRNGKey(0), batch)
    readings = DRIVER.HostStepReadings(
        manifest.reference(CONFIG).optimizer(config),
        {k: np.asarray(v) for k, v in weights.items()},
    )
    for index in range(compare.STEPS):
        state, metrics = compiled.train_step(state, batch, key)
        readings.after_step(index + 1, state, metrics)
    return readings.result()


def test_the_configuration_holds_the_published_widths_and_states_its_cut():
    config = manifest.config(CONFIG)
    model = config["model"]
    widths = {
        "hidden_size": 2304, "intermediate_size": 9216,
        "moe_intermediate_size": 1024, "num_attention_heads": 32,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "router_experts": 256, "num_experts_per_token": 8,
        "num_shared_experts": 1, "routed_scaling_factor": 2.446,
        "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
        "rms_norm_eps": 1e-05,
    }
    assert {k: model[k] for k in widths} == widths
    linear = model["linear_attn_config"]
    assert (linear["num_heads"], linear["head_dim"],
            linear["short_conv_kernel_size"]) == (32, 128, 4)
    assert (model["num_hidden_layers"], model["num_experts"],
            model["vocab_size"]) == (5, 8, 20480)
    assert config["published"] == {
        "num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840}
    assert [r.split()[0] for r in config["reduced"]] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert "32 chips" in config["deployment"]
    ref = manifest.reference(CONFIG)
    assert [kind for kind in ref._settings(config)["kinds"]] == [
        ("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe"),
        ("kda", "moe")]
    shapes = jax.eval_shape(lambda k: ref.init_params(k, config), jax.random.PRNGKey(0))
    total = sum(int(np.prod(v.shape)) for v in shapes.values())
    assert 602.0e6 < total < 603.0e6, total


def test_reference_matches_the_program_in_float32():
    config = tiny_config()
    ref = manifest.reference(CONFIG)
    weights = ref.init_params(jax.random.PRNGKey(1), config)
    raw, key = _raw(7), jax.random.PRNGKey(3)
    program = _program_readings(config, weights, raw, key)
    expected = compare.reference_readings(ref, config, weights, [raw] * 3, key)
    numbers, _ = compare.compared_numbers(program, expected)
    assert max(numbers.values()) < 1e-5, numbers
    assert compare.judge(numbers, TINY_LIMITS)[0]


@pytest.mark.parametrize("resets", [False, True])
def test_reference_chunks_are_the_stepped_recurrence(resets):
    ref = manifest.reference(CONFIG)
    rng = np.random.RandomState(0)
    batch, seq, heads, width = 2, 64, 3, 8
    draw = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    q, k, v = (draw(batch, seq, heads, width) for _ in range(3))
    q, k = ref.unit(q) * width ** -0.5, ref.unit(k)
    g = -6.0 * jnp.asarray(rng.rand(batch, seq, heads, width), jnp.float32)
    beta = jnp.asarray(rng.rand(batch, seq, heads), jnp.float32)
    ids = np.ones((batch, seq), np.int32)
    if resets:
        ids[0, 21:] = 2
        ids[0, 32:] = 3
        ids[1, 50:] = 0
    doc = ref.documents(jnp.asarray(ids))
    stepped = ref.kda_recurrence(q, k, v, g, beta, doc)
    chunked = ref.kda_scan(q, k, v, g, beta, doc, 16)
    np.testing.assert_allclose(chunked, stepped, atol=1e-5)


def test_layer_by_layer_step_is_the_whole_step():
    config = tiny_config()
    ref = manifest.reference(CONFIG)
    weights = ref.init_params(jax.random.PRNGKey(2), config)
    raw, key = _raw(11), jax.random.PRNGKey(5)
    whole = compare.reference_readings(ref, config, weights, [raw] * 3, key)
    step = ref.streaming_step(config)
    params = {k: np.asarray(v) for k, v in weights.items()}
    opt = compare._optimizer_init(ref.optimizer(config), weights)
    losses = []
    for index in range(compare.STEPS):
        params, opt, loss, norms = step(params, opt, raw, key, index)
        losses.append(float(loss))
        if index == 0:
            for name, value in whole["grad_norms"].items():
                assert float(norms[name]) == pytest.approx(value, rel=1e-5, abs=1e-9)
    assert losses == pytest.approx(whole["loss"], rel=1e-6)
    for name, value in whole["update_norms"].items():
        moved = float(np.sqrt(np.sum(np.square(params[name] - np.asarray(weights[name])))))
        # Adam divides a gradient by its own size: the four elements of an
        # A_log move by the rounding of theirs (1.3e-4 between the forms).
        assert moved == pytest.approx(value, rel=5e-4, abs=1e-9), name


def test_scanned_attention_blocks_are_the_looped_ones(monkeypatch):
    """The layer-by-layer step scans its query blocks against all keys; the
    whole step loops over them up to each block's last query."""
    ref = manifest.reference(CONFIG)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    rng = np.random.RandomState(3)
    draw = lambda dim: jnp.asarray(rng.randn(2, SEQ, 4, dim), jnp.float32)
    q, k, v = draw(24), draw(24), draw(16)
    seg = _raw(5)["features"]["segment_ids"]
    weight = draw(16)

    def both(scanned):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(
                ref.attention_core(q, k, v, seg, 0.2, scanned=scanned) * weight),
            argnums=(0, 1, 2))(q, k, v)

    (looped, looped_grads), (scanned, scanned_grads) = both(False), both(True)
    assert float(looped) == pytest.approx(float(scanned), rel=1e-5)
    for a, b in zip(looped_grads, scanned_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_driver_gives_a_well_formed_result_and_counts_routed_rows():
    counters = {}

    class Reporter(report.Reporter):
        def say(self, text):
            if text.startswith("routed rows over the window"):
                counters["said"] = text
            super().say(text)

    result = bench_run.run_cell(
        tiny_cell(), tiny_config(), tiny.args(seed=2_147_483_659, seconds=1.0),
        jax.devices()[:1], Reporter("test"),
    )
    assert set(result) == {
        "correct", "attempted", "failed", "metrics", "device", "compared"
    }
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {m["name"] for m in manifest.end_to_end(CELL)}
    assert {"train.examples_per_s", "train.step_ms.p90", "setup_s"} <= set(result["metrics"])
    # The counter is the sum of the window's steps, each as that step read
    # (the router trains: the last step's count times the steps is not it).
    import ast

    said = counters["said"]
    grown = ast.literal_eval(said[said.index("{"):said.index("}") + 1])
    a_step = ast.literal_eval(said[said.rindex("["):])
    assert len(a_step) == result["attempted"] and len(set(a_step)) > 1
    assert grown["moe.routed_rows"] == sum(a_step)
    assert 0 < grown["moe.max_expert_rows"] < grown["moe.routed_rows"]


@pytest.mark.parametrize("fault", ["control", "half_loss", "state_unchanged", "no_resets"])
def test_control_and_planted_faults_are_not_correct(fault):
    """The reference put in the program's place, computed one precision
    down or with one fault planted, against the sound reference."""
    config = tiny_config()
    ref = manifest.reference(CONFIG)
    weights = ref.init_params(jax.random.PRNGKey(9), config)
    raw, key = _raw(13), jax.random.PRNGKey(4)
    expected = compare.reference_readings(ref, config, weights, [raw] * 3, key)
    own, _ = compare.compared_numbers(expected, expected)
    assert compare.judge(own, TINY_LIMITS)[0]
    batches, kwargs = [raw] * 3, {}
    if fault == "control":
        kwargs["quant"] = config["control"][0]
    elif fault == "state_unchanged":
        kwargs["fault"] = fault
    else:
        batches = [DRIVER.BATCH_FAULTS[fault](raw)] * 3
    other = compare.reference_readings(ref, config, weights, batches, key, **kwargs)
    numbers, _ = compare.compared_numbers(other, expected)
    correct, shown = compare.judge(numbers, TINY_LIMITS)
    assert not correct, shown


def test_readings_script_reads_a_seed_with_control_and_faults():
    import readings_tokens

    line = readings_tokens.read_seed(
        tiny_cell(), tiny_config(), 2_000_104_740, jax.devices()[:1],
        report.Reporter("test"), control=True,
    )
    assert compare.judge(line["program"], TINY_LIMITS)[0], line["program"]
    assert set(line["control"]) == {"bfloat16"}
    assert set(line["faults"]) == {"state_unchanged", "half_loss", "no_resets"}
    for numbers in list(line["control"].values()) + list(line["faults"].values()):
        assert not compare.judge(numbers, TINY_LIMITS)[0], numbers


def whole_step_flops(ref, config):
    return flops.count(
        lambda p, b: ref.loss_fn(p, b, jax.random.PRNGKey(0), config),
        flops.abstract(ref.init_params(jax.random.PRNGKey(0), config)),
        flops.abstract(_raw(1)),
    )["step_flops"]


def test_kernel_counts_against_hand_counts():
    config = tiny_config()
    ref = manifest.reference(CONFIG)
    rows, chunk = 2, TINY_MODEL["kda_chunk_size"]
    heads, dim = 4, 16
    chunks = SEQ // chunk
    costs = ref.kernel_costs(config, rows, SEQ, 2)
    # Forward multiply-accumulates of one chunk and head: k k^T and q k^T
    # over the decayed keys, W S, q S, B V', and the state's update (the
    # triangular solve is no matrix product and is left out).
    macs = rows * heads * (
        2 * chunk * chunk * dim + 3 * chunk * dim * dim + chunk * chunk * dim
    )
    assert costs["kda"]["forward_flops"] == 2 * macs * chunks * 4   # four KDA layers
    assert costs["kda"]["step_flops"] == 3 * costs["kda"]["forward_flops"]
    elements = rows * SEQ * heads * (5 * dim + 1)
    assert costs["kda"]["step_bytes"] == 3 * elements * 2 * 4
    # One block of queries at this size: scores over all keys at width 24,
    # values at width 16.
    q_heads, qk, vdim = 4, 24, 16
    attention = 2 * rows * q_heads * SEQ * SEQ * (qk + vdim)
    assert costs["mla"]["forward_flops"] == attention
    assert costs["mla"]["step_flops"] == 3 * attention
    assert costs["mla"]["step_bytes"] == 3 * rows * SEQ * q_heads * (2 * qk + 2 * vdim + 1) * 2
    routed = ref.moe_costs(config, 100, 2)
    assert routed["step_flops"] == 100 * 3 * 2 * 64 * 32 * 3
    assert routed["step_bytes"] == 3 * (4 * 4 * 3 * 64 * 32 + 100 * (2 * 64 + 3 * 32)) * 2
    # The plain projections: four KDA mixers (q, k, v and o of 4 x 16), the
    # latent mixer (q 4 x 24, down to 32 + 8, up to 4 x 32, o), layer 1's
    # SwiGLU of 128, four shared experts of 32 and the head of 96 rows.
    d, positions = 64, rows * SEQ
    matrices = (
        4 * [(d, 3 * 64), (64, d)]
        + [(d, 4 * 24), (d, 32 + 8), (32, 4 * 32), (4 * 16, d)]
        + [(d, 128), (d, 128), (128, d)] + 4 * [(d, 32), (d, 32), (32, d)]
        + [(d, 96)]
    )
    plain = ref.projection_costs(config, rows, SEQ, 2)
    assert plain["step_flops"] == 3 * 2 * positions * sum(a * b for a, b in matrices)
    assert plain["step_bytes"] == 3 * 2 * sum(
        a * b + positions * (a + b) for a, b in matrices)
    # The whole step counts one chunk of each delta rule and no routed expert.
    assert whole_step_flops(ref, config) > (
        costs["mla"]["step_flops"] + costs["kda"]["step_flops"] / chunks
        + plain["step_flops"])


@pytest.mark.parametrize("label,scope", [
    ("jit(train_step)/transpose(jvp(_KimiLinearLMNet))/layer_1/mixer/kda/delta_rule/dot_general", "kda/delta_rule"),
    ("jit(train_step)/_KimiLinearLMNet/layer_3/mixer/attention/custom_call", "attention"),
    ("jit(train_step)/_KimiLinearLMNet/layer_3/mixer/attention_proj/o_proj/dot_general", "attention_proj"),
    ("jit(train_step)/_KimiLinearLMNet/layer_3/mixer/mla/kv_up/kv_b/dot_general", "mla/kv_up"),
    ("jit(train_step)/_KimiLinearLMNet/layer_1/moe/moe/experts/ragged_dot", "moe/experts"),
    ("jit(train_step)/_KimiLinearLMNet/layer_1/moe/moe/shared/shared/gate/dot_general", "moe/shared"),
    ("jit(train_step)/_KimiLinearLMNet/layer_0/mlp/mlp/gate/dot_general", "mlp"),
    ("jit(train_step)/transpose(jvp(_KimiLinearLMNet))/layer_1/moe/while/body/transpose(jvp(moe/experts))/ragged_dot", "moe/experts"),
    ("ragged-dot-none", "moe/experts"),
    ("jit(train_step)/jit(main)/grasping44/conv2/conv_general_dilated", None),
])
def test_innermost_scope_of_an_op_label(label, scope):
    import kimi_scopes

    assert scope_sums.innermost(label, kimi_scopes.ALL, kimi_scopes.UNLABELLED) == scope
    if label.startswith("ragged-dot"):
        assert scope_sums.innermost(label, kimi_scopes.ALL) is None


def _hand_made_run(counters):
    run = types.SimpleNamespace()
    run.config = dict(manifest.config(CONFIG))
    run.cell = manifest.cell(CELL)
    run.devices = [object()]
    run.counters = counters
    run.window = types.SimpleNamespace(results=lambda: {"steps": 10})
    return run


def test_counter_readers_on_a_hand_made_run():
    even = 16384 * 8 * 8 // 256 * 4          # rows a step under even routing
    run = _hand_made_run({
        "moe.routed_rows": even * 10, "moe.max_expert_rows": 600 * 4 * 10})
    read = lambda name: manifest._load_module("readers", name).read(run)
    assert read("moe_rows_per_token") == pytest.approx(0.25)
    assert read("moe_imbalance") == pytest.approx(600 / 512)
    empty = _hand_made_run({})
    for name in ("moe_rows_per_token", "moe_imbalance", "moe_roofline"):
        assert manifest._load_module("readers", name).read(empty) is None


def test_every_new_metric_has_its_file_and_reader():
    names = [m["name"] for m in manifest.benchmark_json()["per_layer"]
             if m.get("workloads") == [CELL]]
    assert len(names) >= 13
    assert {entry["name"] for entry, _, _ in manifest.per_layer(CELL)} >= set(names)
