"""The Kanana-2-30B-A3B share (configuration kanana_2_30b_a3b_s1) at a tiny
preset on the CPU (presets/kanana_2_30b_a3b_s1.json): the configuration
against the published widths and the constructor's keys, the plain
reference against the program in float32, its layer-by-layer step against
its whole step, the driver through `run.run_cell`, the control and each
planted fault coming out not correct, the kernels' counts against hand
counts and the new metrics' files and readers.

The preset keeps the real cell and configuration files and changes sizes
only (5 layers of latent attention, the first dense, the last four with 4
of 16 experts held, 4 a token). It runs the bare float32 model, so its
stated precision is float32 and the control is bfloat16. Limits are the
Kimi preset's, which part the CPU readings at these sizes on three seeds
(PERF.md section 2's rules): sound runs read 1.7e-6 or less on every
number; the bfloat16 control reads 1.4e-3 or more on the gradient and
2.8e-3 or more on the change (its losses, 2e-6 to 3e-5, do not part)."""

import inspect

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
import tiny

CELL = "kanana_2_30b_a3b_s1.train_packed_16k"
CONFIG = "kanana_2_30b_a3b_s1"
TINY_MODEL = tiny.TINY_MODEL[CONFIG]["model"]
TINY_LIMITS = tiny.TINY_LIMITS[CONFIG]
SEQ = tiny.TINY_MODEL[CONFIG]["arguments"]["sequence_length"]
DRIVER = manifest.driver("train_resident_tokens_moe")
# The metrics that list every cell of their end-to-end metric, and so this one.
APPENDED = (
    "train.examples_per_s", "train.step_ms.p90", "setup.step_build_s",
    "setup.step_trace_lower_s", "setup.step_compile_s",
    "setup.init_state_programs", "setup.program_import_s",
)


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
        "hidden_size": 2048, "intermediate_size": 6144,
        "moe_intermediate_size": 768, "num_attention_heads": 32,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "qk_head_dim": 192, "v_head_dim": 128, "router_experts": 128,
        "num_experts_per_tok": 6, "n_shared_experts": 2,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "norm_topk_prob": True, "n_group": 1,
        "topk_group": 1, "rope_theta": 1000000, "rope_interleave": True,
        "rope_scaling": None, "q_lora_rank": None, "rms_norm_eps": 1e-06,
        "first_k_dense_replace": 1, "moe_layer_freq": 1,
        "tie_word_embeddings": False, "model_type": "deepseek_v3",
    }
    assert {k: model[k] for k in widths} == widths
    assert (model["num_hidden_layers"], model["n_routed_experts"],
            model["vocab_size"], model["first_expert"]) == (5, 16, 16032, 0)
    assert config["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 128, "vocab_size": 128256}
    assert [r.split()[0] for r in config["reduced"]] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert "8 chips" in config["deployment"]
    assert config["arguments"]["sequence_length"] == 16384
    # The catalog's keys sit at the top level as well, cut as `reduced` says.
    assert {k: config[k] for k in widths if k in config} == {
        k: v for k, v in widths.items() if k in config}
    ref = manifest.reference(CONFIG)
    assert ref._settings(config)["kinds"] == [("mla", "dense")] + 4 * [("mla", "moe")]
    shapes = jax.eval_shape(lambda k: ref.init_params(k, config), jax.random.PRNGKey(0))
    assert sum(int(np.prod(v.shape)) for v in shapes.values()) == 575_955_968


def test_every_model_key_is_read_by_the_constructor_or_said_not_to_be():
    """`constructor_arguments` drops every `model` key the constructor does
    not take by name: each such key of this file must be one `assumed` says
    the program does not read."""
    import importlib

    config = manifest.config(CONFIG)
    module_name, class_name = config["constructor"].rsplit(".", 1)
    cls = getattr(importlib.import_module(module_name), class_name)
    taken = set(inspect.signature(cls.__init__).parameters)
    dropped = sorted(set(config["model"]) - taken)
    said = " ".join(config["assumed"])
    assert dropped == ["head_dim", "max_position_embeddings", "model_type",
                       "num_experts", "num_key_value_heads"]
    for key in dropped:
        assert key in said, key
    arguments = DRIVER.constructor_arguments(config)
    assert arguments["rope_theta"] == 1000000 and arguments["n_routed_experts"] == 16
    assert arguments["sequence_length"] == 16384


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
        assert moved == pytest.approx(value, rel=5e-4, abs=1e-9), name


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
    import ast

    said = counters["said"]
    grown = ast.literal_eval(said[said.index("{"):said.index("}") + 1])
    a_step = ast.literal_eval(said[said.rindex("["):])
    assert len(a_step) == result["attempted"]
    assert grown["moe.routed_rows"] == sum(a_step) > 0
    assert 0 < grown["moe.max_expert_rows"] < grown["moe.routed_rows"]


@pytest.mark.parametrize("fault", ["control", "half_loss", "state_unchanged", "no_resets"])
def test_control_and_planted_faults_are_not_correct(fault):
    """The reference put in the program's place, computed one precision
    down or with one fault planted, against the sound reference. Resets
    left out also stop the positions from restarting at each document."""
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


def test_kernel_counts_against_hand_counts():
    config = tiny_config()
    ref = manifest.reference(CONFIG)
    rows = 2
    costs = ref.kernel_costs(config, rows, SEQ, 2)
    # One block of queries at this size: scores over all keys at width 24,
    # values at width 16, five layers.
    heads, qk, vdim = 4, 24, 16
    attention = 2 * rows * heads * SEQ * SEQ * (qk + vdim)
    assert costs["mla"]["forward_flops"] == 5 * attention
    assert costs["mla"]["step_flops"] == 3 * 5 * attention
    assert costs["mla"]["step_bytes"] == 3 * 5 * rows * SEQ * heads * (2 * qk + 2 * vdim + 1) * 2
    routed = ref.moe_costs(config, 100, 2)
    assert routed["step_flops"] == 100 * 3 * 2 * 64 * 32 * 3
    assert routed["step_bytes"] == 3 * (4 * 4 * 3 * 64 * 32 + 100 * (2 * 64 + 3 * 32)) * 2
    # The plain projections: five latent mixers (q 4 x 24, down to 32 + 8,
    # up to 4 x 32, o), layer 1's SwiGLU of 128, four shared SwiGLUs of 2 x
    # 32 and the head of 96 rows.
    d, positions = 64, rows * SEQ
    matrices = (
        5 * [(d, 4 * 24), (d, 32 + 8), (32, 4 * 32), (4 * 16, d)]
        + [(d, 128), (d, 128), (128, d)] + 4 * [(d, 64), (d, 64), (64, d)]
        + [(d, 96)]
    )
    plain = ref.projection_costs(config, rows, SEQ, 2)
    assert plain["step_flops"] == 3 * 2 * positions * sum(a * b for a, b in matrices)
    assert plain["step_bytes"] == 3 * 2 * sum(
        a * b + positions * (a + b) for a, b in matrices)
    whole = flops.count(
        lambda p, b: ref.loss_fn(p, b, jax.random.PRNGKey(0), config),
        flops.abstract(ref.init_params(jax.random.PRNGKey(0), config)),
        flops.abstract(_raw(1)),
    )["step_flops"]
    # The whole step is attention, the projections and the router.
    assert costs["mla"]["step_flops"] + plain["step_flops"] < whole < 1.05 * (
        costs["mla"]["step_flops"] + plain["step_flops"])


def test_kernel_counts_at_the_cells_size_by_hand():
    """At 16,384 positions: 2 x 32 heads x the causal blocks of 512 queries
    (a block's keys stop at its last query: S^2 / 2 and half a block more)
    x (192 + 128) a layer forward, five layers, three passes."""
    config = manifest.config(CONFIG)
    ref = manifest.reference(CONFIG)
    seq, block = 16384, 512
    causal_pairs = sum(block * (start + block) for start in range(0, seq, block))
    forward = 2 * 32 * causal_pairs * (192 + 128)
    costs = ref.kernel_costs(config, 1, seq, 2)
    assert costs["mla"]["forward_flops"] == 5 * forward
    assert costs["mla"]["step_flops"] == 15 * forward
    assert 2.83e12 < forward < 2.84e12


def test_the_cell_is_listed_with_its_configuration():
    benchmark = manifest.benchmark_json()
    (config,) = [c for c in benchmark["configs"] if c["name"] == CONFIG]
    (cell,) = [w for w in benchmark["workloads"] if w["name"] == CELL]
    assert benchmark["workloads"][-1] == cell
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "train_packed_16k", 1)
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["reduced"] == [r.split()[0] for r in manifest.config(CONFIG)["reduced"]]


def test_every_new_metric_has_its_file_and_reader():
    benchmark = manifest.benchmark_json()
    names = [m["name"] for m in benchmark["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(names) == sorted([
        "train_step.mla_ms_per_step.kanana", "kernels.mla_roofline.kanana",
        "kernels.projection_roofline.kanana", "train_step.moe_ms_per_step.kanana",
        "kernels.moe_roofline.kanana", "moe.imbalance.kanana",
        "moe.rows_per_token.kanana", "train_step.rope_ms_per_step",
        "train_step.pad_share.kanana", "setup.init_state_s.kanana",
    ])
    reported = {entry["name"]: data["reader"] for entry, data, _ in manifest.per_layer(CELL)}
    assert set(names) <= set(reported)
    assert reported["train_step.rope_ms_per_step"] == "rope_ms_per_step"
    # The Kimi cell's twins read by the same readers.
    assert (reported["train_step.pad_share.kanana"], reported["setup.init_state_s.kanana"]) == (
        "pad_share", "init_state")
    # The cell reports the accepted metrics with no list of cells, too, and
    # the five of set-up.
    assert {"train_step.mfu", "train_step.device_ms_per_step", "device.idle_share",
            "setup.step_build_s", "setup.program_import_s"} <= set(reported)
    for entry in benchmark["end_to_end"] + benchmark["per_layer"]:
        if entry["name"] in APPENDED:
            assert entry["workloads"][-1] == CELL, entry["name"]


NET = "jit(train_step)/jit(main)/_KimiLinearLMNet/layer_2/mixer"
BACK = "jit(train_step)/jit(main)/transpose(jvp(_KimiLinearLMNet))/layer_2/mixer"
WITH_ROPE = {
    1: f"{NET}/mla/rope/mul",
    2: f"{BACK}/mla/rope/concatenate",
    3: f"{NET}/attention/pallas_call",
    4: f"{NET}/mla/kv_up/kv_b/dot_general",
}
# A program without positions (the parent, the Kimi cell's).
WITHOUT_ROPE = {1: f"{NET}/mul", 2: f"{BACK}/concatenate", 3: WITH_ROPE[3], 4: WITH_ROPE[4]}


def _read(metric, run):
    readers = {entry["name"]: reader for entry, _, reader in manifest.per_layer(CELL)}
    return readers[metric].read(run)


# Operations 1 to 4 run 200, 100, 60 and 300 us in two traced steps, and 50 us
# of a second operation 1 lie inside the window (test_scope_readers._run).
@pytest.mark.parametrize("labels,metric,milliseconds", [
    (WITH_ROPE, "train_step.rope_ms_per_step", (200 + 100 + 50) / 2 / 1e3),
    (WITH_ROPE, "train_step.mla_ms_per_step.kanana", 60 / 2 / 1e3),
    (WITHOUT_ROPE, "train_step.mla_ms_per_step.kanana", 60 / 2 / 1e3),
    (WITHOUT_ROPE, "train_step.rope_ms_per_step", None),
])
def test_the_rotation_is_billed_to_its_metric_alone(tmp_path, labels, metric, milliseconds):
    from test_scope_readers import _run

    read = _read(metric, _run(tmp_path, labels))
    assert read == (None if milliseconds is None else pytest.approx(milliseconds))


def test_the_rope_reader_finds_nothing_without_a_trace(tmp_path):
    from test_scope_readers import _run

    assert _read("train_step.rope_ms_per_step", _run(tmp_path, WITH_ROPE, traced=False)) is None
