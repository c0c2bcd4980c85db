"""The granite-4.0-h-micro first-period configuration at a tiny preset on
the CPU (presets/granite_4_0_h_micro_p1.json: tiny.py is not edited): the
plain reference against the program in float32, the reference's
layer-by-layer step against its whole step, the driver through
`run.run_cell`, the control and each planted fault coming out not correct,
and the kernels' counts against hand counts.

The preset keeps the real cell and configuration files and changes sizes
only. It runs the bare float32 model, so its stated precision is float32
and the control is bfloat16. Limits from CPU readings at these sizes on
three seeds (PERF.md section 2's rules): sound runs read 4e-7 or less on
every number; the bfloat16 control reads 4.4e-4 to 7.0e-4 on the gradient
and 7.5e-4 to 1.3e-3 on the change (its losses, 2e-7 to 3e-6, do not part
it from a sound run: at the seeded start the model is near uniform); half
the loss left out reads 0.65 on the gradient, the state unchanged 1.0 on
the change, the resets left out 0.19 on the gradient (and 1e-6 on the
losses). The gradient's and the change's limits lie between 4e-7 and
4.4e-4; the losses' limit is there for gross faults only."""

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
import scope_time
import tiny

CELL = "granite_4_0_h_micro_p1.train_packed_8k"
CONFIG = "granite_4_0_h_micro_p1"
# The preset is a file (presets/<config>.json), put into tiny.py's dicts
# by benchmark/conftest.py whichever test files are run.
TINY_MODEL = tiny.TINY_MODEL[CONFIG]["model"]
TINY_LIMITS = tiny.TINY_LIMITS[CONFIG]
SEQ = tiny.TINY_MODEL[CONFIG]["arguments"]["sequence_length"]


def tiny_config():
    return tiny.tiny_config(CONFIG)


def tiny_cell(rows=2):
    return tiny.tiny_cell(
        CELL, batch=rows, median_tokens=12, sigma=1.0, min_tokens=4
    )


def _raw(seed, rows=2):
    driver = manifest.driver("train_resident_tokens")
    packed, documents = driver.packed_documents(
        seed, rows, SEQ, TINY_MODEL["vocab_size"], tiny_cell()["traffic"]
    )
    assert documents >= 3
    return {
        "features": {k: jnp.asarray(packed[k]) for k in ("tokens", "segment_ids")},
        "labels": {k: jnp.asarray(packed[k]) for k in ("targets", "loss_mask")},
    }


def _program_readings(config, weights, raw, key, alter=None):
    from tensor2robot_tpu.train.train_eval import CompiledModel

    driver = manifest.driver("train_resident_tokens")
    model = program_side.build_model(
        dict(config, arguments=driver.constructor_arguments(config)), weights
    )
    compiled = CompiledModel(model, donate_state=False)
    batch = compiled.shard_batch(program_side.as_program_batch(raw))
    state = compiled.init_state(jax.random.PRNGKey(0), batch)
    readings = driver.HostStepReadings(
        manifest.reference(CONFIG).optimizer(config),
        {k: np.asarray(v) for k, v in weights.items()},
    )
    for index in range(compare.STEPS):
        state, metrics = compiled.train_step(state, batch, key)
        readings.after_step(index + 1, state, metrics)
    return readings.result()


def test_packing_follows_the_contract():
    raw = _raw(2_147_483_659)
    seg = np.asarray(raw["features"]["segment_ids"])
    tokens = np.asarray(raw["features"]["tokens"])
    targets = np.asarray(raw["labels"]["targets"])
    mask = np.asarray(raw["labels"]["loss_mask"])
    for row in range(seg.shape[0]):
        ends = np.flatnonzero(np.diff(seg[row]) != 0)
        assert np.all(mask[row, ends] == 0)            # a document's last position
        assert np.all(mask[row, seg[row] == 0] == 0)   # padding
        inside = mask[row] == 1
        assert np.all(targets[row, :-1][inside[:-1]] == tokens[row, 1:][inside[:-1]])
        documents = seg[row][seg[row] > 0]
        assert np.all(np.diff(documents) >= 0) and documents[0] == 1


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
        assert moved == pytest.approx(value, rel=1e-4, abs=1e-9), name


def test_driver_gives_a_well_formed_result():
    result = bench_run.run_cell(
        tiny_cell(), tiny_config(), tiny.args(seed=2_147_483_659, seconds=1.0),
        jax.devices()[:1], report.Reporter("test"),
    )
    assert set(result) == {
        "correct", "attempted", "failed", "metrics", "device", "compared"
    }
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {m["name"] for m in manifest.end_to_end(CELL)}
    assert {"train.examples_per_s", "train.step_ms.p90", "setup_s"} <= set(result["metrics"])


DRIVER = manifest.driver("train_resident_tokens")


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


@pytest.mark.parametrize("fault", ["half_loss", "state_unchanged", "no_resets"])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    from tensor2robot_tpu.train import train_eval

    class Broken(train_eval.CompiledModel):
        def __init__(self, *args, **kwargs):
            kwargs["donate_state"] = False
            super().__init__(*args, **kwargs)
            real_step = self.train_step

            def step(state, batch, rng):
                raw = {g: dict(batch[g].items()) for g in ("features", "labels")}
                if fault in DRIVER.BATCH_FAULTS:
                    raw = DRIVER.BATCH_FAULTS[fault](raw)
                new_state, metrics = real_step(
                    state, program_side.as_program_batch(raw), rng
                )
                return (state if fault == "state_unchanged" else new_state), metrics

            self.train_step = step

    monkeypatch.setattr(train_eval, "CompiledModel", Broken)
    result = bench_run.run_cell(
        tiny_cell(), tiny_config(), tiny.args(seed=5, seconds=0.5),
        jax.devices()[:1], report.Reporter("test"),
    )
    assert result["correct"] is False, result["compared"]


def test_readings_script_reads_a_seed_with_control_and_faults():
    """benchmark/readings_tokens.py as the chip runs it, at the tiny
    preset: the layer-by-layer reference in the control's precision too."""
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


def test_the_median_gradient_parts_half_the_loss_on_the_chip_and_loss1_cannot():
    """The chip's readings (data/, PERF.md section 2): `grad_norm_median`
    has a limit because half the loss left out reads far over every sound
    run; `loss1` has none because its sound runs, on seeds whose packing
    leaves few positions with a loss, read over that fault's smallest."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "data", f"readings.{CELL}.jsonl")
    with open(path) as f:
        lines = [json.loads(line) for line in f if line.strip()]
    limits = manifest.cell(CELL)["limits"]
    halved = [line["faults"]["half_loss"] for line in lines if "faults" in line]
    assert len(halved) >= 3
    sound = max(line["program"]["grad_norm_median"] for line in lines)
    assert 3 * sound < limits["grad_norm_median"] < min(
        numbers["grad_norm_median"] for numbers in halved) / 3
    assert limits["loss1"] is None
    assert max(line["program"]["loss1"] for line in lines) > min(
        numbers["loss1"] for numbers in halved)


def test_scan_and_attention_counts_against_hand_counts():
    config = tiny_config()
    ref = manifest.reference(CONFIG)
    rows, chunk = 2, TINY_MODEL["mamba_chunk_size"]
    heads, dim, state = 4, 32, 16
    chunks = SEQ // chunk
    costs = ref.kernel_costs(config, rows, SEQ, 2)
    # Forward multiply-accumulates of one scan: C B^T, (decay * CB) x, the
    # chunk states, the chunk recurrence, the entering state's part.
    macs = rows * (
        chunks * chunk * chunk * state
        + chunks * heads * chunk * chunk * dim
        + chunks * chunk * heads * dim * state
        + heads * (chunks + 1) * (chunks + 1) * dim * state
        + chunks * chunk * heads * dim * state
    )
    assert costs["ssd"]["forward_flops"] == 2 * macs * 2      # two Mamba-2 layers
    assert costs["ssd"]["step_flops"] == 3 * costs["ssd"]["forward_flops"]
    # One block of queries at this size: scores and values over all keys.
    q_heads, head_dim = 4, 16
    attention = 2 * rows * q_heads * SEQ * SEQ * head_dim * 2
    assert costs["attention"]["forward_flops"] == attention
    assert costs["attention"]["step_flops"] == 3 * attention
    # Its bytes are a tiled kernel's: q and o (4 heads), k and v (2 heads)
    # and a log-sum-exp a query head, at bf16, once a pass; no logits.
    kv_heads = 2
    assert costs["attention"]["step_bytes"] == 3 * 2 * rows * SEQ * (
        2 * q_heads * head_dim + 2 * kv_heads * head_dim + q_heads)
    twice = ref.kernel_costs(config, rows, 2 * SEQ, 2)["attention"]
    assert twice["step_bytes"] == 2 * costs["attention"]["step_bytes"]
    assert twice["step_flops"] > 2 * costs["attention"]["step_flops"]
    whole = flops.count(
        lambda p, b: ref.loss_fn(p, b, jax.random.PRNGKey(0), config),
        flops.abstract(ref.init_params(jax.random.PRNGKey(0), config)),
        flops.abstract(_raw(1)),
    )
    assert whole["step_flops"] > costs["ssd"]["step_flops"] + costs["attention"]["step_flops"]


@pytest.mark.parametrize("label,scope", [
    ("jit(train_step)/jit(main)/transpose(jvp(_HybridLMNet))/layer_0/mixer/mamba2/ssd/dot_general", "mamba2/ssd"),
    ("jit(train_step)/jit(main)/_HybridLMNet/layer_5/mixer/attention/dot_general", "attention"),
    ("jit(train_step)/jit(main)/_HybridLMNet/layer_5/mixer/attention_proj/qkv/dot_general", "attention_proj"),
    ("jit(train_step)/jit(main)/_HybridLMNet/layer_5/mlp/mlp/gate/dot_general:", "mlp"),
    ("jit(train_step)/jit(main)/grasping44/conv2/conv_general_dilated", None),
])
def test_scope_of_an_op_label(label, scope):
    assert scope_time.scope_of(label) == scope
