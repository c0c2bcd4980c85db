"""`correct` has to come out false: for the control (the reference put in
the program's place, in the precision below the configuration's) and for a
run whose timed path is broken underneath. Tiny float32 presets on the CPU
with their own limits (tiny.py); test_chip_readings.py holds the cells' own
limits against the readings taken on the chip."""

import jax
import pytest

import compare
import manifest
import program_side
import report
import run as bench_run
import tiny
import traffic

CELLS = [w["name"] for w in manifest.benchmark_json()["workloads"]]
RESIDENT = [name for name in CELLS if name.endswith("train_resident")]


def _batch(cell_name):
    return 4 if "grasp2vec" in cell_name else 8


@pytest.mark.parametrize("cell_name", RESIDENT)
def test_control_is_not_correct(cell_name):
    cell = tiny.tiny_cell(cell_name, batch=_batch(cell_name))
    config = tiny.tiny_config(cell["config"])
    ref = manifest.reference(cell["config"])
    weights = jax.jit(lambda k: ref.init_params(k, config))(jax.random.PRNGKey(9))
    model = program_side.build_model(config, weights)
    raw = traffic.resident_batch(model, cell["batch"], 13, cell["traffic"])
    key = jax.random.PRNGKey(4)
    expected = compare.reference_readings(ref, config, weights, [raw] * 3, key)
    own, _ = compare.compared_numbers(expected, expected)
    assert compare.judge(own, cell["limits"])[0]
    for quant in config["control"]:
        control = compare.reference_readings(
            ref, config, weights, [raw] * 3, key, quant=quant
        )
        numbers, _ = compare.compared_numbers(control, expected)
        correct, shown = compare.judge(numbers, cell["limits"])
        assert not correct, shown


def _broken_compiled_model(fault):
    from tensor2robot_tpu.train import train_eval

    class Broken(train_eval.CompiledModel):
        def __init__(self, *args, **kwargs):
            kwargs["donate_state"] = False
            super().__init__(*args, **kwargs)
            real_step = self.train_step

            def step(state, batch, rng):
                if fault == "half_batch":
                    batch = jax.tree_util.tree_map(
                        lambda x: x[: len(x) // 2], batch
                    )
                new_state, metrics = real_step(state, batch, rng)
                if fault == "state_unchanged":
                    return state, metrics
                return new_state, metrics

            self.train_step = step

    return Broken


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
@pytest.mark.parametrize("cell_name", CELLS)
def test_a_broken_timed_path_is_not_correct(cell_name, fault, monkeypatch):
    from tensor2robot_tpu.train import train_eval

    monkeypatch.setattr(
        train_eval, "CompiledModel", _broken_compiled_model(fault)
    )
    cell = tiny.tiny_cell(cell_name, batch=_batch(cell_name))
    config = tiny.tiny_config(cell["config"])
    result = bench_run.run_cell(
        cell, config, tiny.args(seed=2_147_483_777, seconds=0.5),
        jax.devices()[:1], report.Reporter(f"test {fault}"),
    )
    assert result["correct"] is False, result["compared"]
