"""Each plain reference against the program at a tiny size in float32 (the
bare model, no bf16 wrapper): three steps through `CompiledModel.train_step`
against three plain steps, from the same seeded weights and batch."""

import jax
import pytest

import compare
import manifest
import program_side
import tiny
import traffic


@pytest.mark.parametrize("name,cell_name,batch", [
    ("critic_c64", "critic_c64.train_resident", 8),
    ("grasp2vec_r50", "grasp2vec_r50.train_resident", 4),
])
def test_program_follows_the_reference_in_float32(name, cell_name, batch):
    from tensor2robot_tpu.train.train_eval import CompiledModel

    config = tiny.tiny_config(name)
    # Grasp2Vec's cell is parked (PERF.md section 7): its files stay, and so
    # does this check of its reference.
    cell = tiny.tiny_cell(cell_name, batch=batch, listed=False)
    ref = manifest.reference(name)
    weights = jax.jit(lambda k: ref.init_params(k, config))(jax.random.PRNGKey(5))
    model = program_side.build_model(config, weights, wrap=False)
    compiled = CompiledModel(model, donate_state=False)
    raw = traffic.resident_batch(model, batch, 7, cell["traffic"])
    device_batch = compiled.shard_batch(program_side.as_program_batch(raw))
    state = compiled.init_state(jax.random.PRNGKey(0), device_batch)
    key = jax.random.PRNGKey(11)
    readings = program_side.StepReadings(ref.optimizer(config))
    readings.begin(state)
    for index in range(compare.STEPS):
        state, metrics = compiled.train_step(state, device_batch, key)
        readings.after_step(index + 1, state, metrics)
    expected = compare.reference_readings(ref, config, weights, [raw] * 3, key)
    numbers, _ = compare.compared_numbers(readings.result(), expected)
    # One forward and one backward agree to float32 rounding; the later
    # steps of Grasp2Vec under Adam amplify it (PERF.md section 2).
    assert numbers["loss1"] < 1e-5, numbers
    assert numbers["grad_norm"] < 1e-2, numbers
    if name == "critic_c64":
        assert numbers["loss3"] < 1e-5 and numbers["update_norm"] < 1e-4, numbers
