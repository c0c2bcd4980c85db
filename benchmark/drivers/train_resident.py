"""Driver `train_resident`: the window drives `CompiledModel.train_step`
(donated state) in a plain loop on one seeded batch that lives on the
device. Host input and infeed are bypassed; the train step and its kernels
do all the work.

Cell file: {"driver": "train_resident", "batch": <per chip>,
            "warmup_steps": n, "traffic": {"kind": "resident_batch", ...}}
"""

from __future__ import annotations

import time

import jax

import program_side
import traffic
from window import Window


def run(run):
    from tensor2robot_tpu.train.train_eval import CompiledModel

    cell, config, ref = run.cell, run.config, run.reference
    batch_size = cell["batch"] * len(run.devices)
    seed = run.seed % (2**31)

    marks = [("process start", run.process_start), ("imports", time.perf_counter())]
    weights = jax.jit(lambda key: ref.init_params(key, config))(
        jax.random.PRNGKey(seed)
    )
    model = program_side.build_model(config, weights)
    compiled = CompiledModel(model, donate_state=True)
    raw = traffic.resident_batch(model, batch_size, run.seed, cell["traffic"])
    batch = compiled.shard_batch(program_side.as_program_batch(raw))
    jax.block_until_ready((weights, batch))
    marks.append(("weights and batch", time.perf_counter()))
    state = compiled.init_state(jax.random.PRNGKey(seed), batch)
    jax.block_until_ready(state)
    marks.append(("init_state", time.perf_counter()))
    base_key = jax.random.PRNGKey((seed + 1) % (2**31))

    readings = program_side.StepReadings(ref.optimizer(config))
    readings.begin(state)
    window = Window(
        batch=batch_size, seconds=run.seconds,
        warmup_steps=cell["warmup_steps"], reporter=run.reporter,
        readings=readings, trace_dir=run.trace_dir,
        trace_seconds=cell.get("trace_seconds", 3.0),
    )
    while not window.expired():
        window.before_step()
        state, metrics = compiled.train_step(state, batch, base_key)
        window.after_step(state, metrics)
        if window.steps_done == 1:
            jax.block_until_ready(state)
            marks.append(("first step", time.perf_counter()))
        if window.opened_at is not None and run.setup_s is None:
            run.setup_s = window.opened_at - run.process_start
            marks.append(("warm-up steps", window.opened_at))
            run.reporter.say("set-up: " + ", ".join(
                f"{name} {b - a:.2f} s"
                for (_, a), (name, b) in zip(marks, marks[1:])
            ))
    window.close()

    run.window = window
    run.program_readings = readings.result()
    run.check_inputs = (weights, [raw] * 3, base_key)
    # The state, the batch and the compiled step die with this frame:
    # the reference then has the chip to itself.
