"""Driver `train_fed`: the window drives `train_eval_model`, resolved through
the config layer as `bin/run_t2r_trainer` does, with
`DefaultRecordInputGenerator` over seeded `tf.Example` jpeg records. What a
learner pays for: record read, parse, jpeg decode, batching, H2D, and the
same train step as the resident cell.

The benchmark puts two things of its own around the program:

  * `TimedGenerator`, around the configured input generator: its iterator
    times every `next()` (called inline by `infeed.device_prefetch` in the
    train loop's thread, so this is the time a step waits for data), keeps
    the first batches for the comparison, and ends the data when
    `--seconds` are up;
  * a `HookBuilder` whose hook calls the window before and after each step.

No eval, no exporters, a checkpoint interval beyond the window: the window
holds steps only. (When the data ends the trainer saves one checkpoint;
that is after the window.)

Cell file: {"driver": "train_fed", "batch": <per chip>, "warmup_steps": n,
            "traffic": {"kind": "jpeg_records", "records": N, ...},
            "trainer": {<train_eval_model parameter>: value, ...}}
"""

from __future__ import annotations

import os
import time

import jax
import numpy as np

import compare
import program_side
import traffic
from window import Window

MAX_STEPS = 10**9


class TimedIterator:
    def __init__(self, inner, window, keep):
        self._inner = inner
        self._window = window
        self._keep = keep
        self.first_batches = []
        self.calls = 0          # next() calls inside the window
        self.seconds = 0.0      # and the time inside them
        self.exhausted = False

    def __iter__(self):
        return self

    def __next__(self):
        window = self._window
        if window.expired():
            # The steps still in the prefetch buffer run; the hook stamps
            # them as they complete, so that the window ends at the last
            # completion and not after the trainer's closing checkpoint.
            if not self.exhausted:
                self.exhausted = True
                close = getattr(self._inner, "close", None)
                if close is not None:
                    close()  # stops the dataset's prefetch thread
            raise StopIteration
        started = time.perf_counter()
        with window.span("bench.host_input.next"):
            batch = next(self._inner)
        spent = time.perf_counter() - started
        if window.opened_at is not None:
            self.calls += 1
            self.seconds += spent
        if len(self.first_batches) < self._keep:
            self.first_batches.append(
                jax.tree_util.tree_map(np.array, batch)
            )
        return batch


class TimedGenerator:
    """The configured input generator with a timed iterator; everything
    else is the generator's own."""

    def __init__(self, inner, window, keep):
        self._inner = inner
        self._window = window
        self._keep = keep
        self.iterator = None

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def create_dataset(self, mode):
        self.iterator = TimedIterator(
            iter(self._inner.create_dataset(mode)), self._window, self._keep
        )
        return self.iterator


def _hook_builder(window, readings, run, timed):
    from tensor2robot_tpu.hooks.hook_builder import Hook, HookBuilder

    class WindowHook(HookBuilder, Hook):
        def create_hooks(self, t2r_model, trainer=None):
            return [self]

        def on_train_begin(self, ctx):
            readings.begin(ctx.state)

        def before_step(self, ctx):
            window.before_step()

        def after_step(self, ctx):
            window.after_step(ctx.state, ctx.device_metrics)
            if timed.iterator.exhausted:
                window.drain()
            if window.opened_at is not None and run.setup_s is None:
                run.setup_s = window.opened_at - run.process_start

    return WindowHook()


def run(run):
    import tensor2robot_tpu.config.defaults  # noqa: F401 - registers the surface
    from tensor2robot_tpu import config as cfg

    cell, config, ref = run.cell, run.config, run.reference
    batch_size = cell["batch"] * len(run.devices)
    seed = run.seed % (2**31)

    weights = jax.jit(lambda key: ref.init_params(key, config))(
        jax.random.PRNGKey(seed)
    )
    # train_eval_model wraps the model for the TPU itself.
    model = program_side.build_model(config, weights, wrap=False)
    records_path = os.path.join(run.work_dir, "train.tfrecord")
    started = time.perf_counter()
    from tensor2robot_tpu.train.train_eval import maybe_wrap_for_tpu

    records, image_key = traffic.jpeg_records(
        maybe_wrap_for_tpu(model), run.seed, cell["traffic"], records_path
    )
    run.reporter.say(
        f"records: {len(records)} written in "
        f"{time.perf_counter() - started:.2f} s, "
        f"{os.path.getsize(records_path) / 1e6:.1f} MB"
    )

    readings = program_side.StepReadings(ref.optimizer(config))
    window = Window(
        batch=batch_size, seconds=run.seconds,
        warmup_steps=cell["warmup_steps"], reporter=run.reporter,
        readings=readings, trace_dir=run.trace_dir,
        trace_seconds=cell.get("trace_seconds", 3.0),
    )

    trainer = {
        "model_dir": os.path.join(run.work_dir, "model"),
        "max_train_steps": MAX_STEPS,
        "save_checkpoints_steps": MAX_STEPS,
        "log_every_steps": MAX_STEPS,
        "eval_steps": None,
        "seed": seed,
        **cell.get("trainer", {}),
    }
    bindings = [
        f"train_eval_model.{key} = {value!r}" for key, value in trainer.items()
    ] + [
        f"train/DefaultRecordInputGenerator.file_patterns = {records_path!r}",
        f"train/DefaultRecordInputGenerator.batch_size = {batch_size}",
        f"train/DefaultRecordInputGenerator.seed = {seed}",
    ]
    cfg.parse_config_files_and_bindings([], bindings)
    with cfg.config_scope("train"):
        generator = cfg.get_configurable("DefaultRecordInputGenerator")()
    timed = TimedGenerator(generator, window, keep=compare.STEPS)
    train_eval_model = cfg.get_configurable("train_eval_model")
    # The trainer's default mesh spans every device jax shows. Where that
    # is more than the cell was handed, it gets a mesh over those alone.
    shown = len(jax.devices())
    mesh = {}
    if shown != len(run.devices):
        from tensor2robot_tpu.parallel.mesh import make_mesh

        mesh["mesh"] = make_mesh(devices=run.devices)
    run.reporter.say(
        f"mesh: data-parallel over {len(run.devices)} devices, "
        f"{cell['batch']} rows each, {batch_size} a step (jax shows {shown}: "
        + ("a mesh over the cell's own)" if mesh else "the trainer's default mesh)")
    )
    train_eval_model(
        t2r_model=model,
        input_generator_train=timed,
        hook_builders=[_hook_builder(window, readings, run, timed)],
        **mesh,
    )
    window.close()

    run.window = window
    run.program_readings = readings.result()
    run.counters["host_input.next_calls"] = timed.iterator.calls
    run.counters["host_input.next_seconds"] = timed.iterator.seconds
    from tensor2robot_tpu.data.wire import get_decode_cache

    cache = get_decode_cache()
    if cache is not None:
        stats = cache.stats()
        run.counters["decode_cache.hits"] = stats["hits"]
        run.counters["decode_cache.misses"] = stats["misses"]
    # train_eval_model: rng_init, rng_train = split(PRNGKey(seed)).
    base_key = jax.random.split(jax.random.PRNGKey(trainer["seed"]))[1]
    first = timed.iterator.first_batches

    def check_inputs():
        batches, numbers = traffic.reference_batches(
            first, records, image_key, cell["traffic"]
        )
        run.extra_numbers.update(numbers)
        return weights, batches, base_key

    run.check_inputs = check_inputs
