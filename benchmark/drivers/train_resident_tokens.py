"""Driver `train_resident_tokens`: `train_resident`'s closed loop on
`CompiledModel.train_step` (donated state, one seeded batch that lives on
the device) for a token-sequence model, with its own traffic: documents
packed into sequences.

Cell file: {"driver": "train_resident_tokens", "batch": <sequences per chip>,
            "warmup_steps": n,
            "traffic": {"kind": "packed_documents", "median_tokens": 512,
                        "sigma": 1.25, "min_tokens": 16}}

Traffic, all from `--seed`: document lengths are log-normal (median
`median_tokens`, `sigma`), clipped to `min_tokens` .. the sequence length,
and packed first-fit in arrival order (a document goes into the first
sequence with room) until one fits nowhere; the rest is padding. Token ids
are uniform over the vocabulary held. `targets` is the next token of the
same document; a document's last position and the padding have `loss_mask`
0; documents have segment ids 1, 2, ..; padding is segment 0.

The comparison: the float32 state of this configuration (16 bytes a
parameter) is most of a chip, so `compare.py`'s whole-step jit cannot hold
the reference. The reference offers the same step layer by layer with its
state on the host (`streaming_step`); this driver puts it where
`compare._reference_step` looks first, under the key that function
computes, and hands the seeded weights over as host arrays.
"""

from __future__ import annotations

import inspect
import time

import jax
import numpy as np

import compare
import program_side
from window import Window


def packed_documents(seed, rows, seq, vocab, traffic):
    """({"tokens", "segment_ids", "targets", "loss_mask"}: [rows, seq] numpy,
    number of documents)."""
    rng = np.random.default_rng(seed)
    tokens = np.zeros((rows, seq), np.int32)
    segment_ids = np.zeros((rows, seq), np.int32)
    targets = np.zeros((rows, seq), np.int32)
    loss_mask = np.zeros((rows, seq), np.float32)
    used = [0] * rows
    counts = [0] * rows
    while True:
        length = int(np.clip(
            round(rng.lognormal(np.log(traffic["median_tokens"]), traffic["sigma"])),
            traffic["min_tokens"], seq,
        ))
        row = next((r for r in range(rows) if used[r] + length <= seq), None)
        if row is None:
            break
        ids = rng.integers(0, vocab, size=length, dtype=np.int32)
        span = slice(used[row], used[row] + length)
        counts[row] += 1
        tokens[row, span] = ids
        segment_ids[row, span] = counts[row]
        targets[row, span] = np.append(ids[1:], 0)
        loss_mask[row, span] = np.append(np.ones(length - 1, np.float32), 0.0)
        used[row] += length
    batch = {"tokens": tokens, "segment_ids": segment_ids,
             "targets": targets, "loss_mask": loss_mask}
    return batch, sum(counts)


class HostStepReadings(program_side.StepReadings):
    """`StepReadings` with the weights the steps started from on the host
    (the warm start made the program's parameters equal to them): the
    parameters' change over the three steps is read leaf by leaf, each
    start leaf on the device only while its norm is taken. That waits for
    the third warm-up step; it is set-up, not the window."""

    def __init__(self, optimizer_spec, start):
        super().__init__(optimizer_spec)
        self._start = start
        self._leaf_delta = jax.jit(
            lambda new, old: jax.numpy.sqrt(jax.numpy.sum(jax.numpy.square(new - old)))
        )

    def after_step(self, steps_done, state, metrics):
        if steps_done != compare.STEPS:
            return super().after_step(steps_done, state, metrics)
        self._losses.append(metrics["loss"])
        self._update_norms = {
            name: float(self._leaf_delta(leaf, self._start[name]))
            for name, leaf in program_side.flatten(state.params).items()
        }


def half_the_loss(raw):
    """Planted fault: the later half of each sequence's loss left out."""
    mask = raw["labels"]["loss_mask"]
    kept = jax.numpy.cumsum(mask, axis=1) <= jax.numpy.sum(mask, axis=1, keepdims=True) / 2
    return {**raw, "labels": {**raw["labels"], "loss_mask": mask * kept}}


def no_resets(raw):
    """Planted fault: document resets left out (every document of a
    sequence is one segment; targets and loss mask as they were)."""
    ids = raw["features"]["segment_ids"]
    return {**raw, "features": {**raw["features"],
                                "segment_ids": jax.numpy.minimum(ids, 1)}}


#: Faults planted in the batch the faulty side sees; `state_unchanged` is
#: compare.py's own.
BATCH_FAULTS = {"half_loss": half_the_loss, "no_resets": no_resets}


def constructor_arguments(config):
    """The constructor's keyword arguments: the configuration's `model`
    keys that it takes by name, the optimizer's numbers and `arguments`."""
    module_name, class_name = config["constructor"].rsplit(".", 1)
    import importlib

    cls = getattr(importlib.import_module(module_name), class_name)
    accepted = set(inspect.signature(cls.__init__).parameters)
    spec = config["optimizer"]
    return {
        **{k: v for k, v in config["model"].items() if k in accepted},
        "learning_rate": spec["learning_rate"], "adam_b1": spec["b1"],
        "adam_b2": spec["b2"], "adam_eps": spec["eps"],
        **config.get("arguments", {}),
    }


def install_streaming_reference(ref, config, quant=None):
    """Puts the reference's layer-by-layer step where
    `compare._reference_step` finds a configuration's step, under that
    function's own key, and checks that it is found there: under another
    key `compare.py` would build the whole step, which no chip holds."""
    key = (ref.__name__, repr(sorted(config["model"].items())), quant)
    if key not in compare._STEP_CACHE:
        compare._STEP_CACHE[key] = ref.streaming_step(
            config, compare.quantizer(quant)
        )
    if compare._reference_step(ref, config, quant) is not compare._STEP_CACHE[key]:
        raise RuntimeError(
            "compare._reference_step no longer looks under the key "
            "install_streaming_reference writes"
        )


def run(run):
    # First of all, so that a program without the model fails at once.
    from tensor2robot_tpu.train import train_eval
    from tensor2robot_tpu.utils import tracing

    cell, ref = run.cell, run.reference
    config = dict(run.config, arguments=constructor_arguments(run.config))
    rows = cell["batch"] * len(run.devices)
    seq = config["arguments"]["sequence_length"]
    seed = run.seed % (2**31)

    marks = [("process start", run.process_start), ("imports", time.perf_counter())]
    # The seeded weights live on the host: the program's warm start copies
    # them in, and the comparison reads them again once the window has
    # closed. A second copy on the device would not fit beside the state.
    weights = {
        name: np.asarray(value) for name, value in jax.jit(
            lambda key: ref.init_params(key, run.config)
        )(jax.random.PRNGKey(seed)).items()
    }
    model = program_side.build_model(config, weights)
    compiled = train_eval.CompiledModel(model, donate_state=True)
    packed, documents = packed_documents(
        run.seed, rows, seq, config["model"]["vocab_size"], cell["traffic"]
    )
    pad_share = float(np.mean(packed["segment_ids"] == 0))
    run.reporter.say(
        f"packing: {documents} documents in {rows} x {seq} positions, "
        f"{int(packed['loss_mask'].sum())} with a loss, pad_share "
        f"{100 * pad_share:.2f}%"
    )
    raw = {
        "features": {k: jax.numpy.asarray(packed[k]) for k in ("tokens", "segment_ids")},
        "labels": {k: jax.numpy.asarray(packed[k]) for k in ("targets", "loss_mask")},
    }
    batch = compiled.shard_batch(program_side.as_program_batch(raw))
    jax.block_until_ready(batch)
    marks.append(("weights and batch", time.perf_counter()))
    state = compiled.init_state(jax.random.PRNGKey(seed), batch)
    jax.block_until_ready(state)
    marks.append(("init_state", time.perf_counter()))
    base_key = jax.random.PRNGKey((seed + 1) % (2**31))

    readings = HostStepReadings(ref.optimizer(run.config), weights)
    window = Window(
        batch=rows, seconds=run.seconds,
        warmup_steps=cell["warmup_steps"], reporter=run.reporter,
        readings=readings, trace_dir=run.trace_dir,
        trace_seconds=cell.get("trace_seconds", 3.0),
    )
    metrics = None
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

    # The program's own token counters. The batch is resident, so every
    # step of the window counts what the last one did: its counts times
    # the window's steps go through the program's functions once, here,
    # where `train_eval_model` would add them a step and read them at a log.
    results = window.results()
    counts = jax.device_get(train_eval.add_token_counts(None, metrics))
    before = tracing.counters()
    record = train_eval.token_log_record(
        {name: int(value) * results["steps"] for name, value in counts.items()},
        results["window_s"],
    )
    after = tracing.counters()
    run.counters = {
        name: after.get(name, 0) - before.get(name, 0)
        for name in ("train.tokens", "train.pad_tokens")
    }
    run.reporter.say(
        f"program's token record: {record}; window tokens {run.counters}"
    )

    run.window = window
    run.program_readings = readings.result()
    install_streaming_reference(ref, run.config)
    run.check_inputs = (weights, [raw] * 3, base_key)
    # The state, the batch and the compiled step die with this frame:
    # the reference then has the chip to itself.
