"""chip_smoke.py: the quickest proof that the system still starts on the chip.

One process drives the QT-Opt critic's main path once, through the entry
points a user calls, at the full width of the reference model (512x640
jpeg -> 472x472 crop, batch 64, tower (6,6,3) x 64, bf16 via the TPU model
wrapper), with seeded random weights and a handful of steps:

  records  seeded tf.Example records written with the repo's own encoder
  train    `train_eval_model` resolved through the config layer exactly as
           bin/run_t2r_trainer does: log line, checkpoint, eval, export
  step     the trained step's placement and lowered text: every device
           holds a shard, the pool VJP is the native one, and
           the all-reduce is in the compiled step when devices > 1
  serve    ExportedSavedModelPredictor restore (every bucket from an AOT
           executable), JitCEMPolicy action selection, PolicyServer
           replies over its bucket ladder
  parity   served Q-values against a direct CompiledModel forward on the
           same checkpoint, as a share of how far the Q-values move with
           the image and with the action

Any stage that raises ends the run non-zero; nothing is caught and carried
past. The last line of stdout is one JSON object naming the device as jax
reports it. The timings printed are smoke timings, not benchmark results.

    python chip_smoke.py                                  # on the chip
    JAX_PLATFORMS=cpu python chip_smoke.py --preset tiny  # CPU, tiny model

The full-width preset refuses every platform but `tpu`. The tiny preset is
for the CPU test suite: it refuses every platform but an explicitly
requested `cpu` (JAX_PLATFORMS=cpu) and says `cpu` in every line it prints. One process uses the chip; the
input pipeline stays on its default thread backend (no worker processes).
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import statistics
import sys
import tempfile
import time

PRESETS = {
    # The reference's constants (BASELINE.md). Cut: steps and records only
    # (and BATCH_NORM_MOMENTUM below, which costs nothing to compute).
    "chip": dict(
        image_size=(472, 472), batch_size=64, num_convs=(6, 6, 3), width=64,
        cem_samples=64, train_records=128, eval_records=64, train_steps=6,
        buckets=(1, 2, 4), requests=8,
    ),
    "tiny": dict(
        image_size=(96, 96), batch_size=8, num_convs=(2, 2, 1), width=64,
        cem_samples=8, train_records=16, eval_records=8, train_steps=2,
        buckets=(1, 2), requests=4,
    ),
}
MODEL = "Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom"
ACTION_SIZE = 10
SEED = 0
#: Running BatchNorm statistics are the last train batch's. At the
#: reference's 0.9997 a handful of steps leaves them at their initial 0/1,
#: nothing is normalised in eval mode, and the served Q logits come out
#: ~1e-7 whatever the observation: a parity check on those cannot tell a
#: right input path from a wrong one. The model exposes the momentum for
#: exactly such short trainings (research/qtopt/t2r_models.py).
BATCH_NORM_MOMENTUM = 0.0
#: Served vs direct Q logits: RMS difference as a share of the SIGNAL, the
#: smaller of the logits' spread across the requests' images and across
#: the action population. The two are separately compiled bf16 programs
#: over the same weights (the exported serving program, weights folded in
#: as constants, vs preprocess + predict_step). A random-weight BatchNorm
#: tower this deep amplifies their rounding differences: 0.14-0.22 of the
#: signal measured on the chip, 1e-7 on the CPU where both round alike.
#: It amplifies a wrong input just the same, so a server that dropped,
#: permuted or mis-scaled an image or an action lands at 1.0-1.8 (same
#: tables, rows/columns permuted or averaged), and logits that do not move
#: with their inputs have no signal to pass with.
PARITY_RMS_SHARE_OF_SIGNAL = 0.5


class Reporter:
    """Prints each line tagged with the platform, and splits a stage's wall
    time into compile seconds (the program's own `jit.*` spans of jax's
    trace/lower/compile events, utils/build_trace.py, summed across
    threads) and the rest."""

    def __init__(self, platform: str):
        from tensor2robot_tpu.utils import build_trace, tracing

        build_trace.install()
        self.platform = platform
        self._counters = tracing.counters

    @property
    def compile_s(self) -> float:
        counters = self._counters()
        return sum(
            counters.get(f"jit.{kind}.ns", 0)
            for kind in ("trace", "lower", "compile")
        ) / 1e9

    @property
    def cache_hits(self) -> int:
        return self._counters().get("jit.cache_hits", 0)

    @property
    def cache_misses(self) -> int:
        return self._counters().get("jit.cache_misses", 0)

    def say(self, text: str) -> None:
        print(f"[chip_smoke {self.platform}] {text}", flush=True)

    @contextlib.contextmanager
    def stage(self, name: str):
        self.say(f"stage {name}: start")
        wall0, compile0 = time.perf_counter(), self.compile_s
        yield
        wall = time.perf_counter() - wall0
        compile_s = self.compile_s - compile0
        self.say(
            f"stage {name}: ok wall_s={wall:.2f} compile_s={compile_s:.2f} "
            f"other_s={max(wall - compile_s, 0.0):.2f}"
        )


def device_gate(preset: str):
    """jax's devices, or an error before any stage: a platform other than
    `tpu` needs the explicit CPU request (mesh.require_devices), the
    full-width preset runs on the chip only, and the tiny one on the CPU
    only, so a result line with platform `tpu` is always full width."""
    from tensor2robot_tpu.parallel.mesh import require_devices

    devices = require_devices()
    platform = devices[0].platform
    if (preset == "chip") != (platform == "tpu"):
        raise RuntimeError(
            f"preset {preset!r} on platform {platform!r}: the full-width "
            "preset runs on the chip only (`python chip_smoke.py`) and the "
            "tiny one on the CPU only "
            "(`JAX_PLATFORMS=cpu python chip_smoke.py --preset tiny`)"
        )
    return devices


def _device_ids(tree) -> list:
    import jax

    ids = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        ids.update(device.id for device in leaf.sharding.device_set)
    return sorted(ids)


def camera_like_image(rng, height, width):
    """Smooth 32-pixel blocks + mild noise: uniform noise is jpeg's worst
    case, nothing a robot ever sends, and averages to the same grey in
    every image, which would leave the Q-values blind to the image."""
    import numpy as np

    coarse = rng.randint(0, 256, (height // 32 + 1, width // 32 + 1, 3))
    image = np.kron(coarse, np.ones((32, 32, 1)))[:height, :width]
    image = image + rng.randint(-8, 9, image.shape)
    return np.clip(image, 0, 255).astype(np.uint8)


def write_records(preset, model, work_dir):
    """Seeded train/eval tf.Example files at the model's raw in-spec."""
    import numpy as np

    from tensor2robot_tpu.data import tfrecord
    from tensor2robot_tpu.data.encoder import encode_example
    from tensor2robot_tpu.specs import make_random_numpy

    specs = {
        "features": model.preprocessor.get_in_feature_specification("train"),
        "labels": model.preprocessor.get_in_label_specification("train"),
    }
    height, width, _ = specs["features"]["state/image"].shape
    paths = {}
    for split, count in (
        ("train", preset["train_records"]), ("eval", preset["eval_records"]),
    ):
        rng = np.random.RandomState(SEED + (split == "eval"))
        rows = make_random_numpy(specs, batch_size=count, seed=SEED)
        records = []
        for i in range(count):
            row = {key: np.asarray(value[i]) for key, value in rows.items()}
            row["features/state/image"] = camera_like_image(rng, height, width)
            row["labels/reward"] = np.asarray([float(i % 2)], np.float32)
            records.append(encode_example(specs, row))
        paths[split] = os.path.join(work_dir, f"{split}.tfrecord")
        tfrecord.write_tfrecords(paths[split], records)
    return paths


def make_step_probe(reporter):
    """A HookBuilder that times each train step to block_until_ready,
    marks the loop's phases (wall clock, compile seconds so far), and
    keeps the trainer and its last state for the `step` stage."""
    import jax

    from tensor2robot_tpu.hooks.hook_builder import Hook, HookBuilder

    class StepProbe(HookBuilder, Hook):
        def __init__(self):
            self.trainer = None
            self.state = None
            self.step_seconds = []
            self.losses = []
            self.marks = {}
            self._t0 = None

        def mark(self, name):
            self.marks[name] = (time.perf_counter(), reporter.compile_s)

        def create_hooks(self, t2r_model, trainer=None):
            self.trainer = trainer
            return [self]

        def on_train_begin(self, ctx):
            self.mark("state_ready")

        def before_step(self, ctx):
            self._t0 = time.perf_counter()

        def after_step(self, ctx):
            jax.block_until_ready(ctx.device_metrics)
            self.step_seconds.append(time.perf_counter() - self._t0)
            self.losses.append(float(ctx.device_metrics["loss"]))
            self.state = ctx.state

        def after_checkpoint_saved(self, ctx):
            self.mark("checkpoint_saved")

        def after_eval(self, ctx):
            self.mark("eval_and_export_done")

    return StepProbe()


def run_training(preset, paths, model_dir, probe):
    """train_eval_model through the config layer, as the trainer CLI does."""
    import tensor2robot_tpu.config.defaults  # noqa: F401 — registers the surface
    from tensor2robot_tpu import config as cfg

    steps = preset["train_steps"]
    bindings = [
        f"train_eval_model.t2r_model = @{MODEL}()",
        f"{MODEL}.image_size = {preset['image_size']!r}",
        f"{MODEL}.num_convs = {preset['num_convs']!r}",
        f"{MODEL}.width = {preset['width']}",
        f"{MODEL}.batch_norm_momentum = {BATCH_NORM_MOMENTUM}",
        f"{MODEL}.action_batch_size = {preset['cem_samples']}",
        "train_eval_model.input_generator_train = "
        "@train/DefaultRecordInputGenerator()",
        f"train/DefaultRecordInputGenerator.file_patterns = {paths['train']!r}",
        f"train/DefaultRecordInputGenerator.batch_size = {preset['batch_size']}",
        f"train/DefaultRecordInputGenerator.seed = {SEED}",
        "train_eval_model.input_generator_eval = "
        "@eval/DefaultRecordInputGenerator()",
        f"eval/DefaultRecordInputGenerator.file_patterns = {paths['eval']!r}",
        f"eval/DefaultRecordInputGenerator.batch_size = {preset['batch_size']}",
        "train_eval_model.create_exporters_fn = @create_default_exporters",
        f"create_default_exporters.warmup_batch_sizes = {preset['buckets']!r}",
        f"train_eval_model.model_dir = {model_dir!r}",
        f"train_eval_model.max_train_steps = {steps}",
        f"train_eval_model.save_checkpoints_steps = {steps}",
        f"train_eval_model.log_every_steps = {max(steps // 2, 1)}",
        "train_eval_model.eval_steps = 1",
    ]
    cfg.parse_config_files_and_bindings([], bindings)
    train_eval_model = cfg.get_configurable("train_eval_model")
    return train_eval_model(hook_builders=[probe])


def inspect_step(preset, paths, probe, devices, say):
    """Placement and lowered text of the step the trainer just ran;
    returns the host batch it used."""
    import jax

    from tensor2robot_tpu.data.input_generators import (
        DefaultRecordInputGenerator,
    )

    trainer, state = probe.trainer, probe.state
    n = len(devices)
    all_ids = sorted(device.id for device in devices)
    generator = DefaultRecordInputGenerator(
        file_patterns=paths["train"], batch_size=preset["batch_size"],
        seed=SEED,
    )
    generator.set_specification_from_model(trainer.model, "train")
    host_batch = next(iter(generator.create_dataset("train")))
    batch = trainer.shard_batch(host_batch)
    image = batch["features"]["state/image"]
    say(
        f"train state on devices {_device_ids(state)}; device batch "
        f"(trainer.shard_batch) on {_device_ids(batch)}, image "
        f"{tuple(image.shape)} {image.dtype} as {len(image.addressable_shards)}"
        f" shards of {tuple(image.addressable_shards[0].data.shape)}"
    )
    if _device_ids(state) != all_ids or _device_ids(batch) != all_ids:
        raise RuntimeError(f"state/batch do not span all {n} devices")
    shard_rows = {s.data.shape[0] for s in image.addressable_shards}
    if shard_rows != {preset["batch_size"] // n}:
        raise RuntimeError(
            f"batch is not split {n} ways: per-device rows {shard_rows}"
        )

    lowered = trainer.train_step.lower(state, batch, jax.random.PRNGKey(0))
    if "select_and_scatter" not in lowered.as_text():
        raise RuntimeError(
            "the lowered step holds no select_and_scatter: the pools' "
            "backward is not lax.reduce_window's own (ops/pooling.py)"
        )
    say("pool backward in the lowered step: native select_and_scatter")
    compiled_text = lowered.compile().as_text()
    has_all_reduce = "all-reduce" in compiled_text
    say(f"all-reduce in the compiled step: {has_all_reduce} (devices={n})")
    if n > 1 and not has_all_reduce:
        raise RuntimeError("multi-device step compiled without an all-reduce")
    new_state, metrics = trainer.train_step(state, batch, jax.random.PRNGKey(0))
    jax.block_until_ready(metrics)
    say(f"step outputs on devices: state {_device_ids(new_state)}, "
        f"metrics {_device_ids(metrics)}")
    if _device_ids(new_state) != all_ids:
        raise RuntimeError("step outputs do not span all devices")
    return host_batch


def check_export(model_dir, preset, say):
    from tensor2robot_tpu.export.saved_model import latest_export_dir

    export_root = os.path.join(model_dir, "export", "latest")
    path = latest_export_dir(export_root)
    if path is None:
        raise RuntimeError(f"no export under {export_root}")
    with open(os.path.join(path, "t2r_metadata.json")) as f:
        metadata = json.load(f)
    aot = metadata.get("aot") or {}
    buckets = sorted((aot.get("buckets") or {}).get("none") or [])
    say(f"export {os.path.basename(path)}: stablehlo={metadata.get('stablehlo')}"
        f" aot_buckets={buckets} aot_topology={aot.get('topology')}")
    if buckets != sorted(preset["buckets"]):
        raise RuntimeError(
            f"export carries AOT executables for {buckets}, wanted "
            f"{sorted(preset['buckets'])}: {metadata.get('aot_errors') or aot}"
        )
    checkpoints = glob.glob(os.path.join(model_dir, "checkpoints", "*"))
    say(f"checkpoints: {sorted(os.path.basename(c) for c in checkpoints)}")
    if not any(os.path.basename(c).isdigit() for c in checkpoints):
        raise RuntimeError("trainer wrote no checkpoint")
    return export_root


def serve(preset, export_root, say):
    """Restore, select actions, answer requests; returns (requests, served
    q_predicted rows, the first request's q_predicted served alone)."""
    import jax
    import numpy as np

    from tensor2robot_tpu.policies.policies import JitCEMPolicy
    from tensor2robot_tpu.predictors.exported_savedmodel_predictor import (
        ExportedSavedModelPredictor,
    )
    from tensor2robot_tpu.serving.server import PolicyServer
    from tensor2robot_tpu.specs import flatten_spec_structure, make_random_numpy

    predictor = ExportedSavedModelPredictor(export_dir=export_root)
    if not predictor.restore():
        raise RuntimeError("predictor restore failed")
    # One action population for every request and a different image for
    # each: across requests the Q-values move with the image alone, within
    # a request with the action alone (the parity stage needs both).
    spec = flatten_spec_structure(predictor.get_feature_specification())
    shared = {
        key: np.asarray(value[0])
        for key, value in make_random_numpy(spec, batch_size=1, seed=SEED).items()
    }
    rng = np.random.RandomState(SEED + 2)
    requests = [
        {**shared, "state/image": camera_like_image(
            rng, *spec["state/image"].shape[:2])}
        for _ in range(preset["requests"])
    ]

    policy = JitCEMPolicy(
        predictor, action_size=ACTION_SIZE, cem_samples=preset["cem_samples"],
        cem_iterations=3, seed=SEED,
    )
    for i, request in enumerate(requests[:2]):
        state = {k: v for k, v in request.items() if k.startswith("state")}
        t0 = time.perf_counter()
        action = policy.SelectAction(state)
        say(f"JitCEMPolicy action {i}: "
            f"{[round(float(a), 3) for a in action]} "
            f"({time.perf_counter() - t0:.2f}s)")
        if action.shape != (ACTION_SIZE,) or not np.all(np.isfinite(action)):
            raise RuntimeError(f"bad CEM action {action!r}")
        if np.any(np.abs(action) > 1.0):
            raise RuntimeError(f"CEM action outside [-1, 1]: {action!r}")

    served = []
    with PolicyServer(predictor, max_wait_ms=20).start() as server:
        sources = server.snapshot()["prewarm_source"]
        say(f"warm-up bucket restore tiers: {sources}")
        if set(sources.values()) != {"aot"}:
            # Export and restore share one process and one topology.
            raise RuntimeError(
                f"buckets not served from AOT executables: {sources}; "
                f"fallbacks={predictor.loaded_model.aot_fallbacks}"
            )
        # One at a time (bucket 1), then all at once (coalesced buckets).
        # Generous deadlines: the smoke checks answers, not latency.
        single = server.call(requests[0], deadline_ms=60000)
        futures = [
            server.submit(request, deadline_ms=60000) for request in requests
        ]
        replies = [future.result(120) for future in futures]
        snapshot = server.snapshot()
    for reply in replies:
        served.append(np.asarray(reply.outputs["q_predicted"]))
    loaded = predictor.loaded_model
    output_devices = sorted({
        device.id
        for executable in loaded.aot_executables.values()
        for sharding in jax.tree_util.tree_leaves(executable.output_shardings)
        for device in sharding.device_set
    })
    say(f"PolicyServer: {len(replies) + 1} replies, batches by bucket "
        f"{snapshot['batches_by_bucket']}, predictor outputs on devices "
        f"{output_devices}; fresh_trace_calls={loaded.fresh_trace_calls} "
        "(JitCEMPolicy traces the program once; the server none)")
    if len(output_devices) != 1:
        raise RuntimeError(
            f"bucket executables span devices {output_devices}, want one"
        )
    if not all(np.all(np.isfinite(q)) for q in served):
        raise RuntimeError("non-finite served Q-values")
    return requests, np.stack(served), np.asarray(single.outputs["q_predicted"])


def direct_forward(probe, model_dir, example_batch, requests):
    """The same requests through the trainer's CompiledModel, with the
    weights read back from the checkpoint on disk (not the live state)."""
    import jax
    import numpy as np

    from tensor2robot_tpu.specs import TensorSpecStruct
    from tensor2robot_tpu.train.train_eval import (
        create_checkpoint_manager,
        restore_or_init_state,
    )

    compiled = probe.trainer
    manager = create_checkpoint_manager(model_dir, save_interval_steps=1)
    state = restore_or_init_state(
        manager, compiled, jax.random.PRNGKey(0), example_batch
    )
    manager.close()
    step = int(jax.device_get(state.step))
    variables = state.export_variables(
        use_ema=compiled.model.use_avg_model_params
    )
    batch = TensorSpecStruct({
        key: np.stack([request[key] for request in requests])
        for key in requests[0]
    })
    features, _ = compiled.preprocessor.preprocess(
        batch, None, mode="predict", rng=None
    )
    outputs = compiled.predict_step(variables, features)
    return step, np.asarray(jax.device_get(outputs["q_predicted"]))


def check_parity(served, served_alone, direct) -> str:
    """Holds the served q_predicted logits to the direct forward's, within
    PARITY_RMS_SHARE_OF_SIGNAL; returns the line to report. Rows are
    requests (images differ), columns the shared action population. Bucket
    1 (the first request served alone) and the coalesced bucket are
    different executables; each is held to the bound."""
    import numpy as np

    if served.shape != direct.shape or served_alone.shape != direct[0].shape:
        raise RuntimeError(
            f"served {served.shape} and {served_alone.shape} vs direct "
            f"{direct.shape}"
        )
    by_image = float(direct.std(axis=0).mean())
    by_action = float(direct.std(axis=1).mean())
    signal = min(by_image, by_action)
    error = max(
        float(np.sqrt(np.mean(np.square(served - direct)))),
        float(np.sqrt(np.mean(np.square(served_alone - direct[0])))),
    )
    if not error <= PARITY_RMS_SHARE_OF_SIGNAL * signal:
        raise RuntimeError(
            f"served Q logits differ from the direct forward by {error:.3e} "
            f"rms, more than {PARITY_RMS_SHARE_OF_SIGNAL} of the "
            f"{signal:.3e} they move with their inputs (std {by_image:.3e} "
            f"across images, {by_action:.3e} across actions)"
        )
    return (
        f"q_predicted logits {tuple(served.shape)} in [{direct.min():.3e}, "
        f"{direct.max():.3e}], std {by_image:.2e} across images and "
        f"{by_action:.2e} across actions; rms_diff={error:.2e} = "
        f"{error / signal:.2e} of the signal (bound "
        f"{PARITY_RMS_SHARE_OF_SIGNAL}), max_abs_diff="
        f"{float(np.max(np.abs(served - direct))):.2e}"
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--preset", choices=sorted(PRESETS), default="chip")
    args = parser.parse_args(argv)
    preset = PRESETS[args.preset]

    import jax
    import jaxlib
    import numpy as np

    devices = device_gate(args.preset)
    device = devices[0]
    reporter = Reporter(device.platform)
    say = reporter.say
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not installed"
    say(f"device platform={device.platform} kind={device.device_kind!r} "
        f"count={len(devices)} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu_version} "
        f"preset={args.preset}")

    import tensor2robot_tpu.config.defaults as defaults
    from tensor2robot_tpu.data import parser as parser_lib
    from tensor2robot_tpu.data import tfrecord
    from tensor2robot_tpu.data.dataset import default_parse_backend
    from tensor2robot_tpu.utils.compile_cache import enable_compile_cache
    from tensor2robot_tpu.train.train_eval import maybe_wrap_for_tpu

    cache_dir = enable_compile_cache()
    os.makedirs(cache_dir, exist_ok=True)
    entries_before = len(os.listdir(cache_dir))
    say(f"compile cache dir={cache_dir} entries_before={entries_before} "
        f"(from {'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'the checkout'})")

    t_start = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work_dir:
        model_dir = os.path.join(work_dir, "model")
        with reporter.stage("records"):
            spec_model = maybe_wrap_for_tpu(
                getattr(defaults, MODEL)(image_size=preset["image_size"])
            )
            paths = write_records(preset, spec_model, work_dir)
            say(f"wrote {preset['train_records']}+{preset['eval_records']} "
                f"records, {sum(os.path.getsize(p) for p in paths.values())} "
                "bytes")

        probe = make_step_probe(reporter)
        with reporter.stage("train"):
            probe.mark("start")
            eval_metrics = run_training(preset, paths, model_dir, probe)
            last = "start"
            for name in ("state_ready", "checkpoint_saved",
                         "eval_and_export_done"):
                wall = probe.marks[name][0] - probe.marks[last][0]
                compile_s = probe.marks[name][1] - probe.marks[last][1]
                say(f"train phase {last} -> {name}: wall_s={wall:.2f} "
                    f"compile_s={compile_s:.2f}")
                last = name
            steady = probe.step_seconds[1:] or probe.step_seconds
            say(f"train steps={len(probe.step_seconds)} losses="
                f"{[round(x, 4) for x in probe.losses]} first_step_s="
                f"{probe.step_seconds[0]:.2f} later_step_s_median="
                f"{statistics.median(steady):.3f} (smoke timing, not a "
                f"rate: batches were parsed ahead during the first step's "
                f"compile) eval={eval_metrics}")
            if not np.all(np.isfinite(probe.losses)):
                raise RuntimeError(f"non-finite train loss {probe.losses}")
            if not eval_metrics or not np.all(
                np.isfinite(list(eval_metrics.values()))
            ):
                raise RuntimeError(f"eval produced {eval_metrics}")
            # The host codec that just fed the trainer: a failed native
            # build silently falls back to pure-Python CRC / PIL decode.
            codec = {
                "tfrecord": tfrecord.native_loaded(),
                "jpeg": parser_lib.native_jpeg_loaded(),
            }
            say(f"host codec native={codec} parse_backend="
                f"{default_parse_backend()} (threads; no worker processes)")
            if not all(codec.values()):
                raise RuntimeError(
                    f"host codec fell back to pure Python: {codec}; "
                    "tensor2robot_tpu/native did not build (make, g++, "
                    "libjpeg headers)"
                )

        with reporter.stage("step"):
            example_batch = inspect_step(preset, paths, probe, devices, say)

        with reporter.stage("export"):
            export_root = check_export(model_dir, preset, say)

        with reporter.stage("serve"):
            requests, served, served_alone = serve(preset, export_root, say)

        with reporter.stage("parity"):
            step, direct = direct_forward(
                probe, model_dir, example_batch, requests
            )
            if step != preset["train_steps"]:
                raise RuntimeError(f"restored step {step}, not the last")
            say(f"served vs direct CompiledModel forward on checkpoint "
                f"{step}: " + check_parity(served, served_alone, direct))

    entries_after = len(os.listdir(cache_dir))
    say(f"compile cache entries_written={entries_after - entries_before} "
        f"persistent_hits={reporter.cache_hits} "
        f"persistent_misses={reporter.cache_misses} "
        f"compile_s_total={reporter.compile_s:.2f} "
        f"wall_s_total={time.perf_counter() - t_start:.2f}")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": len(devices),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
