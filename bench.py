"""Benchmark: QT-Opt critic training MFU on real hardware.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.
The reference publishes no benchmark numbers (BASELINE.md); the north star
is the BASELINE.json target of >=50% MFU on the QT-Opt grasp critic, so
vs_baseline reports measured MFU / 0.50.

The flagship workload is the full-fidelity Grasping44 critic: 472x472x3
images at the reference's default batch 64 (research/qtopt/t2r_models.py:41,
77), bf16 forward via the TPU model wrapper (train_in_bfloat16 defaults ON),
crops/distortions fused into the device step. FLOPs come from XLA's compiled
cost analysis with an analytic conv-tower fallback; peak from the device
kind.

Hard failures emit a diagnostic JSON line (never a bare traceback) and exit
nonzero. Every leg resolves its devices through `_devices()`: a platform
other than `tpu` is a failure unless the CPU was asked for explicitly
(JAX_PLATFORMS=cpu), in which case the metric name says `cpu_proxy`.

Timing method: the bench times consecutive fixed-size windows (each closed
by a host readback) and reports the MEDIAN window as steady state, with
the best window and the all-window average in detail.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time
import traceback

from tensor2robot_tpu import flags as t2r_flags

# Per-chip peak dense bf16 FLOPS by device kind.
_PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,  # v6e / Trillium
}

#: Nominal divisor that keeps the `*_cpu_proxy` metrics defined; never a
#: device peak and never reported under a device metric name.
_CPU_PROXY_PEAK_FLOPS = 1e12


def _peak_flops(device) -> float:
    if device.platform == "cpu":
        return _CPU_PROXY_PEAK_FLOPS
    for key, value in _PEAK_FLOPS.items():
        if device.device_kind.startswith(key):
            return value
    raise ValueError(
        f"no peak FLOP/s entry for device_kind {device.device_kind!r} "
        f"(platform {device.platform!r}); add it to _PEAK_FLOPS with its "
        "source rather than reporting utilization against a guess"
    )


def _emit(payload: dict) -> None:
    print(json.dumps(payload), flush=True)


def _fail(
    stage: str,
    err: BaseException,
    metric: str = "qtopt_critic_train_mfu_bs64_472px",
) -> None:
    _emit(
        {
            "metric": metric,
            "value": 0.0,
            "unit": "fraction_of_peak",
            "vs_baseline": 0.0,
            "error": f"{stage}: {type(err).__name__}: {err}",
            "trace_tail": traceback.format_exc().strip().splitlines()[-3:],
        }
    )
    sys.exit(1)


def _devices(metric: str, compile_cache: bool = True):
    """The leg's devices, with the persistent compile cache engaged
    (`compile_cache=False` for legs that measure the compile tier).

    `mesh.require_devices()` refuses any platform but `tpu` unless the
    CPU was asked for explicitly — there is no probe, retry or CPU
    fallback — and a refusal is reported under `metric` through the
    one-JSON-line failure contract."""
    try:
        from tensor2robot_tpu.parallel.mesh import require_devices
        from tensor2robot_tpu.utils.compile_cache import (
            enable_compile_cache,
        )

        devices = require_devices()
        if compile_cache:
            enable_compile_cache()
        return devices
    except Exception as err:  # noqa: BLE001 — reported, then exit 1
        _fail("backend_init", err, metric=metric)


def _refuse_children_on_chip(devices, metric: str, what: str) -> None:
    """One process for each chip: this process has opened the device, so
    a child that needs it would fail or hang. Legs that spawn such
    children run only as explicit CPU proxies."""
    if devices[0].platform != "cpu":
        _fail(
            "one_process_per_chip",
            RuntimeError(
                f"{what} start child processes that open the accelerator "
                f"this process already holds ({devices[0].device_kind!r}); "
                "there is no per-process chip binding yet. Not brought up "
                "on the chip — run the CPU proxy with JAX_PLATFORMS=cpu."
            ),
            metric=metric,
        )


def _require_cpu_request(metric: str, what: str) -> None:
    """Legs that ARE a virtual-CPU-mesh proxy refuse to pick the CPU on
    their own: the caller says JAX_PLATFORMS=cpu or the leg fails."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        _fail(
            "cpu_proxy_not_requested",
            RuntimeError(
                f"{what} runs on the 8-device virtual CPU mesh only; ask "
                "for it explicitly with JAX_PLATFORMS=cpu (its wall-times "
                "are host numbers, never device metrics)"
            ),
            metric=metric,
        )


def _measure_windows(run_window, sync, n_windows: int, window: int):
    """Times n_windows consecutive `window`-step windows, each closed by a
    host readback; returns (median_steps_per_sec, best_steps_per_sec,
    avg_steps_per_sec).

    The MEDIAN of the window times is the headline steady-state estimate:
    robust against both residual warm-up (slow early windows) and timer
    jitter (a max-statistic like best-of-windows is biased upward by
    jitter). Best and all-window average ride along for the detail
    channel. The readback closing each window is included in its time
    (conservative: charges one host RTT per window).
    """
    times = []
    sync()
    for _ in range(n_windows):
        start = time.perf_counter()
        run_window()
        sync()
        times.append(time.perf_counter() - start)
    return (
        window / statistics.median(times),
        window / min(times),
        window * len(times) / sum(times),
    )


def _pin_matmul_ceiling(
    device, n_windows: int = 4, calls: int = 20, n: int = 8192
) -> dict:
    """Same-session achievable-matmul ceiling: an 8192^3 bf16 matmul
    timed in the SAME process as the MFU headline it sits next to.
    Multi-call windows anchored by one scalar readback; median window is
    the estimate.
    """
    import jax
    import jax.numpy as jnp

    a = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.bfloat16), device
    )
    b = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.bfloat16), device
    )
    matmul = jax.jit(lambda a, b: a @ b)
    box = {}

    def run_window():
        for _ in range(calls):
            box["out"] = matmul(a, b)

    def sync():
        if "out" in box:
            float(jax.device_get(box["out"][0, 0]))

    for _ in range(10):  # compile + warm-up executions, untimed
        box["out"] = matmul(a, b)
    calls_per_sec, _, _ = _measure_windows(run_window, sync, n_windows, calls)
    tflops = 2.0 * n * n * n * calls_per_sec / 1e12
    return {
        "matmul_ceiling_tflops": round(tflops, 2),
        "matmul_ceiling_fraction_of_peak": round(
            tflops * 1e12 / _peak_flops(device), 4
        ),
        "matmul_shape": n,
    }


def _analytic_train_flops(
    image_size, batch_size, num_convs=(6, 6, 3), width=64
) -> float:
    """Fallback FLOPs estimate for one Grasping44 train step: summed conv
    and dense MACs x2, x3 for forward+backward (standard 1:2 fwd:bwd).
    `width` is the tower channel count (64 reference / 128 MXU twin)."""
    h, w = image_size
    flops = 0.0

    def conv(h, w, cin, cout, k, stride=1):
        nonlocal flops
        h, w = -(-h // stride), -(-w // stride)
        flops += 2.0 * batch_size * h * w * cout * k * k * cin
        return h, w

    h, w = conv(h, w, 3, width, 6, 2)
    h, w = -(-h // 3), -(-w // 3)
    for _ in range(num_convs[0]):
        h, w = conv(h, w, width, width, 5)
    h, w = -(-h // 3), -(-w // 3)
    for _ in range(num_convs[1]):
        h, w = conv(h, w, width, width, 3)
    h, w = -(-h // 2), -(-w // 2)
    for _ in range(num_convs[2]):
        h, w = h - 2, w - 2
        flops += 2.0 * batch_size * h * w * width * 9 * width
    # Dense head (grasp-param blocks + fc tail) is negligible next to the
    # conv tower but counted for completeness.
    flops += 2.0 * batch_size * (
        10 * 256 + 256 * width + h * w * width * 64 + 64 * 64 + 64
    )
    return flops * 3.0


def _stem_s2d() -> bool:
    """Whether the stem traced with the space-to-depth lowering."""
    from tensor2robot_tpu.layers.s2d_conv import stem_s2d_enabled

    return stem_s2d_enabled()


def _last_onchip(metric_base: str) -> "dict | None":
    """Pointer to the most recent committed ON-CHIP artifact of a metric
    family: {metric, value, artifact, utc}, or None.

    Scans the repo-root *.json artifacts for payloads whose metric starts
    with `metric_base`, excluding proxies and failures; recency comes from
    the artifact's last git commit (falling back to file mtime for
    uncommitted files). Lets a round-close CPU-proxy payload SAY where the
    real hardware number lives instead of burying it in backend_note.
    """
    import datetime
    import glob
    import subprocess

    root = os.path.dirname(os.path.abspath(__file__))
    best = None
    for path in glob.glob(os.path.join(root, "*.json")):
        try:
            with open(path) as f:
                payload = json.loads(f.read(1 << 20))
        except Exception:
            continue
        if not isinstance(payload, dict):
            continue
        metric = payload.get("metric")
        if not isinstance(metric, str) or not metric.startswith(metric_base):
            continue
        if payload.get("proxy") or "cpu_proxy" in metric or "error" in payload:
            continue
        epoch = None
        try:
            out = subprocess.run(
                ["git", "log", "-1", "--format=%ct", "--", path],
                capture_output=True, text=True, cwd=root, timeout=10,
            )
            if out.returncode == 0 and out.stdout.strip():
                epoch = float(out.stdout.strip())
        except Exception:
            pass
        if epoch is None:
            try:
                epoch = os.path.getmtime(path)
            except OSError:
                continue
        if best is None or epoch > best[0]:
            best = (
                epoch,
                {
                    "metric": metric,
                    "value": payload.get("value"),
                    "artifact": os.path.basename(path),
                    "utc": datetime.datetime.fromtimestamp(
                        epoch, datetime.timezone.utc
                    ).strftime("%Y-%m-%dT%H:%M:%SZ"),
                },
            )
    return best[1] if best else None


def _proxy_fields(on_tpu: bool, metric_base: "str | None" = None) -> dict:
    """Top-level self-description for CPU-proxy payloads: an explicit
    "proxy": true plus a note that vs_baseline is computed against a
    synthetic CPU peak / reduced shapes and is not comparable to the TPU
    target — so a proxy artifact can never masquerade as chip evidence
    on one overlookable detail field. With `metric_base` the
    payload also carries `last_onchip` — a pointer to the newest committed
    real-hardware artifact of the family (null when none exists yet)."""
    if on_tpu:
        return {}
    fields = {
        "proxy": True,
        "vs_baseline_note": (
            "cpu proxy (synthetic peak / reduced shapes); not comparable "
            "to the TPU baseline target"
        ),
    }
    if metric_base is not None:
        try:
            fields["last_onchip"] = _last_onchip(metric_base)
        except Exception:  # the pointer is advisory; never fail the bench
            fields["last_onchip"] = None
    return fields


def _overlap_fields(infeed_steps_per_sec: float, steps_per_sec: float) -> dict:
    """Infeed-overlap ratio with the physically-impossible tail clamped.

    A fresh host feed cannot beat a pre-sharded resident batch, so a raw
    ratio above 1.0 is timing noise (BENCH_r04 shipped 1.0431
    uncommented). The headline field is clamped at 1.0; the raw
    ratio always rides alongside, with an explicit note when it was noise.
    """
    if steps_per_sec <= 0:
        return {"infeed_overlap_efficiency": 0.0}
    raw = infeed_steps_per_sec / steps_per_sec
    fields = {
        "infeed_overlap_efficiency": round(min(raw, 1.0), 4),
        "infeed_overlap_efficiency_raw": round(raw, 4),
    }
    if raw > 1.0:
        fields["infeed_overlap_note"] = (
            "raw ratio exceeded 1.0 (timing noise); clamped"
        )
    return fields


def _camera_like_frames(n: int, height: int, width: int, seed: int):
    """Synthetic robot-camera frames: smooth low-frequency background +
    object-like rectangles + mild sensor noise.

    The r05/r06 data legs encoded UNIFORM-NOISE frames — jpeg's entropy
    worst case (~385 KB at q95 for 512x640, vs ~40-150 KB for real camera
    captures), where Huffman decode dominates and per-pixel work (IDCT /
    upsampling / color convert — exactly what ROI decode skips) is a
    minority. Real grasping-bin frames are spatially coherent; these
    frames match that compressibility class so the bench measures the
    decode regime deployments actually run. The noise-content legs still
    ride in the payload (BENCH_DATA_CONTENT=noise for a full noise run)
    for series continuity with r05/r06.
    """
    import numpy as np
    from PIL import Image

    rng = np.random.RandomState(seed)
    frames = np.empty((n, height, width, 3), np.uint8)
    for i in range(n):
        small = rng.randint(0, 256, (height // 16, width // 16, 3))
        base = np.asarray(
            Image.fromarray(small.astype(np.uint8)).resize(
                (width, height), Image.BILINEAR
            ),
            dtype=np.float32,
        )
        for _ in range(rng.randint(3, 8)):  # objects in the bin
            h = rng.randint(height // 16, height // 3)
            w = rng.randint(width // 16, width // 3)
            y = rng.randint(0, height - h)
            x = rng.randint(0, width - w)
            base[y : y + h, x : x + w] = rng.randint(0, 256, 3)
        base += rng.normal(0.0, 4.0, base.shape)  # sensor noise
        frames[i] = np.clip(base, 0, 255).astype(np.uint8)
    return frames


def bench_data() -> None:
    """Input-pipeline throughput: records/sec + images/sec for the QT-Opt
    spec (512x640 jpeg), batch 64, through the parallel parse pipeline.

    Invoked as `python bench.py data`. Emits one JSON line; vs_baseline
    compares pipeline images/sec against the batch rate a 50%-MFU TPU step
    would demand (the pipeline must outrun the chip to keep it fed).

    Regimes measured per run (ISSUE 2):
      * headline — default config (fast parser + decode cache + decode-time
        ROI from the model preprocessor's crop spec) at default workers;
      * worker sweep — parse_workers in {1, 2}, each with cold (no cache),
        fast (cache) and SpecParser-oracle legs: the first measured
        multi-worker scaling points;
      * ROI attribution — the cold leg with ROI disabled (full-frame
        decode, the r06 path) under identical content;
      * content continuity — uniform-noise-frame cold legs (ROI on/off),
        directly comparable to the r05/r06 series (see
        _camera_like_frames for why noise is not the headline content).
    """
    import os
    import tempfile

    import numpy as np

    metric = "qtopt_input_pipeline_images_per_sec"
    try:
        from tensor2robot_tpu.data import tfrecord, wire
        from tensor2robot_tpu.data.dataset import (
            RecordDataset,
            default_decode_roi,
            default_parse_backend,
            default_parse_fast,
            default_parse_workers,
        )
        from tensor2robot_tpu.data.encoder import encode_example
        from tensor2robot_tpu.research.qtopt.t2r_models import (
            Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom,
        )
        from tensor2robot_tpu.specs import make_random_numpy

        model = Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom(
            device_type="cpu"
        )
        specs = {
            "features": model.preprocessor.get_in_feature_specification("train"),
            "labels": model.preprocessor.get_in_label_specification("train"),
        }
        n_records = int(os.environ.get("BENCH_DATA_RECORDS", "256"))
        batch_size = int(os.environ.get("BENCH_DATA_BATCH", "64"))
        content = os.environ.get("BENCH_DATA_CONTENT", "camera")
        if content not in ("camera", "noise"):
            raise ValueError(
                f"BENCH_DATA_CONTENT must be camera|noise, got {content!r}"
            )
        image_spec = specs["features"]["state/image"]
        src_h, src_w = int(image_spec.shape[0]), int(image_spec.shape[1])
        # The preprocessor's crop spec, as a decode-time ROI (the same map
        # DefaultRecordInputGenerator forwards in training).
        roi_map = {
            f"features/{key}": value
            for key, value in model.preprocessor.get_decode_rois(
                "train"
            ).items()
        }
        roi_spec = next(iter(roi_map.values()))
        # Decoded images per record, from the spec: every rate in the
        # payload (sweep legs included) reports images/sec, not records/sec.
        n_images = max(
            sum(
                1
                for s in specs["features"].values()
                if getattr(s, "data_format", None)
            ),
            1,
        )
        rng_values = make_random_numpy(specs, batch_size=n_records, seed=0)

        def write_records(path, frames):
            records = []
            for i in range(n_records):
                row = {
                    key: np.asarray(value[i])
                    for key, value in rng_values.items()
                }
                row["features/state/image"] = frames[i]
                records.append(encode_example(specs, row))
            tfrecord.write_tfrecords(path, records)

        with tempfile.TemporaryDirectory() as tmp:
            camera_path = os.path.join(tmp, "camera.tfrecord")
            noise_path = os.path.join(tmp, "noise.tfrecord")
            write_records(
                camera_path, _camera_like_frames(n_records, src_h, src_w, 7)
            )
            write_records(
                noise_path,
                np.random.RandomState(0).randint(
                    0, 256, (n_records, src_h, src_w, 3), dtype=np.uint8
                ),
            )
            headline_path = camera_path if content == "camera" else noise_path

            def run_leg(
                n_batches, parse_fast, cache_mb, workers=None, roi=True,
                path=None,
            ):
                """Records/sec through the full pipeline for one config."""
                saved = t2r_flags.read_raw("T2R_DECODE_CACHE_MB")
                t2r_flags.write_env("T2R_DECODE_CACHE_MB", cache_mb)
                wire.reset_decode_cache()
                try:
                    dataset = RecordDataset(
                        specs=specs,
                        file_patterns=path or headline_path,
                        batch_size=batch_size,
                        mode="train",
                        shuffle_buffer_size=128,
                        seed=1,
                        parse_fast=parse_fast,
                        num_parse_workers=workers,
                        decode_roi=roi_map if roi else None,
                    )
                    it = iter(dataset)
                    # Warm two full epochs before timing: spins up the pool
                    # AND brings the pipeline to its sustained regime (with
                    # the decode cache on, steady-state training serves
                    # repeat-epoch records from cache; the timed window
                    # reports that sustained rate — warmup_batches and the
                    # hit rate ride in the payload for transparency).
                    for _ in range(warmup_batches):
                        next(it)
                    # Three timed windows, MEDIAN rate (the bench.py MFU
                    # leg's median-of-windows convention): this host's cpu
                    # shares are throttled in bursts, and a single long
                    # window conflates scheduler dips with pipeline rate —
                    # while a too-short window can just drain the prefetch
                    # queue and report queue-pop latency as throughput.
                    # The median is robust to both; every window rides in
                    # the detail payload.
                    per_window = max(1, n_batches // 3)
                    window_rates = []
                    for _ in range(3):
                        start = time.perf_counter()
                        for _ in range(per_window):
                            next(it)
                        elapsed = time.perf_counter() - start
                        window_rates.append(per_window * batch_size / elapsed)
                    # Cache stats are only meaningful for the thread
                    # backend: process workers cache in their own
                    # interpreters, so the parent-side cache never sees
                    # their traffic.
                    cache = (
                        wire.get_decode_cache()
                        if default_parse_backend() == "thread"
                        else None
                    )
                    stats = cache.stats() if cache else None
                    dataset.close()
                    rate = sorted(window_rates)[len(window_rates) // 2]
                    return rate, stats, window_rates
                finally:
                    t2r_flags.restore_env("T2R_DECODE_CACHE_MB", saved)
                    wire.reset_decode_cache()

            n_batches = int(os.environ.get("BENCH_DATA_BATCHES", "24"))
            side_batches = max(2, n_batches // 3)
            # Two epochs of warm-up, shared by run_leg and the payload so
            # the reported value always matches what actually ran.
            warmup_batches = 2 * max(1, -(-n_records // batch_size))
            cache_mb = wire.default_decode_cache_mb()
            parse_fast_default = default_parse_fast()
            roi_enabled = default_decode_roi()
            # Headline: the default configuration (wire-format fast parser,
            # decode cache on, decode-time ROI — overridable via
            # T2R_PARSE_FAST / T2R_DECODE_CACHE_MB / T2R_DECODE_ROI).
            records_per_sec, cache_stats, window_rates = run_leg(
                n_batches, parse_fast=parse_fast_default, cache_mb=cache_mb
            )
            cold_records_per_sec, _, _ = run_leg(
                side_batches, parse_fast=True, cache_mb=0
            )
            slow_records_per_sec, _, _ = run_leg(
                side_batches, parse_fast=False, cache_mb=0
            )
            # ROI attribution: the identical cold leg with full-frame
            # decode (the r06 path) on the same records.
            cold_noroi_records_per_sec, _, _ = run_leg(
                side_batches, parse_fast=True, cache_mb=0, roi=False
            )
            # First measured multi-worker scaling points: cold/fast/oracle per worker count. Even
            # oversubscribed on a 2-cpu host this pins per-worker overhead.
            worker_sweep = {}
            for workers in (1, 2):
                cold_w, _, _ = run_leg(
                    side_batches, parse_fast=True, cache_mb=0, workers=workers
                )
                fast_w, _, _ = run_leg(
                    side_batches,
                    parse_fast=parse_fast_default,
                    cache_mb=cache_mb,
                    workers=workers,
                )
                oracle_w, _, _ = run_leg(
                    side_batches, parse_fast=False, cache_mb=0, workers=workers
                )
                worker_sweep[str(workers)] = {
                    "cold_images_per_sec": round(cold_w * n_images, 2),
                    "fast_images_per_sec": round(fast_w * n_images, 2),
                    "specparser_images_per_sec": round(
                        oracle_w * n_images, 2
                    ),
                }
            # Continuity with the r05/r06 series: uniform-noise frames,
            # cold, ROI on and off. (When the headline content IS noise,
            # these equal the cold legs above; skip the duplicate work.)
            if content == "camera":
                noise_cold, _, _ = run_leg(
                    side_batches, parse_fast=True, cache_mb=0, path=noise_path
                )
                noise_cold_noroi, _, _ = run_leg(
                    side_batches, parse_fast=True, cache_mb=0, roi=False,
                    path=noise_path,
                )
            else:
                noise_cold = cold_records_per_sec
                noise_cold_noroi = cold_noroi_records_per_sec
        images_per_sec = records_per_sec * n_images
        # A 50%-MFU step on v5e consumes ~2.3 batches/sec at bs64 (from the
        # analytic FLOPs of the full tower): the demand the pipeline must
        # meet. FLOPs are computed at the measured batch so the ratio stays
        # batch-independent under BENCH_DATA_BATCH overrides.
        step_flops = _analytic_train_flops((472, 472), batch_size)
        demand = 0.50 * _PEAK_FLOPS["TPU v5e"] / step_flops * batch_size
        _emit(
            {
                "metric": metric,
                "value": round(images_per_sec, 2),
                "unit": "images_per_sec",
                "vs_baseline": round(images_per_sec / demand, 4),
                "detail": {
                    "records_per_sec": round(records_per_sec, 2),
                    "batch_size": batch_size,
                    "parse_workers": default_parse_workers(),
                    "parse_backend": default_parse_backend(),
                    "parse_fast": parse_fast_default,
                    "content": content,
                    "content_note": (
                        "camera-like frames (smooth background + objects "
                        "+ sensor noise; see bench._camera_like_frames) — "
                        "r05/r06 used uniform-noise frames, jpeg's entropy "
                        "worst case; their directly-comparable legs ride "
                        "in noise_content"
                    ),
                    "decode_roi": roi_enabled,
                    "roi": {
                        "keys": sorted(roi_map.keys()),
                        "crop": [roi_spec.height, roi_spec.width],
                        "source": [src_h, src_w],
                        "mode": roi_spec.mode,
                    },
                    "warmup_batches": warmup_batches,
                    "timing": "median_of_3_windows",
                    "window_images_per_sec": [
                        round(r * n_images, 2) for r in window_rates
                    ],
                    "decode_cache_mb": cache_mb,
                    "decode_cache": cache_stats,
                    "fast_no_cache_images_per_sec": round(
                        cold_records_per_sec * n_images, 2
                    ),
                    "cold_noroi_images_per_sec": round(
                        cold_noroi_records_per_sec * n_images, 2
                    ),
                    "roi_cold_speedup": round(
                        cold_records_per_sec
                        / max(cold_noroi_records_per_sec, 1e-9),
                        3,
                    ),
                    "specparser_images_per_sec": round(
                        slow_records_per_sec * n_images, 2
                    ),
                    "fast_vs_specparser": round(
                        records_per_sec / slow_records_per_sec, 2
                    ),
                    "worker_sweep": worker_sweep,
                    "noise_content": {
                        "cold_images_per_sec": round(
                            noise_cold * n_images, 2
                        ),
                        "cold_noroi_images_per_sec": round(
                            noise_cold_noroi * n_images, 2
                        ),
                        "note": (
                            "uniform-noise frames — direct continuation "
                            "of the r05/r06 cold series (r06 cold: 209.85)"
                        ),
                    },
                    "host_cpus": os.cpu_count(),
                    "demand_images_per_sec_at_50pct_mfu": round(demand, 2),
                },
            }
        )
    except Exception as err:
        _fail("bench_data", err, metric=metric)


def bench_auc() -> None:
    """bf16 accuracy budget: trains the QT-Opt critic twice on the same
    synthetic grasp dataset — once with the f32 policy, once under the
    TPU bf16 dtype policy — and reports the eval-AUC delta — the two legs share a backend so the
    dtype policy is the only intended difference. BASELINE.md's north
    star allows <=2%.

    Invoked as `python bench.py auc`. The synthetic task is learnable from
    pixels (reward = bright center patch), so AUC separates from 0.5
    within a few hundred steps and a dtype-policy regression shows up as
    a real separability gap, not noise.

    On TPU both legs run on the chip, so the bf16 leg exercises REAL MXU
    bf16 accumulation — the numerics the <=2% budget exists for;
    the f32 leg runs at XLA's default f32 conv precision.
    An explicit CPU run (JAX_PLATFORMS=cpu) is a policy-only comparison
    under a distinct _cpu_proxy metric. The reduced 96px tower is
    used on both backends: the budget question is dtype policy, and the
    reduced tower runs the same conv/BN/MXU ops at trainable scale.
    """
    import os

    metric_base = "qtopt_bf16_eval_auc_delta"
    devices = _devices(metric_base)

    import jax
    import jax.numpy as jnp
    import numpy as np

    on_tpu = devices[0].platform == "tpu"
    metric = metric_base if on_tpu else metric_base + "_cpu_proxy"
    try:
        from tensor2robot_tpu.research.qtopt.t2r_models import (
            Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom,
        )
        from tensor2robot_tpu.specs import make_random_numpy
        from tensor2robot_tpu.train.train_eval import (
            CompiledModel,
            maybe_wrap_for_tpu,
        )

        image_size = (96, 96)
        num_convs = (2, 2, 1)
        batch_size = int(os.environ.get("BENCH_AUC_BATCH", "16"))
        steps = int(os.environ.get("BENCH_AUC_STEPS", "300"))
        n_train, n_eval = 8 * batch_size, 128

        def make_model(bf16: bool):
            model = Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom(
                device_type="tpu" if bf16 else "cpu",
                image_size=image_size,
                num_convs=num_convs,
                # Eval-mode inference needs ADAPTED running BN stats and
                # an ADAPTED EMA: the reference-scale decays (0.9997 BN,
                # 0.9999 EMA) are tuned for millions of steps and leave
                # init values dominating after 300 — the eval surface
                # would score warm-up garbage, not the dtype policy.
                # Bench-scale decays converge both within ~100 steps;
                # identical in both legs, so the comparison is unaffected.
                batch_norm_momentum=0.9,
                model_weights_averaging=0.99,
            )
            return maybe_wrap_for_tpu(model) if bf16 else model

        def synth(model, n, seed):
            """Spec-conforming batch whose reward is STOCHASTICALLY
            decodable from the image: the center-patch brightness m sets
            P(reward=1) = sigmoid((m-130)/20). The Bayes AUC is therefore
            strictly below 1, so both dtype legs chase the same interior
            ceiling and small policy-induced degradations remain visible
            (a deterministic task saturates both legs at 1.0 and hides
            them)."""
            rng = np.random.RandomState(seed)
            features = make_random_numpy(
                model.preprocessor.get_in_feature_specification("train"),
                batch_size=n,
                seed=seed,
            )
            image = np.asarray(features["state/image"])
            h, w = image.shape[1:3]
            brightness = rng.uniform(60, 200, size=n)
            p_reward = 1.0 / (1.0 + np.exp(-(brightness - 130.0) / 20.0))
            labels = (rng.uniform(size=n) < p_reward).astype(np.float32)
            base = rng.randint(40, 90, size=image.shape).astype(np.int32)
            patch = slice(h // 4, 3 * h // 4), slice(w // 4, 3 * w // 4)
            for i, m in enumerate(brightness):
                base[i][patch] = rng.randint(
                    int(m) - 30, int(m) + 30, size=base[i][patch].shape
                )
            features["state/image"] = np.clip(base, 0, 255).astype(
                image.dtype
            )
            return features, labels.reshape(-1, 1)

        def train_and_auc(bf16: bool):
            model = make_model(bf16)
            features, labels = synth(model, n_train, seed=0)
            eval_features, eval_labels = synth(model, n_eval, seed=1)
            compiled = CompiledModel(model, donate_state=False)

            def make_batch(lo):
                return {
                    "features": {
                        k: np.asarray(v)[lo : lo + batch_size]
                        for k, v in features.items()
                    },
                    "labels": {
                        "reward": labels[lo : lo + batch_size].astype(
                            np.float32
                        )
                    },
                }

            state = compiled.init_state(jax.random.PRNGKey(0), make_batch(0))
            n_batches = n_train // batch_size
            for step in range(steps):
                batch = make_batch((step % n_batches) * batch_size)
                state, metrics = compiled.train_step(
                    state, compiled.shard_batch(batch), jax.random.PRNGKey(2)
                )
            loss = float(jax.device_get(metrics["loss"]))
            # Predict-path q values on held-out data (the export surface a
            # robot would see), scored by rank-based AUC.
            pre_features, _ = model.preprocessor.preprocess(
                {k: jnp.asarray(v) for k, v in eval_features.items()},
                None,
                mode="eval",
            )
            _, _, outputs, _ = model.packed_inference(
                state.export_variables(use_ema=True), pre_features, "eval"
            )
            q = np.asarray(
                jax.device_get(outputs["q_predicted"]), np.float64
            ).reshape(-1)
            y = eval_labels.reshape(-1)
            # Mann-Whitney AUC with AVERAGE ranks over ties: a constant
            # predictor must score exactly 0.5, not whatever the input
            # ordering happens to produce.
            uniq_inverse = np.unique(q, return_inverse=True)[1]
            counts = np.bincount(uniq_inverse)
            last_rank = np.cumsum(counts)
            avg_rank = last_rank - (counts - 1) / 2.0
            ranks = avg_rank[uniq_inverse]
            n_pos, n_neg = float(y.sum()), float(len(y) - y.sum())
            auc = (ranks[y > 0.5].sum() - n_pos * (n_pos + 1) / 2) / (
                n_pos * n_neg
            )
            return auc, loss

        auc_f32, loss_f32 = train_and_auc(bf16=False)
        auc_bf16, loss_bf16 = train_and_auc(bf16=True)
        delta = abs(auc_f32 - auc_bf16)
        _emit(
            {
                "metric": metric,
                "value": round(delta, 4),
                "unit": "auc_delta",
                # Budget: <=0.02 (BASELINE.md); <1 means within budget.
                # vs_baseline on a budget-DELTA metric reads like a
                # throughput ratio at first glance;
                # fraction_of_budget is the same number under its honest
                # name (vs_baseline stays for cross-artifact tooling).
                "vs_baseline": round(delta / 0.02, 4),
                "fraction_of_budget": round(delta / 0.02, 4),
                "budget": 0.02,
                "detail": {
                    "auc_f32": round(auc_f32, 4),
                    "auc_bf16": round(auc_bf16, 4),
                    "final_loss_f32": round(loss_f32, 4),
                    "final_loss_bf16": round(loss_bf16, 4),
                    "train_steps": steps,
                    "batch_size": batch_size,
                    "eval_examples": n_eval,
                    "image_size": list(image_size),
                    "num_convs": list(num_convs),
                    "auc_method": "mann_whitney_rank",
                    "backend": devices[0].platform,
                    "device_kind": getattr(devices[0], "device_kind", "?"),
                    "f32_leg_precision": (
                        "xla_default" if on_tpu else "true_f32"
                    ),
                },
                **_proxy_fields(on_tpu, "qtopt_bf16_eval_auc_delta"),
            }
        )
    except Exception as err:  # noqa: BLE001
        _fail("auc_bench", err, metric=metric)


def bench_predict() -> None:
    """Robot-side serving latency: exported-model predict rate for the
    QT-Opt critic at CEM megabatch size (one call = one CEM iteration's
    objective evaluation over all samples).

    Invoked as `python bench.py predict`. The reference's design target is
    1-10 Hz action selection on a robot workstation (README.md:54-55);
    vs_baseline reports predict-calls/sec against the top of that band, so
    1.0 means every CEM iteration fits a 10 Hz loop with one iteration.
    """
    import os
    import tempfile

    devices = _devices("qtopt_cem_predict_hz")

    import jax

    on_tpu = devices[0].platform == "tpu"
    if on_tpu:
        image_size, num_convs = (472, 472), (6, 6, 3)
        metric = "qtopt_cem_predict_hz"
    else:
        image_size, num_convs = (96, 96), (2, 2, 1)
        metric = "qtopt_cem_predict_hz_cpu_proxy"
    cem_samples = int(os.environ.get("BENCH_PREDICT_SAMPLES", "64"))

    try:
        from __graft_entry__ import _flagship

        from tensor2robot_tpu.export.export_generators import (
            DefaultExportGenerator,
        )
        from tensor2robot_tpu.export.saved_model import save_exported_model
        from tensor2robot_tpu.predictors.exported_savedmodel_predictor import (
            ExportedSavedModelPredictor,
        )
        from tensor2robot_tpu.specs import make_random_numpy
        from tensor2robot_tpu.train.train_eval import CompiledModel

        def export_and_restore(export_root, action_batch_size=None):
            """One flagship export + restored predictor (the shared recipe
            for the raw-predict and jit-CEM legs — keep them identical)."""
            model, batch = _flagship(
                image_size=image_size,
                batch_size=2,
                num_convs=num_convs,
                action_batch_size=action_batch_size,
            )
            compiled = CompiledModel(model, donate_state=False)
            state = compiled.init_state(jax.random.PRNGKey(0), batch)
            generator = DefaultExportGenerator()
            generator.set_specification_from_model(compiled.model)
            variables = state.export_variables()
            save_exported_model(
                export_root,
                variables=variables,
                feature_spec=generator.serving_input_spec(),
                label_spec=generator.label_spec,
                global_step=0,
                predict_fn=generator.create_serving_fn(compiled, variables),
                example_features=generator.create_example_features(),
                serialize_stablehlo=True,
            )
            predictor = ExportedSavedModelPredictor(export_dir=export_root)
            if not predictor.restore():
                raise RuntimeError("predictor restore failed")
            return predictor, generator

        with tempfile.TemporaryDirectory() as root:
            predictor, generator = export_and_restore(root)
            features = make_random_numpy(
                generator.serving_input_spec(), batch_size=cem_samples, seed=0
            )

            n_windows, window = (8, 5) if on_tpu else (4, 3)

            def run_window():
                # _measure_windows divides by `window`, so run that many
                # calls; predict returns host numpy, hence self-syncing.
                for _ in range(window):
                    predictor.predict(features)

            run_window()  # compile + warm-up, untimed
            median_hz, best_hz, avg_hz = _measure_windows(
                run_window, lambda: None, n_windows, window
            )

            # Full action-selection rate under the jit-native CEM (the
            # whole sample/score/refit loop in ONE dispatch,
            # policies.JitCEMPolicy). Needs its own export with the CEM
            # population baked into the action spec (the tiling contract
            # an on-robot CEM deployment exports with).
            jit_cem_hz = 0.0
            jit_cem_error = None
            try:
                from tensor2robot_tpu.policies import JitCEMPolicy

                cem_predictor, cem_generator = export_and_restore(
                    os.path.join(root, "cem"),
                    action_batch_size=cem_samples,
                )
                policy = JitCEMPolicy(
                    cem_predictor,
                    action_size=10,
                    cem_samples=cem_samples,
                    cem_iterations=3,
                    seed=0,
                )
                cem_features = make_random_numpy(
                    cem_generator.serving_input_spec(), batch_size=1, seed=0
                )
                state_features = {
                    key: value[0]
                    for key, value in cem_features.items()
                    if key.startswith("state")
                }

                def run_select_window():
                    for _ in range(window):
                        policy.SelectAction(state_features)

                run_select_window()  # compile + warm-up
                jit_cem_hz, _, _ = _measure_windows(
                    run_select_window, lambda: None, n_windows, window
                )
            except Exception as cem_err:  # noqa: BLE001 — optional metric;
                # the error rides in the payload so a 0.0 is self-diagnosing.
                jit_cem_error = f"{type(cem_err).__name__}: {cem_err}"
                print(f"bench: jit-CEM path failed: {cem_err}", file=sys.stderr)
        _emit(
            {
                "metric": metric,
                "value": round(median_hz, 3),
                "unit": "predict_calls_per_sec",
                "vs_baseline": round(median_hz / 10.0, 4),
                "detail": {
                    "best_calls_per_sec": round(best_hz, 3),
                    "avg_calls_per_sec": round(avg_hz, 3),
                    "jit_cem_action_selects_per_sec": round(jit_cem_hz, 3),
                    **(
                        {"jit_cem_error": jit_cem_error}
                        if jit_cem_error
                        else {}
                    ),
                    "cem_samples_per_call": cem_samples,
                    "image_size": list(image_size),
                    "interface": "stablehlo_exported_model",
                    "reference_design_band_hz": [1, 10],
                },
                **_proxy_fields(on_tpu, "qtopt_cem_predict_hz"),
            }
        )
    except Exception as err:
        _fail("bench_predict", err, metric=metric)


def _analytic_bc_train_flops(
    batch, steps, image, d_model, num_layers, num_heads, head_dim,
    pose=14, action=7, mlp_ratio=4, attn_window=None,
) -> float:
    """One transformer-BC train step (fwd x3): conv embed + causal
    attention + MLP MACs x2. Analytic because the flash path's Pallas
    FLOPs are invisible to XLA cost analysis.

    attn_window counts only the USEFUL windowed pairs (sum_t min(t+1, W)
    = S*W - W*(W-1)/2) so the windowed metric cannot inflate its MFU with
    work the kernel skipped."""
    bt = float(batch * steps)
    h = image // 2
    flops = 2.0 * bt * h * h * 9 * 3 * 32  # conv1 3->32 /2
    h = h // 2
    flops += 2.0 * bt * h * h * 9 * 32 * 64  # conv2 32->64 /2
    flops += 2.0 * bt * (2 * 64 + pose) * d_model  # embed dense
    per_layer = (8.0 + 2.0 * mlp_ratio * 2.0) * bt * d_model * d_model
    if attn_window:
        w = min(attn_window, steps)
        pairs = float(steps) * w - w * (w - 1) / 2.0
    else:
        pairs = float(steps) * steps / 2.0  # causal half
    attn = 4.0 * batch * pairs * (num_heads * head_dim)  # QK^T + PV MACs
    flops += num_layers * (per_layer + attn)
    flops += 2.0 * bt * d_model * action
    return flops * 3.0


def bench_bc() -> None:
    """Long-context transformer BC train-step MFU — the attention family's
    headline (the flash kernels' model-level number, vs the conv critic's
    qtopt metric). TPU: batch 8 x 1024-step episodes, d_model 256; CPU
    proxy: tiny shapes under a distinct metric name."""
    devices = _devices("transformer_bc_train_mfu")

    import jax

    device = devices[0]
    on_tpu = device.platform == "tpu"
    if on_tpu:
        batch, steps, image = 8, 1024, 64
        d_model, num_layers, num_heads, head_dim = 256, 4, 8, 32
        n_windows, window = 8, 10
        metric = f"transformer_bc_train_mfu_b{batch}_t{steps}"
        # BENCH_BC_WINDOW=W benches the sliding-window variant (O(T*W)
        # attention) under a distinct metric name for the full-vs-window
        # on-chip comparison.
        attn_window = int(os.environ.get("BENCH_BC_WINDOW", "0")) or None
        if attn_window:
            metric += f"_w{attn_window}"
    else:
        batch, steps, image = 2, 64, 16
        d_model, num_layers, num_heads, head_dim = 32, 2, 2, 16
        n_windows, window = 3, 3
        metric = "transformer_bc_train_mfu_cpu_proxy"
        attn_window = None

    try:
        from tensor2robot_tpu.models.transformer_models import (
            TransformerBCModel,
        )
        from tensor2robot_tpu.specs import make_random_numpy
        from tensor2robot_tpu.train.train_eval import CompiledModel

        model = TransformerBCModel(
            pose_size=14,
            episode_length=steps,
            image_size=(image, image),
            d_model=d_model,
            num_layers=num_layers,
            num_heads=num_heads,
            head_dim=head_dim,
            attention_window=attn_window,
        )
        batch_np = {
            "features": make_random_numpy(
                model.get_feature_specification("train"), batch_size=batch
            ),
            "labels": make_random_numpy(
                model.get_label_specification("train"), batch_size=batch
            ),
        }
        compiled = CompiledModel(model, donate_state=True)
        state = compiled.init_state(jax.random.PRNGKey(0), batch_np)
        sharded = compiled.shard_batch(batch_np)
        rng = jax.random.PRNGKey(1)

        flops_per_step = _analytic_bc_train_flops(
            batch, steps, image, d_model, num_layers, num_heads, head_dim,
            attn_window=attn_window,
        )

        box = {"state": state}

        def run_window():
            for _ in range(window):
                box["state"], box["metrics"] = compiled.train_step(
                    box["state"], sharded, rng
                )

        def sync():
            if "metrics" in box:
                float(jax.device_get(box["metrics"]["loss"]))

        run_window()  # compile + warm-up, untimed
        steps_per_sec, best_steps_per_sec, avg_steps_per_sec = (
            _measure_windows(run_window, sync, n_windows, window)
        )

        peak = _peak_flops(device)
        mfu = flops_per_step * steps_per_sec / peak
        if mfu > 1.0:
            raise RuntimeError(
                f"implied MFU {mfu:.2f} exceeds 1.0 — timing did not "
                "capture execution (readback anchoring failed?)"
            )
        # Same-session matmul ceiling (as in the qtopt headline): the BC
        # family is the width-aligned workload of the ceiling proof, so
        # its MFU must be interpretable against what THIS session's MXU
        # actually sustains, not the nameplate peak.
        ceiling = {}
        if on_tpu:
            try:
                ceiling = _pin_matmul_ceiling(device)
            except Exception as pin_err:  # noqa: BLE001 — optional leg
                print(f"bench: ceiling pin failed: {pin_err}", file=sys.stderr)
        _emit(
            {
                "metric": metric,
                "value": round(mfu, 4),
                "unit": "fraction_of_peak",
                "vs_baseline": round(mfu / 0.5, 4),
                "detail": {
                    "steps_per_sec": round(steps_per_sec, 3),
                    "best_steps_per_sec": round(best_steps_per_sec, 3),
                    "avg_steps_per_sec": round(avg_steps_per_sec, 3),
                    "timing": "median_of_windows",
                    **ceiling,
                    **(
                        {
                            "mfu_vs_matmul_ceiling": round(
                                flops_per_step
                                * steps_per_sec
                                / (ceiling["matmul_ceiling_tflops"] * 1e12),
                                4,
                            )
                        }
                        if ceiling.get("matmul_ceiling_tflops")
                        else {}
                    ),
                    "flops_per_step": flops_per_step,
                    "flops_source": "analytic_transformer",
                    "device_kind": getattr(device, "device_kind", "?"),
                    "peak_flops": peak,
                    "shape": {
                        "batch": batch, "steps": steps, "image": image,
                        "d_model": d_model, "num_layers": num_layers,
                        "num_heads": num_heads, "head_dim": head_dim,
                    },
                    "attention": (
                        "xla reference (model default; flash is opt-in "
                        "after BENCH_FLASH_r03 measured the pallas kernel "
                        "at 0.7% MFU)"
                    ),
                },
                **_proxy_fields(on_tpu, "transformer_bc_train_mfu"),
            }
        )
    except Exception as err:  # noqa: BLE001
        _fail("bc_bench", err, metric=metric)


def bench_stream() -> None:
    """Streaming BC serving rate: control-loop steps/sec through the
    KV-cache StreamingBCPolicy (one jitted dispatch per step, O(window)
    attention). The serving-side counterpart of `bench.py bc`."""
    metric_base = "streaming_bc_policy_steps_per_sec"
    devices = _devices(metric_base)

    import jax
    import numpy as np

    device = devices[0]
    on_tpu = device.platform == "tpu"
    if on_tpu:
        episode, image, window = 1024, 64, 128
        d_model, num_layers, num_heads, head_dim = 256, 4, 8, 32
        metric = metric_base
    else:
        episode, image, window = 64, 16, 16
        d_model, num_layers, num_heads, head_dim = 32, 2, 2, 16
        metric = metric_base + "_cpu_proxy"

    try:
        from tensor2robot_tpu.models.transformer_models import (
            TransformerBCModel,
        )
        from tensor2robot_tpu.specs import make_random_numpy

        model = TransformerBCModel(
            pose_size=14, episode_length=episode, image_size=(image, image),
            d_model=d_model, num_layers=num_layers, num_heads=num_heads,
            head_dim=head_dim, attention_window=window,
        )
        features = make_random_numpy(
            model.get_feature_specification("predict"), batch_size=1
        )
        variables = model.init_variables(jax.random.PRNGKey(0), features)
        policy = model.create_streaming_policy(variables)
        img = np.asarray(features["image"])[0, 0]
        pose = np.asarray(features["gripper_pose"])[0, 0]

        policy.step(img, pose)  # compile
        for _ in range(5):
            policy.step(img, pose)  # warm-up
        # policy.step device_gets the action every call — self-anchoring.
        n_windows, calls = 5, 20
        times = []
        for _ in range(n_windows):
            policy.reset()
            t0 = time.perf_counter()
            for _ in range(calls):
                policy.step(img, pose)
            times.append((time.perf_counter() - t0) / calls)
        per_step = statistics.median(times)
        _emit(
            {
                "metric": metric,
                "value": round(1.0 / per_step, 2),
                "unit": "control_steps_per_sec",
                # Design band: the reference targets 1-10 Hz control.
                "vs_baseline": round((1.0 / per_step) / 10.0, 2),
                "detail": {
                    "per_step_ms": round(per_step * 1e3, 3),
                    "episode_capacity": episode,
                    "attention_window": window,
                    "image_size": [image, image],
                    "d_model": d_model,
                    "num_layers": num_layers,
                    "device_kind": getattr(device, "device_kind", "?"),
                    "timing": "median_of_windows",
                },
                **_proxy_fields(on_tpu, "streaming_bc_policy_steps_per_sec"),
            }
        )
    except Exception as err:  # noqa: BLE001
        _fail("stream_bench", err, metric=metric)


def bench_pipe() -> None:
    """End-to-end input composite: the REAL tfrecord
    parse pipeline — DefaultRecordInputGenerator -> parallel parse workers
    -> device_prefetch double-buffering — feeding the flagship train step,
    measured against the same step on a resident pre-sharded batch.

    Invoked as `python bench.py pipe`. value = end-to-end steps/sec;
    vs_baseline = e2e / resident ratio, i.e. the fraction of the chip's
    compute rate the host pipeline sustains when it must parse, decode,
    and transfer every batch (1.0 = host keeps the chip fed). `bench.py
    data` measures the host side alone; this leg closes the loop through
    the device.
    """
    import itertools
    import tempfile

    metric_base = "qtopt_e2e_pipeline_steps_per_sec"
    devices = _devices(metric_base)

    import jax
    import numpy as np

    device = devices[0]
    on_tpu = device.platform == "tpu"
    if on_tpu:
        image_size, num_convs, batch_size = (472, 472), (6, 6, 3), 64
        n_windows, window = 4, 5
        metric = metric_base
    else:
        image_size, num_convs, batch_size = (96, 96), (2, 2, 1), 4
        n_windows, window = 3, 2
        metric = metric_base + "_cpu_proxy"

    try:
        n_records = int(
            os.environ.get("BENCH_PIPE_RECORDS", str(batch_size * 2))
        )
    except ValueError as err:
        _fail("config", err, metric=metric)

    try:
        from __graft_entry__ import _flagship

        from tensor2robot_tpu.data import tfrecord
        from tensor2robot_tpu.data.dataset import (
            default_parse_backend,
            default_parse_workers,
        )
        from tensor2robot_tpu.data.encoder import encode_example
        from tensor2robot_tpu.data.input_generators import (
            DefaultRecordInputGenerator,
        )
        from tensor2robot_tpu.specs import make_random_numpy
        from tensor2robot_tpu.train import infeed as infeed_lib
        from tensor2robot_tpu.train.train_eval import CompiledModel

        model, batch = _flagship(
            image_size=image_size, batch_size=batch_size, num_convs=num_convs
        )
        specs = {
            "features": model.preprocessor.get_in_feature_specification(
                "train"
            ),
            "labels": model.preprocessor.get_in_label_specification("train"),
        }
        compiled = CompiledModel(model, donate_state=True)
        state = compiled.init_state(jax.random.PRNGKey(0), batch)
        resident = compiled.shard_batch(batch)
        rng = jax.random.PRNGKey(1)
        box = {"state": state}

        def run_resident_window():
            for _ in range(window):
                box["state"], box["metrics"] = compiled.train_step(
                    box["state"], resident, rng
                )

        def sync():
            if "metrics" in box:
                float(jax.device_get(box["metrics"]["loss"]))

        run_resident_window()  # compile + warm-up, untimed
        resident_sps, _, _ = _measure_windows(
            run_resident_window, sync, n_windows, window
        )

        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "pipe.tfrecord")
            rows = make_random_numpy(specs, batch_size=n_records, seed=0)
            records = [
                encode_example(
                    specs,
                    {key: np.asarray(value[i]) for key, value in rows.items()},
                )
                for i in range(n_records)
            ]
            tfrecord.write_tfrecords(path, records)

            generator = DefaultRecordInputGenerator(
                file_patterns=path, batch_size=batch_size
            )
            generator.set_specification_from_model(model, mode="train")
            batches = generator.create_dataset("train")

            def run_pipe_window():
                feed = infeed_lib.device_prefetch(
                    itertools.islice(batches, window),
                    compiled.shard_batch,
                    depth=2,
                )
                for device_batch in feed:
                    box["state"], box["metrics"] = compiled.train_step(
                        box["state"], device_batch, rng
                    )

            run_pipe_window()  # parse-pool + transfer-path warm-up, untimed
            sync()
            pipe_sps, best_pipe_sps, avg_pipe_sps = _measure_windows(
                run_pipe_window, sync, n_windows, window
            )

        # Same clamp discipline as the infeed ratio (_overlap_fields): a
        # parsed-and-transferred feed cannot beat the resident batch, so
        # a raw ratio above 1.0 is timing noise.
        raw_ratio = pipe_sps / resident_sps if resident_sps > 0 else 0.0
        ratio = min(raw_ratio, 1.0)
        _emit(
            {
                "metric": metric,
                "value": round(pipe_sps, 3),
                "unit": "steps_per_sec",
                "vs_baseline": round(ratio, 4),
                "detail": {
                    "resident_batch_steps_per_sec": round(resident_sps, 3),
                    "e2e_fraction_of_compute_rate": round(ratio, 4),
                    "e2e_fraction_of_compute_rate_raw": round(raw_ratio, 4),
                    **(
                        {
                            "e2e_fraction_note": (
                                "raw ratio exceeded 1.0 (timing noise); "
                                "clamped"
                            )
                        }
                        if raw_ratio > 1.0
                        else {}
                    ),
                    "best_e2e_steps_per_sec": round(best_pipe_sps, 3),
                    "avg_e2e_steps_per_sec": round(avg_pipe_sps, 3),
                    "batch_size": batch_size,
                    "records_in_file": n_records,
                    "parse_workers": default_parse_workers(),
                    "parse_backend": default_parse_backend(),
                    "host_cpus": os.cpu_count(),
                    "image_size": list(image_size),
                    "device_kind": getattr(device, "device_kind", "?"),
                    "timing": "median_of_windows",
                },
                **_proxy_fields(on_tpu, "qtopt_e2e_pipeline_steps_per_sec"),
            }
        )
    except Exception as err:  # noqa: BLE001
        _fail("pipe_bench", err, metric=metric)


def _serve_fixture(warmup_batch_sizes):
    """One exported mock model + restored predictor under a temp root.

    The serve bench measures the SERVER (queueing, coalescing, padding,
    hot-swap), not the model: the mock MLP makes per-call dispatch
    overhead the dominant cost, which is exactly the regime where
    micro-batching must earn its keep. Returns (tmpdir_handle,
    export_root, predictor, compiled, state, exporter)."""
    import tempfile

    import jax

    from tensor2robot_tpu.export.exporters import LatestExporter
    from tensor2robot_tpu.predictors.exported_savedmodel_predictor import (
        ExportedSavedModelPredictor,
    )
    from tensor2robot_tpu.train.train_eval import CompiledModel
    from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

    model = MockT2RModel(device_type="cpu")
    generator = MockInputGenerator(batch_size=8)
    generator.set_specification_from_model(model, "train")
    batches = iter(generator.create_dataset("train"))
    compiled = CompiledModel(model, donate_state=False)
    state = compiled.init_state(jax.random.PRNGKey(0), next(batches))
    tmpdir = tempfile.TemporaryDirectory(prefix="bench_serve_")
    exporter = LatestExporter(
        name="latest", warmup_batch_sizes=warmup_batch_sizes
    )
    exporter.maybe_export(
        step=1, state=state, eval_metrics={"loss": 1.0},
        compiled=compiled, model_dir=tmpdir.name,
    )
    export_root = exporter.export_root(tmpdir.name)
    predictor = ExportedSavedModelPredictor(export_dir=export_root)
    if not predictor.restore():
        raise RuntimeError("serve fixture: predictor restore failed")
    return tmpdir, export_root, predictor, compiled, state, exporter


def _serve_open_loop(
    server, request_fn, rate_hz, duration_s, deadline_ms, seed,
    swap_at_s=None, swap_fn=None,
):
    """Open-loop Poisson arrivals: interarrival times are drawn ahead of
    the clock and NEVER stretched by completions — the load the server
    sees at an offered rate is independent of how it is coping, which is
    what makes deadline-miss/shed counts meaningful. Returns the leg's
    measurement dict."""
    import numpy as np

    from tensor2robot_tpu.serving import ServeError
    from tensor2robot_tpu.serving.metrics import percentile

    rng = np.random.RandomState(seed)
    futures = []
    refused = 0
    swapped = swap_at_s is None
    t_start = time.monotonic()
    t_next = t_start
    t_end = t_start + duration_s
    while True:
        t_next += rng.exponential(1.0 / rate_hz)
        if t_next >= t_end:
            break
        if not swapped and t_next - t_start >= swap_at_s:
            swap_fn()
            swapped = True
        delay = t_next - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        try:
            futures.append((t_next - t_start, server.submit(
                request_fn(), deadline_ms=deadline_ms
            )))
        except ServeError:
            refused += 1  # reject-policy admission refusal
    offered = len(futures) + refused
    completions = []
    errors = {}
    versions = {}
    for t_offset, future in futures:
        try:
            response = future.result(timeout=deadline_ms / 1e3 + 30.0)
            completions.append((t_offset, response.spans.get("total_ms", 0.0)))
            versions[response.model_version] = (
                versions.get(response.model_version, 0) + 1
            )
        except Exception as err:  # noqa: BLE001 — shed/deadline failures are
            # the measurement, not a bench failure.
            errors[type(err).__name__] = errors.get(type(err).__name__, 0) + 1
    latencies = sorted(lat for _, lat in completions)

    def pct(q):
        return percentile(latencies, q)

    wall = time.monotonic() - t_start
    snap = server.snapshot()
    return {
        "offered_hz": round(rate_hz, 2),
        "offered_requests": offered,
        "completed": len(completions),
        "completed_hz": round(len(completions) / wall, 2),
        "refused_at_admission": refused,
        "errors": errors,
        "p50_ms": round(pct(0.50), 3),
        "p99_ms": round(pct(0.99), 3),
        "batch_fill_ratio": round(snap["batch_fill_ratio"], 4),
        "deadline_missed": snap["counters"]["deadline_missed"],
        "shed": snap["counters"]["shed"],
        "rejected": snap["counters"]["rejected"],
        "versions_seen": {str(k): v for k, v in sorted(versions.items())},
        "latencies_by_offset": [
            (round(t, 3), round(lat, 3)) for t, lat in completions
        ],
    }


def bench_serve(args) -> None:
    """Fleet-serving leg: policy-server throughput/latency vs the
    sequential single-request baseline, open-loop Poisson load sweep,
    and a hot-swap under load (docs/SERVING.md "Fleet serving").

    Invoked as `python bench.py serve`. Always a host-side measurement
    (the server IS host code); on this image it runs on the CPU proxy
    and reports proxy fields like the other legs.
    """
    import os

    devices = _devices("policy_serve_throughput")
    on_tpu = devices[0].platform == "tpu"
    metric = (
        "policy_serve_throughput"
        if on_tpu
        else "policy_serve_throughput_cpu_proxy"
    )
    if not args.no_quant:
        _refuse_children_on_chip(
            devices,
            metric,
            "bench.py serve's static-calib boot twins (part of the quant "
            "leg; --no-quant runs the in-process legs alone)",
        )

    import numpy as np

    try:
        from tensor2robot_tpu.serving import PolicyServer

        buckets = tuple(int(b) for b in args.buckets.split(","))
        tmpdir, export_root, predictor, compiled, state, exporter = (
            _serve_fixture(buckets)
        )
        rng = np.random.RandomState(0)

        def request_fn():
            return {"x": rng.uniform(-1, 1, size=(3,)).astype(np.float32)}

        # -- sequential single-request baseline (no server): one client,
        # one predict per request, batch 1 — the pre-subsystem topology.
        # Median of 3 windows: this host's clock throttling makes single
        # windows swing +/-30%.
        one = {"x": np.zeros((1, 3), np.float32)}
        predictor.predict(one)  # compile batch-1, untimed

        def seq_window():
            t0 = time.monotonic()
            calls = 0
            while time.monotonic() - t0 < max(0.8, args.baseline_secs / 3):
                predictor.predict(one)
                calls += 1
            return calls / (time.monotonic() - t0)

        seq_rates = sorted(seq_window() for _ in range(3))
        seq_hz = seq_rates[1]

        # -- saturation: a burst far deeper than any bucket, drained
        # through the server. Batched throughput at 100% fill.
        def make_saturation_server(prewarm):
            return PolicyServer(
                predictor, max_queue=args.burst + 8, max_wait_ms=2,
                default_deadline_ms=120000,
            ).start(prewarm=prewarm)

        def run_burst(server, n):
            t0 = time.monotonic()
            futures = [server.submit(request_fn()) for _ in range(n)]
            for future in futures:
                future.result(timeout=120)
            return n / (time.monotonic() - t0)

        warm_server = make_saturation_server(prewarm=True)  # compiles buckets
        run_burst(warm_server, args.burst // 2)  # thread warm-up, untimed
        warm_server.stop()
        # Fresh server for the timed bursts so the snapshot (batch fill,
        # batches-by-bucket) describes ONLY the measured saturation
        # traffic, not warm-up partial batches.
        server = make_saturation_server(prewarm=False)
        burst_rates = sorted(run_burst(server, args.burst) for _ in range(5))
        sat_hz = burst_rates[2]  # median of 5
        sat_snapshot = server.snapshot()
        server.stop()
        speedup = sat_hz / seq_hz if seq_hz > 0 else 0.0

        # -- open-loop capacity probe: burst saturation overstates what
        # the OPEN-LOOP topology sustains (the Poisson submitter thread
        # itself costs GIL share), so offered-load fractions must be
        # calibrated against a measured open-loop ceiling, not the burst
        # number — otherwise "25% load" silently means overload.
        server = PolicyServer(
            predictor, max_wait_ms=args.max_wait_ms, max_queue=1024
        )
        server.start(prewarm=False)  # shapes already compiled above
        probe = _serve_open_loop(
            server, request_fn, rate_hz=max(10.0, 0.5 * sat_hz),
            duration_s=2.5, deadline_ms=10000, seed=99,
        )
        server.stop()
        capacity_hz = max(1.0, probe["completed_hz"])

        # -- open-loop Poisson sweep at fractions of the open-loop
        # capacity. Fresh server per leg isolates the counters.
        # max_queue sized to ride out this host's observed multi-hundred-
        # ms throttle stalls (visible in the burst-rate spread) without
        # shedding at sub-saturation loads; the queue-full policies are
        # measured explicitly at load_90 and in the unit tests.
        legs = {}
        for fraction in (0.25, 0.45, 0.9):
            server = PolicyServer(
                predictor, max_wait_ms=args.max_wait_ms, max_queue=1024
            )
            server.start(prewarm=False)
            leg = _serve_open_loop(
                server,
                request_fn,
                rate_hz=max(1.0, fraction * capacity_hz),
                duration_s=args.leg_secs,
                deadline_ms=args.deadline_ms,
                seed=int(fraction * 100),
            )
            leg.pop("latencies_by_offset")
            leg["offered_load_fraction"] = fraction
            legs[f"load_{int(fraction * 100):02d}"] = leg
            server.stop()

        # -- hot-swap under load: export v2 mid-leg, async restore; no
        # request may fail, versions must transition within the leg.
        # Moderate (25%) load + a deep queue: the claim under test is
        # zero-downtime swap, not backpressure (measured above).
        server = PolicyServer(
            predictor, max_wait_ms=args.max_wait_ms, max_queue=2048
        )
        server.start(prewarm=False)
        v1 = predictor.model_version
        swap_threads = []

        def do_swap():
            # The in-leg export writes the PRE-AOT layout: this leg
            # measures serving continuity under a rolling swap, and the
            # exporter's per-bucket AOT compiles (several GIL-held
            # seconds on one host) belong to the learner's publish
            # process in production — bench.py aot measures that side
            # (publish->swap 17.5 ms with AOT artifacts, BENCH_AOT_r15).
            # Colocating them here would charge the dispatcher for
            # compile stalls no serving replica ever pays.
            from tensor2robot_tpu import flags as _flags

            saved_aot_export = _flags.read_raw("T2R_AOT_EXPORT")
            _flags.write_env("T2R_AOT_EXPORT", False)
            try:
                exporter.maybe_export(
                    step=2, state=state, eval_metrics={"loss": 0.9},
                    compiled=compiled, model_dir=tmpdir.name,
                )
            finally:
                _flags.restore_env("T2R_AOT_EXPORT", saved_aot_export)
            server.hot_swap()

        def swap_fn():
            # Export + restore run off the submitter thread: the arrival
            # process must not pause while the new version materializes
            # (that IS the zero-downtime claim under test).
            import threading

            thread = threading.Thread(target=do_swap, daemon=True)
            thread.start()
            swap_threads.append(thread)

        swap_at = args.leg_secs * 0.35
        swap_leg = _serve_open_loop(
            server,
            request_fn,
            rate_hz=max(1.0, 0.25 * capacity_hz),
            duration_s=args.leg_secs,
            # Generous deadline: this leg measures swap continuity (zero
            # failed requests), not deadline behavior — that's the sweep's
            # job. The blip magnitude still rides in the payload.
            deadline_ms=max(args.deadline_ms, 4 * 1e3),
            seed=7,
            swap_at_s=swap_at,
            swap_fn=swap_fn,
        )
        for thread in swap_threads:
            thread.join(timeout=60)
        # The async restore may still be deserializing; give the swap a
        # bounded window to land before reading the final version.
        poll_deadline = time.monotonic() + 30
        while predictor.model_version == v1 and time.monotonic() < poll_deadline:
            time.sleep(0.05)
        server.stop()
        v2 = predictor.model_version
        from tensor2robot_tpu.serving.metrics import percentile

        by_offset = swap_leg.pop("latencies_by_offset")
        pre = sorted(l for t, l in by_offset if t < swap_at)
        post_window = sorted(l for t, l in by_offset if swap_at <= t < swap_at + 1.0)
        swap_leg.update(
            {
                "swap_at_s": swap_at,
                "version_before": v1,
                "version_after": v2,
                "swap_observed": v2 > v1,
                "failed_requests": sum(swap_leg["errors"].values()),
                "p99_before_swap_ms": round(percentile(pre, 0.99), 3),
                "blip_max_ms_1s_after_swap": round(
                    max(post_window), 3
                ) if post_window else 0.0,
            }
        )

        # -- quant legs (BENCH_SERVE_r11, compute attribution added in
        # r16): the SAME trained weights exported with blockwise
        # fp16/int8/fp8 serve-quant payloads, served through the same
        # policy-server topology per regime. Metrics: bytes-of-param
        # (the restore/deploy cost a replica fleet pays per version),
        # saturated req/s, and — new in r16 — the compiled-program dot
        # audit: contraction ops per regime by OPERAND dtype, proving
        # whether the matmuls executed on int8/fp8 operands (native
        # lowering) or dequantized back to f32 first. On a CPU proxy
        # there are no int8/fp8 matmul units, so the bytes win plus the
        # dtype attribution are the headline and req/s is reported with
        # attribution either way.
        quant_detail = None
        if not args.no_quant:
            from tensor2robot_tpu import flags as t2r_flags
            from tensor2robot_tpu.export import serve_quant as sq_lib
            from tensor2robot_tpu.export.exporters import LatestExporter
            from tensor2robot_tpu.export.saved_model import (
                STABLEHLO_DIR,
                STABLEHLO_FILENAME,
                latest_export_dir,
                quant_payload_relpath,
                quant_stablehlo_relpath,
            )
            from tensor2robot_tpu.predictors.exported_savedmodel_predictor import (
                ExportedSavedModelPredictor,
            )

            quant_regimes = ("fp16", "int8", "fp8_e4m3", "fp8_e5m2")
            quant_exporter = LatestExporter(
                name="quant", warmup_batch_sizes=buckets,
                serve_quant=quant_regimes,
            )
            quant_exporter.maybe_export(
                step=1, state=state, eval_metrics={"loss": 1.0},
                compiled=compiled, model_dir=tmpdir.name,
            )
            quant_root = quant_exporter.export_root(tmpdir.name)
            quant_path = latest_export_dir(quant_root)

            def _dir_bytes(root):
                total = 0
                for base, _dirs, files in os.walk(root):
                    total += sum(
                        os.path.getsize(os.path.join(base, name))
                        for name in files
                    )
                return total

            with open(
                os.path.join(quant_path, "t2r_metadata.json")
            ) as meta_f:
                quant_meta = json.load(meta_f)["serve_quant"]
            fp32_params_bytes = os.path.getsize(
                os.path.join(quant_path, "variables.msgpack")
            )
            saved_regime = t2r_flags.read_raw("T2R_SERVE_QUANT")
            # Every in-process req/s in this section serves through the
            # SAME restore tier (fresh jit): the main artifact carries
            # aot/ while the A/B variants deliberately don't, and a
            # deserialized-executable dispatch vs a jitted dispatch
            # would contaminate the native-vs-dequant and
            # static-vs-dynamic ratios. The AOT tier is measured by the
            # out-of-process cold-boot gate below, against this same
            # artifact.
            saved_serve_aot = t2r_flags.read_raw("T2R_SERVE_AOT")
            t2r_flags.write_env("T2R_SERVE_AOT", False)

            def low_precision_ops(audit):
                return sum(
                    count
                    for key, count in audit.items()
                    if key != "total" and ("i8" in key or "f8" in key)
                )

            regimes = {}
            try:
                for regime in ("none",) + quant_regimes:
                    t2r_flags.write_env("T2R_SERVE_QUANT", regime)
                    quant_predictor = ExportedSavedModelPredictor(
                        export_dir=quant_root
                    )
                    if not quant_predictor.restore():
                        raise RuntimeError(
                            f"quant leg: restore failed for {regime}"
                        )
                    t_restore0 = time.monotonic()
                    quant_server = PolicyServer(
                        quant_predictor, max_queue=args.burst + 8,
                        max_wait_ms=2, default_deadline_ms=120000,
                    ).start(prewarm=True)
                    prewarm_s = time.monotonic() - t_restore0
                    try:
                        run_burst(quant_server, args.burst // 2)  # warm-up
                        regime_rates = sorted(
                            run_burst(quant_server, args.burst)
                            for _ in range(3)
                        )
                        served = quant_server.snapshot()["serve_quant"]
                        if served != regime:
                            raise RuntimeError(
                                f"quant leg served regime {served!r}, "
                                f"wanted {regime!r}"
                            )
                    finally:
                        # A failed leg must not leak the dispatcher/
                        # monitor threads into the rest of the bench.
                        quant_server.stop()
                    params_bytes = (
                        fp32_params_bytes
                        if regime == "none"
                        else os.path.getsize(
                            os.path.join(
                                quant_path, quant_payload_relpath(regime)
                            )
                        )
                    )
                    if regime == "none":
                        compute_attr = {}
                    else:
                        # Compute attribution: re-audit the ARTIFACT
                        # bytes this leg just served (contraction ops by
                        # operand dtype) and cross-check against the
                        # audit the export recorded — the proof that
                        # native regimes' matmuls stayed int8/fp8 in
                        # the program that actually dispatched.
                        with open(
                            os.path.join(
                                quant_path, quant_stablehlo_relpath(regime)
                            ),
                            "rb",
                        ) as program_f:
                            measured_audit = sq_lib.audit_dot_dtypes(
                                program_f.read()
                            )
                        recorded_audit = quant_meta.get("dot_audit", {}).get(
                            regime
                        )
                        low_precision_dots = low_precision_ops(
                            measured_audit
                        )
                        compute_attr = {
                            "dot_ops": measured_audit,
                            "dot_ops_match_export_record": (
                                recorded_audit == measured_audit
                            ),
                            "low_precision_dot_ops": low_precision_dots,
                            "native_layers": quant_meta["native"][regime][
                                "layers"
                            ],
                            "native_demoted": quant_meta["native"][regime][
                                "demoted"
                            ],
                            "parity_recorded": quant_meta["parity"][regime],
                        }
                    regimes[regime] = {
                        "saturated_hz": round(regime_rates[1], 2),
                        "burst_rates_hz": [
                            round(rate, 2) for rate in regime_rates
                        ],
                        "params_bytes": params_bytes,
                        "params_bytes_reduction_x": round(
                            fp32_params_bytes / params_bytes, 3
                        ),
                        "prewarm_s": round(prewarm_s, 3),
                        **compute_attr,
                    }
            finally:
                t2r_flags.restore_env("T2R_SERVE_QUANT", saved_regime)

            # -- dequant-vs-native A/B (the leg PERFORMANCE.md round 16
            # promised): the SAME weights re-exported with native
            # lowering forced off (T2R_SERVE_NATIVE_LAYERS=none), served
            # through the identical topology — attributed req/s plus the
            # audit delta proving the two artifacts differ exactly in
            # WHERE they compute, nothing else. A second A/B flips the
            # calibration mode (static vs dynamic) and re-audits the
            # reduce counts on the artifacts this leg just served.
            def export_int8_variant(name, env_flags=(), **exporter_kwargs):
                saved = {key: t2r_flags.read_raw(key) for key, _ in env_flags}
                saved["T2R_AOT_EXPORT"] = t2r_flags.read_raw("T2R_AOT_EXPORT")
                for key, value in env_flags:
                    t2r_flags.write_env(key, value)
                # The A/B exports measure serving, not deploys: skip
                # their AOT compiles (the MAIN quant export keeps its
                # aot/ dir for the static cold-boot gate below).
                t2r_flags.write_env("T2R_AOT_EXPORT", False)
                try:
                    variant_exporter = LatestExporter(
                        name=name, warmup_batch_sizes=buckets,
                        serve_quant=("int8",), **exporter_kwargs,
                    )
                    variant_exporter.maybe_export(
                        step=1, state=state, eval_metrics={"loss": 1.0},
                        compiled=compiled, model_dir=tmpdir.name,
                    )
                finally:
                    for key, value in saved.items():
                        t2r_flags.restore_env(key, value)
                root = variant_exporter.export_root(tmpdir.name)
                return root, latest_export_dir(root)

            def serve_int8_burst(root):
                saved = t2r_flags.read_raw("T2R_SERVE_QUANT")
                t2r_flags.write_env("T2R_SERVE_QUANT", "int8")
                try:
                    variant_predictor = ExportedSavedModelPredictor(
                        export_dir=root
                    )
                    if not variant_predictor.restore():
                        raise RuntimeError("A/B leg: restore failed")
                    variant_server = PolicyServer(
                        variant_predictor, max_queue=args.burst + 8,
                        max_wait_ms=2, default_deadline_ms=120000,
                    ).start(prewarm=True)
                    try:
                        run_burst(variant_server, args.burst // 2)  # warm-up
                        rates = sorted(
                            run_burst(variant_server, args.burst)
                            for _ in range(3)
                        )
                    finally:
                        variant_server.stop()
                    return rates[1]
                finally:
                    t2r_flags.restore_env("T2R_SERVE_QUANT", saved)

            def artifact_audits(path):
                with open(
                    os.path.join(path, quant_stablehlo_relpath("int8")), "rb"
                ) as program_f:
                    program = program_f.read()
                with open(
                    os.path.join(path, STABLEHLO_DIR, STABLEHLO_FILENAME),
                    "rb",
                ) as baseline_f:
                    baseline = baseline_f.read()
                return (
                    sq_lib.audit_dot_dtypes(program),
                    sq_lib.audit_quant_reduces(program, baseline),
                )

            dequant_root, dequant_path = export_int8_variant(
                "quant_dequant",
                env_flags=(
                    ("T2R_SERVE_NATIVE_LAYERS", "none"),
                    ("T2R_SERVE_NATIVE_ATTN", "none"),
                ),
            )
            dequant_hz = serve_int8_burst(dequant_root)
            dequant_dots, dequant_reduces = artifact_audits(dequant_path)
            native_hz = regimes["int8"]["saturated_hz"]
            native_ab = {
                "native_saturated_hz": native_hz,
                "dequant_saturated_hz": round(dequant_hz, 2),
                "native_vs_dequant_req_s_x": round(
                    native_hz / max(dequant_hz, 1e-9), 3
                ),
                "native_low_precision_dot_ops": low_precision_ops(
                    regimes["int8"]["dot_ops"]
                ),
                "dequant_low_precision_dot_ops": low_precision_ops(
                    dequant_dots
                ),
                "dequant_dot_ops": dequant_dots,
                # The audit delta is the attribution: same weights, same
                # corpus, the dequant twin shows ZERO low-precision
                # contractions while the native artifact shows them all.
                "audit_delta_proves_lowering": (
                    low_precision_ops(regimes["int8"]["dot_ops"]) >= 1
                    and low_precision_ops(dequant_dots) == 0
                ),
            }

            dyncalib_root, dyncalib_path = export_int8_variant(
                "quant_dyncalib", serve_calib="dynamic"
            )
            dynamic_hz = serve_int8_burst(dyncalib_root)
            _, dynamic_reduces = artifact_audits(dyncalib_path)
            static_dots, static_reduces = artifact_audits(quant_path)
            static_mode = quant_meta.get("calib", {}).get("int8", {}).get(
                "mode"
            )
            calib_ab = {
                "static_calib_mode": static_mode,
                "static_saturated_hz": regimes["int8"]["saturated_hz"],
                "dynamic_saturated_hz": round(dynamic_hz, 2),
                "static_vs_dynamic_req_s_x": round(
                    regimes["int8"]["saturated_hz"] / max(dynamic_hz, 1e-9),
                    3,
                ),
                # Re-audited from the ARTIFACT bytes each sub-leg just
                # served, cross-checked against the export record.
                "static_reduce_audit": static_reduces,
                "dynamic_reduce_audit": dynamic_reduces,
                "reduce_audit_match_export_record": (
                    quant_meta.get("reduce_audit", {}).get("int8")
                    == static_reduces
                ),
                "static_zero_reduce_pass": (
                    static_mode == "static"
                    and static_reduces.get("activation_quant_reduces") == 0
                ),
                "dynamic_reduces_match_native_layers": (
                    dynamic_reduces.get("activation_quant_reduces")
                    == len(quant_meta["native"]["int8"]["layers"])
                ),
            }

            t2r_flags.restore_env("T2R_SERVE_AOT", saved_serve_aot)

            # -- static-calib AOT cold boot (out of process, like
            # bench.py aot's twins): the statically-calibrated int8
            # artifact must deserialize every bucket (zero fresh
            # compiles) and serve BITWISE what its fresh-compile twin
            # serves — the full-artifact-ladder acceptance for the new
            # calibration mode.
            import subprocess

            def run_quant_boot(mode, serve_aot):
                out_path = os.path.join(
                    tmpdir.name, f"boot_quant_{mode}.json"
                )
                cmd = [
                    sys.executable, os.path.abspath(__file__), "aot",
                    "--_boot", "--export-root", quant_root,
                    "--json-out", out_path,
                ]
                env = _aot_scrubbed_env(serve_aot, devices[0].platform)
                env["T2R_SERVE_QUANT"] = "int8"
                proc = subprocess.run(
                    cmd, env=env, capture_output=True, text=True,
                    timeout=420,
                )
                if proc.returncode != 0:
                    raise RuntimeError(
                        f"static-calib boot twin {mode!r} failed "
                        f"rc={proc.returncode}: "
                        + "\n".join((proc.stderr or "").splitlines()[-5:])
                    )
                with open(out_path) as report_f:
                    return json.load(report_f)

            aot_boot = run_quant_boot("aot", serve_aot=True)
            fresh_boot = run_quant_boot("fresh", serve_aot=False)
            static_aot = {
                "calib_mode": aot_boot.get("serve_quant_calib"),
                "fresh_trace_calls": aot_boot["fresh_trace_calls"],
                "prewarm_source": aot_boot["prewarm_source"],
                "aot_cold_start_s": aot_boot["cold_start_s"],
                "fresh_cold_start_s": fresh_boot["cold_start_s"],
                "bitwise_vs_fresh": (
                    aot_boot["outputs_sha256"] == fresh_boot["outputs_sha256"]
                ),
                "zero_fresh_compiles": (
                    aot_boot["fresh_trace_calls"] == 0
                    and aot_boot["aot_misses"] == 0
                    and set(aot_boot["prewarm_source"].values()) == {"aot"}
                ),
            }

            int8_x = regimes["int8"]["params_bytes_reduction_x"]
            int8_speed = (
                regimes["int8"]["saturated_hz"]
                / max(regimes["none"]["saturated_hz"], 1e-9)
            )
            native_regime_audit = {
                regime: regimes[regime]["low_precision_dot_ops"]
                for regime in quant_regimes
                if regimes[regime].get("native_layers")
            }
            native_audit_pass = bool(native_regime_audit) and all(
                count >= 1 for count in native_regime_audit.values()
            )
            quant_detail = {
                "regimes": regimes,
                "artifact_bytes_total": _dir_bytes(quant_path),
                "int8_params_bytes_reduction_x": int8_x,
                "int8_reduction_target": 3.5,
                "int8_req_s_vs_none_x": round(int8_speed, 3),
                # The r16 acceptance surface: every native regime shows
                # >= 1 contraction executing on int8/fp8 operands in the
                # program it served this leg with.
                "native_low_precision_dot_ops": native_regime_audit,
                "native_audit_pass": native_audit_pass,
                # Round-18 legs: dequant-vs-native req/s attribution,
                # static-vs-dynamic calibration with re-audited reduce
                # counts, and the static-calib AOT cold-boot gate.
                "native_ab": native_ab,
                "calib_ab": calib_ab,
                "static_aot_boot": static_aot,
                "r18_all_green": bool(
                    native_audit_pass
                    and native_ab["audit_delta_proves_lowering"]
                    and calib_ab["static_zero_reduce_pass"]
                    and calib_ab["dynamic_reduces_match_native_layers"]
                    and calib_ab["reduce_audit_match_export_record"]
                    and static_aot["bitwise_vs_fresh"]
                    and static_aot["zero_fresh_compiles"]
                ),
                "req_s_attribution": (
                    "CPU proxy: no int8/fp8 matmul units, so the native "
                    "dot_generals in the audited programs execute via "
                    "XLA:CPU emulation and req/s reflects host dispatch "
                    "+ emulated low-precision compute. The dtype audit "
                    "(dot_ops per regime) is the transferable result: "
                    "the SAME artifact bytes dispatch int8/fp8 "
                    "contractions on hardware with native units, where "
                    "the smaller operand reads and 2x-4x matmul "
                    "throughput are the lever. Bytes-of-param reduction "
                    "(restore/deploy cost) holds on every host."
                ),
            }

        tmpdir.cleanup()
        payload = {
            "metric": metric,
            "value": round(sat_hz, 2),
            "unit": "requests_per_sec",
            # Target: batched serving >= 3x the sequential baseline.
            "vs_baseline": round(speedup / 3.0, 4),
            "detail": {
                "sequential_baseline_hz": round(seq_hz, 2),
                "sequential_baseline_windows_hz": [
                    round(rate, 2) for rate in seq_rates
                ],
                "saturated_hz": round(sat_hz, 2),
                "open_loop_capacity_hz": round(capacity_hz, 2),
                "saturation_burst_rates_hz": [
                    round(rate, 2) for rate in burst_rates
                ],
                "batched_speedup": round(speedup, 2),
                "speedup_target": 3.0,
                "buckets": list(buckets),
                "saturation_batch_fill": round(
                    sat_snapshot["batch_fill_ratio"], 4
                ),
                "saturation_batches_by_bucket": sat_snapshot[
                    "batches_by_bucket"
                ],
                "open_loop": legs,
                "hot_swap": swap_leg,
                **({"quant": quant_detail} if quant_detail else {}),
                "deadline_ms": args.deadline_ms,
                "max_wait_ms": args.max_wait_ms,
                "host_cpus": os.cpu_count(),
                "device_kind": getattr(devices[0], "device_kind", "?"),
                "model": "mock_mlp_3feature",
            },
            **_proxy_fields(on_tpu, "policy_serve_throughput"),
        }
        if args.out:
            with open(args.out, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
        _emit(payload)
    except Exception as err:  # noqa: BLE001
        _fail("bench_serve", err, metric=metric)


def _aot_scrubbed_env(serve_aot: bool, platform: str, cache_dir=None) -> dict:
    """Child-boot environment: ambient AOT/cache settings scrubbed so
    each twin measures exactly its own tier (a leaked
    JAX_COMPILATION_CACHE_DIR would silently turn the fresh-compile twin
    into the cache twin). `platform` pins the child to the PARENT's
    backend — the fixture's executables are topology-keyed, so a child
    on a different platform would measure the fallback path, not the
    AOT tier."""
    import os

    env = dict(os.environ)
    # Every serving flag the child resolves is scrubbed: a leaked bucket
    # ladder or quant regime would change what the twins boot (and fail
    # the acceptance gates) as surely as a leaked cache dir would.
    for key in (
        "T2R_SERVE_AOT", "T2R_AOT_REQUIRE", "JAX_COMPILATION_CACHE_DIR",
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
        "T2R_SERVE_BUCKETS", "T2R_SERVE_QUANT",
    ):
        env.pop(key, None)
    env["T2R_SERVE_AOT"] = "1" if serve_aot else "0"
    if cache_dir:
        # jax reads both at import; the child places no cache in code.
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PLATFORMS"] = str(platform)
    return env


def _aot_boot_child(args) -> None:
    """Hidden `bench.py aot --_boot` mode: ONE fresh process = one cold
    replica boot. Measures restore -> full-prewarm server start -> first
    reply against whatever restore tier the environment selects (the
    parent sets T2R_SERVE_AOT / JAX_COMPILATION_CACHE_DIR), and reports the
    audit surface (prewarm sources, aot counters, fresh_trace_calls) the
    acceptance gates read. Out-of-process on purpose: jax's in-memory
    executable caches would otherwise let the second twin ride the
    first's compiles."""
    import os

    import numpy as np

    from tensor2robot_tpu.predictors.exported_savedmodel_predictor import (
        ExportedSavedModelPredictor,
    )
    from tensor2robot_tpu.serving import PolicyServer
    from tensor2robot_tpu.utils.compile_cache import placed_cache_dir
    from tensor2robot_tpu.specs import flatten_spec_structure, make_random_numpy

    import jax  # noqa: F401 — placed_cache_dir reads the imported config

    cache_dir = placed_cache_dir()
    cache_before = (
        len(os.listdir(cache_dir))
        if cache_dir and os.path.isdir(cache_dir)
        else 0
    )
    t0 = time.monotonic()
    predictor = ExportedSavedModelPredictor(export_dir=args.export_root)
    if not predictor.restore():
        raise RuntimeError("aot boot child: restore failed")
    t_restored = time.monotonic()
    server = PolicyServer(predictor, max_wait_ms=1).start(prewarm=True)
    t_started = time.monotonic()
    spec = predictor.get_feature_specification()
    row = {
        key: np.asarray(value)[0]
        for key, value in flatten_spec_structure(
            make_random_numpy(spec, batch_size=1, seed=0)
        ).items()
    }
    response = server.call(row, deadline_ms=120000, timeout=120)
    t_first_reply = time.monotonic()
    snap = server.snapshot()
    server.stop()
    loaded = predictor.loaded_model
    # Bitwise-comparison surface: the reply digest over the seeded
    # request row (identical across twins by construction), so the
    # parent can assert an AOT boot serves bit-identically to its
    # fresh-compile twin without shipping arrays through JSON.
    import hashlib

    digest = hashlib.sha256()
    for key in sorted(response.outputs):
        digest.update(key.encode())
        digest.update(np.ascontiguousarray(response.outputs[key]).tobytes())
    report = {
        "outputs_sha256": digest.hexdigest(),
        "serve_quant": snap.get("serve_quant"),
        "serve_quant_calib": snap.get("serve_quant_calib"),
        "restore_s": round(t_restored - t0, 4),
        "server_start_s": round(t_started - t_restored, 4),
        "first_reply_ms": round((t_first_reply - t_started) * 1e3, 3),
        "cold_start_s": round(t_first_reply - t0, 4),
        "prewarm_source": snap["prewarm_source"],
        "aot_hits": snap["counters"]["aot_hits"],
        "aot_misses": snap["counters"]["aot_misses"],
        "aot_fallbacks": snap.get("aot_fallbacks", {}),
        "fresh_trace_calls": getattr(loaded, "fresh_trace_calls", None),
        "model_version": response.model_version,
        "cache_entries_added": (
            len(os.listdir(cache_dir)) - cache_before
            if cache_dir and os.path.isdir(cache_dir)
            else 0
        ),
    }
    with open(args.json_out, "w") as f:
        json.dump(report, f)


def bench_aot(args) -> None:
    """Instant-deploy leg (`python bench.py aot`): cold-start-to-first-
    reply and rolling-swap behavior with serialized AOT executables vs
    the persistent-cache and fresh-compile tiers (docs/SERVING.md "AOT
    executables").

    Three out-of-process boot twins over the SAME exported artifact:
    `fresh` (T2R_SERVE_AOT=0, no cache), `cache` (T2R_SERVE_AOT=0 +
    JAX_COMPILATION_CACHE_DIR; booted twice, the second boot is the
    steady-state measurement), and `aot` (deserialize per bucket).
    Acceptance: the AOT boot performs ZERO fresh bucket compiles
    (prewarm_source all "aot", fresh_trace_calls == 0, no misses) and
    its cold start is strictly below the fresh twin's. The in-process
    half measures the publish->swap cycle: hot-swap latency (swap
    request -> new version serving, prewarm included) with AOT vs with
    the compile path, under open-loop load with zero failed requests.
    """
    import os
    import subprocess

    if getattr(args, "boot", False):
        _aot_boot_child(args)
        return
    # The parent measures the compile tier in-process (the swap legs),
    # so it engages no persistent cache of its own.
    devices = _devices("serve_cold_start_aot_speedup", compile_cache=False)
    _refuse_children_on_chip(
        devices, "serve_cold_start_aot_speedup", "bench.py aot boot twins"
    )
    on_tpu = False
    metric = "serve_cold_start_aot_speedup_cpu_proxy"

    import numpy as np

    try:
        from tensor2robot_tpu import flags as t2r_flags
        from tensor2robot_tpu.serving import PolicyServer
        from tensor2robot_tpu.serving.metrics import percentile

        buckets = tuple(int(b) for b in args.buckets.split(","))
        # The fixture export carries AOT executables (T2R_AOT_EXPORT
        # default); the same artifact serves every twin — only the
        # restore tier differs.
        tmpdir, export_root, predictor, compiled, state, exporter = (
            _serve_fixture(buckets)
        )
        with open(
            os.path.join(
                _latest_export_dir_for(export_root), "t2r_metadata.json"
            )
        ) as f:
            export_meta = json.load(f)
        if "aot" not in export_meta:
            raise RuntimeError(
                "fixture export carries no AOT block; cannot measure "
                f"the AOT tier ({export_meta.get('stablehlo_error')})"
            )

        def run_boot(mode, serve_aot, cache_dir=None):
            out_path = os.path.join(tmpdir.name, f"boot_{mode}.json")
            cmd = [
                sys.executable, os.path.abspath(__file__), "aot", "--_boot",
                "--export-root", export_root, "--json-out", out_path,
            ]
            proc = subprocess.run(
                cmd,
                env=_aot_scrubbed_env(
                    serve_aot, devices[0].platform, cache_dir
                ),
                capture_output=True, text=True, timeout=420,
            )
            if proc.returncode != 0:
                raise RuntimeError(
                    f"boot twin {mode!r} failed rc={proc.returncode}: "
                    + "\n".join((proc.stderr or "").splitlines()[-5:])
                )
            with open(out_path) as f:
                report = json.load(f)
            report["mode"] = mode
            return report

        # The cache twin's dir lives under the fixture tmpdir so the
        # one cleanup() reaps it, success or failure.
        cache_dir = os.path.join(tmpdir.name, "cache")
        os.makedirs(cache_dir, exist_ok=True)
        boots = {}
        boots["fresh"] = run_boot("fresh", serve_aot=False)
        boots["cache_first"] = run_boot(
            "cache_first", serve_aot=False, cache_dir=cache_dir
        )
        boots["cache"] = run_boot("cache", serve_aot=False, cache_dir=cache_dir)
        boots["aot"] = run_boot("aot", serve_aot=True)

        # -- the publish->swap half (in-process): hot-swap latency with
        # the incoming version prewarmed from AOT vs from compiles, under
        # open-loop load. Swap latency = swap request -> new version
        # serving (restore + per-bucket prewarm + atomic flip).
        def swap_leg(serve_aot: bool, step: int):
            saved = t2r_flags.read_raw("T2R_SERVE_AOT")
            t2r_flags.write_env("T2R_SERVE_AOT", serve_aot)
            try:
                server = PolicyServer(
                    predictor, max_wait_ms=2, max_queue=4096
                )
                server.start(prewarm=True)
                rng = np.random.RandomState(step)

                def request_fn():
                    return {
                        "x": rng.uniform(-1, 1, size=(3,)).astype(np.float32)
                    }

                v_before = predictor.model_version
                timings = {}

                def do_swap():
                    t_swap0 = time.monotonic()
                    exporter.maybe_export(
                        step=step, state=state,
                        eval_metrics={"loss": 1.0 / step},
                        compiled=compiled, model_dir=tmpdir.name,
                    )
                    timings["export_s"] = time.monotonic() - t_swap0
                    t_swap1 = time.monotonic()
                    server.hot_swap()
                    while (
                        predictor.model_version == v_before
                        and time.monotonic() - t_swap1 < 120
                    ):
                        time.sleep(0.005)
                    timings["swap_latency_s"] = time.monotonic() - t_swap1

                def swap_fn():
                    import threading

                    thread = threading.Thread(target=do_swap, daemon=True)
                    thread.start()
                    timings["thread"] = thread

                swap_at = args.leg_secs * 0.3
                leg = _serve_open_loop(
                    server, request_fn, rate_hz=args.swap_rate_hz,
                    duration_s=args.leg_secs, deadline_ms=8000.0,
                    seed=step, swap_at_s=swap_at, swap_fn=swap_fn,
                )
                timings["thread"].join(timeout=180)
                server.stop()
                by_offset = leg.pop("latencies_by_offset")
                post = sorted(
                    latency
                    for offset, latency in by_offset
                    if swap_at <= offset < swap_at + 2.0
                )
                return {
                    "tier": "aot" if serve_aot else "compile",
                    "swap_latency_s": round(
                        timings.get("swap_latency_s", float("nan")), 4
                    ),
                    "export_s": round(timings.get("export_s", 0.0), 4),
                    "failed_requests": sum(leg["errors"].values()),
                    "completed": leg["completed"],
                    "version_before": v_before,
                    "version_after": predictor.model_version,
                    "p99_post_swap_ms": round(percentile(post, 0.99), 3),
                    "blip_max_ms_2s_after_swap": round(
                        max(post), 3
                    ) if post else 0.0,
                }
            finally:
                t2r_flags.restore_env("T2R_SERVE_AOT", saved)

        swap_aot = swap_leg(serve_aot=True, step=2)
        swap_compile = swap_leg(serve_aot=False, step=3)

        aot_boot, fresh_boot = boots["aot"], boots["fresh"]
        acceptance = {
            # Zero fresh bucket compiles on the AOT-hit boot: every
            # bucket prewarmed from a deserialized executable, the
            # stablehlo trace path never dispatched, nothing fell back.
            "aot_zero_fresh_compiles": (
                aot_boot["fresh_trace_calls"] == 0
                and aot_boot["aot_misses"] == 0
                and set(aot_boot["prewarm_source"].values()) == {"aot"}
                and len(aot_boot["prewarm_source"]) == len(buckets)
            ),
            # Deserialize beats compile on the same artifact + host.
            "aot_cold_start_below_fresh": (
                aot_boot["cold_start_s"] < fresh_boot["cold_start_s"]
            ),
            # The cache tier still holds its PR 7 contract: the second
            # cached boot adds no persistent-cache entries.
            "cache_second_boot_adds_no_entries": (
                boots["cache"]["cache_entries_added"] == 0
            ),
            # Swaps stay zero-downtime in both tiers.
            "swap_zero_failed_requests": (
                swap_aot["failed_requests"] == 0
                and swap_compile["failed_requests"] == 0
            ),
            "swap_versions_advanced": (
                swap_aot["version_after"] > swap_aot["version_before"]
                and swap_compile["version_after"]
                > swap_compile["version_before"]
            ),
        }
        speedup = fresh_boot["cold_start_s"] / max(
            aot_boot["cold_start_s"], 1e-9
        )
        tmpdir.cleanup()
        payload = {
            "metric": metric,
            "value": round(speedup, 3),
            "unit": "x_cold_start_speedup",
            # Target: an AOT boot at least matches the fresh twin; the
            # real bar is the strict acceptance block below.
            "vs_baseline": round(speedup, 4),
            "detail": {
                "boots": boots,
                "cold_start_s": {
                    mode: boots[mode]["cold_start_s"] for mode in boots
                },
                "aot_vs_fresh_cold_start_x": round(speedup, 3),
                "aot_vs_cache_cold_start_x": round(
                    boots["cache"]["cold_start_s"]
                    / max(aot_boot["cold_start_s"], 1e-9),
                    3,
                ),
                "rolling_swap": {"aot": swap_aot, "compile": swap_compile},
                "swap_latency_aot_vs_compile_x": round(
                    swap_compile["swap_latency_s"]
                    / max(swap_aot["swap_latency_s"], 1e-9),
                    3,
                ),
                "acceptance": acceptance,
                "buckets": list(buckets),
                "aot_artifact_nbytes": export_meta["aot"]["nbytes"],
                "aot_topology": export_meta["aot"]["topology"],
                "host_cpus": os.cpu_count(),
                "device_kind": getattr(devices[0], "device_kind", "?"),
                "model": "mock_mlp_3feature",
            },
            **_proxy_fields(on_tpu, "serve_cold_start_aot_speedup"),
        }
        if args.out:
            with open(args.out, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
        _emit(payload)
        if not all(acceptance.values()):
            _fail(
                "aot_acceptance",
                RuntimeError(f"acceptance failed: {acceptance}"),
                metric=metric,
            )
    except SystemExit:
        raise
    except Exception as err:  # noqa: BLE001
        _fail("bench_aot", err, metric=metric)


def _latest_export_dir_for(export_root: str):
    from tensor2robot_tpu.export.saved_model import latest_export_dir

    path = latest_export_dir(export_root)
    if path is None:
        raise RuntimeError(f"no export under {export_root}")
    return path


def bench_fleet(args) -> None:
    """Replica-fleet routing leg (`python bench.py fleet`).

    Measures the FleetRouter fabric — dispatch, transport, retry,
    hedging, respawn — over N replica *processes* on the jax-free mock
    backend (fixed per-request service time), so the numbers attribute
    to the router layer and not to XLA compute; `bench.py serve`
    already measures real-model serving inside one process. Four legs:

      * closed-loop capacity (requests/s through the full fabric),
      * an open-loop Poisson sweep at fractions of that capacity with
        p50/p99/p999 and availability per leg,
      * a chaos leg: one replica SIGKILLed mid-sweep — every request
        must resolve (retried or shed WITH a typed error; zero lost,
        zero hung) and p99 degradation vs the fault-free twin leg at
        the same rate is reported against the bounded target,
      * a rolling hot-swap across the whole fleet under load, with the
        failed-request count (target: 0) and versions observed.

    All arrival processes and jitter are seeded: rerunning the leg
    replays the same schedule.
    """
    import os
    import signal as signal_mod
    import threading

    metric = "fleet_router_capacity_cpu_proxy"
    try:
        import numpy as np

        from tensor2robot_tpu.serving import (
            FleetError,
            FleetRouter,
            ReplicaSpec,
            mock_server_factory,
        )
        from tensor2robot_tpu.serving.metrics import percentile

        n = args.replicas
        spec = ReplicaSpec(
            factory=mock_server_factory,
            factory_kwargs={"service_ms": args.service_ms},
        )

        def make_router(**overrides):
            kwargs = dict(
                num_replicas=n,
                # Tolerant probe budget (1 s of silence before SUSPECT):
                # on this oversubscribed proxy host a saturating load leg
                # can scheduling-starve health replies, and the monitor
                # hard-killing CPU-starved-but-healthy replicas would
                # measure the HOST, not the router.
                probe_interval_ms=200.0,
                probe_miss_limit=5,
                backoff_ms=10.0,
                max_respawns=5,
                seed=11,
            )
            kwargs.update(overrides)
            return FleetRouter(spec, **kwargs).start(timeout_s=120.0)

        def wait_all_up(router, timeout=60.0):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                if all(s == "up" for s in router.replica_states()):
                    return
                time.sleep(0.02)
            raise RuntimeError(
                f"fleet never fully up: {router.replica_states()}"
            )

        rng_payload = np.random.RandomState(3)
        payload_x = rng_payload.uniform(-1, 1, size=(8,)).astype(np.float32)

        def request():
            return {"x": payload_x}

        # -- closed-loop capacity: keep the fabric saturated for a
        # window; completed/elapsed is what the router can actually move.
        def measure_capacity(router, secs, request_fn=None):
            request_fn = request_fn or request
            done = []
            t0 = time.monotonic()
            outstanding = 0
            lock = threading.Lock()
            cv = threading.Condition(lock)

            def on_done(_):
                nonlocal outstanding
                with cv:
                    outstanding -= 1
                    done.append(time.monotonic())
                    cv.notify()

            while time.monotonic() - t0 < secs:
                try:
                    future = router.submit(request_fn(), deadline_ms=10_000)
                except FleetError:
                    with cv:
                        cv.wait(0.005)
                    continue
                with cv:
                    outstanding += 1
                future.add_done_callback(on_done)
            with cv:
                deadline = time.monotonic() + 30
                while outstanding and time.monotonic() < deadline:
                    cv.wait(0.1)
            elapsed = (done[-1] if done else time.monotonic()) - t0
            return len(done) / max(elapsed, 1e-9)

        # -- one open-loop Poisson leg. Seeded arrivals; every future's
        # outcome is recorded by a done callback; at drain time nothing
        # may remain unresolved (lost==0 is the zero-lost guarantee).
        def open_loop(router, rate_hz, secs, seed, kill_at_s=None,
                      kill_index=0, swap_fn=None, swap_at_s=None):
            rng = np.random.RandomState(seed)
            records = []  # (t_submit_rel, latency_ms, error_type or None)
            rec_lock = threading.Lock()
            admission_errors: dict = {}
            versions: dict = {}
            killed_pid = None
            swap_thread = None
            swap_result = {}
            t0 = time.monotonic()
            t_next = t0
            submitted = 0
            while t_next - t0 < secs:
                now = time.monotonic()
                if now < t_next:
                    time.sleep(t_next - now)
                rel = time.monotonic() - t0
                if (
                    kill_at_s is not None
                    and killed_pid is None
                    and rel >= kill_at_s
                ):
                    pid = router.replica_pids()[kill_index]
                    if pid is not None:
                        os.kill(pid, signal_mod.SIGKILL)
                        killed_pid = pid
                if swap_at_s is not None and swap_thread is None and rel >= swap_at_s:
                    swap_thread = threading.Thread(
                        target=lambda: swap_result.update(swap_fn()),
                        daemon=True,
                    )
                    swap_thread.start()
                try:
                    future = router.submit(
                        request(), deadline_ms=args.deadline_ms
                    )
                except FleetError as err:
                    # Typed admission shed (saturated/unavailable): the
                    # graceful-degradation path, never a hang.
                    name = type(err).__name__
                    with rec_lock:
                        admission_errors[name] = (
                            admission_errors.get(name, 0) + 1
                        )
                    submitted += 1
                    t_next += rng.exponential(1.0 / rate_hz)
                    continue

                def on_done(fut, t_submit=time.monotonic(), rel=rel):
                    err = fut.error()
                    latency = (time.monotonic() - t_submit) * 1e3
                    if err is None:
                        version = fut.result(0).model_version
                    with rec_lock:
                        records.append(
                            (rel, latency,
                             None if err is None else type(err).__name__)
                        )
                        if err is None:
                            versions[version] = versions.get(version, 0) + 1

                future.add_done_callback(on_done)
                submitted += 1
                t_next += rng.exponential(1.0 / rate_hz)
            # Drain: every submitted future must resolve inside its
            # deadline + retry envelope. Anything still missing is LOST.
            drain_deadline = time.monotonic() + args.deadline_ms / 1e3 + 30
            expected = submitted - sum(admission_errors.values())
            while time.monotonic() < drain_deadline:
                with rec_lock:
                    if len(records) >= expected:
                        break
                time.sleep(0.02)
            if swap_thread is not None:
                swap_thread.join(timeout=60)
            with rec_lock:
                ok = sorted(r[1] for r in records if r[2] is None)
                failed: dict = {}
                for _, _, err_name in records:
                    if err_name is not None:
                        failed[err_name] = failed.get(err_name, 0) + 1
            lost = expected - len(records)
            leg = {
                "offered_hz": round(rate_hz, 2),
                "secs": secs,
                "submitted": submitted,
                "completed": len(ok),
                "availability": round(len(ok) / max(submitted, 1), 5),
                "p50_ms": round(percentile(ok, 0.50), 3),
                "p99_ms": round(percentile(ok, 0.99), 3),
                "p999_ms": round(percentile(ok, 0.999), 3),
                "failed_typed": failed,
                "shed_at_admission": admission_errors,
                "lost": lost,  # futures that never resolved: MUST be 0
            }
            if versions:
                leg["versions_observed"] = {
                    str(k): v for k, v in sorted(versions.items())
                }
            if kill_at_s is not None:
                leg["killed_pid"] = killed_pid
                leg["kill_at_s"] = kill_at_s
            if swap_result:
                leg["swap_result"] = {
                    "swapped": swap_result.get("swapped"),
                    "failed": swap_result.get("failed"),
                }
            return leg

        # ---- leg 1: capacity + Poisson sweep on one fleet. The fleet
        # must be fully recovered before each leg, or a previous leg's
        # saturation transient (evictions mid-respawn) bleeds in.
        with make_router() as router:
            wait_all_up(router)
            capacity_hz = measure_capacity(router, args.capacity_secs)
            sweep = []
            for i, frac in enumerate((0.3, 0.6, 0.9)):
                wait_all_up(router)
                sweep.append(
                    open_loop(
                        router, capacity_hz * frac, args.leg_secs,
                        seed=23 + i,
                    )
                )
            sweep_snapshot = router.snapshot()

        # ---- leg 2: fault-free twin + chaos twin at the same rate, on
        # fresh fleets (clean death/retry counters). Rate sized so the
        # fleet minus one replica still has headroom: the leg measures
        # failover + retry behavior, not overload (the sweep above
        # already characterizes saturation).
        chaos_rate = capacity_hz * 0.35
        with make_router() as router:
            wait_all_up(router)
            fault_free = open_loop(router, chaos_rate, args.leg_secs, seed=41)
        with make_router() as router:
            wait_all_up(router)
            chaos_leg = open_loop(
                router, chaos_rate, max(args.leg_secs, 2.0), seed=41,
                kill_at_s=max(args.leg_secs, 2.0) / 2,
            )
            # Let the respawn land so the payload records the fleet
            # RECOVERED, not the mid-respawn transient.
            settle_deadline = time.monotonic() + 30
            while time.monotonic() < settle_deadline and not all(
                s == "up" for s in router.replica_states()
            ):
                time.sleep(0.05)
            chaos_snapshot = router.snapshot()
        p99_degradation = (
            chaos_leg["p99_ms"] / fault_free["p99_ms"]
            if fault_free["p99_ms"] > 0
            else float("inf")
        )

        # ---- leg 3: rolling hot-swap across the fleet under load.
        with make_router() as router:
            wait_all_up(router)
            version_before = [
                r["version"] for r in router.snapshot()["replicas"]
            ]
            swap_leg = open_loop(
                router, capacity_hz * 0.3, max(args.leg_secs, 2.0),
                seed=59,
                swap_fn=lambda: router.rolling_swap(swap_timeout_s=30.0),
                swap_at_s=0.5,
            )
            version_after = [
                r["version"] for r in router.snapshot()["replicas"]
            ]
        swap_failed_requests = (
            sum(swap_leg["failed_typed"].values())
            + sum(swap_leg["shed_at_admission"].values())
            + swap_leg["lost"]
        )

        # ---- leg 4 (r11): mixed-precision POLICY-backend fleet. Real
        # PolicyServer replicas over one serve-quant export — replica 0
        # serves T2R_SERVE_QUANT=none, the rest int8 (a mid-rollout
        # fleet). The router's health snapshots must report the regime
        # per replica (mix-verification), and the mixed fabric must move
        # traffic with zero lost requests.
        quant_leg = None
        if args.quant_replicas > 0:
            import tempfile

            import jax

            from tensor2robot_tpu.export.exporters import LatestExporter
            from tensor2robot_tpu.export.saved_model import (
                latest_export_dir,
                quant_payload_relpath,
            )
            from tensor2robot_tpu.serving import policy_server_factory
            from tensor2robot_tpu.train.train_eval import CompiledModel
            from tensor2robot_tpu.utils.mocks import (
                MockInputGenerator,
                MockT2RModel,
            )

            qtmp = tempfile.TemporaryDirectory(prefix="bench_fleet_quant_")
            try:
                model = MockT2RModel(device_type="cpu")
                generator = MockInputGenerator(batch_size=8)
                generator.set_specification_from_model(model, "train")
                batches = iter(generator.create_dataset("train"))
                compiled = CompiledModel(model, donate_state=False)
                state = compiled.init_state(
                    jax.random.PRNGKey(0), next(batches)
                )
                exporter = LatestExporter(
                    name="latest", warmup_batch_sizes=(1, 4),
                    serve_quant=("int8",),
                )
                exporter.maybe_export(
                    step=1, state=state, eval_metrics={"loss": 1.0},
                    compiled=compiled, model_dir=qtmp.name,
                )
                export_root = exporter.export_root(qtmp.name)
                export_path = latest_export_dir(export_root)
                qn = args.quant_replicas
                specs = [
                    ReplicaSpec(
                        factory=policy_server_factory,
                        factory_kwargs={
                            "export_root": export_root, "max_wait_ms": 2,
                        },
                        env={
                            "T2R_SERVE_QUANT": "none" if i == 0 else "int8",
                        },
                    )
                    for i in range(qn)
                ]
                rng_q = np.random.RandomState(5)

                def request_q():
                    return {
                        "x": rng_q.uniform(-1, 1, size=(3,)).astype(
                            np.float32
                        )
                    }

                with FleetRouter(
                    specs, probe_interval_ms=200.0, probe_miss_limit=10,
                    backoff_ms=10.0, seed=11, boot_timeout_s=600.0,
                ).start(timeout_s=600.0) as router:
                    wait_all_up(router, timeout=300.0)
                    # Health snapshots carry serve_quant; wait for one
                    # probe round so mix-verification reads real data.
                    verify_deadline = time.monotonic() + 30
                    while time.monotonic() < verify_deadline:
                        replica_snaps = router.snapshot()["replicas"]
                        if all(
                            r["serve_quant"] is not None
                            for r in replica_snaps
                        ):
                            break
                        time.sleep(0.05)
                    quant_capacity = measure_capacity(
                        router, args.quant_secs, request_fn=request_q
                    )
                    quant_snapshot = router.snapshot()
                regimes_seen = [
                    r["serve_quant"] for r in quant_snapshot["replicas"]
                ]
                fp32_bytes = os.path.getsize(
                    os.path.join(export_path, "variables.msgpack")
                )
                int8_bytes = os.path.getsize(
                    os.path.join(export_path, quant_payload_relpath("int8"))
                )
                quant_leg = {
                    "replicas": qn,
                    "backend": "policy_server_processes",
                    "closed_loop_capacity_hz": round(quant_capacity, 2),
                    "replica_serve_quant": regimes_seen,
                    "mixed_fleet_verified": (
                        regimes_seen[0] == "none"
                        and all(r == "int8" for r in regimes_seen[1:])
                    ),
                    "export_fp32_params_bytes": fp32_bytes,
                    "export_int8_params_bytes": int8_bytes,
                    "int8_params_bytes_reduction_x": round(
                        fp32_bytes / int8_bytes, 3
                    ),
                }
            finally:
                # A failed leg must still remove the export tree.
                qtmp.cleanup()

        chaos_ok = (
            chaos_leg["lost"] == 0
            and chaos_leg["availability"] > 0
            and p99_degradation <= args.p99_degradation_max
        )
        payload = {
            "metric": metric,
            "value": round(capacity_hz, 2),
            "unit": "requests_per_sec",
            # Target: the chaos leg loses nothing and p99 degradation
            # stays inside the bound (1.0 = exactly at the bar).
            "vs_baseline": round(
                (args.p99_degradation_max / p99_degradation)
                if chaos_leg["lost"] == 0 and p99_degradation > 0
                else 0.0,
                4,
            ),
            "detail": {
                "replicas": n,
                "service_ms": args.service_ms,
                "deadline_ms": args.deadline_ms,
                "closed_loop_capacity_hz": round(capacity_hz, 2),
                "open_loop": sweep,
                "sweep_counters": sweep_snapshot["counters"],
                "chaos": {
                    "fault_free_leg": fault_free,
                    "sigkill_leg": chaos_leg,
                    "counters": chaos_snapshot["counters"],
                    "replica_states_after": [
                        r["state"]
                        for r in chaos_snapshot["replicas"]
                    ],
                    "p99_degradation_x": round(p99_degradation, 3),
                    "p99_degradation_max": args.p99_degradation_max,
                    "zero_lost": chaos_leg["lost"] == 0,
                    "ok": chaos_ok,
                },
                "rolling_swap": {
                    **swap_leg,
                    "failed_requests": swap_failed_requests,
                    "version_before": version_before,
                    "version_after": version_after,
                },
                **({"quant": quant_leg} if quant_leg else {}),
                "backend": "mock_replica_processes",
                "host_cpus": os.cpu_count(),
            },
            "cpu_proxy": True,
            "proxy_note": (
                "router fabric measured over mock replica processes on "
                "CPU; absolute rates are host-bound, the availability/"
                "degradation contracts are platform-independent"
            ),
        }
        if args.out:
            with open(args.out, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
        _emit(payload)
    except Exception as err:  # noqa: BLE001
        _fail("bench_fleet", err, metric=metric)


def bench_gateway(args) -> None:
    """Multi-tenant front-door leg (`python bench.py gateway`).

    Drives the FULL production story through one pool: a Gateway
    (per-tenant quotas, gold/silver/bronze strict priority, coalescing)
    over a FleetRouter of mock replicas with a load-driven Autoscaler —
    replaying a seeded diurnal, bursty multi-tenant trace with

      * a hot silver tenant whose observations repeat (coalescing),
      * a flash crowd (crowd tenants x`--crowd-factor` mid-trace),
      * a rogue bronze tenant offered at 10x its admission quota,

    twice: a fault-free twin, and a chaos twin where a replica is
    SIGKILLed mid-crowd AND a rolling swap publishes a new model
    version through the same pool. Gates (the acceptance criteria):
    gold availability 1.0 with bounded p99 degradation vs the twin,
    every bronze outcome typed (zero hung or silently lost requests
    anywhere, by per-request accounting), coalescing measurably cutting
    dispatches with bitwise-equal responses, and the autoscaler
    reaching the crowd's replica ceiling then draining back without
    killing an in-flight request or flapping.

    All arrivals, burst windows, and jitter are seeded: rerunning the
    leg replays the same trace.
    """
    import math
    import os
    import signal as signal_mod
    import threading

    metric = "gateway_multitenant_slo_cpu_proxy"
    try:
        import numpy as np

        from tensor2robot_tpu.serving import (
            Autoscaler,
            FleetRouter,
            GateError,
            Gateway,
            ReplicaSpec,
            TenantBinding,
            mock_server_factory,
        )
        from tensor2robot_tpu.serving.metrics import percentile

        scale = args.rate_scale
        trace_secs = args.trace_secs
        crowd_window = (0.4 * trace_secs, 0.6 * trace_secs)
        kill_at = 0.5 * trace_secs
        swap_at = 0.55 * trace_secs

        # The tenant universe: (name, tier, base_hz, unique_obs, crowd).
        # unique_obs=None -> every request a distinct observation;
        # a small int -> observations repeat (the coalescing regime).
        rogue_offered_hz = args.rogue_rate * scale
        tenant_cfg = [
            ("web-gold", "gold", 80.0 * scale, None, True),
            ("app-silver-hot", "silver", 120.0 * scale, 4, True),
            ("app-silver", "silver", 60.0 * scale, None, False),
            ("batch-bronze", "bronze", 50.0 * scale, None, False),
            ("rogue-bronze", "bronze", rogue_offered_hz, None, False),
        ]
        tier_deadline_ms = {"gold": 800.0, "silver": 800.0, "bronze": 500.0}

        def make_bindings():
            bindings = []
            for name, tier, _hz, _uniq, _crowd in tenant_cfg:
                quota = (
                    # The rogue's quota is a TENTH of its offered rate:
                    # ~90% of its traffic must shed typed at admission.
                    max(1.0, rogue_offered_hz / 10.0)
                    if name == "rogue-bronze"
                    else 1e6
                )
                bindings.append(
                    TenantBinding(
                        tenant=name, tier=tier, quota_rps=quota,
                        burst=max(4, int(quota / 4)),
                        deadline_ms=tier_deadline_ms[tier],
                    )
                )
            return bindings

        # -- the seeded trace: merged (t, tenant_index) arrivals ---------------
        def build_trace(seed):
            rng = np.random.RandomState(seed)
            slot_s = 0.2  # burst-modulation window
            n_slots = int(math.ceil(trace_secs / slot_s)) + 1
            merged = []
            for idx, (_name, _tier, base_hz, _uniq, crowd) in enumerate(
                tenant_cfg
            ):
                # Doubly-stochastic arrivals: diurnal envelope x per-slot
                # burst multiplier x flash crowd, thinned to a Poisson
                # process per tenant.
                bursts = rng.choice([1.0, 1.0, 1.0, 2.5], size=n_slots)
                t = rng.uniform(0, 0.01)
                while t < trace_secs:
                    rate = base_hz * (
                        1.0 + 0.5 * math.sin(2 * math.pi * t / trace_secs)
                    )
                    rate *= bursts[int(t / slot_s)]
                    if crowd and crowd_window[0] <= t <= crowd_window[1]:
                        rate *= args.crowd_factor
                    rate = max(rate, 0.5)
                    t += rng.exponential(1.0 / rate)
                    merged.append((t, idx))
            merged.sort()
            return merged

        def run_leg(trace, *, chaos_leg):
            spec = ReplicaSpec(
                factory=mock_server_factory,
                factory_kwargs={"service_ms": args.service_ms},
            )
            router = FleetRouter(
                spec, args.replicas,
                max_inflight=args.max_inflight,
                hedge_ms=args.hedge_ms,
                # Tight death detection: the SIGKILL latency tail is
                # bounded by probe interval + failover retry, and the
                # gold p99-degradation gate rides on it.
                probe_interval_ms=25.0,
                probe_miss_limit=10,
                backoff_ms=10.0,
                max_respawns=5,
                seed=11,
            ).start(timeout_s=120.0)
            gateway = Gateway(
                router, make_bindings(),
                max_queue=1024,
                tier_queue_budget_ms={"bronze": 250.0},
                seed=17,
            ).start()
            scaler = Autoscaler(
                router,
                min_replicas=args.replicas,
                max_replicas=args.max_replicas,
                high_watermark=0.7,
                low_watermark=0.2,
                # Asymmetric hysteresis: react to overload in two ticks,
                # but demand ~a second of sustained idleness before
                # giving capacity back — a burst lull mid-trace must not
                # thrash the pool (the no-flap gate pins this).
                scale_up_ticks=2,
                scale_down_ticks=12,
                cooloff_base_ms=150.0,
                cooloff_cap_ms=1200.0,
                tick_interval_s=0.08,
                drain_timeout_s=20.0,
                seed=7,
            ).start()
            try:
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline and not all(
                    s == "up" for s in router.replica_states()
                ):
                    time.sleep(0.02)

                unique_counter = [0]
                obs_cache = {}

                def observation(tenant_idx):
                    _name, _tier, _hz, uniq, _crowd = tenant_cfg[tenant_idx]
                    if uniq is None:
                        unique_counter[0] += 1
                        key = (tenant_idx, unique_counter[0])
                        value = 1000.0 + unique_counter[0]
                    else:
                        key = (tenant_idx, unique_counter[0] % uniq)
                        value = float((unique_counter[0] % uniq) + 1)
                        unique_counter[0] += 1
                    features = obs_cache.get(key)
                    if features is None:
                        features = {
                            "x": np.full((8,), value, np.float32)
                        }
                        obs_cache[key] = features
                        if len(obs_cache) > 4096:
                            obs_cache.clear()
                    return key, features

                records = []
                rec_lock = threading.Lock()
                admission = {}  # tenant -> {error_class: count}
                submitted = {}  # tenant -> count
                hot_y = {}  # obs_key -> set of y values (bitwise check)
                killed_pid = None
                swap_thread = None
                swap_result = {}
                t0 = time.monotonic()
                for t_arrival, tenant_idx in trace:
                    name, tier, _hz, uniq, _crowd = tenant_cfg[tenant_idx]
                    now = time.monotonic()
                    if now - t0 < t_arrival:
                        time.sleep(t_arrival - (now - t0))
                    rel = time.monotonic() - t0
                    if chaos_leg and killed_pid is None and rel >= kill_at:
                        for r in router.snapshot()["replicas"]:
                            if r["state"] == "up":
                                pid = router.replica_pids()[r["index"]]
                                if pid is not None:
                                    os.kill(pid, signal_mod.SIGKILL)
                                    killed_pid = pid
                                    break
                    if (
                        chaos_leg
                        and swap_thread is None
                        and rel >= swap_at
                    ):
                        swap_thread = threading.Thread(
                            target=lambda: swap_result.update(
                                gateway.rolling_swap(swap_timeout_s=30.0)
                            ),
                            daemon=True,
                        )
                        swap_thread.start()
                    obs_key, features = observation(tenant_idx)
                    submitted[name] = submitted.get(name, 0) + 1
                    try:
                        future = gateway.submit(name, features)
                    except GateError as err:
                        with rec_lock:
                            admission.setdefault(name, {})
                            cls = type(err).__name__
                            admission[name][cls] = (
                                admission[name].get(cls, 0) + 1
                            )
                        continue

                    def on_done(fut, tenant=name, rel=rel,
                                t_submit=time.monotonic(),
                                obs_key=obs_key, track_y=uniq is not None):
                        err = fut.error()
                        latency = (time.monotonic() - t_submit) * 1e3
                        version = None
                        coalesced = False
                        if err is None:
                            response = fut.result(0)
                            version = response.model_version
                            coalesced = response.coalesced
                        with rec_lock:
                            records.append(
                                (tenant, rel, latency,
                                 None if err is None else type(err).__name__,
                                 coalesced, version)
                            )
                            if err is None and track_y:
                                hot_y.setdefault(obs_key, set()).add(
                                    float(response.outputs["y"])
                                )

                    future.add_done_callback(on_done)

                # Drain: every admitted future must resolve, typed or ok.
                expected = sum(submitted.values()) - sum(
                    sum(v.values()) for v in admission.values()
                )
                drain_deadline = time.monotonic() + 30
                while time.monotonic() < drain_deadline:
                    with rec_lock:
                        if len(records) >= expected:
                            break
                    time.sleep(0.02)
                if swap_thread is not None:
                    swap_thread.join(timeout=60)
                # Idle window: the autoscaler must drain back unaided.
                idle_deadline = time.monotonic() + args.drain_secs
                while time.monotonic() < idle_deadline:
                    if router.load()["replicas_up"] <= args.replicas:
                        break
                    time.sleep(0.05)
                with rec_lock:
                    frozen = list(records)
                lost = expected - len(frozen)

                per_tenant = {}
                for name, tier, _hz, _uniq, _crowd in tenant_cfg:
                    mine = [r for r in frozen if r[0] == name]
                    ok = sorted(r[2] for r in mine if r[3] is None)
                    failed = {}
                    for r in mine:
                        if r[3] is not None:
                            failed[r[3]] = failed.get(r[3], 0) + 1
                    n_submitted = submitted.get(name, 0)
                    admission_typed = admission.get(name, {})
                    resolved = len(mine) + sum(admission_typed.values())
                    per_tenant[name] = {
                        "tier": tier,
                        "submitted": n_submitted,
                        "completed": len(ok),
                        "availability": round(
                            len(ok) / max(n_submitted, 1), 5
                        ),
                        "p50_ms": round(percentile(ok, 0.50), 3),
                        "p99_ms": round(percentile(ok, 0.99), 3),
                        "failed_typed": failed,
                        "shed_at_admission": admission_typed,
                        "coalesced": sum(1 for r in mine if r[4]),
                        "lost": n_submitted - resolved,
                    }
                versions = sorted(
                    {r[5] for r in frozen if r[5] is not None}
                )
                gate_snap = gateway.snapshot()
                scaler_snap = scaler.snapshot()
                router_snap = router.snapshot()
                final_load = router.load()
                reversals = sum(
                    1
                    for a, b in zip(
                        scaler_snap["actions"], scaler_snap["actions"][1:]
                    )
                    if a["direction"] != b["direction"]
                )
                return {
                    "per_tenant": per_tenant,
                    "lost_total": lost,
                    "versions_observed": versions,
                    "killed_pid": killed_pid,
                    "swap_result": (
                        {
                            "swapped": swap_result.get("swapped"),
                            "failed": swap_result.get("failed"),
                        }
                        if swap_result
                        else None
                    ),
                    "gateway_counters": gate_snap["counters"],
                    "router_counters": router_snap["counters"],
                    "autoscaler": {
                        "counters": scaler_snap["counters"],
                        "actions": scaler_snap["actions"],
                        "peak_replicas_up": scaler_snap["peak_replicas_up"],
                        "reversals": reversals,
                    },
                    "final_replicas_up": final_load["replicas_up"],
                    "hot_y_groups": {
                        str(k): sorted(v) for k, v in hot_y.items()
                    },
                }
            finally:
                scaler.stop()
                gateway.stop()
                router.stop()

        trace = build_trace(seed=29)
        fault_free = run_leg(trace, chaos_leg=False)
        chaos_leg = run_leg(trace, chaos_leg=True)

        # -- gates (the acceptance criteria) -----------------------------------
        gold_c = chaos_leg["per_tenant"]["web-gold"]
        gold_f = fault_free["per_tenant"]["web-gold"]
        # Sub-floor p99s on a CPU proxy host are scheduler noise; the
        # ratio is measured against max(twin, floor) and both raw
        # numbers ride in the payload.
        p99_base = max(gold_f["p99_ms"], args.p99_floor_ms)
        p99_degradation = (
            gold_c["p99_ms"] / p99_base if p99_base > 0 else float("inf")
        )
        bronze_names = [
            name for name, tier, *_ in tenant_cfg if tier == "bronze"
        ]
        bronze_typed_ok = all(
            chaos_leg["per_tenant"][n]["lost"] == 0 for n in bronze_names
        )
        rogue = chaos_leg["per_tenant"]["rogue-bronze"]
        rogue_throttled = rogue["shed_at_admission"].get(
            "TenantThrottled", 0
        )
        hot = chaos_leg["per_tenant"]["app-silver-hot"]
        coalesce_bitwise_ok = all(
            len(values) == 1
            for values in chaos_leg["hot_y_groups"].values()
        ) and len(chaos_leg["hot_y_groups"]) > 0
        zero_lost = (
            chaos_leg["lost_total"] == 0
            and fault_free["lost_total"] == 0
            and all(
                t["lost"] == 0
                for leg in (chaos_leg, fault_free)
                for t in leg["per_tenant"].values()
            )
        )
        scaler_c = chaos_leg["autoscaler"]
        retire_clean = scaler_c["counters"].get("scale_down", 0) >= 1 and (
            chaos_leg["router_counters"].get("retirement_aborts", 0) == 0
        )
        gates = {
            "gold_availability_1": gold_c["availability"] == 1.0
            and not gold_c["failed_typed"]
            and not gold_c["shed_at_admission"],
            "gold_p99_bounded": (
                p99_degradation <= args.p99_degradation_max
            ),
            "bronze_overload_typed": bronze_typed_ok
            and rogue_throttled > 0
            and rogue["availability"] < 0.5,  # the quota really bit
            "zero_lost_all_tiers": zero_lost,
            "coalesce_effective": (
                hot["coalesced"] > 0
                and chaos_leg["gateway_counters"].get("coalesced_joins", 0)
                > 0
                and coalesce_bitwise_ok
            ),
            "autoscaler_reached_ceiling": (
                scaler_c["peak_replicas_up"] >= args.max_replicas
            ),
            "autoscaler_drained_back": (
                chaos_leg["final_replicas_up"] <= args.replicas + 1
                and retire_clean
            ),
            # Convergence, not rigidity: a bursty trace legitimately
            # re-scales after an early drain (a post-crowd burst saturates
            # the shrunk pool), so the flap bound is a few reversals with
            # TERMINAL convergence — the run must END in a drain phase at
            # the floor, not oscillating.
            "autoscaler_no_flap": (
                scaler_c["reversals"] <= 3
                and (
                    not scaler_c["actions"]
                    or scaler_c["actions"][-1]["direction"] == "down"
                )
                and chaos_leg["final_replicas_up"] <= args.replicas + 1
            ),
            "killed_and_recovered": (
                chaos_leg["killed_pid"] is not None
                and chaos_leg["router_counters"].get("replica_deaths", 0)
                >= 1
                and chaos_leg["router_counters"].get("respawns", 0) >= 1
            ),
            "swap_published_through_pool": (
                chaos_leg["swap_result"] is not None
                and chaos_leg["swap_result"]["failed"] is None
                and max(chaos_leg["versions_observed"], default=1) >= 2
            ),
        }
        all_green = all(gates.values())
        completed_total = sum(
            t["completed"] for t in chaos_leg["per_tenant"].values()
        )
        payload = {
            "metric": metric,
            "value": round(completed_total / trace_secs, 2),
            "unit": "requests_per_sec",
            "vs_baseline": round(
                (args.p99_degradation_max / p99_degradation)
                if all_green and p99_degradation > 0
                else 0.0,
                4,
            ),
            "all_green": all_green,
            "gates": gates,
            "detail": {
                "trace_secs": trace_secs,
                "rate_scale": scale,
                "crowd_factor": args.crowd_factor,
                "crowd_window_s": list(crowd_window),
                "kill_at_s": kill_at,
                "swap_at_s": swap_at,
                "replicas_min": args.replicas,
                "replicas_max": args.max_replicas,
                "service_ms": args.service_ms,
                "max_inflight": args.max_inflight,
                "hedge_ms": args.hedge_ms,
                "gold_p99_degradation_x": round(p99_degradation, 3),
                "gold_p99_floor_ms": args.p99_floor_ms,
                "fault_free": fault_free,
                "chaos": chaos_leg,
                "backend": "mock_replica_processes",
                "host_cpus": os.cpu_count(),
            },
            "cpu_proxy": True,
            "proxy_note": (
                "gateway/autoscaler control plane measured over mock "
                "replica processes on CPU; absolute rates are host-bound, "
                "the per-tier SLO / typed-shed / zero-lost contracts are "
                "platform-independent"
            ),
        }
        if args.out:
            with open(args.out, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
        _emit(payload)
    except Exception as err:  # noqa: BLE001
        _fail("bench_gateway", err, metric=metric)


def bench_policies(args) -> None:
    """Multi-policy fleet leg (`python bench.py policies`).

    One fleet, many policies (ROADMAP item 2), measured end to end:

      1. **Store phase.** Publishes `--variants` fine-tuned siblings of
         one base export into a content-addressed ArtifactStore — the
         program blobs dedup by hash, every sibling's weights land as a
         quantized per-leaf delta vs the base — and gates the disk
         accounting: the store must be >= 5x smaller than the same
         policies stored dense, with every reconstruction hash-verified.
      2. **Serving phase.** A 4-replica fleet hosts the whole catalog
         behind the Gateway (each mock policy's (scale, bias) is derived
         from its store manifest's weights sha, tying the serving
         identity to the stored artifact), replaying a seeded diurnal
         trace whose per-policy mix is Zipf-distributed with a ROTATING
         hot set — the memory budget forces real eviction/cold-load
         churn, all counted. Mid-trace, ONE policy rolling-swaps.

    Gates: >= `--variants` (>=100 by default) policies; delta >= 5x
    denser than dense; every response bitwise-equal to a single-policy
    twin serving the same (scale, bias); ZERO cross-policy coalesce
    joins (every served value belongs to the policy that asked); churn
    counters nonzero at every layer (replica evictions/cold loads,
    router placement hits/misses); the swapped policy's publish causes
    zero failed requests on every OTHER policy; zero lost requests.

    All arrivals and the policy mix are seeded: rerunning replays the
    same trace.
    """
    import hashlib
    import math
    import shutil
    import tempfile
    import threading

    metric = "multi_policy_fleet_delta_store_cpu_proxy"
    try:
        import numpy as np
        from flax import serialization

        from tensor2robot_tpu.export.artifact_store import ArtifactStore
        from tensor2robot_tpu.serving import (
            FleetRouter,
            GateError,
            Gateway,
            ReplicaSpec,
            TenantBinding,
            multi_policy_mock_factory,
        )
        from tensor2robot_tpu.serving.metrics import percentile

        n_variants = args.variants
        trace_secs = args.trace_secs
        swap_at = 0.5 * trace_secs

        # -- store phase: one base, n_variants delta siblings ------------------
        rng = np.random.RandomState(41)
        base_params = {
            "dense0": {
                "kernel": rng.standard_normal((96, 96)).astype(np.float32),
                "bias": rng.standard_normal((96,)).astype(np.float32),
            },
            "dense1": {
                "kernel": rng.standard_normal((96, 64)).astype(np.float32),
                "bias": rng.standard_normal((64,)).astype(np.float32),
            },
            "step": np.int64(1000),
        }
        # The shared serving program: identical bytes in every sibling
        # export, so the store dedups it down to ONE blob.
        program_bytes = rng.bytes(192 * 1024)

        def write_export(dirname, params):
            os.makedirs(os.path.join(dirname, "stablehlo"))
            with open(
                os.path.join(dirname, "stablehlo", "forward.mlir"), "wb"
            ) as f:
                f.write(program_bytes)
            with open(
                os.path.join(dirname, "t2r_metadata.json"), "w"
            ) as f:
                json.dump({"bench": "policies"}, f)
            with open(
                os.path.join(dirname, "variables.msgpack"), "wb"
            ) as f:
                f.write(serialization.to_bytes(params))

        def perturb(params, seed):
            prng = np.random.RandomState(seed)
            out = {}
            for name, group in params.items():
                if isinstance(group, dict):
                    out[name] = {
                        k: (
                            v + prng.standard_normal(v.shape).astype(
                                np.float32
                            ) * 1e-3
                        )
                        for k, v in group.items()
                    }
                else:
                    out[name] = group  # the int64 step leaf ships dense
            return out

        store_root = tempfile.mkdtemp(prefix="t2r-bench-policy-store-")
        scratch = tempfile.mkdtemp(prefix="t2r-bench-policy-exports-")
        t_store0 = time.monotonic()
        try:
            store = ArtifactStore(store_root)
            base_dir = os.path.join(scratch, "base")
            write_export(base_dir, base_params)
            store.put(base_dir, "base", regime="int8")
            policy_ids = []
            for i in range(n_variants):
                pid = f"policy-{i:04d}"
                export_dir = os.path.join(scratch, pid)
                write_export(export_dir, perturb(base_params, seed=100 + i))
                store.put(export_dir, pid, base_policy="base",
                          regime="int8")
                shutil.rmtree(export_dir)
                policy_ids.append(pid)
            store_secs = time.monotonic() - t_store0
            stats = store.stats()
            delta_ratio = stats["dense_bytes"] / max(
                stats["store_bytes"], 1
            )
            # Hash-verified reconstruction on a seeded sample: a failed
            # round trip raises typed out of load_weights.
            sample = list(policy_ids[:: max(1, n_variants // 10)])
            for pid in sample:
                store.load_weights(pid)

            # -- serving catalog off the store manifests -------------------
            # (scale, bias) are index-spaced for guaranteed-distinct twin
            # values, with a sha-derived component so the serving identity
            # is a function of the STORED artifact, not just the index.
            catalog = {}
            twin_params = {}
            for idx, pid in enumerate(policy_ids):
                sha = store.manifest(pid)["payload"]["weights_sha"]
                scale = 1.0 + idx * 1e-3
                bias = idx * 0.01 + (int(sha[:6], 16) % 997) * 1e-7
                catalog[pid] = {
                    "scale": scale, "bias": bias, "version": 1,
                    "mem_bytes": args.policy_mem_mb << 20,
                }
                twin_params[pid] = (scale, bias)
        finally:
            shutil.rmtree(scratch, ignore_errors=True)

        def twin_value(pid, features):
            """The single-policy twin: the exact float path _MockServer
            computes — float64 accumulate over sorted keys, one cast."""
            scale, bias = twin_params[pid]
            total = 0.0
            for key in sorted(features):
                total += float(np.sum(features[key].astype(np.float64)))
            return float(np.float32(total * scale + bias))

        # -- serving phase: 4-replica fleet, rotating-Zipf diurnal mix ---------
        spec = ReplicaSpec(
            factory=multi_policy_mock_factory,
            factory_kwargs={
                "catalog": catalog,
                "service_ms": args.service_ms,
                "load_ms": args.load_ms,
                "mem_budget_mb": args.mem_budget_mb,
            },
        )
        router = FleetRouter(
            spec, args.replicas,
            max_inflight=args.max_inflight,
            hedge_ms=0,
            probe_interval_ms=50.0,
            seed=11,
        ).start(timeout_s=120.0)
        gateway = Gateway(
            router,
            [
                TenantBinding(tenant="robots-gold", tier="gold",
                              quota_rps=1e6, deadline_ms=4000.0),
                TenantBinding(tenant="eval-bronze", tier="bronze",
                              quota_rps=1e6, deadline_ms=4000.0),
            ],
            max_queue=4096,
            coalesce=True,
            seed=17,
        ).start()
        try:
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and not all(
                s == "up" for s in router.replica_states()
            ):
                time.sleep(0.02)

            # Seeded trace: Poisson arrivals under a diurnal envelope;
            # each arrival draws (tenant, policy rank, obs id); the
            # Zipf-ranked policy window ROTATES through the catalog so
            # the resident sets must churn.
            trng = np.random.RandomState(53)
            ranks = np.arange(1, min(16, n_variants) + 1, dtype=np.float64)
            rank_p = (1.0 / ranks) / np.sum(1.0 / ranks)
            trace = []
            t = trng.uniform(0, 0.01)
            while t < trace_secs:
                rate = args.rate * (
                    1.0 + 0.5 * math.sin(2 * math.pi * t / trace_secs)
                )
                t += trng.exponential(1.0 / max(rate, 1.0))
                rotation = int(t / max(trace_secs / 5.0, 1e-9)) * 13
                rank = trng.choice(len(ranks), p=rank_p)
                pid = policy_ids[(rotation + rank) % n_variants]
                obs = int(trng.randint(1, 9))
                tenant = (
                    "robots-gold" if trng.uniform() < 0.7 else "eval-bronze"
                )
                # Echoes: back-to-back duplicates of this observation.
                # "same" re-asks the SAME policy (must coalesce onto the
                # leader's dispatch); "other" asks a DIFFERENT policy
                # with bitwise-identical features — the exact request
                # shape the old observation-only coalescing key would
                # have joined across policies.
                draw = trng.uniform()
                echo = (
                    "same" if draw < 0.25
                    else "other" if draw < 0.40
                    else None
                )
                trace.append((t, tenant, pid, obs, echo))
            obs_cache = {
                v: {"x": np.full((8,), float(v), np.float32)}
                for v in range(1, 9)
            }

            records = []
            rec_lock = threading.Lock()
            admission = {}
            swap_target = trace[len(trace) // 2][2]
            swap_thread = None
            swap_result = {}
            submitted = 0

            def fire(tenant, pid, obs, rel):
                nonlocal submitted
                submitted += 1
                try:
                    future = gateway.submit(
                        tenant, obs_cache[obs], policy_id=pid
                    )
                except GateError as err:
                    cls = type(err).__name__
                    admission[cls] = admission.get(cls, 0) + 1
                    return

                def on_done(fut, pid=pid, obs=obs, rel=rel,
                            t_submit=time.monotonic()):
                    err = fut.error()
                    latency = (time.monotonic() - t_submit) * 1e3
                    y = None
                    coalesced = False
                    if err is None:
                        response = fut.result(0)
                        y = float(response.outputs["y"])
                        coalesced = response.coalesced
                    with rec_lock:
                        records.append(
                            (pid, obs, rel, latency, y, coalesced,
                             None if err is None else type(err).__name__)
                        )

                future.add_done_callback(on_done)

            t0 = time.monotonic()
            for t_arrival, tenant, pid, obs, echo in trace:
                now = time.monotonic()
                if now - t0 < t_arrival:
                    time.sleep(t_arrival - (now - t0))
                rel = time.monotonic() - t0
                if swap_thread is None and rel >= swap_at:
                    swap_thread = threading.Thread(
                        target=lambda: swap_result.update(
                            gateway.rolling_swap(
                                swap_timeout_s=30.0,
                                policy_id=swap_target,
                            )
                        ),
                        daemon=True,
                    )
                    swap_thread.start()
                fire(tenant, pid, obs, rel)
                if echo == "same":
                    fire(tenant, pid, obs, rel)
                elif echo == "other":
                    other = policy_ids[
                        (policy_ids.index(pid) + 1) % n_variants
                    ]
                    fire(tenant, other, obs, rel)

            expected = submitted - sum(admission.values())
            drain_deadline = time.monotonic() + 30
            while time.monotonic() < drain_deadline:
                with rec_lock:
                    if len(records) >= expected:
                        break
                time.sleep(0.02)
            if swap_thread is not None:
                swap_thread.join(timeout=60)
            with rec_lock:
                frozen = list(records)
            lost = expected - len(frozen)

            router_snap = router.snapshot()
            gate_snap = gateway.snapshot()
        finally:
            gateway.stop()
            router.stop()
            shutil.rmtree(store_root, ignore_errors=True)

        # -- audits ------------------------------------------------------------
        ok = [r for r in frozen if r[6] is None]
        failed = {}
        for r in frozen:
            if r[6] is not None:
                failed[r[6]] = failed.get(r[6], 0) + 1
        # Per-policy bitwise audit vs the single-policy twin, and the
        # cross-policy forensic: a response whose value is NOT its own
        # policy's twin but IS some other policy's twin for the same
        # observation is a smoking-gun cross-policy coalesce join.
        twin_by_obs = {
            obs: {
                round(twin_value(pid, obs_cache[obs]), 9): pid
                for pid in policy_ids
            }
            for obs in range(1, 9)
        }
        bitwise_mismatches = 0
        cross_policy_joins = 0
        group_values = {}
        for pid, obs, _rel, _lat, y, _co, _err in ok:
            group_values.setdefault((pid, obs), set()).add(y)
            expected_y = twin_value(pid, obs_cache[obs])
            if y != expected_y:
                bitwise_mismatches += 1
                owner = twin_by_obs[obs].get(round(y, 9))
                if owner is not None and owner != pid:
                    cross_policy_joins += 1
        groups_single_valued = all(
            len(v) == 1 for v in group_values.values()
        )
        policies_served = len({r[0] for r in ok})
        coalesced_count = sum(1 for r in ok if r[5])
        other_policy_failures = sum(
            1 for r in frozen
            if r[6] is not None and r[0] != swap_target
        )
        evictions = sum(
            r.get("policy_evictions") or 0
            for r in router_snap["replicas"]
        )
        cold_loads = sum(
            r.get("policy_cold_loads") or 0
            for r in router_snap["replicas"]
        )
        latencies = sorted(r[3] for r in ok)
        rc = router_snap["counters"]

        gates = {
            "variants_ge_target": (
                stats["n_policies"] >= n_variants + 1
                and len(catalog) >= n_variants
            ),
            "delta_store_ge_5x": (
                delta_ratio >= 5.0
                and stats["n_delta_policies"] == n_variants
            ),
            "per_policy_bitwise_vs_twin": (
                bitwise_mismatches == 0
                and groups_single_valued
                and len(ok) > 0
            ),
            "zero_cross_policy_joins": cross_policy_joins == 0,
            "coalesce_still_effective": (
                coalesced_count > 0
                and gate_snap["counters"].get("coalesced_joins", 0) > 0
            ),
            "eviction_churn_counted": (
                evictions >= 1
                and cold_loads >= 1
                and (
                    rc.get("policy_resident_dispatches", 0)
                    + rc.get("policy_cold_dispatches", 0)
                )
                > 0
            ),
            "swap_zero_blip_other_policies": (
                swap_result.get("failed", "never-ran") is None
                and other_policy_failures == 0
            ),
            "zero_lost": lost == 0 and not admission,
        }
        all_green = all(gates.values())
        payload = {
            "metric": metric,
            "value": round(delta_ratio, 3),
            "unit": "dense_over_store_bytes",
            "vs_baseline": round(delta_ratio / 5.0, 4),
            "all_green": all_green,
            "gates": gates,
            "detail": {
                "variants": n_variants,
                "store": {
                    **stats,
                    "delta_ratio": round(delta_ratio, 3),
                    "publish_secs": round(store_secs, 3),
                    "verified_sample": len(sample),
                },
                "trace_secs": trace_secs,
                "offered_rate_hz": args.rate,
                "replicas": args.replicas,
                "mem_budget_mb": args.mem_budget_mb,
                "policy_mem_mb": args.policy_mem_mb,
                "submitted": submitted,
                "completed": len(ok),
                "failed_typed": failed,
                "shed_at_admission": admission,
                "lost": lost,
                "policies_served": policies_served,
                "coalesced": coalesced_count,
                "bitwise_mismatches": bitwise_mismatches,
                "cross_policy_joins": cross_policy_joins,
                "p50_ms": round(percentile(latencies, 0.50), 3),
                "p99_ms": round(percentile(latencies, 0.99), 3),
                "evictions": evictions,
                "cold_loads": cold_loads,
                "router_policy_counters": {
                    k: v for k, v in rc.items() if "policy" in k
                },
                "swap_target": swap_target,
                "swap_result": (
                    {
                        "swapped": swap_result.get("swapped"),
                        "failed": swap_result.get("failed"),
                    }
                    if swap_result
                    else None
                ),
                "backend": "multi_policy_mock_replica_processes",
                "host_cpus": os.cpu_count(),
            },
            "cpu_proxy": True,
            "proxy_note": (
                "placement/eviction/coalescing control plane measured "
                "over mock replica processes on CPU; the store's delta "
                "compression ratio and every bitwise/isolation contract "
                "are platform-independent"
            ),
        }
        if args.out:
            with open(args.out, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
        _emit(payload)
    except Exception as err:  # noqa: BLE001
        _fail("bench_policies", err, metric=metric)


def bench_fabric(args) -> None:
    """Cross-host serving fabric leg (`python bench.py fabric`).

    Runs the round-21 acceptance story end to end:

      1. **Fleet.** Two availability zones, each a FleetRouter of
         `--replicas-per-zone` mock replicas on the SOCKET transport —
         every replica its own session/process group, registered by
         published address (audited: no replica shares the bench's
         process group, the fleet spans >= 2 distinct groups).
      2. **Fault-free twin.** A Gateway spanning both zones as pools
         (gold tenant homed in z1, a bronze flash crowd in z0) replays
         a seeded trace with a mid-trace crowd window; per-zone
         admission/shed ledgers are read off the gateway snapshot.
      3. **Partition twin.** The SAME trace, but z1's replicas are
         partitioned at the serving wire (chaos `net_send`/`net_recv`
         partition, symmetric) for the crowd window. Gates: gold
         availability >= the fault-free twin's, ZERO lost requests
         (every future resolves; every failure a typed GateError), all
         shed typed and counted per zone. After the heal, z1 must
         serve again — the link re-resolves the zone's replicas by
         their published (incarnation-stamped) addresses.
      4. **Zone-router leg.** The ZoneRouter over the same two zones,
         partitioned again: every request survives via cross-zone
         dispatch/retry (typed zone counters, zero lost), and after
         the heal z1 wins requests again.
      5. **Heterogeneity.** Per-host AOT key resolution on a forged
         `aot/` set: the matching host's report is all-"aot"; a host
         with a transplanted topology gets typed fallback rows (never
         a silent mismatch load); the two zones' replies to one
         request are bitwise-identical.
      6. **Local byte-compat.** `T2R_FLEET_TRANSPORT=local` rides the
         pre-fabric mp path and returns bitwise the same outputs as
         the socket path.

    All arrivals are seeded: rerunning the leg replays the trace.
    """
    import shutil
    import tempfile
    import threading

    metric = "fabric_cross_host_partition_slo_cpu_proxy"
    try:
        import numpy as np

        from tensor2robot_tpu.export import aot as aot_lib
        from tensor2robot_tpu.serving import (
            FleetRouter,
            GateError,
            Gateway,
            ReplicaSpec,
            TenantBinding,
            ZoneRouter,
            host_aot_report,
            mock_server_factory,
        )
        from tensor2robot_tpu.testing import chaos

        n_per_zone = args.replicas_per_zone
        trace_secs = args.trace_secs
        crowd_window = (0.4 * trace_secs, 0.6 * trace_secs)
        partition_until = 0.7 * trace_secs
        root = tempfile.mkdtemp(prefix="bench-fabric-")
        spec = ReplicaSpec(
            factory=mock_server_factory,
            factory_kwargs={
                "service_ms": args.service_ms,
                "version": 1,
                # Shared artifact identity: the two zones DECLARE
                # interchangeability, which is what gateway cross-pool
                # failover matches on before moving a request.
                "fingerprint": "fabric-artifact-r21",
            },
        )

        def _features(value=1.0):
            return {"x": np.full((4,), value, np.float32)}

        def _partition_plan():
            peers = "+".join(f"z1.r{i}" for i in range(n_per_zone))
            return f"net_send:1:partition:{peers}"

        pools = {}
        for zone in ("0", "1"):
            pools[f"z{zone}"] = FleetRouter(
                spec, n_per_zone,
                transport_mode="socket",
                fabric_root=os.path.join(root, f"z{zone}"),
                zone=zone,
                probe_interval_ms=50.0,
                probe_miss_limit=6,
                backoff_ms=10.0,
                hedge_ms=args.hedge_ms,
                max_inflight=args.max_inflight,
                max_respawns=50,
                seed=11,
            ).start(timeout_s=120.0)
        try:
            deadline = time.monotonic() + 90
            while time.monotonic() < deadline and not all(
                s == "up"
                for pool in pools.values()
                for s in pool.replica_states()
            ):
                time.sleep(0.02)

            # -- process-group audit ----------------------------------
            own_pgid = os.getpgid(0)
            replica_pids = {}
            for name, pool in pools.items():
                replica_pids[name] = [
                    r["host"]["pid"]
                    for r in pool.snapshot()["replicas"]
                ]
            pgids = {
                pid: os.getpgid(pid)
                for pids in replica_pids.values()
                for pid in pids
            }
            process_groups_ok = (
                own_pgid not in pgids.values()
                and len(set(pgids.values())) >= 2
            )

            # -- seeded two-tenant trace over the gateway -------------
            def run_trace(label, partition):
                gateway = Gateway(
                    dict(pools),
                    [
                        TenantBinding(
                            tenant="robots-gold", pool="z1",
                            tier="gold", quota_rps=1e6,
                            deadline_ms=args.deadline_ms,
                        ),
                        TenantBinding(
                            tenant="crowd-bronze", pool="z0",
                            tier="bronze", quota_rps=30.0, burst=15,
                            deadline_ms=args.deadline_ms,
                        ),
                    ],
                    max_queue=4096,
                    seed=17,
                ).start()
                rng = np.random.RandomState(23)
                record_lock = threading.Lock()
                stats = {
                    tenant: {
                        "submitted": 0, "completed": 0,
                        "typed_failures": {}, "lost": 0,
                    }
                    for tenant in ("robots-gold", "crowd-bronze")
                }
                futures = []

                def _account(tenant, future):
                    err = future.error()
                    with record_lock:
                        if err is None:
                            stats[tenant]["completed"] += 1
                        elif isinstance(err, GateError):
                            bucket = stats[tenant]["typed_failures"]
                            cls = type(err).__name__
                            bucket[cls] = bucket.get(cls, 0) + 1
                        else:  # untyped = lost discipline broken
                            stats[tenant]["lost"] += 1

                def _drive(tenant, base_rps, crowd_factor):
                    t0 = time.monotonic()
                    while True:
                        now = time.monotonic() - t0
                        if now >= trace_secs:
                            return
                        in_crowd = (
                            crowd_window[0] <= now < crowd_window[1]
                        )
                        rate = base_rps * (
                            crowd_factor if in_crowd else 1.0
                        )
                        with record_lock:
                            stats[tenant]["submitted"] += 1
                        try:
                            future = gateway.submit(
                                tenant, _features(value=1.0)
                            )
                        except GateError as err:
                            with record_lock:
                                bucket = stats[tenant]["typed_failures"]
                                cls = type(err).__name__
                                bucket[cls] = bucket.get(cls, 0) + 1
                        else:
                            future.add_done_callback(
                                lambda f, t=tenant: _account(t, f)
                            )
                            with record_lock:
                                futures.append((tenant, future))
                        time.sleep(
                            max(0.002, rng.exponential(1.0 / rate))
                        )

                def _chaos_clock():
                    time.sleep(crowd_window[0])
                    chaos.configure(_partition_plan())
                    time.sleep(partition_until - crowd_window[0])
                    chaos.configure(None)

                threads = [
                    threading.Thread(
                        target=_drive,
                        args=("robots-gold", args.gold_rps, 1.0),
                        daemon=True,
                    ),
                    threading.Thread(
                        target=_drive,
                        args=(
                            "crowd-bronze", args.bronze_rps,
                            args.crowd_factor,
                        ),
                        daemon=True,
                    ),
                ]
                if partition:
                    threads.append(threading.Thread(
                        target=_chaos_clock, daemon=True
                    ))
                for thread in threads:
                    thread.start()
                for thread in threads:
                    thread.join()
                # Every future resolves, always: anything still
                # pending after its deadline + slack was LOST, which
                # the fabric forbids.
                settle = time.monotonic() + args.deadline_ms / 1e3 + 30
                for tenant, future in futures:
                    remaining = settle - time.monotonic()
                    try:
                        future.result(max(0.01, remaining))
                    except GateError:
                        pass  # typed: already accounted by callback
                    except TimeoutError:
                        with record_lock:
                            stats[tenant]["lost"] += 1
                    except Exception:
                        pass  # untyped: callback counted it as lost
                gate_snap = gateway.snapshot()
                gateway.stop()
                chaos.configure(None)
                per_zone_ledgers = {
                    name: pool_snap.get("counters", {})
                    for name, pool_snap in gate_snap["pools"].items()
                }
                gold = stats["robots-gold"]
                answered = gold["completed"] + sum(
                    gold["typed_failures"].values()
                )
                availability = (
                    gold["completed"] / answered if answered else 0.0
                )
                return {
                    "label": label,
                    "tenants": stats,
                    "gold_availability": round(availability, 5),
                    "lost": sum(
                        s["lost"] for s in stats.values()
                    ),
                    "zone_ledgers": per_zone_ledgers,
                    "cross_pool_retries": gate_snap["counters"].get(
                        "cross_pool_retries", 0
                    ),
                }

            fault_free = run_trace("fault_free", partition=False)
            partitioned = run_trace("partition", partition=True)

            # Post-heal: z1 must serve again (its links re-resolved the
            # replicas' published, incarnation-stamped addresses).
            heal_deadline = time.monotonic() + 60
            z1_healed = False
            while time.monotonic() < heal_deadline:
                try:
                    pools["z1"].call(_features(), deadline_ms=2000)
                    z1_healed = True
                    break
                except Exception:
                    time.sleep(0.1)
            z1_post = pools["z1"].snapshot()
            z1_pids_after = [
                (r.get("host") or {}).get("pid")
                for r in z1_post["replicas"]
            ]

            # -- zone-router leg: typed cross-zone survival -----------
            zone_router = ZoneRouter(dict(pools), hedge_ms=30)
            zr_before = zone_router.snapshot()["counters"]
            chaos.configure(_partition_plan())
            zr_lost = 0
            for _ in range(16):
                try:
                    zone_router.call(
                        _features(), deadline_ms=args.deadline_ms
                    )
                except Exception:
                    zr_lost += 1
            chaos.configure(None)
            zr_mid = zone_router.snapshot()["counters"]
            z0_wins_during = zr_mid.get("zone_win_z0", 0) - (
                zr_before.get("zone_win_z0", 0)
            )
            zr_heal_deadline = time.monotonic() + 60
            z1_wins_back = False
            while time.monotonic() < zr_heal_deadline:
                base = zone_router.snapshot()["counters"].get(
                    "zone_win_z1", 0
                )
                try:
                    for _ in range(4):
                        zone_router.call(_features(), deadline_ms=2000)
                except Exception:
                    time.sleep(0.1)
                    continue
                if zone_router.snapshot()["counters"].get(
                    "zone_win_z1", 0
                ) > base:
                    z1_wins_back = True
                    break
            zr_counters = zone_router.snapshot()["counters"]

            # -- heterogeneity: per-host AOT key resolution -----------
            import jax

            export_root = os.path.join(root, "export")
            aot_dir = os.path.join(export_root, aot_lib.AOT_DIR)
            os.makedirs(aot_dir)
            host_topology = aot_lib.device_topology()
            for bucket in (8, 16):
                header = {
                    "format_version": aot_lib.AOT_FORMAT_VERSION,
                    "jax": jax.__version__,
                    "topology": dict(host_topology),
                    "fingerprint": "fabric-artifact-r21",
                    "regime": "serve",
                    "bucket": bucket,
                }
                with open(
                    os.path.join(aot_dir, f"exec_serve_b{bucket}.bin"),
                    "wb",
                ) as f:
                    f.write(aot_lib._pack(header, b"bench-payload"))
            report_match = host_aot_report(export_root)
            report_other = host_aot_report(
                export_root,
                topology={
                    "platform": "tpu", "device_kind": "TPU v4",
                    "device_count": 8,
                },
            )
            reply_a = pools["z0"].call(
                _features(value=2.0), deadline_ms=10000
            ).outputs["y"]
            reply_b = pools["z1"].call(
                _features(value=2.0), deadline_ms=10000
            ).outputs["y"]
            replies_bitwise = (
                np.asarray(reply_a).tobytes()
                == np.asarray(reply_b).tobytes()
            )
            heterogeneity_ok = (
                report_match["all_aot"]
                and report_match["counts"]["aot"] == 2
                and not report_other["all_aot"]
                and report_other["counts"]["topology"] == 2
                and replies_bitwise
            )

            # -- local byte-compat leg --------------------------------
            local_router = FleetRouter(
                spec, 1, transport_mode="local",
                probe_interval_ms=50.0, backoff_ms=10.0,
            ).start(timeout_s=90.0)
            try:
                local_reply = local_router.call(
                    _features(value=2.0), deadline_ms=10000
                ).outputs["y"]
                local_transport = local_router.snapshot()["transport"]
            finally:
                local_router.stop()
            local_compat_ok = (
                local_transport == "local"
                and np.asarray(local_reply).tobytes()
                == np.asarray(reply_a).tobytes()
            )
        finally:
            chaos.configure(None)
            for pool in pools.values():
                try:
                    pool.stop()
                except Exception:
                    pass
            shutil.rmtree(root, ignore_errors=True)

        gates = {
            "fleet_spans_separate_process_groups": process_groups_ok,
            "fault_free_zero_lost": fault_free["lost"] == 0,
            "partition_zero_lost": partitioned["lost"] == 0,
            "partition_gold_holds_fault_free_bar": (
                partitioned["gold_availability"]
                >= fault_free["gold_availability"]
            ),
            "all_shed_typed": all(
                s["lost"] == 0
                for leg in (fault_free, partitioned)
                for s in leg["tenants"].values()
            ),
            "per_zone_ledgers_present": all(
                set(leg["zone_ledgers"]) == {"z0", "z1"}
                for leg in (fault_free, partitioned)
            ),
            "healed_zone_reresolved_and_serving": z1_healed,
            "zone_router_zero_lost_under_partition": zr_lost == 0,
            "zone_router_z0_absorbed_partition": z0_wins_during >= 16,
            "zone_router_z1_wins_after_heal": z1_wins_back,
            "heterogeneity_typed_aot_keys_bitwise_replies": (
                heterogeneity_ok
            ),
            "local_transport_byte_compatible": local_compat_ok,
        }
        ok = all(gates.values())
        payload = {
            "metric": metric,
            "value": partitioned["gold_availability"],
            "unit": "gold_availability_under_zone_partition",
            "vs_baseline": fault_free["gold_availability"],
            "ok": ok,
            "gates": gates,
            "detail": {
                "zones": {
                    name: {
                        "replicas": n_per_zone,
                        "pids": replica_pids[name],
                    }
                    for name in pools
                },
                "process_groups": sorted(set(pgids.values())),
                "fault_free_leg": fault_free,
                "partition_leg": partitioned,
                "z1_pids_after_heal": z1_pids_after,
                "zone_router_leg": {
                    "lost": zr_lost,
                    "z0_wins_during_partition": z0_wins_during,
                    "z1_wins_after_heal": z1_wins_back,
                    "counters": zr_counters,
                },
                "heterogeneity": {
                    "host_topology": host_topology,
                    "matching_host": report_match["counts"],
                    "matching_all_aot": report_match["all_aot"],
                    "transplanted_host": report_other["counts"],
                    "replies_bitwise_identical": replies_bitwise,
                },
                "trace_secs": trace_secs,
                "deadline_ms": args.deadline_ms,
                "backend": "mock_replica_processes_socket_transport",
                "host_cpus": os.cpu_count(),
            },
            "cpu_proxy": True,
            "proxy_note": (
                "cross-host fabric measured over socket-transport mock "
                "replica processes on one host; absolute rates are "
                "host-bound, the availability/typed-loss/bitwise "
                "contracts are platform-independent"
            ),
        }
        if args.out:
            with open(args.out, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
        _emit(payload)
    except Exception as err:  # noqa: BLE001
        _fail("bench_fabric", err, metric=metric)


def bench_wire(args) -> None:
    """Zero-copy spec-native wire codec leg (`python bench.py wire`).

    Measures the round-22 serving wire end to end on a socketpair —
    real `write_frame`/`read_frame`, an echo server that decodes the
    request exactly as a replica does (`transport.decode_request`) and
    frames a reply back — with camera-sized observations
    (`--image-hw` square uint8 + `--state-dim` float32), then gates the
    acceptance story:

      1. **Throughput.** Requests/s for `T2R_WIRE=pickle` (the
         pre-spec wire, bit-identical frames) vs `T2R_WIRE=spec`
         (scatter-gather segments, pooled receive, adler32 body +
         crc32 structural integrity). Gate: spec >= `--speedup-min`
         x pickle (median of `--trials` timed windows after warmup).
      2. **Bitwise.** The features the server decodes and the replies
         the client reads are bit-identical across the two codecs;
         a live socket-mode FleetRouter pool returns bit-identical
         outputs under pickle wire, spec wire, and the local mp
         transport.
      3. **Quant.** `T2R_WIRE_QUANT=<--quant>` rides the
         BlockScaledCollective {'q','s'} format: uint8 image planes
         untouched (bitwise), float features within the declared
         rel-Linf parity gate, wire bytes attributed per segment
         class.
      4. **Zero-allocation receive.** The codec buffer pool's `allocs`
         counter is FLAT across the steady-state window (every frame
         lands in a reused buffer).
      5. **Hostile bytes.** Every `corrupt_frame_variants` family
         against a spec frame is rejected with a typed error.
      6. **Pipelining.** `PipelinedChannel` overlaps `--pipeline-requests`
         in-flight requests on one connection vs SocketChannel lockstep.

    The artifact lands per-stage wire timings (serialize/crc/send/
    recv/deserialize) and per-segment-class byte counters from the
    codec's own observability surface.
    """
    import hashlib
    import shutil
    import socket as socket_lib
    import tempfile
    import threading

    metric = "wire_codec_spec_vs_pickle_reqs_per_sec"
    try:
        import numpy as np

        from tensor2robot_tpu import flags as t2r_flags
        from tensor2robot_tpu.analysis import corpus
        from tensor2robot_tpu.net import codec, frames
        from tensor2robot_tpu.serving import (
            FleetRouter,
            ReplicaSpec,
            mock_server_factory,
        )
        from tensor2robot_tpu.serving import transport as serving_transport

        rng = np.random.RandomState(22)
        hw = args.image_hw
        features = {
            "image": rng.randint(0, 256, (hw, hw, 3), dtype=np.uint8),
            "state": (rng.randn(args.state_dim) * 1.7).astype(np.float32),
        }
        reply_outputs = {
            "y": np.float32(1.25),
            "nbytes": np.int64(sum(v.nbytes for v in features.values())),
        }

        def _request(i, wire):
            if wire == "spec":
                payload = ("raw", dict(features))
            else:
                payload = ("inline",) + serving_transport.pack(
                    dict(features)
                )
            return ("req", i, 1, None, payload)

        def _echo_loop(sock, n, digest_out):
            """Replica-shaped echo: decode the request payload exactly
            as a replica does, frame back a reply whose bytes are
            request-independent. When `digest_out` is given (the
            untimed verification window), every decoded feature is
            sha256'd — the cross-codec bitwise evidence. The timed
            windows skip the digest: hashing 670 KB per frame would be
            a constant added to BOTH codecs, compressing the ratio the
            gate measures."""
            cache = serving_transport.ReplicaSlotCache()
            digest = hashlib.sha256() if digest_out is not None else None
            try:
                for _ in range(n):
                    message = frames.read_frame(
                        sock, deadline=time.monotonic() + 60
                    )
                    feats = serving_transport.decode_request(
                        message[4], None, cache
                    )
                    if digest is not None:
                        for key in sorted(feats):
                            arr = np.ascontiguousarray(feats[key])
                            digest.update(key.encode())
                            digest.update(arr.tobytes())
                    feats = None
                    reply = (message[1], "ok") + serving_transport.pack(
                        reply_outputs
                    )
                    frames.write_frame(sock, reply)
            finally:
                if digest_out is not None:
                    digest_out.append(digest.hexdigest())

        def _run_window(wire, n, verify=False):
            """(elapsed_s, features_digest, replies_digest) for n
            request/reply round trips on one socketpair."""
            a, b = socket_lib.socketpair()
            a.settimeout(60.0)
            b.settimeout(60.0)
            digest_out = [] if verify else None
            server = threading.Thread(
                target=_echo_loop, args=(b, n, digest_out), daemon=True
            )
            server.start()
            replies = hashlib.sha256() if verify else None
            t0 = time.perf_counter()
            try:
                for i in range(n):
                    frames.write_frame(a, _request(i, wire))
                    reply = frames.read_frame(
                        a, deadline=time.monotonic() + 60
                    )
                    if replies is not None:
                        replies.update(repr(reply[:2]).encode())
                        replies.update(reply[3])
                elapsed = time.perf_counter() - t0
            finally:
                server.join(timeout=60)
                a.close()
                b.close()
            if not verify:
                return elapsed, None, None
            return elapsed, digest_out[0], replies.hexdigest()

        saved_wire = t2r_flags.read_raw("T2R_WIRE")
        saved_quant = t2r_flags.read_raw("T2R_WIRE_QUANT")
        results = {}
        pool_before = pool_after = None
        try:
            t2r_flags.write_env("T2R_WIRE_QUANT", "none")
            for wire in ("pickle", "spec"):
                t2r_flags.write_env("T2R_WIRE", wire)
                _run_window(wire, args.warmup)
                _, feats_digest, replies_digest = _run_window(
                    wire, 12, verify=True
                )
                if wire == "spec":
                    pool_before = codec.POOL.snapshot()
                trials = []
                for _ in range(args.trials):
                    elapsed, _, _ = _run_window(wire, args.frames)
                    trials.append(args.frames / elapsed)
                if wire == "spec":
                    pool_after = codec.POOL.snapshot()
                results[wire] = {
                    "reqs_per_sec": float(np.median(trials)),
                    "trials": [round(t, 2) for t in trials],
                    "features_digest": feats_digest,
                    "replies_digest": replies_digest,
                }

            # -- quant leg ------------------------------------------------
            t2r_flags.write_env("T2R_WIRE", "spec")
            t2r_flags.write_env("T2R_WIRE_QUANT", args.quant)
            _run_window("spec", max(4, args.warmup // 4))
            q_elapsed, _, _ = _run_window("spec", args.frames)
            # Parity evidence measured directly on one round trip.
            q, s = None, None
            encoded = codec.quant_encode_array(
                features["state"],
                args.quant,
                t2r_flags.get_int("T2R_COLLECTIVE_BLOCK"),
            )
            quant_applied = encoded is not None
            if quant_applied:
                q, s = encoded
                dequant = codec.quant_decode_array(
                    q, s, features["state"].shape, np.float32
                )
                quant_rel_linf = float(
                    np.max(np.abs(dequant - features["state"]))
                    / np.max(np.abs(features["state"]))
                )
            else:
                quant_rel_linf = 0.0  # dense fallback is bitwise
            results["quant"] = {
                "mode": args.quant,
                "reqs_per_sec": round(args.frames / q_elapsed, 2),
                "applied": quant_applied,
                "rel_linf": quant_rel_linf,
                "parity_gate": codec.QUANT_PARITY_REL_LINF[args.quant],
            }
        finally:
            t2r_flags.restore_env("T2R_WIRE", saved_wire)
            t2r_flags.restore_env("T2R_WIRE_QUANT", saved_quant)

        speedup = (
            results["spec"]["reqs_per_sec"]
            / results["pickle"]["reqs_per_sec"]
        )

        # -- live pool: bitwise replies across codecs ---------------------
        root = tempfile.mkdtemp(prefix="bench-wire-")
        pool_outputs = {}
        try:
            for wire in ("pickle", "spec", "local"):
                if wire == "local":
                    t2r_flags.restore_env("T2R_WIRE", saved_wire)
                    transport_kwargs = {}
                else:
                    t2r_flags.write_env("T2R_WIRE", wire)
                    transport_kwargs = {
                        "transport_mode": "socket",
                        "fabric_root": os.path.join(root, wire),
                    }
                router = FleetRouter(
                    ReplicaSpec(
                        factory=mock_server_factory,
                        factory_kwargs={"service_ms": 0.5, "version": 1},
                        env={"T2R_WIRE": wire} if wire != "local" else {},
                    ),
                    args.replicas,
                    probe_interval_ms=50.0,
                    backoff_ms=10.0,
                    **transport_kwargs,
                ).start(timeout_s=120.0)
                try:
                    response = router.submit(
                        dict(features), deadline_ms=30000
                    ).result(60)
                    pool_outputs[wire] = {
                        k: np.asarray(v).tobytes()
                        for k, v in response.outputs.items()
                    }
                finally:
                    router.stop()
        finally:
            t2r_flags.restore_env("T2R_WIRE", saved_wire)
            shutil.rmtree(root, ignore_errors=True)
        pool_bitwise = (
            pool_outputs["pickle"] == pool_outputs["spec"]
            == pool_outputs["local"]
        )

        # -- hostile bytes: the corpus against a spec frame ---------------
        # A small frame: it must fit the socketpair buffer whole, since
        # the reader only runs after the hostile bytes are fully sent.
        spec_frame = codec.encode_spec_frame_bytes(
            ("req", 0, 1, None, ("raw", {
                "image": features["image"][:24, :24].copy(),
                "state": features["state"][:128].copy(),
            }))
        )
        variants = corpus.corrupt_frame_variants(
            spec_frame, header_size=codec.SPEC_PREFIX.size
        )
        rejected = 0
        for name, variant in sorted(variants.items()):
            a, b = socket_lib.socketpair()
            a.settimeout(10.0)
            b.settimeout(10.0)
            try:
                a.sendall(variant)
                a.close()
                try:
                    frames.read_frame(b, deadline=time.monotonic() + 5)
                except frames.TransportError:
                    rejected += 1
            finally:
                b.close()

        # -- pipelining: overlapped in-flight vs lockstep -----------------
        service_s = args.pipeline_service_ms / 1e3

        def _pipeline_handler(request, send):
            req_id, payload = request

            def _reply():
                time.sleep(service_s)
                send((req_id, "ok", payload))

            threading.Thread(target=_reply, daemon=True).start()

        pipe_root = tempfile.mkdtemp(prefix="bench-wire-pipe-")
        server = frames.FrameServer(_pipeline_handler, duplex=True).start()
        try:
            frames.publish_address(pipe_root, server.port, incarnation=1)
            n_pipe = args.pipeline_requests
            lockstep = frames.SocketChannel(pipe_root)
            t0 = time.perf_counter()
            for i in range(n_pipe):
                lockstep.call((i, "x"), i, timeout_s=30)
            lockstep_s = time.perf_counter() - t0
            lockstep.close()
            piped = frames.PipelinedChannel(pipe_root)
            t0 = time.perf_counter()
            pendings = [piped.submit((i, "x"), i) for i in range(n_pipe)]
            for pending in pendings:
                piped.result(pending, timeout_s=30)
            pipelined_s = time.perf_counter() - t0
            piped.close()
        finally:
            server.stop()
            shutil.rmtree(pipe_root, ignore_errors=True)
        pipeline_overlap = lockstep_s / max(pipelined_s, 1e-9)

        wire_stats = codec.wire_snapshot()
        gates = {
            "spec_speedup_over_pickle": speedup >= args.speedup_min,
            "replies_bitwise_identical_across_codecs": (
                results["pickle"]["replies_digest"]
                == results["spec"]["replies_digest"]
            ),
            "decoded_features_bitwise_identical_across_codecs": (
                results["pickle"]["features_digest"]
                == results["spec"]["features_digest"]
            ),
            "pool_replies_bitwise_identical": pool_bitwise,
            "quant_within_parity_gate": (
                results["quant"]["rel_linf"]
                <= results["quant"]["parity_gate"]
            ),
            "zero_steady_state_receive_allocs": (
                pool_after["allocs"] == pool_before["allocs"]
            ),
            "all_corruption_variants_typed_rejected": (
                rejected == len(variants)
            ),
            "pipelining_overlaps_lockstep": pipeline_overlap >= 1.5,
        }
        ok = all(gates.values())
        payload = {
            "metric": metric,
            "value": round(speedup, 3),
            "unit": "spec_over_pickle_reqs_per_sec_ratio",
            "vs_baseline": round(results["pickle"]["reqs_per_sec"], 2),
            "ok": ok,
            "gates": gates,
            "detail": {
                "pickle_reqs_per_sec": results["pickle"]["reqs_per_sec"],
                "spec_reqs_per_sec": results["spec"]["reqs_per_sec"],
                "trials": {
                    wire: results[wire]["trials"]
                    for wire in ("pickle", "spec")
                },
                "quant_leg": results["quant"],
                "message_shape": {
                    "image": [hw, hw, 3],
                    "image_dtype": "uint8",
                    "state": [args.state_dim],
                    "state_dtype": "float32",
                },
                "frames_per_trial": args.frames,
                "pool_audit": {
                    "before_steady_window": pool_before,
                    "after_steady_window": pool_after,
                },
                "corruption_variants": {
                    "total": len(variants),
                    "typed_rejected": rejected,
                },
                "pipelining": {
                    "requests": args.pipeline_requests,
                    "service_ms": args.pipeline_service_ms,
                    "lockstep_s": round(lockstep_s, 4),
                    "pipelined_s": round(pipelined_s, 4),
                    "overlap_ratio": round(pipeline_overlap, 2),
                },
                "wire_stats": wire_stats,
                "host_cpus": os.cpu_count(),
            },
            "cpu_proxy": True,
            "proxy_note": (
                "wire measured over a local socketpair on one host; "
                "absolute reqs/s are host-bound, the speedup ratio, "
                "bitwise/parity contracts, allocation audit and typed "
                "rejection are platform-independent"
            ),
        }
        if args.out:
            with open(args.out, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
        _emit(payload)
    except Exception as err:  # noqa: BLE001
        _fail("bench_wire", err, metric=metric)


def bench_comms(args) -> None:
    """Quantized gradient-collective leg (`python bench.py comms`).

    Builds the forced 8-device host-platform mesh (the same GSPMD/
    collective lowering a TPU slice uses; wall-times are CPU proxies,
    byte counts are exact) and measures the ZeRO-2 gradient exchange —
    quantized reduce-scatter + update all-gather — for fp32/fp16/int8 on
    a QT-Opt-sized gradient tree (the flagship critic's real parameter
    count via eval_shape). Then two correctness legs: a mock-model
    loss-parity check (quantized-with-error-feedback vs exact within
    tolerance after --steps training steps) and the `none`-path
    byte-identity check against the default ZeRO-2 step.

    value = int8 bytes-on-the-wire reduction vs fp32; vs_baseline =
    reduction / 3.5 (the acceptance bar).
    """
    import subprocess

    metric = "zero2_collective_bytes_reduction"
    _require_cpu_request(metric, "bench.py comms")
    if not getattr(args, "inner", False):
        # The 8-device host mesh must be configured before the jax
        # backend initializes (XLA_FLAGS is read at backend creation) —
        # re-exec to be safe against any earlier leg having touched the
        # backend.
        env = dict(os.environ)
        # The leg owns its mesh: an inherited device-count flag (e.g. a
        # 4-device convention from another run) is replaced, not kept —
        # the inner process asserts exactly 8 devices.
        kept = [
            part
            for part in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in part
        ]
        env["XLA_FLAGS"] = " ".join(
            kept + ["--xla_force_host_platform_device_count=8"]
        )
        # The legs own the wire format (train(None) IS the exact GSPMD
        # baseline): an ambient fleet-wide T2R_COLLECTIVE_QUANT export
        # must not quantize the baseline and degrade the parity check to
        # quantized-vs-quantized.
        env.pop("T2R_COLLECTIVE_QUANT", None)
        env.pop("T2R_COLLECTIVE_BLOCK", None)
        proc = subprocess.run(
            [
                sys.executable, os.path.abspath(__file__), "comms",
                "--_inner", "--block", str(args.block),
                "--steps", str(args.steps),
                "--repeats", str(args.repeats), "--out", args.out,
            ],
            env=env, text=True, capture_output=True,
        )
        if proc.stderr:
            sys.stderr.write(proc.stderr[-4000:])
        lines = proc.stdout.strip().splitlines()
        print(lines[-1] if lines else "")
        sys.exit(proc.returncode)

    try:
        import jax
        import jax.flatten_util
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec

        devices = jax.devices()
        if len(devices) != 8 or devices[0].platform != "cpu":
            raise RuntimeError(
                f"expected the forced 8-device host mesh, got {devices}"
            )
        from __graft_entry__ import _flagship

        from tensor2robot_tpu.parallel import collectives
        from tensor2robot_tpu.parallel import mesh as mesh_lib
        from tensor2robot_tpu.train import train_eval
        from tensor2robot_tpu.train.metrics import collective_record
        from tensor2robot_tpu.utils.mocks import (
            MockInputGenerator,
            MockT2RModel,
        )

        mesh = mesh_lib.make_mesh(data=8)
        axis = mesh_lib.DATA_AXIS
        block = args.block

        # The QT-Opt-sized gradient tree: the flagship critic's true
        # parameter count, shapes only (eval_shape — nothing large is
        # materialized at 472px on this host).
        model, fbatch = _flagship(batch_size=1)
        feats, _ = model.preprocessor.preprocess(
            fbatch["features"], fbatch.get("labels"),
            mode="train", rng=jax.random.PRNGKey(0),
        )
        var_shapes = jax.eval_shape(
            lambda rng: model.init_variables(rng, feats),
            jax.random.PRNGKey(0),
        )
        n_params = sum(
            int(np.prod(leaf.shape))
            for leaf in jax.tree_util.tree_leaves(var_shapes["params"])
        )
        layout = collectives.FlatShardLayout(n_params, 8, block)
        payload = jnp.asarray(
            np.random.RandomState(0)
            .randn(layout.padded)
            .astype(np.float32)
            * 1e-3
        )

        legs = {}
        for name in ("none", "fp16", "int8"):
            coll = collectives.get_collective(name, block)

            def exchange(flat, coll=coll):
                reduced, _ = coll.reduce_scatter(layout.rows(flat), axis)
                full, _ = coll.all_gather_shard(reduced / 8.0, axis)
                return full

            fn = jax.jit(
                collectives.smap(
                    exchange, mesh, (PartitionSpec(),), PartitionSpec()
                )
            )
            jax.block_until_ready(fn(payload))  # compile outside timing
            times = []
            for _ in range(args.repeats):
                start = time.perf_counter()
                jax.block_until_ready(fn(payload))
                times.append((time.perf_counter() - start) * 1e3)
            times.sort()
            pre, post = collectives.wire_summary(coll, layout.padded)
            legs[name] = collective_record(
                pre, post, wall_ms=times[len(times) // 2]
            )
        reduction = legs["int8"]["collective/compression"]

        # Mock-model loss parity: same data, same seeds, N training
        # steps; quantized-with-feedback must land within tolerance of
        # the exact GSPMD step.
        def train(quant):
            mock = MockT2RModel(device_type="cpu", use_batch_norm=False)
            generator = MockInputGenerator(batch_size=16)
            generator.set_specification_from_model(mock, "train")
            batches = iter(generator.create_dataset("train"))
            first = next(batches)
            kwargs = (
                {}
                if quant is None
                else {"collective_quant": quant, "collective_block": block}
            )
            compiled = train_eval.CompiledModel(
                mock, mesh=mesh, donate_state=False,
                shard_weight_update=True, **kwargs
            )
            state = compiled.init_state(jax.random.PRNGKey(0), first)
            rng = jax.random.PRNGKey(7)
            batch, metrics = first, None
            for _ in range(args.steps):
                state, metrics = compiled.train_step(
                    state, compiled.shard_batch(batch), rng
                )
                batch = next(batches)
            return state, float(jax.device_get(metrics["loss"]))

        exact_state, exact_loss = train(None)
        _, fp16_loss = train("fp16")
        _, int8_loss = train("int8")
        tolerance = 5e-3
        parity = {
            "steps": args.steps,
            "exact_loss": exact_loss,
            "fp16_loss": fp16_loss,
            "int8_loss": int8_loss,
            "fp16_abs_diff": abs(fp16_loss - exact_loss),
            "int8_abs_diff": abs(int8_loss - exact_loss),
            "tolerance": tolerance,
            "ok": (
                abs(fp16_loss - exact_loss) < tolerance
                and abs(int8_loss - exact_loss) < tolerance
            ),
        }

        # `none` must not even engage the manual step: bitwise-identical
        # params to the default ZeRO-2 run. (A wiring check — both legs
        # compile the same GSPMD program, so this catches the flag
        # accidentally engaging the manual path, not ExactCollective
        # regressions; those live in tests/test_collectives.py.)
        none_state, _ = train("none")
        flat_none = jax.flatten_util.ravel_pytree(
            jax.device_get(none_state.params)
        )[0]
        flat_exact = jax.flatten_util.ravel_pytree(
            jax.device_get(exact_state.params)
        )[0]
        none_byte_identical = bool((flat_none == flat_exact).all())

        payload_out = {
            "metric": metric,
            "value": reduction,
            "unit": "x_fewer_wire_bytes",
            "vs_baseline": reduction / 3.5,
            "proxy": True,
            "vs_baseline_note": (
                "byte counts are exact (payload sizes); wall-times are "
                "8-virtual-device host-mesh CPU proxies — on-chip ICI "
                "timing needs a real slice"
            ),
            "parity_ok": parity["ok"],
            "none_byte_identical": none_byte_identical,
            "detail": {
                "legs": legs,
                "parity": parity,
                "gradient_tree": "qtopt_grasping44_critic_params",
                "n_params": n_params,
                "padded": layout.padded,
                "block": block,
                "mesh": "8dev_host_platform_data8",
                "host_cpus": os.cpu_count(),
                "timing": "median_of_repeats",
                "repeats": args.repeats,
            },
        }
        _emit(payload_out)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(payload_out, f, indent=1)
        if not parity["ok"] or not none_byte_identical or reduction < 3.5:
            sys.exit(1)
    except SystemExit:
        raise
    except Exception as err:  # noqa: BLE001
        _fail("comms_bench", err, metric=metric)


def bench_plan(args) -> None:
    """Sharding-planner leg (`python bench.py plan`).

    On the forced 8-device host mesh (same GSPMD/collective lowering a
    TPU slice uses): (1) the byte-equality audit — every hand-wired
    regime vs its planner preset, leaf-for-leaf identical TrainState
    shardings plus the planner's own layout audit; (2) loss parity of
    the planner-driven train step vs the hand-wired step for the DP
    family (none/int8/fp8 — same regime, same program, so the gate is
    BITWISE, not approximate); (3) the 3D DP x SP x PP (2x2x2) leg that
    did not exist pre-PR: trains end-to-end with the weight update
    sharded over BOTH replica axes, gated on loss parity against the
    hand-wirable DP x PP twin, with per-axis wire-byte attribution from
    the plan's collective schedule; (4) the ranked factorization table
    from `plan()` for this host's topology; (5) the round-19 widened
    points — TP (the fsdp axis) against its dp8 twin and ulysses
    attention inside the pipeline shard_map against the ring-in-pipe
    twin (same pipelined parameter structure), each gated on loss
    parity and on appearing feasible in the widened ranked table;
    (6) the measured search + persistent plan cache: a cold
    T2R_PLAN=auto run compiles/times its shortlist and stores the
    winner, the warm run replays it byte-for-byte with ZERO search
    compiles (audited via the probe compile counter), with the
    analytic-vs-measured memory-error and rank-agreement audits in the
    artifact.

    value = fraction of audited presets byte-equal (must be 1.0).
    """
    import subprocess

    metric = "plan_preset_byte_equality"
    _require_cpu_request(metric, "bench.py plan")
    if not getattr(args, "inner", False):
        env = dict(os.environ)
        kept = [
            part
            for part in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in part
        ]
        env["XLA_FLAGS"] = " ".join(
            kept + ["--xla_force_host_platform_device_count=8"]
        )
        # The leg owns its regimes: ambient plan/quant exports must not
        # re-plan the hand-wired baselines out from under the audit.
        for key in (
            "T2R_PLAN", "T2R_PLAN_MEM_BUDGET",
            "T2R_COLLECTIVE_QUANT", "T2R_COLLECTIVE_BLOCK",
            "T2R_PLAN_CACHE_DIR", "T2R_PLAN_MEASURE",
            "T2R_PLAN_MEASURE_STEPS",
        ):
            env.pop(key, None)
        proc = subprocess.run(
            [
                sys.executable, os.path.abspath(__file__), "plan",
                "--_inner", "--steps", str(args.steps),
                "--steps-3d", str(args.steps_3d),
                "--block", str(args.block), "--out", args.out,
            ],
            env=env, text=True, capture_output=True,
        )
        if proc.stderr:
            sys.stderr.write(proc.stderr[-4000:])
        lines = proc.stdout.strip().splitlines()
        print(lines[-1] if lines else "")
        sys.exit(proc.returncode)

    try:
        import dataclasses

        import jax
        import jax.flatten_util
        import numpy as np

        devices = jax.devices()
        if len(devices) != 8 or devices[0].platform != "cpu":
            raise RuntimeError(
                f"expected the forced 8-device host mesh, got {devices}"
            )
        from tensor2robot_tpu.models.transformer_models import (
            TransformerBCModel,
        )
        from tensor2robot_tpu.parallel import mesh as mesh_lib
        from tensor2robot_tpu.parallel import planner
        from tensor2robot_tpu.specs import make_random_numpy
        from tensor2robot_tpu.train import train_eval
        from tensor2robot_tpu.utils.mocks import (
            MockInputGenerator,
            MockT2RModel,
        )

        block = args.block

        def leaf_shardings(state):
            return [
                (jax.tree_util.keystr(path), str(leaf.sharding))
                for path, leaf in jax.tree_util.tree_leaves_with_path(state)
                if hasattr(leaf, "sharding")
            ]

        def flat_params(state):
            return jax.flatten_util.ravel_pytree(
                jax.device_get(state.params)
            )[0]

        def mock_setup(plan=None, **kwargs):
            model = MockT2RModel(device_type="cpu", use_batch_norm=False)
            generator = MockInputGenerator(batch_size=16, seed=0)
            generator.set_specification_from_model(model, "train")
            batch = next(iter(generator.create_dataset("train")))
            compiled = train_eval.CompiledModel(
                model, donate_state=False, plan=plan, **kwargs
            )
            state = compiled.init_state(jax.random.PRNGKey(0), batch)
            return compiled, state, batch

        def run_steps(compiled, state, batch, steps):
            rng = jax.random.PRNGKey(7)
            metrics = None
            for _ in range(steps):
                state, metrics = compiled.train_step(
                    state, compiled.shard_batch(batch), rng
                )
            return state, float(jax.device_get(metrics["loss"]))

        # -- leg 1+2: DP family byte-equality + planner-vs-hand parity --
        dp_family = {
            "dp": {},
            "dp_zero2": dict(shard_weight_update=True),
            "dp_zero2_int8": dict(
                shard_weight_update=True, collective_quant="int8",
                collective_block=block,
            ),
            "dp_zero2_fp8_e4m3": dict(
                shard_weight_update=True, collective_quant="fp8_e4m3",
                collective_block=block,
            ),
            "dp_zero2_fp8_e5m2": dict(
                shard_weight_update=True, collective_quant="fp8_e5m2",
                collective_block=block,
            ),
        }
        byte_audit = {}
        for preset, kwargs in dp_family.items():
            plan_obj = planner.resolve_preset(preset)
            if "collective_block" in kwargs:
                plan_obj = dataclasses.replace(
                    plan_obj, collective_block=block
                )
            hand, state_h, batch = mock_setup(**kwargs)
            planned, state_p, _ = mock_setup(plan=plan_obj)
            layouts_equal = leaf_shardings(state_h) == leaf_shardings(
                state_p
            )
            audit = planner.audit_state_layout(
                plan_obj, planned.mesh, state_p
            )
            state_h, loss_h = run_steps(hand, state_h, batch, args.steps)
            state_p, loss_p = run_steps(
                planned, state_p, batch, args.steps
            )
            bitwise = bool(
                (flat_params(state_h) == flat_params(state_p)).all()
            )
            byte_audit[preset] = {
                "layouts_equal": layouts_equal,
                "audit_leaves": audit["leaves"],
                "audit_mismatches": len(audit["mismatches"]),
                "hand_loss": loss_h,
                "planned_loss": loss_p,
                "loss_abs_diff": abs(loss_h - loss_p),
                "params_bitwise_equal": bitwise,
            }

        # -- composed presets: layout-only audit on the transformer --
        def transformer(mesh, **kwargs):
            return TransformerBCModel(
                action_size=2, episode_length=8, image_size=(16, 16),
                num_layers=2, num_heads=4, mesh=mesh, use_flash=False,
                **kwargs,
            )

        def transformer_batch(model, seed=0):
            return {
                "features": make_random_numpy(
                    model.get_feature_specification("train"),
                    batch_size=8, seed=seed,
                ),
                "labels": make_random_numpy(
                    model.get_label_specification("train"),
                    batch_size=8, seed=seed + 1,
                ),
            }

        composed = {
            "dp_sp": (dict(data=2, sequence=4), {}, {}),
            "dp_pp": (
                dict(data=2, pipe=2),
                dict(pipeline_stages=2, pipeline_microbatches=2),
                {},
            ),
            "dp_pp_zero2": (
                dict(data=2, pipe=2),
                dict(pipeline_stages=2, pipeline_microbatches=2),
                dict(shard_weight_update=True, param_min_shard_size=0),
            ),
        }
        for preset, (mesh_kwargs, model_kwargs, ckw) in composed.items():
            plan_obj = planner.resolve_preset(preset)
            if ckw.get("param_min_shard_size") == 0:
                plan_obj = dataclasses.replace(
                    plan_obj, param_min_shard_size=0
                )
            n_dev = int(np.prod(list(mesh_kwargs.values())))
            mesh = mesh_lib.make_mesh(
                devices=jax.devices()[:n_dev], **mesh_kwargs
            )
            model = transformer(mesh, **model_kwargs)
            batch = transformer_batch(model)
            hand = train_eval.CompiledModel(
                model, mesh=mesh, donate_state=False, **ckw
            )
            state_h = hand.init_state(jax.random.PRNGKey(0), batch)
            model_p = transformer(plan_obj.build_mesh(), **model_kwargs)
            planned = train_eval.CompiledModel(
                model_p, donate_state=False, plan=plan_obj
            )
            state_p = planned.init_state(jax.random.PRNGKey(0), batch)
            audit = planner.audit_state_layout(
                plan_obj, planned.mesh, state_p
            )
            byte_audit[preset] = {
                "layouts_equal": leaf_shardings(state_h)
                == leaf_shardings(state_p),
                "audit_leaves": audit["leaves"],
                "audit_mismatches": len(audit["mismatches"]),
            }

        # -- leg 3: the 3D DP x SP x PP (2x2x2) regime --
        plan_3d = dataclasses.replace(
            planner.resolve_preset("dp_sp_pp"), param_min_shard_size=0
        )
        model_3d = transformer(
            plan_3d.build_mesh(),
            pipeline_stages=2, pipeline_microbatches=2,
        )
        batch_3d = transformer_batch(model_3d)
        compiled_3d = train_eval.CompiledModel(
            model_3d, donate_state=False, plan=plan_3d
        )
        state_3d = compiled_3d.init_state(jax.random.PRNGKey(0), batch_3d)
        audit_3d = planner.audit_state_layout(
            plan_3d, compiled_3d.mesh, state_3d
        )
        losses_3d = []
        rng = jax.random.PRNGKey(1)
        for _ in range(args.steps_3d):
            state_3d, m = compiled_3d.train_step(
                state_3d, compiled_3d.shard_batch(batch_3d), rng
            )
            losses_3d.append(float(jax.device_get(m["loss"])))
        # The hand-wirable 2D twin: same model/init/batch on DP x PP.
        twin_mesh = mesh_lib.make_mesh(data=4, pipe=2)
        model_2d = transformer(
            twin_mesh, pipeline_stages=2, pipeline_microbatches=2
        )
        compiled_2d = train_eval.CompiledModel(
            model_2d, mesh=twin_mesh, donate_state=False,
            shard_weight_update=True, param_min_shard_size=0,
        )
        state_2d = compiled_2d.init_state(jax.random.PRNGKey(0), batch_3d)
        losses_2d = []
        for _ in range(args.steps_3d):
            state_2d, m = compiled_2d.train_step(
                state_2d, compiled_2d.shard_batch(batch_3d), rng
            )
            losses_2d.append(float(jax.device_get(m["loss"])))
        parity_3d = max(
            abs(a - b) for a, b in zip(losses_3d, losses_2d)
        )
        spec_3d = planner.ModelSpec.from_model(model_3d, batch_3d)
        wire_attribution = plan_3d.collective_schedule(spec_3d)

        # -- leg 4: the ranked factorization table --
        table = planner.plan(
            spec_3d, planner.Topology(num_devices=8)
        ).to_json()

        # -- leg 5: the widened factorization points (round 19) --
        # TP (the fsdp axis) and ulysses-inside-the-pipeline were
        # unreachable before this round; each passes its loss-parity
        # twin and appears feasible in the widened ranked table.
        table_widened = planner.plan(
            spec_3d, planner.Topology(num_devices=8),
            constraints=planner.Constraints(
                param_min_shard_size=0,
                sequence_parallel_mode="ulysses",
            ),
        ).to_json()
        widened_feasible = {
            e["plan"]["name"]
            for e in table_widened["table"]
            if e["feasible"]
        }

        def run_plan_losses(plan_obj, model_kwargs=None, steps=None):
            model = transformer(
                plan_obj.build_mesh(), **(model_kwargs or {})
            )
            compiled = train_eval.CompiledModel(
                model, donate_state=False, plan=plan_obj
            )
            batch = transformer_batch(model)
            state = compiled.init_state(jax.random.PRNGKey(0), batch)
            losses = []
            rng_w = jax.random.PRNGKey(7)
            for _ in range(steps or args.steps_3d):
                state, m = compiled.train_step(
                    state, compiled.shard_batch(batch), rng_w
                )
                losses.append(float(jax.device_get(m["loss"])))
            return losses

        tp_plan = dataclasses.replace(
            planner.ShardingPlan(name="dp4_sp1_pp1_tp2", data=4, fsdp=2),
            param_min_shard_size=0,
        )
        dp_twin = dataclasses.replace(
            planner.ShardingPlan(name="dp8", data=8),
            param_min_shard_size=0,
        )
        losses_tp = run_plan_losses(tp_plan)
        losses_tp_twin = run_plan_losses(dp_twin)
        parity_tp = max(
            abs(a - b) for a, b in zip(losses_tp, losses_tp_twin)
        )

        def pipe_plan(mode):
            return dataclasses.replace(
                planner.ShardingPlan(
                    name=f"sp4_{mode}_pp2", sequence=4, pipe=2,
                    sequence_parallel_mode=mode,
                ),
                param_min_shard_size=0,
            )

        # The twin shares the pipelined parameter structure (per-stage
        # init from split rngs): ring-in-pipe, the PR 13 known-good path.
        losses_up = run_plan_losses(
            pipe_plan("ulysses"),
            dict(pipeline_stages=2, sequence_parallel_mode="ulysses"),
        )
        losses_rp = run_plan_losses(
            pipe_plan("ring"),
            dict(pipeline_stages=2, sequence_parallel_mode="ring"),
        )
        parity_up = max(abs(a - b) for a, b in zip(losses_up, losses_rp))

        # -- leg 6: the measured search + persistent plan cache --
        import shutil
        import tempfile
        import time as time_lib

        from tensor2robot_tpu import flags as t2r_flags
        from tensor2robot_tpu.parallel import plan_cache

        cache_root = tempfile.mkdtemp(prefix="t2r_plan_cache_bench_")
        flag_saves = {
            name: t2r_flags.read_raw(name)
            for name in (
                "T2R_PLAN", "T2R_PLAN_CACHE_DIR", "T2R_PLAN_MEASURE",
                "T2R_PLAN_MEASURE_STEPS",
            )
        }
        try:
            t2r_flags.write_env("T2R_PLAN", "auto")
            t2r_flags.write_env("T2R_PLAN_CACHE_DIR", cache_root)
            t2r_flags.write_env("T2R_PLAN_MEASURE", "shortlist-3")
            t2r_flags.write_env(
                "T2R_PLAN_MEASURE_STEPS", max(args.steps, 2)
            )
            model_m = MockT2RModel(device_type="cpu", use_batch_norm=False)
            gen_m = MockInputGenerator(batch_size=16, seed=0)
            gen_m.set_specification_from_model(model_m, "train")
            batch_m = next(iter(gen_m.create_dataset("train")))
            start = time_lib.perf_counter()
            cold_plan = planner.resolve_plan_from_flag(model_m, batch_m)
            cold_wall_s = time_lib.perf_counter() - start
            cold_stats = planner.last_search()
            start = time_lib.perf_counter()
            warm_plan = planner.resolve_plan_from_flag(model_m, batch_m)
            warm_wall_s = time_lib.perf_counter() - start
            warm_stats = planner.last_search()
            stored = plan_cache.load(
                cold_stats["fingerprint"], cache_root
            )
            # The analytic-vs-measured audits ride the stored table.
            measured_entries = [
                e["measured"]
                for e in (stored or {}).get("table", [])
                if e.get("measured") is not None
            ]
            memory_error_audit = [
                {
                    "name": m["name"],
                    "analytic_memory_error": m.get(
                        "analytic_memory_error"
                    ),
                    "memory_per_device_bytes": m.get(
                        "memory_per_device_bytes"
                    ),
                }
                for m in measured_entries
            ]
            timed = sorted(
                (
                    m
                    for m in measured_entries
                    if m.get("step_time_ms") is not None
                ),
                key=lambda m: m["analytic_rank"],
            )
            pairs = agree = 0
            for i in range(len(timed)):
                for j in range(i + 1, len(timed)):
                    pairs += 1
                    if timed[i]["step_time_ms"] <= timed[j]["step_time_ms"]:
                        agree += 1
            rank_agreement = agree / pairs if pairs else 1.0
            winner_time = min(
                (m["step_time_ms"] for m in timed), default=None
            )
            # The acceptance bar: the measured winner is no slower than
            # the best preset's own measured step time (1.5x absorbs
            # host-CPU timing noise between two medians).
            preset_probe = train_eval.measure_plan_candidate(
                model_m,
                planner.resolve_preset("dp"),
                batch_m,
                steps=max(args.steps, 2),
            )
            preset_time = preset_probe.get("step_time_ms")
        finally:
            for name, value in flag_saves.items():
                t2r_flags.restore_env(name, value)
            shutil.rmtree(cache_root, ignore_errors=True)

        presets_equal = sum(
            1 for entry in byte_audit.values() if entry["layouts_equal"]
        )
        gates = {
            "presets_byte_equal": presets_equal == len(byte_audit),
            "audits_clean": all(
                entry["audit_mismatches"] == 0
                for entry in byte_audit.values()
            ),
            "dp_family_bitwise": all(
                entry["params_bitwise_equal"]
                for name, entry in byte_audit.items()
                if name in dp_family
            ),
            "plan3d_audit_clean": not audit_3d["mismatches"],
            "plan3d_loss_decreasing": losses_3d[-1] < losses_3d[0],
            "plan3d_parity_with_2d_twin": parity_3d < 1e-3,
            "plan3d_wire_bytes_attributed": all(
                entry["bytes_per_device_step"]
                for entry in wire_attribution
            )
            and {"data", "sequence", "pipe"}
            <= {a for e in wire_attribution for a in e["axes"]},
            # round 19: the widened factorization points.
            "tp_point_loss_parity": parity_tp < 1e-3,
            "ulysses_in_pipe_loss_parity": parity_up < 1e-3,
            "widened_points_in_ranked_table": (
                "dp4_sp1_pp1_tp2" in widened_feasible
                and "dp1_sp4_pp2" in widened_feasible
            ),
            # round 19: the measured search + persistent plan cache.
            "cold_search_measured": (
                cold_stats.get("source") == "measured"
                and cold_stats.get("probe_compiles", 0) >= 1
            ),
            "warm_cache_zero_compiles": (
                warm_stats.get("source") == "cache"
                and warm_stats.get("probe_compiles") == 0
            ),
            "warm_plan_byte_identical": (
                warm_plan.to_json() == cold_plan.to_json()
            ),
            "measured_winner_not_slower_than_preset": (
                winner_time is not None
                and preset_time is not None
                and winner_time <= preset_time * 1.5
            ),
        }
        value = presets_equal / len(byte_audit)
        payload = {
            "metric": metric,
            "value": value,
            "unit": "fraction_presets_byte_equal",
            "vs_baseline": value,
            "proxy": True,
            "vs_baseline_note": (
                "layout equality and bitwise-step checks are exact on the "
                "8-virtual-device host mesh (same GSPMD partitioner as a "
                "TPU slice); wire bytes are analytic payload sizes"
            ),
            "gates": gates,
            "detail": {
                "byte_audit": byte_audit,
                "plan3d": {
                    "preset": plan_3d.to_json(),
                    "losses": losses_3d,
                    "twin_losses_dp_pp": losses_2d,
                    "loss_parity_max_abs_diff": parity_3d,
                    "audit_leaves": audit_3d["leaves"],
                    "wire_byte_attribution": wire_attribution,
                },
                "ranked_plan_table": table,
                "widened": {
                    "ranked_plan_table": table_widened,
                    "tp": {
                        "plan": tp_plan.to_json(),
                        "losses": losses_tp,
                        "twin_losses_dp8": losses_tp_twin,
                        "loss_parity_max_abs_diff": parity_tp,
                    },
                    "ulysses_in_pipe": {
                        "plan": pipe_plan("ulysses").to_json(),
                        "losses": losses_up,
                        "twin_losses_ring_in_pipe": losses_rp,
                        "loss_parity_max_abs_diff": parity_up,
                    },
                },
                "measured_search": {
                    "cold_wall_s": cold_wall_s,
                    "warm_wall_s": warm_wall_s,
                    "cold_stats": cold_stats,
                    "warm_stats": warm_stats,
                    "winner_step_time_ms": winner_time,
                    "best_preset_step_time_ms": preset_time,
                    "analytic_vs_measured_rank_agreement": rank_agreement,
                    "memory_error_audit": memory_error_audit,
                },
                "steps": args.steps,
                "steps_3d": args.steps_3d,
                "block": block,
                "mesh": "8dev_host_platform",
                "host_cpus": os.cpu_count(),
            },
        }
        _emit(payload)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(payload, f, indent=1)
        if not all(gates.values()):
            sys.exit(1)
    except SystemExit:
        raise
    except Exception as err:  # noqa: BLE001
        _fail("bench_plan", err, metric=metric)


def main() -> None:
    import os

    # The INTENDED (TPU) metric name, derived from the env knobs before
    # anything can fail, so backend-init/config failures are labeled with
    # the regime that was requested — a failed bs128 run must not report
    # under the canonical bs64 name.
    use_remat = os.environ.get("BENCH_REMAT", "0") == "1"
    try:
        env_batch = int(os.environ.get("BENCH_BATCH", "64"))
        env_width = int(os.environ.get("BENCH_WIDTH", "64"))
    except ValueError as err:
        # A distinct name: a malformed request must not pollute any real
        # metric series (the batch size it asked for is unknowable).
        _fail(
            "config",
            err,
            metric="qtopt_critic_train_mfu_invalid_config"
            + ("_remat" if use_remat else ""),
        )
    # BENCH_WIDTH != 64 runs the MXU-width-aligned tower twin (the c128
    # half of the two-number ceiling proof) under a distinct metric name.
    intended_metric = (
        f"qtopt_critic_train_mfu_bs{env_batch}_472px"
        + (f"_c{env_width}" if env_width != 64 else "")
        + ("_remat" if use_remat else "")
    )

    devices = _devices(intended_metric)

    import jax
    import numpy as np

    device = devices[0]
    on_tpu = device.platform == "tpu"
    # Full fidelity on the real chip; a reduced proxy keeps the metric
    # defined (and the script testable) on CPU-only hosts.
    if on_tpu:
        # BENCH_BATCH / BENCH_REMAT explore larger batches (remat trades
        # recompute for the activation memory a bigger batch needs); the
        # default keeps the driver's canonical bs64 metric name, and a
        # remat run always reports under a distinct "_remat" name.
        batch_size = env_batch
        image_size, num_convs = (472, 472), (6, 6, 3)
        width = env_width
        n_windows, window = 8, 15
        metric = intended_metric
    else:
        image_size, num_convs, batch_size = (96, 96), (2, 2, 1), 8
        width = 64
        n_windows, window = 3, 3
        metric = "qtopt_critic_train_mfu_cpu_proxy"
        # The CPU proxy measures one fixed regime; a remat'd (or widened)
        # proxy under the same metric name would pollute comparisons.
        use_remat = False

    try:
        from __graft_entry__ import _flagship

        from tensor2robot_tpu.train.train_eval import CompiledModel

        # Same construction the driver's dryrun exercises — the bench must
        # measure the workload the compile checks validate. State donation
        # lets XLA alias param/opt buffers in place across steps.
        model, batch = _flagship(
            image_size=image_size, batch_size=batch_size,
            num_convs=num_convs, width=width,
        )
        compiled = CompiledModel(model, donate_state=True, remat=use_remat)
        state = compiled.init_state(jax.random.PRNGKey(0), batch)
        sharded = compiled.shard_batch(batch)
        rng = jax.random.PRNGKey(1)

        flops_source = "xla_cost_analysis"
        try:
            # MFU's numerator is USEFUL model flops: always cost-analyse a
            # non-remat lowering — remat's recompute ops are real work the
            # chip does but not work the model needs, and counting them
            # would let a remat run report inflated MFU.
            flops_step = (
                CompiledModel(model, donate_state=False).train_step
                if use_remat
                else compiled.train_step
            )
            cost = flops_step.lower(state, sharded, rng).compile()
            flops_per_step = float(cost.cost_analysis()["flops"])
            if not np.isfinite(flops_per_step) or flops_per_step <= 0:
                raise ValueError(f"bogus flops {flops_per_step}")
        except Exception:
            flops_per_step = _analytic_train_flops(
                image_size, batch_size, num_convs, width=width
            )
            flops_source = "analytic"

        # Windows are anchored by HOST READBACKS of data computed by the
        # step, so every timed window ends with the queue drained.
        box = {"state": state}

        def run_window():
            for _ in range(window):
                box["state"], box["metrics"] = compiled.train_step(
                    box["state"], sharded, rng
                )

        def sync():
            if "metrics" in box:
                float(jax.device_get(box["metrics"]["loss"]))

        run_window()  # compile + first calls, untimed
        steps_per_sec, best_steps_window, avg_steps_per_sec = (
            _measure_windows(run_window, sync, n_windows, window)
        )

        # The phases below are each either REQUESTED — and then a failure
        # fails the run through the outer handler — or not requested, and
        # then the payload says `skipped`. None is caught and carried.
        profile_dir = os.environ.get("BENCH_PROFILE_DIR")
        if profile_dir:
            # One post-warm-up window under the profiler: the trace that
            # explains any gap between measured MFU and the matmul
            # ceiling (untimed — tracing overhead must not touch the
            # reported numbers).
            with jax.profiler.trace(profile_dir):
                run_window()
                sync()

        # Multi-step dispatch (iterations_per_loop equivalent): K scanned
        # steps per host round-trip amortize dispatch latency. The
        # headline is the better of the two regimes. Only meaningful on
        # the chip: on CPU, XLA runs while-loop bodies single-threaded,
        # so the scanned step is ~n_cores slower than the standalone
        # step. BENCH_SKIP_SCAN=1 drops it. CAUTION: the headline value
        # is max(per-step, scan), so skipping scan makes the value
        # regime-inconsistent with full runs.
        scan_steps_per_sec = 0.0
        scan_k = int(os.environ.get("BENCH_SCAN_K", "10"))
        skip_scan = (
            os.environ.get("BENCH_SKIP_SCAN") == "1"
            or scan_k <= 1
            or not on_tpu
        )
        if not skip_scan:
            from tensor2robot_tpu.train import infeed

            stacked = infeed.shard_stacked_batch(
                infeed.stack_batches([batch] * scan_k), compiled.mesh
            )

            def run_scan_window():
                box["state"], box["m"] = compiled.train_scan(
                    box["state"], stacked, rng
                )

            def sync_scan():
                if "m" in box:
                    float(jax.device_get(box["m"]["loss"][-1]))

            # Compile plus untimed warm-up executions, so the timed
            # windows measure steady state.
            warm_calls = int(os.environ.get("BENCH_WARMUP_CALLS", "10"))
            for _ in range(max(warm_calls, 1)):
                run_scan_window()
            sync_scan()
            per_call, _, _ = _measure_windows(
                run_scan_window, sync_scan, max(4, n_windows), 1
            )
            scan_steps_per_sec = per_call * scan_k

        # Infeed-in-the-loop leg: fresh HOST batches through
        # train/infeed.py double-buffering each step, instead of the
        # pre-sharded device batch. The ratio to the pre-sharded rate is
        # the overlap efficiency — 1.0 means host->device transfer fully
        # hides behind compute. BENCH_SKIP_INFEED=1 drops it.
        infeed_steps_per_sec = 0.0
        skip_infeed = os.environ.get("BENCH_SKIP_INFEED") == "1"
        if not skip_infeed:
            import itertools

            from tensor2robot_tpu.train import infeed as infeed_lib

            # Distinct host arrays so no transfer can be deduplicated.
            host_batches = [
                jax.tree_util.tree_map(lambda x: np.asarray(x).copy(), batch)
                for _ in range(3)
            ]

            def run_infeed_window():
                feed = infeed_lib.device_prefetch(
                    itertools.islice(itertools.cycle(host_batches), window),
                    compiled.shard_batch,
                    depth=2,
                )
                for device_batch in feed:
                    box["state"], box["metrics"] = compiled.train_step(
                        box["state"], device_batch, rng
                    )

            run_infeed_window()  # transfer-path warm-up, untimed
            sync()
            infeed_steps_per_sec, _, _ = _measure_windows(
                run_infeed_window, sync, max(3, n_windows // 2), window
            )

        # The matmul ceiling is a device number: taken on the chip only.
        ceiling = _pin_matmul_ceiling(device) if on_tpu else {}

        # Across REGIMES (per-step vs scan dispatch) the better one is the
        # headline — a deliberate design choice, not a max-statistic over
        # jittery samples; WITHIN each regime the estimate is the median.
        best_steps_per_sec = max(steps_per_sec, scan_steps_per_sec)

        peak = _peak_flops(device)
        mfu = flops_per_step * best_steps_per_sec / peak
        if mfu > 1.0:
            raise RuntimeError(
                f"implied MFU {mfu:.2f} exceeds 1.0 — timing did not "
                f"capture real execution ({best_steps_per_sec:.1f} steps/s, "
                f"{flops_per_step:.3g} flops/step); refusing to report a "
                "bogus number"
            )
        _emit(
            {
                "metric": metric,
                "value": round(mfu, 4),
                "unit": "fraction_of_peak",
                "vs_baseline": round(mfu / 0.50, 4),
                "detail": {
                    "steps_per_sec": round(best_steps_per_sec, 3),
                    "per_step_dispatch_steps_per_sec": round(steps_per_sec, 3),
                    "per_step_dispatch_best_steps_per_sec": round(
                        best_steps_window, 3
                    ),
                    "per_step_dispatch_avg_steps_per_sec": round(
                        avg_steps_per_sec, 3
                    ),
                    "scan_dispatch_steps_per_sec": round(scan_steps_per_sec, 3),
                    "infeed_steps_per_sec": round(infeed_steps_per_sec, 3),
                    **({"infeed_leg": "skipped"} if skip_infeed else {}),
                    **({"scan_leg": "skipped"} if skip_scan else {}),
                    **({} if on_tpu else {"ceiling_leg": "skipped"}),
                    **({} if profile_dir else {"profile_leg": "skipped"}),
                    **_overlap_fields(infeed_steps_per_sec, steps_per_sec),
                    **ceiling,
                    **(
                        {
                            "mfu_vs_matmul_ceiling": round(
                                flops_per_step
                                * best_steps_per_sec
                                / (ceiling["matmul_ceiling_tflops"] * 1e12),
                                4,
                            )
                        }
                        if ceiling.get("matmul_ceiling_tflops")
                        else {}
                    ),
                    "timing": "median_of_windows_best_regime",
                    "flops_per_step": flops_per_step,
                    "flops_source": flops_source,
                    "device_kind": getattr(device, "device_kind", "?"),
                    "peak_flops": peak,
                    "bf16_forward": True,
                    "batch_size": batch_size,
                    "tower_width": width,
                    "remat": use_remat,
                    "stem_s2d": _stem_s2d(),
                },
                **_proxy_fields(on_tpu, "qtopt_critic_train_mfu"),
            }
        )
    except Exception as err:
        _fail("bench_run", err, metric=metric)


def bench_rl(args) -> None:
    """Closed online-RL loop leg (`python bench.py rl`).

    Runs the full QT-Opt topology on this host: pose_env actor
    processes get actions from a FleetRouter over policy-server replica
    processes (serving the learner's exported artifact), append
    episodes as wire bytes to the replay-service process, and the
    learner trains from the service's sampler, publishing a fresh
    policy (export -> rolling fleet swap) at every checkpoint. Reports
    episodes/s, samples/s, replay ratio and policy staleness.

    Four legs, same seeds:

      * fault-free — the throughput + staleness numbers;
      * chaos — the replay service AND one actor are SIGKILLed mid-run.
        Acceptance: the learner finishes the SAME number of steps as
        the fault-free twin, zero torn segments are ever sampled
        (verified against the on-disk manifests after the fact), and
        the loss is bounded to the unsealed tail — counted and
        reported, never guessed.
      * sharded fault-free — the same loop over `--shards` (>= 3)
        replay-service shards on the SOCKET transport
        (replay/transport.py): consistent-hash episode placement,
        per-shard durability, rotation sampling.
      * sharded chaos — one shard SIGKILLed AND another partitioned at
        the driver (chaos `net_send partition` clause) mid-run.
        Acceptance: equal learner steps vs the sharded fault-free
        twin, zero torn segments sampled, ZERO duplicate appends
        (cross-shard episode-uid audit over the sealed manifests),
        per-shard loss bounded to the unsealed tail and counted, and
        the partition's coverage loss COUNTED (degraded, never
        silent).
    """
    import shutil
    import tempfile
    import threading

    devices = _devices("rl_loop_episodes_per_sec")
    _refuse_children_on_chip(
        devices,
        "rl_loop_episodes_per_sec",
        "bench.py rl's policy-server replica processes",
    )
    on_tpu = devices[0].platform == "tpu"
    metric = (
        "rl_loop_episodes_per_sec"
        if on_tpu
        else "rl_loop_episodes_per_sec_cpu_proxy"
    )

    try:
        import jax
        import numpy as np

        from tensor2robot_tpu.export.exporters import LatestExporter
        from tensor2robot_tpu.replay import OnlineLoop
        from tensor2robot_tpu.replay.segment import list_sealed_segments
        from tensor2robot_tpu.replay.sharded import (
            audit_episode_uids,
            shard_root,
        )
        from tensor2robot_tpu.testing import chaos as chaos_lib
        from tensor2robot_tpu.research.pose_env.pose_env_models import (
            PoseEnvRegressionModel,
        )
        from tensor2robot_tpu.serving import FleetRouter, ReplicaSpec
        from tensor2robot_tpu.serving.replica import policy_server_factory
        from tensor2robot_tpu.train.train_eval import CompiledModel

        def bootstrap_artifact(model_dir):
            """Initial (untrained) policy artifact the fleet boots on."""
            from tensor2robot_tpu.specs import TensorSpecStruct

            model = PoseEnvRegressionModel()
            generator_batch = TensorSpecStruct()
            generator_batch["features/state"] = np.zeros(
                (4, 64, 64, 3), np.uint8
            )
            generator_batch["labels/target_pose"] = np.zeros(
                (4, 2), np.float32
            )
            generator_batch["labels/reward"] = np.ones((4, 1), np.float32)
            compiled = CompiledModel(model, donate_state=False)
            state = compiled.init_state(
                jax.random.PRNGKey(0), generator_batch
            )
            exporter = LatestExporter(
                name="latest", warmup_batch_sizes=(1,)
            )
            path = exporter.maybe_export(
                step=0, state=state, eval_metrics={"loss": 1.0},
                compiled=compiled, model_dir=model_dir,
            )
            return exporter.export_root(model_dir), path

        def run_leg(tag, with_chaos):
            root = tempfile.mkdtemp(prefix=f"bench_rl_{tag}_")
            loop = OnlineLoop(
                root,
                num_actors=args.actors,
                batch_size=args.batch,
                seal_episodes=args.seal_episodes,
                seed=11,
                use_router=True,
                wait_timeout_s=300.0,
                actor_throttle_s=args.actor_throttle_ms / 1e3,
            )
            export_root, path = bootstrap_artifact(loop.model_dir)
            base = os.path.basename(path.rstrip("/"))
            if base.isdigit():
                loop.register_artifact_version(int(base), 0)
            router = FleetRouter(
                ReplicaSpec(
                    factory=policy_server_factory,
                    factory_args=(export_root,),
                ),
                num_replicas=args.replicas,
                probe_interval_ms=200.0,
                probe_miss_limit=10,
                seed=11,
            ).start(timeout_s=300.0)
            loop._router = router
            loop.start()
            chaos_events = {}
            try:
                if with_chaos:
                    def mid_run_chaos():
                        time.sleep(args.chaos_at_s)
                        chaos_events["replay_pid"] = (
                            loop.kill_replay_service()
                        )
                        chaos_events["actor_pid"] = loop.kill_actor(0)

                    chaos_thread = threading.Thread(
                        target=mid_run_chaos, daemon=True
                    )
                    chaos_thread.start()
                loop.run_learner(
                    max_steps=args.steps,
                    save_steps=max(1, args.steps // 3),
                    publish=True,
                )
                if with_chaos:
                    chaos_thread.join()
            finally:
                report = loop.stop()
                router.stop()
            # Torn-segment audit: every coordinate the learner sampled
            # must name a segment that is durable ON DISK right now.
            sealed = {
                seq for seq, _ in list_sealed_segments(loop.replay_root)
            }
            sampled = {
                seq
                for batch in (loop._generator.coords_log if loop._generator
                              else [])
                for seq, _ in batch
            }
            torn_sampled = sorted(sampled - sealed)
            payload = report.to_json()
            payload.pop("actor_reports", None)
            payload["torn_segments_sampled"] = torn_sampled
            payload["chaos"] = chaos_events if with_chaos else None
            shutil.rmtree(root, ignore_errors=True)
            return payload

        def run_sharded_leg(tag, with_chaos):
            """The sharded fabric on the socket transport: no serving
            fleet (actors run the seeded random policy) — this leg
            measures the REPLAY fabric under shard faults; the fleet
            integration is the two legs above."""
            root = tempfile.mkdtemp(prefix=f"bench_rl_{tag}_")
            loop = OnlineLoop(
                root,
                num_actors=args.actors,
                batch_size=args.batch,
                seal_episodes=args.seal_episodes,
                seed=11,
                shards=args.shards,
                transport="socket",
                wait_timeout_s=300.0,
                actor_throttle_s=args.actor_throttle_ms / 1e3,
            )
            loop.start()
            chaos_events = {}
            try:
                if with_chaos:
                    def mid_run_chaos():
                        # Progress-based trigger, not wall-clock: the
                        # faults must land while the learner is still
                        # SAMPLING (a partition installed after the
                        # last draw degrades nothing and the coverage
                        # gate would measure an empty window). Wait for
                        # about a third of the learner's batches, then
                        # strike; chaos_at_s is the fallback ceiling.
                        deadline = time.monotonic() + max(
                            args.chaos_at_s, 30.0
                        )
                        target = max(2, args.steps // 3)
                        while time.monotonic() < deadline:
                            generator = loop._generator
                            if (
                                generator is not None
                                and generator.batches_drawn >= target
                            ):
                                break
                            time.sleep(0.05)
                        # SIGKILL one shard (its supervisor respawns
                        # it) AND partition another at the driver: the
                        # learner's sampling link to s<N-1> drops from
                        # here on, via the seeded chaos machinery.
                        chaos_events["shard_killed"] = 1
                        chaos_events["shard_pid"] = loop.kill_shard(1)
                        partitioned = args.shards - 1
                        chaos_events["shard_partitioned"] = partitioned
                        chaos_lib.configure(
                            f"net_send:1:partition:s{partitioned}"
                        )

                    chaos_thread = threading.Thread(
                        target=mid_run_chaos, daemon=True
                    )
                    chaos_thread.start()
                loop.run_learner(
                    max_steps=args.steps,
                    save_steps=max(1, args.steps // 3),
                    publish=True,
                )
                if with_chaos:
                    chaos_thread.join()
            finally:
                chaos_lib.reset()
                report = loop.stop()
            shard_roots = [
                shard_root(loop.replay_root, k) for k in range(args.shards)
            ]
            # Torn-segment audit, per shard: every (shard, seq, record)
            # the learner sampled must name a segment durable on disk.
            sealed = {
                (k, seq)
                for k, sroot in enumerate(shard_roots)
                for seq, _ in list_sealed_segments(sroot)
            }
            sampled = {
                (coord[0], coord[1])
                for batch in (loop._generator.coords_log if loop._generator
                              else [])
                for coord in batch
            }
            torn_sampled = sorted(sampled - sealed)
            # Zero-duplicate-appends audit: episode uids across every
            # shard's sealed manifests.
            audit = audit_episode_uids(shard_roots)
            payload = report.to_json()
            payload.pop("actor_reports", None)
            payload["torn_segments_sampled"] = torn_sampled
            payload["uid_audit"] = {
                "episodes": audit["episodes"],
                "unaudited_episodes": audit["unaudited_episodes"],
                "duplicate_count": audit["duplicate_count"],
            }
            payload["chaos"] = chaos_events if with_chaos else None
            shutil.rmtree(root, ignore_errors=True)
            return payload

        fault_free = run_leg("clean", with_chaos=False)
        chaos_leg = run_leg("chaos", with_chaos=True)
        sharded_free = run_sharded_leg("shard_clean", with_chaos=False)
        sharded_chaos = run_sharded_leg("shard_chaos", with_chaos=True)

        acceptance = {
            "stats_measured": (
                chaos_leg["stats_ok"] and fault_free["stats_ok"]
            ),
            "learner_steps_equal": (
                chaos_leg["learner_steps"] == fault_free["learner_steps"]
                and chaos_leg["learner_steps"] > 0
            ),
            "zero_torn_segments_sampled": (
                not chaos_leg["torn_segments_sampled"]
                and not fault_free["torn_segments_sampled"]
            ),
            "loss_bounded_to_unsealed_tail": (
                chaos_leg["episodes_lost"] <= args.seal_episodes
            ),
            "loss_counted": chaos_leg["episodes_lost"],
            "replay_service_respawned": chaos_leg["replay_restarts"] >= 1,
            "actor_killed": chaos_leg["actors_killed"] == 1,
            # -- the sharded chaos contract (ISSUE 10) --
            "sharded_stats_measured": (
                sharded_chaos["stats_ok"] and sharded_free["stats_ok"]
            ),
            "sharded_learner_steps_equal": (
                sharded_chaos["learner_steps"]
                == sharded_free["learner_steps"]
                and sharded_chaos["learner_steps"] > 0
            ),
            "sharded_zero_torn_segments_sampled": (
                not sharded_chaos["torn_segments_sampled"]
                and not sharded_free["torn_segments_sampled"]
            ),
            "sharded_zero_duplicate_appends": (
                sharded_chaos["uid_audit"]["duplicate_count"] == 0
                and sharded_chaos["uid_audit"]["unaudited_episodes"] == 0
                and sharded_free["uid_audit"]["duplicate_count"] == 0
            ),
            "sharded_per_shard_loss_bounded": all(
                entry.get("episodes_lost_total", 0) <= args.seal_episodes
                for entry in sharded_chaos["per_shard"]
            ),
            "sharded_loss_counted": (
                sharded_chaos["episodes_lost"]
                + sharded_chaos["spill_dropped_episodes"]
            ),
            "sharded_shard_respawned": (
                sharded_chaos["replay_restarts"] >= 1
            ),
            "sharded_coverage_loss_counted": (
                sum(sharded_chaos["coverage_lost_draws"]) > 0
            ),
        }
        payload = {
            "metric": metric,
            "value": fault_free["episodes_per_s"],
            "unit": "episodes_per_sec",
            "vs_baseline": 0.0,
            "detail": {
                "fault_free": fault_free,
                "chaos": chaos_leg,
                "sharded_fault_free": sharded_free,
                "sharded_chaos": sharded_chaos,
                "acceptance": acceptance,
                "samples_per_sec": fault_free["samples_per_s"],
                "replay_ratio": fault_free["replay_ratio"],
                "staleness_mean": fault_free["staleness_mean"],
                "staleness_max": fault_free["staleness_max"],
                "sharded_episodes_per_sec": sharded_free["episodes_per_s"],
                "sharded_samples_per_sec": sharded_free["samples_per_s"],
                "actors": args.actors,
                "replicas": args.replicas,
                "shards": args.shards,
                "replay_transport": "socket",
                "learner_steps": args.steps,
                "batch": args.batch,
                "seal_episodes": args.seal_episodes,
            },
            **_proxy_fields(on_tpu, "rl_loop_episodes_per_sec"),
        }
        _emit(payload)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
        if not all(
            v is True
            for k, v in acceptance.items()
            if isinstance(v, bool)
        ):
            _fail(
                "rl_acceptance",
                RuntimeError(f"acceptance failed: {acceptance}"),
                metric=metric,
            )
    except SystemExit:
        raise
    except Exception as err:
        _fail("bench_rl", err, metric=metric)


def _build_cli():
    """bench legs as argparse subcommands: `python bench.py --help` lists
    every leg, `python bench.py <leg> --help` its options and env knobs.
    No subcommand runs the headline MFU leg (the round-end default)."""
    import argparse

    parser = argparse.ArgumentParser(
        prog="bench.py",
        description=(
            "tensor2robot_tpu benchmark suite. Each leg prints ONE JSON "
            "line: {metric, value, unit, vs_baseline, detail}. With no "
            "leg, runs the headline QT-Opt critic train-MFU benchmark."
        ),
        epilog=(
            "headline env knobs: BENCH_BATCH, BENCH_WIDTH, BENCH_REMAT, "
            "BENCH_SCAN_K, "
            "BENCH_SKIP_SCAN, BENCH_SKIP_INFEED, BENCH_PROFILE_DIR, "
            "BENCH_BACKEND_WAIT"
        ),
    )
    parser.set_defaults(func=lambda args: main())
    sub = parser.add_subparsers(dest="leg", metavar="LEG")

    def leg(name, fn, help_text, epilog=None):
        sp = sub.add_parser(
            name, help=help_text, description=help_text, epilog=epilog
        )
        sp.set_defaults(func=fn)
        return sp

    leg(
        "data", lambda a: bench_data(),
        "host input-pipeline throughput (images/s): fast/cold/oracle legs, "
        "ROI attribution, parse-worker sweep",
        epilog="env knobs: BENCH_DATA_RECORDS, BENCH_DATA_BATCH, "
               "BENCH_DATA_BATCHES, BENCH_DATA_CONTENT=camera|noise",
    )
    leg(
        "auc", lambda a: bench_auc(),
        "training-quality AUC budget leg on the mock critic",
        epilog="env knobs: BENCH_AUC_BATCH, BENCH_AUC_STEPS",
    )
    leg(
        "predict", lambda a: bench_predict(),
        "robot-side exported-model predict rate + jit-CEM action selects",
        epilog="env knobs: BENCH_PREDICT_SAMPLES",
    )
    leg(
        "bc", lambda a: bench_bc(),
        "transformer-BC train throughput",
        epilog="env knobs: BENCH_BC_WINDOW",
    )
    leg(
        "stream", lambda a: bench_stream(),
        "streaming KV-cache control-loop rate (steps/s)",
    )
    leg(
        "pipe", lambda a: bench_pipe(),
        "end-to-end host-feed -> device-step pipeline",
        epilog="env knobs: BENCH_PIPE_RECORDS",
    )
    comms = leg(
        "comms", bench_comms,
        "quantized ZeRO-2 gradient-collective leg on the forced 8-device "
        "host mesh: bytes moved + wall-time for fp32/fp16/int8 on the "
        "QT-Opt-sized gradient tree, mock-model loss parity, and the "
        "none-path byte-identity check (docs/PARALLELISM.md)",
    )
    comms.add_argument(
        "--block", type=int, default=512,
        help="quantization block size, elements per scale "
             "(default %(default)s)",
    )
    comms.add_argument(
        "--steps", type=int, default=30,
        help="mock-model training steps for the loss-parity leg "
             "(default %(default)s)",
    )
    comms.add_argument(
        "--repeats", type=int, default=7,
        help="timed exchange repetitions per wire format "
             "(default %(default)s)",
    )
    comms.add_argument(
        "--out", default="BENCH_COMMS_r09.json",
        help="also write the payload to this file ('' disables; "
             "default %(default)s)",
    )
    comms.add_argument(
        "--_inner", dest="inner", action="store_true",
        help=argparse.SUPPRESS,
    )
    plan_leg = leg(
        "plan", bench_plan,
        "sharding-planner leg on the forced 8-device host mesh: "
        "byte-equality audit of planner presets vs the hand-wired "
        "regimes, bitwise planner-vs-hand DP parity (none/int8/fp8), "
        "the 3D DP x SP x PP (2x2x2) leg with per-axis wire-byte "
        "attribution, the ranked factorization table, loss-parity twins "
        "for the widened TP / ulysses-in-pipeline points, and the "
        "measured search + plan cache (cold measures and stores, warm "
        "replays with zero compiles) "
        "(docs/PARALLELISM.md \"Sharding planner\")",
    )
    plan_leg.add_argument(
        "--steps", type=int, default=4,
        help="train steps per DP parity twin (default %(default)s)",
    )
    plan_leg.add_argument(
        "--steps-3d", dest="steps_3d", type=int, default=5,
        help="train steps for the 3D leg and its 2D twin "
             "(default %(default)s)",
    )
    plan_leg.add_argument(
        "--block", type=int, default=64,
        help="quantization block for the quantized presets "
             "(default %(default)s)",
    )
    plan_leg.add_argument(
        "--out", default="BENCH_PLAN_r19.json",
        help="also write the payload to this file ('' disables; "
             "default %(default)s)",
    )
    plan_leg.add_argument(
        "--_inner", dest="inner", action="store_true",
        help=argparse.SUPPRESS,
    )
    serve = leg(
        "serve", bench_serve,
        "fleet-serving leg: policy-server micro-batching throughput vs the "
        "sequential baseline, open-loop Poisson load sweep, hot-swap under "
        "load (docs/SERVING.md)",
    )
    serve.add_argument(
        "--buckets", default="1,2,4,8,16,32",
        help="warmup/bucket ladder exported with the fixture model "
             "(default %(default)s)",
    )
    serve.add_argument(
        "--burst", type=int, default=1024,
        help="request count for the saturation burst (default %(default)s)",
    )
    serve.add_argument(
        "--baseline-secs", type=float, default=2.0,
        help="sequential-baseline measurement window (default %(default)s)",
    )
    serve.add_argument(
        "--leg-secs", type=float, default=8.0,
        help="duration of each open-loop Poisson leg (default %(default)s)",
    )
    serve.add_argument(
        "--deadline-ms", type=float, default=500.0,
        help="per-request deadline in the open-loop legs (default %(default)s)",
    )
    serve.add_argument(
        "--max-wait-ms", type=int, default=5,
        help="micro-batcher coalesce window (default %(default)s)",
    )
    serve.add_argument(
        "--no-quant", action="store_true",
        help="skip the serve-quant regime legs (none/fp16/int8/fp8 "
             "req/s + bytes-of-param + compiled-program dot/reduce "
             "audits, the dequant-vs-native and static-vs-dynamic "
             "calibration A/Bs, and the static-calib AOT boot gate)",
    )
    serve.add_argument(
        "--out", default="BENCH_SERVE_r18.json",
        help="also write the payload to this file ('' disables; "
             "default %(default)s)",
    )
    aot = leg(
        "aot", bench_aot,
        "instant-deploy leg: cold-start-to-first-reply and rolling-swap "
        "latency with serialized AOT executables vs the persistent-cache "
        "and fresh-compile tiers, over the SAME exported artifact; gates "
        "on zero fresh bucket compiles for the AOT boot "
        "(docs/SERVING.md \"AOT executables\")",
    )
    aot.add_argument(
        "--buckets", default="1,2,4,8,16,32",
        help="warmup/bucket ladder exported with the fixture model "
             "(default %(default)s)",
    )
    aot.add_argument(
        "--leg-secs", type=float, default=6.0,
        help="duration of each open-loop rolling-swap leg "
             "(default %(default)s)",
    )
    aot.add_argument(
        "--swap-rate-hz", type=float, default=50.0,
        help="open-loop request rate during the swap legs "
             "(default %(default)s)",
    )
    aot.add_argument(
        "--out", default="BENCH_AOT_r15.json",
        help="also write the payload to this file ('' disables; "
             "default %(default)s)",
    )
    aot.add_argument(
        "--_boot", dest="boot", action="store_true", help=argparse.SUPPRESS,
    )
    aot.add_argument("--export-root", default=None, help=argparse.SUPPRESS)
    aot.add_argument("--json-out", default=None, help=argparse.SUPPRESS)
    fleet = leg(
        "fleet", bench_fleet,
        "replica-fleet routing leg: closed-loop capacity + open-loop "
        "Poisson sweep (p50/p99/p999, availability) over N replica "
        "processes, a SIGKILL-mid-sweep chaos leg (zero lost requests, "
        "bounded p99 degradation), and a rolling fleet-wide hot-swap "
        "under load (docs/RESILIENCE.md)",
    )
    fleet.add_argument(
        "--replicas", type=int, default=4,
        help="replica process count, >= 3 for the acceptance sweep "
             "(default %(default)s)",
    )
    fleet.add_argument(
        "--service-ms", type=float, default=2.0,
        help="mock per-request service time in the replicas "
             "(default %(default)s)",
    )
    fleet.add_argument(
        "--capacity-secs", type=float, default=2.0,
        help="closed-loop capacity window (default %(default)s)",
    )
    fleet.add_argument(
        "--leg-secs", type=float, default=4.0,
        help="duration of each open-loop Poisson leg (default %(default)s)",
    )
    fleet.add_argument(
        "--deadline-ms", type=float, default=400.0,
        help="per-request deadline (default %(default)s)",
    )
    fleet.add_argument(
        "--p99-degradation-max", type=float, default=10.0,
        help="chaos-leg p99 may be at most this multiple of the "
             "fault-free twin leg's (default %(default)s)",
    )
    fleet.add_argument(
        "--quant-replicas", type=int, default=2,
        help="replica count for the mixed-precision policy-backend leg; "
             "0 skips it (default %(default)s)",
    )
    fleet.add_argument(
        "--quant-secs", type=float, default=1.5,
        help="closed-loop window of the mixed-precision leg "
             "(default %(default)s)",
    )
    fleet.add_argument(
        "--out", default="BENCH_FLEET_r11.json",
        help="also write the payload to this file ('' disables; "
             "default %(default)s)",
    )
    gateway = leg(
        "gateway", bench_gateway,
        "multi-tenant front-door leg: Gateway (quotas, gold/silver/bronze "
        "priority shedding, coalescing) + Autoscaler over a mock replica "
        "pool, replaying a seeded diurnal bursty trace with a flash "
        "crowd, a rogue bronze tenant at 10x quota, a replica SIGKILL "
        "mid-crowd and a rolling swap through the same pool; gates on "
        "per-tier SLOs, typed sheds, zero lost requests, coalescing, and "
        "autoscaler convergence (docs/SERVING.md, docs/RESILIENCE.md)",
    )
    gateway.add_argument(
        "--replicas", type=int, default=2,
        help="starting (and minimum) replica count (default %(default)s)",
    )
    gateway.add_argument(
        "--max-replicas", type=int, default=5,
        help="autoscaler ceiling the flash crowd must reach "
             "(default %(default)s)",
    )
    gateway.add_argument(
        "--service-ms", type=float, default=3.0,
        help="mock per-request service time (default %(default)s)",
    )
    gateway.add_argument(
        "--max-inflight", type=int, default=4,
        help="router per-replica in-flight cap (default %(default)s)",
    )
    gateway.add_argument(
        "--hedge-ms", type=int, default=25,
        help="router hedge delay, amputates the SIGKILL latency tail "
             "(default %(default)s)",
    )
    gateway.add_argument(
        "--trace-secs", type=float, default=10.0,
        help="trace duration; the flash crowd spans [0.4, 0.6] of it "
             "(default %(default)s)",
    )
    gateway.add_argument(
        "--drain-secs", type=float, default=6.0,
        help="post-trace idle window for the autoscaler to drain back "
             "(default %(default)s)",
    )
    gateway.add_argument(
        "--rate-scale", type=float, default=1.0,
        help="multiplier on every tenant's offered rate "
             "(default %(default)s)",
    )
    gateway.add_argument(
        "--crowd-factor", type=float, default=6.0,
        help="flash-crowd rate multiplier on the crowd tenants "
             "(default %(default)s)",
    )
    gateway.add_argument(
        "--rogue-rate", type=float, default=300.0,
        help="rogue bronze tenant's offered rate; its quota is a tenth "
             "of this (default %(default)s)",
    )
    gateway.add_argument(
        "--p99-degradation-max", type=float, default=2.0,
        help="chaos-leg gold p99 may be at most this multiple of the "
             "fault-free twin's (default %(default)s)",
    )
    gateway.add_argument(
        "--p99-floor-ms", type=float, default=25.0,
        help="twin p99 floor for the degradation ratio (sub-floor p99s "
             "are CPU-proxy scheduler noise) (default %(default)s)",
    )
    gateway.add_argument(
        "--out", default="BENCH_GATE_r14.json",
        help="also write the payload to this file ('' disables; "
             "default %(default)s)",
    )
    fabric = leg(
        "fabric", bench_fabric,
        "cross-host serving fabric leg: two availability zones of "
        "socket-transport replica processes (separate process groups, "
        "published-address discovery), a gateway spanning the zones "
        "through a seeded flash-crowd trace twice (fault-free twin, "
        "mid-crowd zone partition twin — gold availability holds, zero "
        "lost, all shed typed per zone), heal + re-resolution, the "
        "zone-router cross-zone survival leg, per-host AOT key "
        "resolution, and the local-transport byte-compat pin "
        "(docs/SERVING.md \"Cross-host fabric\")",
    )
    fabric.add_argument(
        "--replicas-per-zone", type=int, default=2,
        help="replica process count per zone (default %(default)s)",
    )
    fabric.add_argument(
        "--service-ms", type=float, default=2.0,
        help="mock per-request service time (default %(default)s)",
    )
    fabric.add_argument(
        "--trace-secs", type=float, default=8.0,
        help="trace duration; the flash crowd spans [0.4, 0.6] and the "
             "partition [0.4, 0.7] of it (default %(default)s)",
    )
    fabric.add_argument(
        "--deadline-ms", type=float, default=1500.0,
        help="per-request deadline (default %(default)s)",
    )
    fabric.add_argument(
        "--hedge-ms", type=int, default=25,
        help="in-zone router hedge delay (default %(default)s)",
    )
    fabric.add_argument(
        "--max-inflight", type=int, default=4,
        help="router per-replica in-flight cap (default %(default)s)",
    )
    fabric.add_argument(
        "--gold-rps", type=float, default=25.0,
        help="gold tenant offered rate (default %(default)s)",
    )
    fabric.add_argument(
        "--bronze-rps", type=float, default=20.0,
        help="bronze tenant base offered rate; the flash crowd "
             "multiplies it (default %(default)s)",
    )
    fabric.add_argument(
        "--crowd-factor", type=float, default=6.0,
        help="flash-crowd rate multiplier on the bronze tenant "
             "(default %(default)s)",
    )
    fabric.add_argument(
        "--out", default="BENCH_FABRIC_r21.json",
        help="also write the payload to this file ('' disables; "
             "default %(default)s)",
    )
    wire = leg(
        "wire", bench_wire,
        "zero-copy wire codec leg: camera-sized observations through the "
        "real frame codec on a socketpair (T2R_WIRE=pickle vs spec), "
        "gating spec speedup, bitwise replies across codecs (socketpair "
        "echo AND a live socket-mode pool vs local mp), quantized-payload "
        "parity (T2R_WIRE_QUANT), zero steady-state receive allocations "
        "(buffer-pool audit), typed rejection of every corpus corruption "
        "variant, and PipelinedChannel overlap vs lockstep "
        "(docs/SERVING.md \"Wire protocol\")",
    )
    wire.add_argument(
        "--frames", type=int, default=150,
        help="request/reply round trips per timed trial (default "
             "%(default)s)",
    )
    wire.add_argument(
        "--trials", type=int, default=3,
        help="timed trials per codec; the median is reported "
             "(default %(default)s)",
    )
    wire.add_argument(
        "--warmup", type=int, default=30,
        help="untimed warmup round trips per codec (fills the receive "
             "pool; the steady-state alloc audit spans the timed "
             "windows) (default %(default)s)",
    )
    wire.add_argument(
        "--image-hw", type=int, default=472,
        help="square uint8 camera observation edge (472 = the paper's "
             "native capture) (default %(default)s)",
    )
    wire.add_argument(
        "--state-dim", type=int, default=2048,
        help="float32 proprio/state vector length (default %(default)s)",
    )
    wire.add_argument(
        "--speedup-min", type=float, default=3.0,
        help="gate: spec reqs/s must be at least this multiple of "
             "pickle's (default %(default)s)",
    )
    wire.add_argument(
        "--quant", default="int8",
        choices=("fp16", "int8", "fp8_e4m3", "fp8_e5m2"),
        help="T2R_WIRE_QUANT mode for the quantized-payload leg "
             "(default %(default)s)",
    )
    wire.add_argument(
        "--replicas", type=int, default=1,
        help="replica count for the live-pool bitwise leg "
             "(default %(default)s)",
    )
    wire.add_argument(
        "--pipeline-requests", type=int, default=32,
        help="in-flight requests for the pipelining leg "
             "(default %(default)s)",
    )
    wire.add_argument(
        "--pipeline-service-ms", type=float, default=2.0,
        help="mock per-request service time the pipelined channel must "
             "overlap (default %(default)s)",
    )
    wire.add_argument(
        "--out", default="BENCH_WIRE_r22.json",
        help="also write the payload to this file ('' disables; "
             "default %(default)s)",
    )
    policies = leg(
        "policies", bench_policies,
        "multi-policy fleet leg: content-addressed artifact store "
        "(program dedup + quantized weight deltas, >= 5x smaller than "
        "dense), then a 4-replica fleet serving 100+ policy variants "
        "under a memory budget behind the Gateway — seeded rotating-Zipf "
        "diurnal mix, eviction/cold-load churn counted at every layer, "
        "per-policy responses bitwise-audited against single-policy "
        "twins, zero cross-policy coalesce joins, and a one-policy "
        "rolling swap that never blips the others (docs/SERVING.md "
        "\"Multi-policy serving\")",
    )
    policies.add_argument(
        "--variants", type=int, default=100,
        help="fine-tuned sibling count published to the store and served "
             "(default %(default)s)",
    )
    policies.add_argument(
        "--replicas", type=int, default=4,
        help="fleet replica count (default %(default)s)",
    )
    policies.add_argument(
        "--trace-secs", type=float, default=8.0,
        help="trace duration; the one-policy rolling swap fires at half "
             "of it (default %(default)s)",
    )
    policies.add_argument(
        "--rate", type=float, default=120.0,
        help="offered request rate (Hz) at the diurnal envelope's mean "
             "(default %(default)s)",
    )
    policies.add_argument(
        "--service-ms", type=float, default=1.0,
        help="mock per-request service time (default %(default)s)",
    )
    policies.add_argument(
        "--load-ms", type=float, default=5.0,
        help="mock per-policy cold-load (materialize + prewarm) cost "
             "(default %(default)s)",
    )
    policies.add_argument(
        "--max-inflight", type=int, default=8,
        help="router per-replica in-flight cap (default %(default)s)",
    )
    policies.add_argument(
        "--policy-mem-mb", type=int, default=4,
        help="declared resident footprint per policy (default %(default)s)",
    )
    policies.add_argument(
        "--mem-budget-mb", type=int, default=24,
        help="per-replica resident-set budget; << variants x policy mem, "
             "so the rotating mix forces eviction churn "
             "(default %(default)s)",
    )
    policies.add_argument(
        "--out", default="BENCH_POLICY_r20.json",
        help="also write the payload to this file ('' disables; "
             "default %(default)s)",
    )
    rl = leg(
        "rl", bench_rl,
        "closed online-RL loop leg: pose_env actor processes -> replay "
        "service -> learner -> exported policy -> serving fleet -> "
        "actors; fault-free + chaos (replay-service AND actor SIGKILL "
        "mid-run) twins with episodes/s, samples/s, replay ratio and "
        "policy staleness, plus sharded-fabric twins (--shards "
        "replay shards on the socket transport; chaos variant SIGKILLs "
        "one shard AND partitions another — zero duplicate appends, "
        "counted per-shard + coverage loss) (docs/RL_LOOP.md)",
    )
    rl.add_argument(
        "--actors", type=int, default=2,
        help="actor process count (default %(default)s)",
    )
    rl.add_argument(
        "--replicas", type=int, default=1,
        help="policy-server replica count behind the router "
             "(default %(default)s)",
    )
    rl.add_argument(
        "--steps", type=int, default=12,
        help="learner steps per leg (default %(default)s)",
    )
    rl.add_argument(
        "--batch", type=int, default=4,
        help="learner batch size (default %(default)s)",
    )
    rl.add_argument(
        "--seal-episodes", type=int, default=4,
        help="episodes per sealed segment — also the crash-loss bound "
             "(default %(default)s)",
    )
    rl.add_argument(
        "--shards", type=int, default=3,
        help="replay-service shard count for the sharded legs (socket "
             "transport, consistent-hash placement); >= 3 for the "
             "kill-one-partition-another chaos acceptance "
             "(default %(default)s)",
    )
    rl.add_argument(
        "--actor-throttle-ms", type=float, default=20.0,
        help="per-episode actor throttle (default %(default)s)",
    )
    rl.add_argument(
        "--chaos-at-s", type=float, default=4.0,
        help="when the chaos leg SIGKILLs the replay service + actor 0 "
             "(default %(default)s)",
    )
    rl.add_argument(
        "--out", default="BENCH_RL_r13.json",
        help="also write the payload to this file ('' disables; "
             "default %(default)s)",
    )
    return parser


if __name__ == "__main__":
    cli = _build_cli().parse_args()
    cli.func(cli)
