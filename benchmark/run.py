"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs on the machine it is started on and refuses any platform but `tpu`
before set-up (a CPU rehearsal goes through `run_cell` from
benchmark/tests, never through a result line). Earlier lines go to standard
error; the last line of standard output is the one JSON result.

The cell, its configuration, its driver, its per-layer metrics and their
readers are found by name (manifest.py); nothing in this file names one.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _path in (BENCH_DIR, ROOT):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import manifest  # noqa: E402

#: Scratch of a run (records, trace, model_dir): inside the checkout,
#: git-ignored, emptied before and after.
WORK_ROOT = os.path.join(ROOT, ".bench_work")


def device_gate(chips):
    """jax's devices, or an error before any set-up: the platform must be
    `tpu` and hold the chips the cell asks for."""
    from tensor2robot_tpu.parallel.mesh import require_devices

    devices = require_devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"benchmark/run.py runs on the chip only: jax reports platform "
            f"{devices[0].platform!r}"
        )
    if len(devices) < chips:
        raise SystemExit(
            f"the cell asks for {chips} chips, jax reports {len(devices)}"
        )
    return devices[:chips]


def versions():
    import jax
    import jaxlib

    out = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        from importlib import metadata

        out["libtpu"] = metadata.version("libtpu")
    except Exception:  # noqa: BLE001 - a version string is not worth a crash
        out["libtpu"] = "unknown"
    return out


class Run:
    """What a driver gets and what it fills in for the readers."""

    def __init__(self, cell, config, reference, args, reporter, devices, work_dir):
        self.cell = cell
        self.config = config
        self.reference = reference
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.reporter = reporter
        self.devices = devices
        self.work_dir = work_dir
        self.process_start = _PROCESS_START
        # filled by the driver
        self.window = None            # window.Window after close()
        self.setup_s = None
        self.program_readings = None  # program_side.StepReadings.result()
        self.check_inputs = None      # (weights, batches, base key) or a callable
        self.extra_numbers = {}       # driver's own compared numbers
        self.counters = {}            # name -> number, read after the window
        # filled by run_cell for the readers
        self.trace_summary = None
        self.flops = None
        self.peaks = None

    @property
    def trace_dir(self):
        return os.path.join(self.work_dir, "trace") if self.trace else None


def run_cell(cell, config, args, devices, reporter):
    """Set-up, window, comparison, metrics: the result dict of one run."""
    import jax
    import ml_dtypes  # noqa: F401 - numpy learns 'bfloat16'
    import numpy as np

    import compare
    import flops
    import xplane

    work_dir = os.path.join(WORK_ROOT, cell["name"])
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    reference = manifest.reference(cell["config"])
    run = Run(cell, config, reference, args, reporter, devices, work_dir)
    try:
        manifest.driver(cell["driver"]).run(run)
        window = run.window.results()
        reporter.say(
            f"window: {window['steps']} steps in {window['window_s']:.3f} s, "
            f"step ms p50 {window['step_ms_p50']:.3f} p90 "
            f"{window['metrics']['train.step_ms.p90']:.3f} max "
            f"{window['step_ms_max']:.3f} "
            f"(samples {window['steps']}), compilations inside the window "
            f"{run.window.compiles_in_window}, generator lateness: none "
            f"(closed loop, no schedule)"
        )
        reporter.say(
            f"compile cache {jax.config.jax_compilation_cache_dir}: hits "
            f"{reporter.cache_hits} misses {reporter.cache_misses}, compile "
            f"seconds so far {reporter.compile_s:.2f}"
        )
        stats = [d.memory_stats() or {} for d in devices]
        # The allocator's peak of live buffers, or what the chip holds while
        # the window runs, whichever is larger: the live buffers now plus
        # the scratch reserved for the step's program, which
        # `peak_bytes_in_use` leaves out (Grasp2Vec: 0.9 GB live beside
        # 10.8 GB reserved, against a peak of 3.4 GB live during set-up).
        memory_peak = max(
            max(s.get("peak_bytes_in_use", 0),
                s.get("bytes_in_use", 0) + s.get("peak_bytes_reserved", 0))
            for s in stats
        )
        reporter.say(f"device memory: {stats[0]}")

        # -- correct: the plain reference follows the first three steps ----
        check_started = time.perf_counter()
        weights, batches, base_key = (
            run.check_inputs() if callable(run.check_inputs) else run.check_inputs
        )
        expected = compare.reference_readings(
            reference, config, weights, batches, base_key, devices=devices
        )
        numbers, leaves = compare.compared_numbers(run.program_readings, expected)
        numbers.update(run.extra_numbers)
        correct, shown = compare.judge(numbers, cell["limits"])
        if run.window.compiles_in_window:
            correct = False
        if window["failed"]:
            correct = False
        reporter.say(
            f"comparison took {time.perf_counter() - check_started:.2f} s; "
            f"worst leaves {leaves}"
        )

        device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(memory_peak),
        }
        result = {
            "correct": bool(correct),
            "attempted": window["steps"],
            "failed": window["failed"],
            "metrics": {},
            "device": device,
        }
        if run.trace:
            run.peaks = manifest.peaks(devices[0].device_kind)
            run.flops = flops.count(
                lambda p, b: reference.loss_fn(p, b, base_key, config),
                flops.abstract(weights), flops.abstract(batches[0]),
                bytes_per_element=np.dtype(config["compute_dtype"]).itemsize,
            )
            trace_file = xplane.find(run.trace_dir)
            summary = xplane.summarize(
                xplane.load(trace_file), steps=run.window.trace_steps,
                epoch_spans=run.window.spans,
            )
            reporter.say(
                f"trace file {os.path.getsize(trace_file) / 1e6:.1f} MB, host "
                f"spans {len(run.window.spans)} laid over it by "
                f"{summary['host_clock']}: the first device op starts "
                f"{1e3 * summary['lead_s']:.3f} ms into the window, the last "
                f"ends {1e3 * summary['tail_s']:.3f} ms before its close"
            )
            run.trace_summary = summary
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            reporter.say(
                f"trace: {summary['steps']} steps in {summary['window_s']:.3f} s, "
                f"busy {summary['busy_s']:.3f} s; model FLOPs a step "
                f"{run.flops['step_flops']:.4g}, kernel bytes a step "
                f"{run.flops['step_bytes']:.4g}"
            )
            reporter.say("device time by category, ms a step: " + ", ".join(
                f"{name} {1e3 * seconds / max(summary['steps'], 1):.2f}"
                for name, seconds in list(summary["category_s"].items())[:12]
            ) + "; idle by host span, ms a step: " + ", ".join(
                f"{name} {1e3 * seconds / max(summary['steps'], 1):.2f}"
                for name, seconds in summary["idle_by_span_s"].items()
            ))
            for entry, data, reader in manifest.per_layer(cell["name"]):
                value = reader.read(run)
                if value is not None:
                    result["metrics"][entry["name"]] = {
                        "value": value, "unit": data["unit"],
                    }
            result["breakdown"] = {
                "device_ops": summary["device_ops"],
                "idle_gaps": summary["idle_gaps"],
            }
        else:
            values = dict(window["metrics"], setup_s=run.setup_s)
            # A cell may report a window's number under a name of its own
            # (`end_to_end` in its file: the fed cell's rate has its own
            # bound).
            renamed = cell.get("end_to_end", {})
            for entry in manifest.end_to_end(cell["name"]):
                source = renamed.get(entry["name"], entry["name"])
                result["metrics"][entry["name"]] = {
                    "value": values[source], "unit": entry["unit"],
                }
        result["compared"] = shown
        return result
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    cell = manifest.cell(args.workload)
    config = manifest.config(cell["config"])
    os.environ.update(cell.get("environment", {}))

    devices = device_gate(cell["chips"])
    from tensor2robot_tpu.utils.compile_cache import enable_compile_cache

    import report

    cache_dir = enable_compile_cache()
    reporter = report.Reporter(f"{cell['name']} {devices[0].platform}")
    reporter.say(
        f"platform {devices[0].platform}, device_kind {devices[0].device_kind!r}, "
        f"devices {len(devices)}, versions {versions()}, compile cache "
        f"{cache_dir}, seed {args.seed}, seconds {args.seconds}, trace {args.trace}"
    )
    result = run_cell(cell, config, args, devices, reporter)
    for name, (value, limit) in result["compared"].items():
        reporter.say(f"compared {name}: {value!r} limit {limit!r}")
    reporter.say(f"correct {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
