"""Readings that the limits of `correct` are set from, taken on the chip at
a cell's own size, many seeds in one process (set-up is long, the readings
need no measured window):

    python benchmark/readings.py --workload <cell> --seeds 12 --control-seeds 3 \
        --first-seed 1000 --out chiprun_out/readings.<cell>.jsonl

For every seed the cell's own driver builds the program and drives its
first steps through the window's call and feed (a window of half a second
follows and is thrown away). The plain reference follows the same steps,
and the numbers compared are printed: these are the *lower* readings. On
the first `--control-seeds` seeds the reference is then put in the
program's place and computed in the nearest precision below the
configuration's (`control` in the configuration file: fp8 operands for a
bfloat16 program), and with each fault a training cell can have planted in
it (half of the batch left out; the state returned unchanged): the *upper*
readings. One JSON object a seed goes to `--out` and to standard output.

The benchmark's own runs never call this; PERF.md records what it printed.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _path in (BENCH_DIR, ROOT):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import manifest  # noqa: E402
import run as bench_run  # noqa: E402

FAULTS = ("half_batch", "state_unchanged")


def read_seed(cell, config, seed, devices, reporter, *, control, also=()):
    """{"seed", "program": {...}, "control": {...}, "faults": {...}}."""
    import compare

    reference = manifest.reference(cell["config"])
    args = argparse.Namespace(seed=seed, seconds=0.5, trace=0)
    work_dir = os.path.join(bench_run.WORK_ROOT, "readings." + cell["name"])
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    run = bench_run.Run(cell, config, reference, args, reporter, devices, work_dir)
    started = time.perf_counter()
    manifest.driver(cell["driver"]).run(run)
    program_s = time.perf_counter() - started
    weights, batches, base_key = (
        run.check_inputs() if callable(run.check_inputs) else run.check_inputs
    )
    started = time.perf_counter()
    expected = compare.reference_readings(
        reference, config, weights, batches, base_key, devices=devices
    )
    reference_s = time.perf_counter() - started
    numbers, leaves = compare.compared_numbers(run.program_readings, expected)
    numbers.update(run.extra_numbers)
    out = {
        "seed": seed, "program": numbers, "worst_leaves": leaves,
        "program_loss": run.program_readings["loss"],
        "reference_loss": expected["loss"],
        "program_s": program_s, "reference_s": reference_s,
    }
    if control:
        out["control"] = {}
        for quant in list(config["control"]) + list(also):
            other = compare.reference_readings(
                reference, config, weights, batches, base_key, quant=quant,
                devices=devices,
            )
            out["control"][quant] = compare.compared_numbers(other, expected)[0]
        out["faults"] = {}
        for fault in FAULTS:
            other = compare.reference_readings(
                reference, config, weights, batches, base_key, fault=fault,
                devices=devices,
            )
            out["faults"][fault] = compare.compared_numbers(other, expected)[0]
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--first-seed", type=int, default=1000)
    parser.add_argument("--out", default=None)
    parser.add_argument("--also", action="append", default=[],
                        help="one more precision to read the reference in "
                             "(bfloat16: what rounding the operands alone costs)")
    args = parser.parse_args(argv)

    cell = manifest.cell(args.workload)
    config = manifest.config(cell["config"])
    os.environ.update(cell.get("environment", {}))
    devices = bench_run.device_gate(cell["chips"])
    from tensor2robot_tpu.utils.compile_cache import enable_compile_cache

    import report

    enable_compile_cache()
    reporter = report.Reporter(f"readings {cell['name']}")
    sink = None
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        sink = open(args.out, "a")
    for index in range(args.seeds):
        # Large and far apart, as the driver's seeds are.
        seed = args.first_seed + index * 104729 + (index % 2) * 2_000_000_011
        line = json.dumps(read_seed(
            cell, config, seed, devices, reporter,
            control=index < args.control_seeds, also=args.also,
        ))
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()
    if sink:
        sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
