"""`readings.py` for a cell of driver `train_resident_tokens`: the readings
that the limits of `correct` are set from, on the chip at the cell's own
size, many seeds in one process.

    python benchmark/readings_tokens.py --workload <cell> --seeds 12 \
        --control-seeds 3 --out chiprun_out/readings.<cell>.jsonl

Differs from `readings.py` in two things a token cell needs. The plain
reference follows its steps layer by layer with its state on the host (the
driver's `install_streaming_reference`), for the control's precision too.
The planted faults are this driver's: `half_loss` (half the tokens' loss
left out; `readings.py` halves the rows, and there is one), `no_resets`
(document resets left out) and `compare.py`'s `state_unchanged`. The lines
have `readings.py`'s form; benchmark/tests/test_chip_readings.py reads them.
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


def read_seed(cell, config, seed, devices, reporter, *, control):
    import compare

    reference = manifest.reference(cell["config"])
    driver = manifest.driver(cell["driver"])
    args = argparse.Namespace(seed=seed, seconds=0.5, trace=0)
    work_dir = os.path.join(bench_run.WORK_ROOT, "readings." + cell["name"])
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    run = bench_run.Run(cell, config, reference, args, reporter, devices, work_dir)
    started = time.perf_counter()
    driver.run(run)
    program_s = time.perf_counter() - started
    weights, batches, base_key = run.check_inputs

    def follow(batches=batches, **kwargs):
        driver.install_streaming_reference(reference, config, kwargs.get("quant"))
        return compare.reference_readings(
            reference, config, weights, batches, base_key, **kwargs
        )

    started = time.perf_counter()
    expected = follow()
    reference_s = time.perf_counter() - started
    numbers, leaves = compare.compared_numbers(run.program_readings, expected)
    out = {
        "seed": seed, "program": numbers, "worst_leaves": leaves,
        "program_loss": run.program_readings["loss"],
        "reference_loss": expected["loss"],
        "program_s": program_s, "reference_s": reference_s,
    }
    if control:
        gap = lambda other: compare.compared_numbers(other, expected)[0]
        out["control"] = {q: gap(follow(quant=q)) for q in config["control"]}
        out["faults"] = {"state_unchanged": gap(follow(fault="state_unchanged"))}
        for name, plant in driver.BATCH_FAULTS.items():
            out["faults"][name] = gap(follow([plant(b) for b in batches]))
    shutil.rmtree(work_dir, ignore_errors=True)
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--first-seed", type=int, default=1000)
    parser.add_argument("--out", default=None)
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
            control=index < args.control_seeds,
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
