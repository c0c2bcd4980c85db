"""What benchmark/tests/test_four_chips.py asserts on, computed in a process
of its own on four virtual CPU devices (the test sets XLA_FLAGS for it; the
other rehearsals keep their one device). One JSON object on the last line:

  reference        the plain reference's three steps on 4 x tiny rows,
                   on one device and with the rows over four
  sound, half_batch, state_unchanged
                   `run.run_cell` of `critic_c64.train_fed_dp4` (parked:
                   its file, no entry) at the tiny preset on the four
                   devices, as it is and with each fault planted under the
                   timed path
"""

import json
import os
import sys

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(TESTS_DIR)
for _path in (TESTS_DIR, BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if _path not in sys.path:
        sys.path.insert(0, _path)

CELL = "critic_c64.train_fed_dp4"
ROWS_A_DEVICE = 4


def reference_on_one_and_four(devices):
    import jax

    import compare
    import manifest
    import program_side
    import tiny
    import traffic

    cell = tiny.tiny_cell(CELL, batch=ROWS_A_DEVICE, listed=False)
    config = tiny.tiny_config(cell["config"])
    ref = manifest.reference(cell["config"])
    weights = jax.jit(lambda k: ref.init_params(k, config))(jax.random.PRNGKey(9))
    model = program_side.build_model(config, weights)
    rows = ROWS_A_DEVICE * len(devices)
    raws = [
        traffic.resident_batch(model, rows, seed, {"kind": "resident_batch"})
        for seed in (13, 14, 15)
    ]
    key = jax.random.PRNGKey(4)
    return {
        name: compare.reference_readings(
            ref, config, weights, raws, key, devices=where
        )
        for name, where in (("one", devices[:1]), ("four", devices))
    }


def cell_on_four(devices, fault=None):
    import report
    import run as bench_run
    import tiny
    from tensor2robot_tpu.train import train_eval

    whole = train_eval.CompiledModel
    if fault is not None:
        from test_not_correct import _broken_compiled_model

        train_eval.CompiledModel = _broken_compiled_model(fault)
    try:
        cell = tiny.tiny_cell(CELL, batch=ROWS_A_DEVICE, listed=False)
        result = bench_run.run_cell(
            cell, tiny.tiny_config(cell["config"]),
            tiny.args(seed=2_147_483_777, seconds=0.5), devices,
            report.Reporter(f"four devices {fault}"),
        )
    finally:
        train_eval.CompiledModel = whole
    return result


def main():
    import jax

    devices = jax.devices()
    if len(devices) != 4 or devices[0].platform != "cpu":
        raise SystemExit(f"wants four CPU devices, jax shows {devices}")
    out = {"reference": reference_on_one_and_four(devices)}
    out["sound"] = cell_on_four(devices)
    for fault in ("half_batch", "state_unchanged"):
        out[fault] = cell_on_four(devices, fault)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
