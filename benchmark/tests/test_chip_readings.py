"""The cells' own limits against the readings they were set from.

`benchmark/tests/data/readings.<cell>.jsonl` is what `readings.py` printed
on the chip at the cell's own size (PERF.md section 2): a dozen seeds of
the program, and on the first three the control and each planted fault.
Every sound run has to pass the cell's limits, every control and every
fault has to fail one of them."""

import json
import os

import pytest

import compare
import manifest

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELLS = [w["name"] for w in manifest.benchmark_json()["workloads"]]
PARKED = ["grasp2vec_r50.train_resident"]  # files here, no entry yet (PERF.md)


def _lines(cell_name):
    path = os.path.join(DATA, f"readings.{cell_name}.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


@pytest.mark.parametrize("cell_name", CELLS + PARKED)
def test_limits_part_sound_runs_from_control_and_faults(cell_name):
    cell = manifest.cell(cell_name, listed=cell_name in CELLS)
    config = manifest.config(cell["config"])
    lines = _lines(cell_name)
    assert len(lines) >= 12
    controls = faults = 0
    for line in lines:
        assert compare.judge(line["program"], cell["limits"])[0], line["seed"]
        for quant in config["control"]:
            if quant in line.get("control", {}):
                controls += 1
                assert not compare.judge(
                    line["control"][quant], cell["limits"]
                )[0], (line["seed"], quant)
        for name, numbers in line.get("faults", {}).items():
            faults += 1
            assert not compare.judge(numbers, cell["limits"])[0], (
                line["seed"], name
            )
    assert controls >= 3 and faults >= 6
