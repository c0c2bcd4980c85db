"""Each driver at a tiny preset on the CPU: a well-formed result dict out of
`run.run_cell`, the same function a chip run goes through (the look for a
chip is all that is skipped)."""

import jax
import pytest

import manifest
import report
import run as bench_run
import tiny

CELLS = [w["name"] for w in manifest.benchmark_json()["workloads"]]


@pytest.mark.parametrize("cell_name", CELLS)
def test_driver_gives_a_well_formed_result(cell_name):
    cell = tiny.tiny_cell(cell_name, batch=4 if "grasp2vec" in cell_name else 8)
    config = tiny.tiny_config(cell["config"])
    result = bench_run.run_cell(
        cell, config, tiny.args(seed=2_147_483_659, seconds=1.0),
        jax.devices()[:1], report.Reporter("test"),
    )
    assert list(result)[-1] == "compared"
    assert set(result) == {
        "correct", "attempted", "failed", "metrics", "device", "compared"
    }
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert result["correct"] is True, result["compared"]
    wanted = {m["name"] for m in manifest.end_to_end(cell_name)}
    assert set(result["metrics"]) == wanted
    for name, metric in result["metrics"].items():
        assert metric["value"] > 0 and metric["unit"], name
    assert result["device"]["count"] == 1
    for name, (value, limit) in result["compared"].items():
        assert value == value, name  # no NaN
