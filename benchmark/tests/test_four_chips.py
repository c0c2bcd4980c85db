"""The four-chip cell on four virtual CPU devices: the reference that
shards its rows against the same reference on one device, and
`critic_c64.train_fed_dp4` at the tiny preset through `run.run_cell`, sound
and with each fault planted under its timed path.

Everything is computed once, in a child process that is given four devices
(four_devices.py); the other rehearsals keep their one device, so that
their programs are the ones they were."""

import json
import os
import subprocess
import sys

import pytest

import compare
import manifest

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def four():
    flags = os.environ.get("XLA_FLAGS", "").split()
    flags = [f for f in flags if "xla_force_host_platform_device_count" not in f]
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS=" ".join(flags + ["--xla_force_host_platform_device_count=4"]),
    )
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "four_devices.py")],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_rows_over_four_devices_give_the_one_device_reference(four):
    """Three steps on 4 x 4 rows that all differ: were the BatchNorm
    statistics, the loss or the gradient a shard's and not the batch's, the
    losses and the norms would part at the first step."""
    one, split = four["reference"]["one"], four["reference"]["four"]
    assert split["loss"] == pytest.approx(one["loss"], rel=1e-6)
    assert len(one["grad_norms"]) > 40
    numbers, _ = compare.compared_numbers(split, one)
    for name in ("loss1", "loss2", "loss3", "grad_norm", "update_norm"):
        assert numbers[name] < 1e-5, (name, numbers[name])
    # Every leaf that the reference moves, not the worst by one measure.
    median = sorted(one["grad_norms"].values())[len(one["grad_norms"]) // 2]
    for kind in ("grad_norms", "update_norms"):
        for leaf, norm in one[kind].items():
            if one["grad_norms"][leaf] < compare.SKIP_UPDATE_BELOW * median:
                continue  # nought to rounding: a bias in front of BatchNorm
            assert split[kind][leaf] == pytest.approx(norm, rel=2e-5), (kind, leaf)


def test_the_cell_on_four_devices_is_correct(four):
    result = four["sound"]
    assert result["correct"] is True, result["compared"]
    assert result["device"]["count"] == 4
    assert result["failed"] == 0 and result["attempted"] >= 2
    # Parked, the cell is in no metric's list: set-up alone applies to it.
    assert set(result["metrics"]) == {
        m["name"] for m in manifest.end_to_end("critic_c64.train_fed_dp4")
    } == {"setup_s"}
    compared = result["compared"]
    assert compared["parse_max_abs"] == [0.0, 0]
    assert compared["grad_norm"][0] < 1e-4 and compared["update_norm"][0] < 1e-4


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged"])
def test_a_fault_under_the_timed_path_on_four_devices_is_not_correct(four, fault):
    result = four[fault]
    assert result["correct"] is False, result["compared"]
    value, limit = result["compared"]["update_norm"]
    assert value > 100 * limit
