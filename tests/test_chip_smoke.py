"""chip_smoke.py on the CPU: the tiny preset end to end, and the gates.

The full-width run happens on the chip (README "Running on the chip");
this pins what a CPU can: every stage runs through the same entry points
and is reported, the device gate refuses before any stage, and a plain
`python chip_smoke.py` in a CPU-only sandbox fails without printing a
result — which is exactly what the driver checks first.
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO_ROOT, "chip_smoke.py")
STAGES = ("records", "train", "step", "export", "serve", "parity")


def _run_smoke(args, env_extra, timeout=600):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **env_extra}
    return subprocess.run(
        [sys.executable, SMOKE, *args],
        capture_output=True, text=True, env=env, timeout=timeout,
        cwd=REPO_ROOT,
    )


def test_tiny_preset_reports_every_stage_on_cpu(tmp_path):
    proc = _run_smoke(
        ["--preset", "tiny"],
        {
            # Two virtual devices: the multi-device assertions (batch
            # split, all-reduce, one-device AOT restore) run for real.
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"),
        },
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 2},
    }
    said = [line for line in lines if line.startswith("[chip_smoke")]
    assert all("cpu" in line for line in said), said
    for stage in STAGES:
        assert any(f"stage {stage}: ok wall_s=" in line for line in said), (
            stage, said,
        )
    text = "\n".join(said)
    assert "device platform=cpu kind='cpu' count=2 jax=" in text
    assert "warm-up bucket restore tiers: {'1': 'aot', '2': 'aot'}" in text
    assert "host codec native={'tfrecord': True, 'jpeg': True}" in text
    assert "all-reduce in the compiled step: True (devices=2)" in text
    assert (
        "pool backward in the lowered step: native select_and_scatter" in text
    )
    assert f"compile cache dir={tmp_path / 'jax_cache'}" in text
    assert "persistent_misses=0" not in text  # cold cache: it compiled
    assert os.listdir(tmp_path / "jax_cache")


def test_default_preset_fails_without_result_on_cpu():
    """`python chip_smoke.py` where jax finds no accelerator: non-zero
    exit before any stage and no result line, even when the environment
    holds jax to the CPU (as this sandbox does)."""
    proc = _run_smoke([], {})
    assert proc.returncode != 0
    assert "runs on the chip only" in proc.stderr
    assert "stage" not in proc.stdout
    assert '"ok"' not in proc.stdout


@pytest.fixture()
def smoke():
    sys.path.insert(0, REPO_ROOT)
    try:
        import chip_smoke

        yield chip_smoke
    finally:
        sys.path.remove(REPO_ROOT)


class TestDeviceGate:
    def test_non_tpu_platform_without_the_request_raises(
        self, smoke, monkeypatch
    ):
        from tensor2robot_tpu.parallel.mesh import require_devices

        monkeypatch.delenv("JAX_PLATFORMS")
        with pytest.raises(RuntimeError, match="not 'tpu'.*JAX_PLATFORMS=cpu"):
            require_devices()
        for preset in smoke.PRESETS:
            with pytest.raises(RuntimeError, match="not 'tpu'"):
                smoke.device_gate(preset)

    def test_explicit_cpu_request_admits_only_the_tiny_preset(
        self, smoke, monkeypatch
    ):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert smoke.device_gate("tiny")[0].platform == "cpu"
        with pytest.raises(RuntimeError, match="runs on the chip only"):
            smoke.device_gate("chip")

    def test_the_chip_admits_only_the_full_width_preset(
        self, smoke, monkeypatch
    ):
        """The result line carries no preset, so platform `tpu` in it must
        always mean full width."""
        from tensor2robot_tpu.parallel import mesh

        chip = [types.SimpleNamespace(platform="tpu")]
        monkeypatch.setattr(mesh, "require_devices", lambda: chip)
        assert smoke.device_gate("chip") is chip
        with pytest.raises(RuntimeError, match="tiny one on the CPU only"):
            smoke.device_gate("tiny")


class TestParityCarriesSignal:
    """The served-vs-direct bound is a share of how far the Q logits move
    with their inputs, so a wrong input path cannot pass it (the bound's
    measured margins are at chip_smoke.PARITY_RMS_SHARE_OF_SIGNAL)."""

    @pytest.fixture()
    def direct(self):
        # [requests, actions]: every image scores the action population
        # its own way, as the random-weight critic does on the chip.
        return (0.025 * np.random.RandomState(0).randn(8, 64)).astype(
            np.float32
        )

    def test_amplified_rounding_noise_passes(self, smoke, direct):
        """The chip's measured level: 0.15-0.2 of the signal."""
        noise = 0.2 * 0.025 * np.random.RandomState(1).randn(*direct.shape)
        noisy = direct + noise.astype(np.float32)
        assert "of the signal" in smoke.check_parity(noisy, noisy[0], direct)

    @pytest.mark.parametrize("wrong", ["permuted_images", "dropped_images",
                                       "permuted_actions", "alone_differs"])
    def test_a_wrong_input_path_fails(self, smoke, direct, wrong):
        served, alone = direct.copy(), direct[0].copy()
        if wrong == "permuted_images":
            served = served[::-1]
        elif wrong == "dropped_images":
            served = np.repeat(served.mean(axis=0, keepdims=True), 8, axis=0)
        elif wrong == "permuted_actions":
            served = served[:, ::-1]
        else:
            alone = direct[1]
        with pytest.raises(RuntimeError, match="move with their inputs"):
            smoke.check_parity(served, alone, direct)

    def test_input_independent_logits_fail(self, smoke, direct):
        """What a six-step critic at the reference's BatchNorm momentum
        serves: ~1e-7 logits that differ by less than they are rounded."""
        flat = (8e-7 + 1e-10 * direct).astype(np.float32)
        with pytest.raises(RuntimeError, match="move with their inputs"):
            smoke.check_parity(flat * (1 + 2e-3), flat[0], flat)
