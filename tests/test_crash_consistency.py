"""Crash-consistent checkpoint recovery under seeded T2R_CHAOS kills.

The contract under test (train/durability.py + train_eval wiring):

  1. A SIGKILL mid-orbax-save (injected at the `save` chaos site, no
     cleanup handlers) never corrupts the trainer's recovery: the next
     run quarantines any torn directory, resumes from the last DURABLE
     checkpoint, and — because the host batch stream is realigned to
     the restored step — replays to a trajectory BITWISE identical to a
     run that never crashed, error-feedback residual included (the
     suite trains in the quantized-collective ZeRO-2 regime so
     `TrainState.collective_residual` is live and checkpointed).
  2. A torn/partial *final-named* checkpoint directory (partial copy,
     fsync-less crash — forms orbax's atomic rename cannot rule out) is
     detected by the durability manifest, skipped by every reader, and
     quarantined by the owning trainer. It is never loaded.

Everything is seeded: the fault plan (`T2R_CHAOS=save:2:sigkill`), the
model/data seeds, and the tampering (explicit file surgery). No
wall-clock-dependent assertions.
"""

import hashlib
import json
import os
import shutil
import signal
import subprocess
import sys

import numpy as np
import pytest

from tensor2robot_tpu.testing import chaos
from tensor2robot_tpu.train import durability

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# One trainer program for every leg: quantized-collective ZeRO-2 regime
# on the forced 8-device host mesh (so the error-feedback residual is
# real, sharded state), save every 5 steps, then restore the final
# durable checkpoint and print a digest over the FULL persistable
# TrainState — params, opt state, EMA, residual, step. Bitwise equality
# of that digest is the "same trajectory" oracle.
_TRAINER = r"""
import sys
import jax
jax.config.update("jax_platforms", "cpu")
model_dir, max_steps = sys.argv[1], int(sys.argv[2])
import hashlib
import numpy as np
from tensor2robot_tpu.train import durability
from tensor2robot_tpu.train import train_eval as te
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

print("DURABLE_BEFORE", durability.durable_steps(model_dir), flush=True)

te.train_eval_model(
    MockT2RModel(device_type="cpu", use_batch_norm=False),
    input_generator_train=MockInputGenerator(batch_size=8, seed=7),
    model_dir=model_dir,
    max_train_steps=max_steps,
    eval_steps=None,
    save_checkpoints_steps=5,
    log_every_steps=5,
    seed=31,
    shard_weight_update=True,
)
print("TRAINING_DONE", flush=True)

model = MockT2RModel(device_type="cpu", use_batch_norm=False)
gen = MockInputGenerator(batch_size=8, seed=7)
gen.set_specification_from_model(model, "train")
compiled = te.CompiledModel(
    model, donate_state=False, shard_weight_update=True
)
manager = te.create_checkpoint_manager(model_dir, save_interval_steps=5)
state = te.restore_or_init_state(
    manager, compiled, jax.random.PRNGKey(0),
    next(iter(gen.create_dataset("train"))),
)
digest = hashlib.sha256()
for leaf in jax.tree_util.tree_leaves(jax.device_get(state)):
    digest.update(np.ascontiguousarray(leaf).tobytes())
print(
    "STATE_SHA256", digest.hexdigest(), "STEP", int(state.step), flush=True
)
manager.close()
"""


def _run_trainer(model_dir, max_steps, chaos_plan=None, check=True):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["T2R_COLLECTIVE_QUANT"] = "int8"
    env.pop("T2R_CHAOS", None)
    if chaos_plan is not None:
        env["T2R_CHAOS"] = chaos_plan
    proc = subprocess.run(
        [sys.executable, "-c", _TRAINER, str(model_dir), str(max_steps)],
        capture_output=True,
        text=True,
        timeout=420,
        env=env,
        cwd=REPO_ROOT,
    )
    if check:
        assert proc.returncode == 0, proc.stdout[-2500:] + proc.stderr[-2500:]
    return proc


def _digest_line(proc):
    lines = [
        l for l in proc.stdout.splitlines() if l.startswith("STATE_SHA256")
    ]
    assert lines, proc.stdout[-2500:] + proc.stderr[-2500:]
    return lines[-1]


def _checkpoint_steps(model_dir):
    root = os.path.join(str(model_dir), "checkpoints")
    if not os.path.isdir(root):
        return []
    return sorted(int(n) for n in os.listdir(root) if n.isdigit())


@pytest.fixture(scope="module")
def reference_run(tmp_path_factory):
    """One uninterrupted 15-step run: the trajectory oracle every chaos
    leg must reproduce bitwise."""
    model_dir = tmp_path_factory.mktemp("crash") / "reference"
    proc = _run_trainer(model_dir, 15)
    return {"model_dir": str(model_dir), "digest": _digest_line(proc)}


class TestKillMidSave:
    def test_sigkill_mid_save_then_resume_bitwise(
        self, tmp_path, reference_run
    ):
        model_dir = str(tmp_path / "victim")

        # Leg 1: the seeded fault plan SIGKILLs the trainer at its 2nd
        # save (step 10), with the async orbax write for step 10 in
        # flight — the mid-save crash, no cleanup handlers.
        crashed = _run_trainer(
            model_dir, 15, chaos_plan="save:2:sigkill", check=False
        )
        assert crashed.returncode == -signal.SIGKILL, (
            crashed.returncode,
            crashed.stdout[-2000:],
        )
        assert "TRAINING_DONE" not in crashed.stdout

        # The durable set can only be {5} (write didn't finish: torn
        # tmp or absent) or {5, 10} (rename won the race) — never empty,
        # never a torn dir presenting as durable.
        survivors = durability.durable_steps(model_dir)
        assert survivors in ([5], [5, 10]), survivors

        # Leg 2: restart. Must quarantine/skip any wreckage, resume
        # from the last durable step, and land on the SAME final state
        # as the run that never crashed — bitwise, residual included.
        resumed = _run_trainer(model_dir, 15)
        assert "TRAINING_DONE" in resumed.stdout
        before = [
            l for l in resumed.stdout.splitlines()
            if l.startswith("DURABLE_BEFORE")
        ][0]
        assert before.endswith(str(survivors)), (before, survivors)
        assert _digest_line(resumed) == reference_run["digest"]
        # Every checkpoint on disk after recovery is durable.
        assert durability.durable_steps(model_dir) == _checkpoint_steps(
            model_dir
        )

    @pytest.mark.slow
    def test_torn_final_named_dir_quarantined_never_loaded(
        self, tmp_path, reference_run
    ):
        """A checkpoint directory that LOOKS committed (bare step name)
        but is internally torn — the failure orbax's atomic rename
        cannot express — must be detected via the durability manifest,
        quarantined by the resuming trainer, and never restored.

        Slow slice: this is the end-to-end (subprocess, bitwise-replay)
        twin of coverage the tier-1 slice already has in-process —
        TestDurabilityModule's surgery/quarantine tests and
        TestRestoreChaosSites.test_restore_skips_torn_latest."""
        model_dir = str(tmp_path / "torn")
        shutil.copytree(reference_run["model_dir"], model_dir)
        step_dir = os.path.join(model_dir, "checkpoints", "15")
        manifest = json.load(
            open(os.path.join(step_dir, durability.MANIFEST_NAME))
        )
        # Seeded surgery: truncate the largest manifest-listed file.
        victim = max(manifest["files"], key=lambda e: e["size"])
        victim_path = os.path.join(step_dir, victim["path"])
        with open(victim_path, "r+b") as f:
            f.truncate(max(victim["size"] // 2, 1))
        assert durability.validate_step_dir(step_dir) is not None
        assert durability.durable_steps(model_dir) == [5, 10]

        resumed = _run_trainer(model_dir, 15)
        assert "Quarantined torn checkpoint '15'" in resumed.stdout
        # Resumed from 10 (the last durable), replayed 10->15, and the
        # replayed trajectory is bitwise the reference one.
        assert "DURABLE_BEFORE [5, 10]" in resumed.stdout
        assert _digest_line(resumed) == reference_run["digest"]
        # The wreckage moved to quarantine (forensics, not deletion) and
        # a fresh durable 15 exists.
        quarantine = os.path.join(
            model_dir, durability.QUARANTINE_DIRNAME
        )
        assert os.path.isdir(quarantine)
        assert any(
            entry.startswith("15.") for entry in os.listdir(quarantine)
        )
        assert 15 in durability.durable_steps(model_dir)


class TestDurabilityModule:
    """Pure-filesystem unit tests: no jax, no subprocesses."""

    def _fake_checkpoint(self, root, step, payload=b"x" * 64):
        step_dir = os.path.join(str(root), "checkpoints", str(step))
        item = os.path.join(step_dir, "default")
        os.makedirs(item)
        with open(os.path.join(step_dir, "_CHECKPOINT_METADATA"), "wb") as f:
            f.write(b"{}")
        with open(os.path.join(item, "_METADATA"), "wb") as f:
            f.write(b"{}")
        with open(os.path.join(item, "data.bin"), "wb") as f:
            f.write(payload)
        return step_dir

    def test_manifest_roundtrip_validates(self, tmp_path):
        step_dir = self._fake_checkpoint(tmp_path, 5)
        durability.write_manifest(step_dir)
        assert durability.validate_step_dir(step_dir) is None
        manifest = json.load(
            open(os.path.join(step_dir, durability.MANIFEST_NAME))
        )
        assert {e["path"] for e in manifest["files"]} == {
            "_CHECKPOINT_METADATA",
            os.path.join("default", "_METADATA"),
            os.path.join("default", "data.bin"),
        }

    def test_truncated_file_fails_manifest(self, tmp_path):
        step_dir = self._fake_checkpoint(tmp_path, 5)
        durability.write_manifest(step_dir)
        with open(os.path.join(step_dir, "default", "data.bin"), "r+b") as f:
            f.truncate(10)
        assert "size mismatch" in durability.validate_step_dir(step_dir)

    def test_missing_file_fails_manifest(self, tmp_path):
        step_dir = self._fake_checkpoint(tmp_path, 5)
        durability.write_manifest(step_dir)
        os.unlink(os.path.join(step_dir, "default", "data.bin"))
        assert "missing" in durability.validate_step_dir(step_dir)

    def test_orbax_tmp_name_is_torn(self, tmp_path):
        path = str(tmp_path / "7.orbax-checkpoint-tmp-123")
        os.makedirs(path)
        assert "tmp" in durability.validate_step_dir(path)

    def test_structural_fallback_without_manifest(self, tmp_path):
        # Committed-by-orbax but not yet blessed (the window between the
        # rename and the manifest write): structurally sound -> durable.
        step_dir = self._fake_checkpoint(tmp_path, 5)
        assert durability.validate_step_dir(step_dir) is None
        # An empty final-named dir (the orbax latest_step() trap) is torn.
        empty = os.path.join(str(tmp_path), "checkpoints", "10")
        os.makedirs(empty)
        assert durability.validate_step_dir(empty) is not None
        assert durability.durable_steps(str(tmp_path)) == [5]

    def test_sweep_quarantines_and_preserves(self, tmp_path):
        good = self._fake_checkpoint(tmp_path, 5)
        durability.write_manifest(good)
        bad = self._fake_checkpoint(tmp_path, 10)
        durability.write_manifest(bad)
        os.unlink(os.path.join(bad, "default", "data.bin"))
        tmp_dir = os.path.join(
            str(tmp_path), "checkpoints", "15.orbax-checkpoint-tmp-9"
        )
        os.makedirs(tmp_dir)
        report = durability.sweep_torn_checkpoints(str(tmp_path))
        assert sorted(name for name, _ in report) == [
            "10",
            "15.orbax-checkpoint-tmp-9",
        ]
        assert durability.durable_steps(str(tmp_path)) == [5]
        quarantine = durability.quarantine_root(str(tmp_path))
        moved = sorted(os.listdir(quarantine))
        assert len(moved) == 2
        # Quarantine preserves the wreckage byte-for-byte (forensics).
        ten = [m for m in moved if m.startswith("10.")][0]
        assert os.path.isfile(
            os.path.join(quarantine, ten, "_CHECKPOINT_METADATA")
        )

    def test_sweep_second_run_is_noop(self, tmp_path):
        bad = self._fake_checkpoint(tmp_path, 10)
        durability.write_manifest(bad)
        os.unlink(os.path.join(bad, "default", "data.bin"))
        assert durability.sweep_torn_checkpoints(str(tmp_path))
        assert durability.sweep_torn_checkpoints(str(tmp_path)) == []

    def test_publish_durable_refuses_torn(self, tmp_path):
        step_dir = self._fake_checkpoint(tmp_path, 5)
        os.unlink(os.path.join(step_dir, "_CHECKPOINT_METADATA"))
        assert not durability.publish_durable(str(tmp_path), 5)
        assert not os.path.exists(
            os.path.join(step_dir, durability.MANIFEST_NAME)
        )

    def test_publish_durable_idempotent(self, tmp_path):
        self._fake_checkpoint(tmp_path, 5)
        assert durability.publish_durable(str(tmp_path), 5)
        assert durability.publish_durable(str(tmp_path), 5)
        assert durability.publish_durable(str(tmp_path), 99) is False


class TestRestoreChaosSites:
    """In-process chaos at the restore site, over one small real run."""

    @pytest.fixture()
    def trained_dir(self, tmp_path):
        import jax

        from tensor2robot_tpu.train import train_eval as te
        from tensor2robot_tpu.utils.mocks import (
            MockInputGenerator,
            MockT2RModel,
        )

        model_dir = str(tmp_path / "run")
        te.train_eval_model(
            MockT2RModel(device_type="cpu", use_batch_norm=False),
            input_generator_train=MockInputGenerator(batch_size=8, seed=7),
            model_dir=model_dir,
            max_train_steps=4,
            eval_steps=None,
            save_checkpoints_steps=4,
            log_every_steps=4,
            seed=31,
        )
        return model_dir

    def _restore(self, model_dir):
        import jax

        from tensor2robot_tpu.train import train_eval as te
        from tensor2robot_tpu.utils.mocks import (
            MockInputGenerator,
            MockT2RModel,
        )

        model = MockT2RModel(device_type="cpu", use_batch_norm=False)
        gen = MockInputGenerator(batch_size=8, seed=7)
        gen.set_specification_from_model(model, "train")
        compiled = te.CompiledModel(model, donate_state=False)
        manager = te.create_checkpoint_manager(
            model_dir, save_interval_steps=4
        )
        try:
            return te.restore_or_init_state(
                manager,
                compiled,
                jax.random.PRNGKey(0),
                next(iter(gen.create_dataset("train"))),
            )
        finally:
            manager.close()

    def test_slow_restore_injection_fires_site(self, trained_dir):
        chaos.reset()
        try:
            chaos.configure("restore:1:delay:50")
            state = self._restore(trained_dir)
            assert int(state.step) == 4
            assert chaos.fired() == ["restore:1:delay:50"]
        finally:
            chaos.reset()

    def test_restore_exception_injection_propagates(self, trained_dir):
        chaos.reset()
        try:
            chaos.configure("restore:1:raise")
            with pytest.raises(chaos.ChaosFault):
                self._restore(trained_dir)
        finally:
            chaos.reset()

    def test_restore_skips_torn_latest(self, trained_dir):
        """restore_or_init_state walks PAST a torn newer dir — the
        orbax latest_step() trap — to the durable one (read-only: the
        torn dir stays in place for the owner to quarantine)."""
        torn = os.path.join(trained_dir, "checkpoints", "8")
        os.makedirs(torn)
        state = self._restore(trained_dir)
        assert int(state.step) == 4
        assert os.path.isdir(torn)  # reader never quarantines

    def test_predict_from_model_refuses_torn_only_dir(self, tmp_path):
        from tensor2robot_tpu.train import train_eval as te
        from tensor2robot_tpu.utils.mocks import (
            MockInputGenerator,
            MockT2RModel,
        )

        model_dir = str(tmp_path / "torn_only")
        os.makedirs(os.path.join(model_dir, "checkpoints", "5"))
        model = MockT2RModel(device_type="cpu", use_batch_norm=False)
        gen = MockInputGenerator(batch_size=8, seed=7)
        with pytest.raises(FileNotFoundError, match="durable"):
            next(
                te.predict_from_model(model, gen, model_dir)
            )
