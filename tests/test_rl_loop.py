"""The closed online loop end to end + the learner crash-recovery pin.

Acceptance contracts (ISSUE 9):

  1. The closed loop runs: actors -> replay -> learner -> published
     policy -> actors, with episodes/s, samples/s, replay ratio and
     policy staleness all measured (tier-1: the in-process twin; the
     multi-process topology with real SIGKILLs rides the slow slice —
     `bench.py rl` exercises the same path with the serving fleet).
  2. DETERMINISTIC learner recovery: a SIGKILL mid-orbax-save during
     replay-fed training resumes from the last durable step with the
     replay sampling state restored — the resumed run trains on exactly
     the batches the uninterrupted run trained on for those steps (no
     sealed segment double-sampled relative to the schedule), and the
     final TrainState is BITWISE equal to the uninterrupted twin's.
  3. A policy publish propagates to actors within a bounded staleness
     window (next episode, for the in-process loop).

Everything is seeded; the only subprocesses in the tier-1 slice are the
crash-recovery trainer legs (the same shape test_crash_consistency.py
already runs tier-1).
"""

import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

from tensor2robot_tpu.replay.service import ReplayBuffer
from tensor2robot_tpu.testing import chaos


@pytest.fixture(autouse=True)
def _lock_sanitizer_armed(locksmith_sanitizer):
    """Every run of this chaos suite doubles as a deadlock hunt: the
    lock sanitizer (testing/locksmith.py) is armed for each test and
    teardown fails on any observed lock-order cycle or hold-budget
    violation (fixture: tests/conftest.py)."""
    yield


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _collect_replay_dir(root, episodes=10, seal_episodes=3, seed=1):
    """A frozen, sealed replay directory: the deterministic sample
    substrate for the crash legs."""
    from tensor2robot_tpu.replay.actor import (
        EpisodeCollector,
        RandomPolicyClient,
    )
    from tensor2robot_tpu.research.pose_env.pose_env import PoseToyEnv

    buffer = ReplayBuffer(str(root), seal_episodes=seal_episodes)
    collector = EpisodeCollector(
        PoseToyEnv(seed=seed), RandomPolicyClient(seed=seed + 1)
    )
    for _ in range(episodes):
        records, info = collector.collect()
        buffer.append(
            records,
            policy_version=max(info["policy_version"], 0),
            priority=info["priority"],
        )
    buffer.close(seal_tail=True)
    return str(root)


# One replay-fed trainer program for every crash leg: train over the
# frozen replay dir (FIFO dir mode — deterministic), save every 4 steps,
# then restore the final durable checkpoint and print (a) a sha256 over
# the FULL persistable TrainState and (b) the (segment, record) sample
# schedule actually TRAINED on. Bitwise digest equality + schedule
# equality are the two halves of the recovery contract.
_TRAINER = r"""
import sys
import jax
jax.config.update("jax_platforms", "cpu")
replay_root, model_dir, max_steps = sys.argv[1], sys.argv[2], int(sys.argv[3])
import hashlib
import json
import numpy as np
from tensor2robot_tpu.replay.input_generator import ReplayInputGenerator
from tensor2robot_tpu.research.pose_env.pose_env_models import (
    PoseEnvRegressionModel,
)
from tensor2robot_tpu.train import durability
from tensor2robot_tpu.train import train_eval as te

print("DURABLE_BEFORE", durability.durable_steps(model_dir), flush=True)

generator = ReplayInputGenerator(replay_root, batch_size=4, wait_timeout_s=10)
te.train_eval_model(
    PoseEnvRegressionModel(),
    input_generator_train=generator,
    model_dir=model_dir,
    max_train_steps=max_steps,
    eval_steps=None,
    save_checkpoints_steps=4,
    log_every_steps=4,
    seed=31,
)
print("TRAINING_DONE", flush=True)

# The batches the loop TRAINED on this process: the stream was realigned
# to the restored step, so everything before start_step was drawn only
# to be skipped. coords_log[start:max_steps] is the trained schedule.
start = int(sys.argv[4]) if len(sys.argv) > 4 else 0
trained = generator.coords_log[start:max_steps]
print("TRAINED_COORDS", json.dumps(trained), flush=True)

model = PoseEnvRegressionModel()
gen2 = ReplayInputGenerator(replay_root, batch_size=4, wait_timeout_s=10)
gen2.set_specification_from_model(model, "train")
compiled = te.CompiledModel(model, donate_state=False)
manager = te.create_checkpoint_manager(model_dir, save_interval_steps=4)
state = te.restore_or_init_state(
    manager, compiled, jax.random.PRNGKey(0),
    next(iter(gen2.create_dataset("train"))),
)
digest = hashlib.sha256()
for leaf in jax.tree_util.tree_leaves(jax.device_get(state)):
    digest.update(np.ascontiguousarray(leaf).tobytes())
print(
    "STATE_SHA256", digest.hexdigest(), "STEP", int(state.step), flush=True
)
manager.close()
"""


def _run_trainer(replay_root, model_dir, max_steps, start_step=0,
                 chaos_plan=None, check=True):
    env = dict(os.environ)
    env.pop("T2R_CHAOS", None)
    if chaos_plan is not None:
        env["T2R_CHAOS"] = chaos_plan
    proc = subprocess.run(
        [
            sys.executable, "-c", _TRAINER, str(replay_root),
            str(model_dir), str(max_steps), str(start_step),
        ],
        capture_output=True,
        text=True,
        timeout=420,
        env=env,
        cwd=REPO_ROOT,
    )
    if check:
        assert proc.returncode == 0, proc.stdout[-2500:] + proc.stderr[-2500:]
    return proc


def _line(proc, prefix):
    lines = [
        l for l in proc.stdout.splitlines() if l.startswith(prefix)
    ]
    assert lines, (prefix, proc.stdout[-2500:], proc.stderr[-2500:])
    return lines[-1]


def _trained_coords(proc):
    return json.loads(_line(proc, "TRAINED_COORDS")[len("TRAINED_COORDS "):])


@pytest.fixture(scope="module")
def frozen_replay(tmp_path_factory):
    root = tmp_path_factory.mktemp("rl") / "replay"
    return _collect_replay_dir(root)


@pytest.fixture(scope="module")
def reference_run(tmp_path_factory, frozen_replay):
    """One uninterrupted 12-step replay-fed run: the trajectory AND
    sample-schedule oracle the crash leg must reproduce."""
    model_dir = str(tmp_path_factory.mktemp("rl") / "reference")
    proc = _run_trainer(frozen_replay, model_dir, 12)
    return {
        "digest": _line(proc, "STATE_SHA256"),
        "coords": _trained_coords(proc),
    }


# ~34s (trained-fixture setup + SIGKILL/respawn) on 1 cpu: slow slice;
# the offline SIGKILL-mid-save bitwise pin in test_crash_consistency
# keeps the save-atomicity contract on the fast tier.
@pytest.mark.slow
class TestLearnerSigkillMidSaveOnline:
    def test_resume_restores_sampling_state_bitwise(
        self, tmp_path, frozen_replay, reference_run
    ):
        """THE acceptance pin: SIGKILL mid-orbax-save during online
        (replay-fed) training; the resumed run must (a) resume from the
        last durable step, (b) continue the uninterrupted run's exact
        sample schedule — no sealed segment double-sampled relative to
        it, (c) finish with a bitwise-identical TrainState."""
        from tensor2robot_tpu.train import durability

        model_dir = str(tmp_path / "victim")
        crashed = _run_trainer(
            frozen_replay, model_dir, 12,
            chaos_plan="save:2:sigkill", check=False,
        )
        assert crashed.returncode == -signal.SIGKILL, (
            crashed.returncode, crashed.stdout[-2000:],
        )
        assert "TRAINING_DONE" not in crashed.stdout

        survivors = durability.durable_steps(model_dir)
        assert survivors in ([4], [4, 8]), survivors
        start = survivors[-1]

        resumed = _run_trainer(
            frozen_replay, model_dir, 12, start_step=start
        )
        assert "TRAINING_DONE" in resumed.stdout
        # (a) resumed from the last durable step.
        assert _line(resumed, "DURABLE_BEFORE").endswith(str(survivors))
        # (b) sampling state restored: the resumed run trained on
        # EXACTLY the reference schedule's tail — batch for batch,
        # (segment_seq, record_index) for (segment_seq, record_index).
        assert _trained_coords(resumed) == reference_run["coords"][start:12]
        # (c) bitwise-identical final TrainState.
        assert _line(resumed, "STATE_SHA256") == reference_run["digest"]
        # And every checkpoint on disk after recovery is durable.
        assert durability.durable_steps(model_dir)[-1] == 12

    def test_reference_schedule_covers_each_record_once_per_pass(
        self, frozen_replay, reference_run
    ):
        """FIFO pass structure: within one cycle over the sealed data no
        (segment, record) repeats — 'no sealed segment double-sampled'
        in its within-epoch form."""
        flat = [tuple(c) for batch in reference_run["coords"] for c in batch]
        from tensor2robot_tpu.replay.segment import list_sealed_segments

        total = sum(
            m.records for _, m in list_sealed_segments(frozen_replay)
        )
        first_pass = flat[:total]
        assert len(set(first_pass)) == len(first_pass)


class TestInProcessClosedLoop:
    """Tier-1 twin of the multi-process loop: same sites, same counters,
    no subprocesses beyond jax's own."""

    def test_loop_closes_and_reports(self, tmp_path):
        from tensor2robot_tpu.replay import OnlineLoop

        loop = OnlineLoop(
            str(tmp_path), num_actors=2, batch_size=4, seal_episodes=4,
            in_process=True, seed=3, wait_timeout_s=60,
            actor_throttle_s=0.01,
        ).start()
        try:
            loop.run_learner(max_steps=4, save_steps=2, publish=True)
        finally:
            report = loop.stop()
        assert report.learner_steps == 4
        assert report.publishes == 2
        assert report.episodes_appended > 0
        assert report.samples_drawn >= 4 * 4
        assert report.replay_ratio > 0
        assert report.episodes_lost == 0
        assert report.episodes_per_s > 0
        assert report.samples_per_s > 0

    def test_publish_staleness_window_bounded(self, tmp_path):
        """A policy publish must reach actors within one episode: the
        next appended episode carries the new version, and the buffer's
        staleness anchor moved with it."""
        from tensor2robot_tpu.replay.actor import EpisodeCollector
        from tensor2robot_tpu.replay.loop import OnlineLoop
        from tensor2robot_tpu.research.pose_env.pose_env import PoseToyEnv

        loop = OnlineLoop(str(tmp_path), num_actors=0, in_process=True,
                          seal_episodes=2).start()
        try:
            collector = EpisodeCollector(
                PoseToyEnv(seed=5), loop._local_policy_client(seed=6)
            )

            def append_one():
                records, info = collector.collect()
                return loop._buffer.append(
                    records,
                    policy_version=max(info["policy_version"], 0),
                )

            append_one()
            loop._publish(step=1, state=None)  # publish v1
            append_one()  # within one episode of the publish
            loop._publish(step=2, state=None)  # v2
            append_one(); append_one()
            _, _, info = loop._buffer.sample(4)
            # Episodes: v0, v1, v2, v2 against anchor 2 -> staleness
            # [2, 1, 0, 0]: the window is bounded at one episode.
            assert info["staleness_max"] == 2.0
            assert info["staleness_mean"] == pytest.approx(0.75)
            stats = loop._buffer.stats()
            assert stats["policy_version"] == 2
        finally:
            loop.stop()

    # ~6s on 1 cpu: slow slice; the other chaos sites' containment
    # pins keep the fault-plan contract fast.
    @pytest.mark.slow
    def test_chaos_publish_site_fires_and_is_contained(self, tmp_path):
        """A fault at publish_policy must not kill the learner: the
        publish is skipped (counted), training continues."""
        from tensor2robot_tpu.replay import OnlineLoop

        chaos.reset()
        try:
            chaos.configure("publish_policy:1:raise")
            loop = OnlineLoop(
                str(tmp_path), num_actors=1, batch_size=4,
                seal_episodes=2, in_process=True, seed=4,
                wait_timeout_s=60, actor_throttle_s=0.01,
            ).start()
            try:
                loop.run_learner(max_steps=4, save_steps=2, publish=True)
            finally:
                report = loop.stop()
            assert report.learner_steps == 4
            assert "publish_policy:1:raise" in chaos.fired()
        finally:
            chaos.reset()


@pytest.mark.slow
class TestMultiProcessSoak:
    """The end-to-end multi-process topology with REAL SIGKILLs: the
    slow-slice twin of the tier-1 in-process loop (and of `bench.py
    rl`'s chaos leg, which adds the serving fleet)."""

    def test_service_and_actor_sigkill_mid_run(self, tmp_path):
        import time

        from tensor2robot_tpu.replay import OnlineLoop

        loop = OnlineLoop(
            str(tmp_path), num_actors=2, batch_size=4, seal_episodes=4,
            seed=3, wait_timeout_s=180, actor_throttle_s=0.02,
        ).start()
        try:
            import threading

            def chaos_mid_run():
                time.sleep(3.0)
                loop.kill_replay_service()
                loop.kill_actor(0)

            chaos_thread = threading.Thread(
                target=chaos_mid_run, daemon=True
            )
            chaos_thread.start()
            loop.run_learner(max_steps=8, save_steps=4, publish=True)
            chaos_thread.join()
        finally:
            report = loop.stop()
        # The learner finished every step through the service crash.
        assert report.learner_steps == 8
        assert report.replay_restarts >= 1
        assert report.actors_killed == 1
        # Loss is bounded to the unsealed tail and COUNTED.
        assert report.episodes_lost <= loop.seal_episodes
        assert report.recovery.get("segments_quarantined", 0) >= 0
        assert report.samples_drawn > 0
