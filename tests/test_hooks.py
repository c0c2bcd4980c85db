"""Hook subsystem tests (reference hooks/*_test.py: checkpoint_hooks_test,
td3_test, golden values, async export)."""

import json
import os
import time

import numpy as np
import pytest

from tensor2robot_tpu.hooks import (
    AsyncExportHookBuilder,
    CheckpointExportListener,
    ConfigLoggerHookBuilder,
    GoldenValuesHookBuilder,
    LaggedCheckpointListener,
    TD3Hooks,
    VariableLoggerHookBuilder,
    add_golden_tensor,
    load_golden_values,
)
from tensor2robot_tpu.predictors import ExportedSavedModelPredictor
from tensor2robot_tpu.train import train_eval
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel


def _fake_export_fn(counter):
    """Creates versioned dirs like the real export fn."""

    def export_fn(export_dir, global_step):
        counter["n"] += 1
        path = os.path.join(export_dir, f"{counter['n']:010d}")
        os.makedirs(path)
        with open(os.path.join(path, "model.txt"), "w") as f:
            f.write(str(global_step))
        return path

    return export_fn


class TestCheckpointExportListener:
    def test_export_and_gc(self, tmp_path):
        counter = {"n": 0}
        listener = CheckpointExportListener(
            _fake_export_fn(counter), str(tmp_path / "export"), num_versions=2
        )
        for step in range(4):
            listener.after_save(step)
        versions = sorted(os.listdir(tmp_path / "export"))
        assert versions == ["0000000003", "0000000004"]

    def test_preexisting_dirs_counted_by_gc(self, tmp_path):
        export_dir = tmp_path / "export"
        os.makedirs(export_dir / "0000000001")
        counter = {"n": 1}
        listener = CheckpointExportListener(
            _fake_export_fn(counter), str(export_dir), num_versions=2
        )
        listener.after_save(1)
        listener.after_save(2)
        versions = sorted(os.listdir(export_dir))
        assert versions == ["0000000002", "0000000003"]


class TestLaggedCheckpointListener:
    def make(self, tmp_path, counter=None):
        counter = counter or {"n": 0}
        return LaggedCheckpointListener(
            _fake_export_fn(counter),
            str(tmp_path / "latest"),
            str(tmp_path / "lagged"),
            num_versions=3,
        ), counter

    def test_lagged_stays_one_behind(self, tmp_path):
        listener, _ = self.make(tmp_path)
        listener.after_save(1)
        # First export: lagged mirrors it (nothing older exists).
        assert sorted(os.listdir(tmp_path / "latest")) == ["0000000001"]
        assert sorted(os.listdir(tmp_path / "lagged")) == ["0000000001"]
        listener.after_save(2)
        assert sorted(os.listdir(tmp_path / "latest")) == [
            "0000000001", "0000000002",
        ]
        assert sorted(os.listdir(tmp_path / "lagged")) == ["0000000001"]
        listener.after_save(3)
        assert sorted(os.listdir(tmp_path / "lagged")) == [
            "0000000001", "0000000002",
        ]

    def test_startup_resync(self, tmp_path):
        # Two prior exports, empty lagged dir: startup copies the
        # second-newest into lagged (reference :128-155).
        os.makedirs(tmp_path / "latest" / "0000000001")
        os.makedirs(tmp_path / "latest" / "0000000002")
        counter = {"n": 2}
        listener, _ = self.make(tmp_path, counter)
        assert sorted(os.listdir(tmp_path / "lagged")) == ["0000000001"]
        listener.after_save(3)
        assert sorted(os.listdir(tmp_path / "lagged")) == [
            "0000000001", "0000000002",
        ]


class _GoldenMockModel(MockT2RModel):
    def model_train_fn(self, features, labels, inference_outputs, mode):
        loss, metrics = super().model_train_fn(
            features, labels, inference_outputs, mode
        )
        add_golden_tensor(metrics, inference_outputs["a_predicted"], "logits")
        return loss, metrics


class TestGoldenValuesHook:
    def test_capture_through_training(self, tmp_path):
        model_dir = str(tmp_path / "run")
        train_eval.train_eval_model(
            t2r_model=_GoldenMockModel(device_type="cpu"),
            input_generator_train=MockInputGenerator(batch_size=4),
            model_dir=model_dir,
            max_train_steps=5,
            save_checkpoints_steps=5,
            log_every_steps=1,
            hook_builders=[GoldenValuesHookBuilder(model_dir)],
        )
        values = load_golden_values(model_dir)
        assert len(values) == 5
        assert values[0]["logits"].shape == (4, 1)
        # Values evolve as training progresses.
        assert not np.allclose(values[0]["logits"], values[-1]["logits"])


class TestAsyncExportHooks:
    def test_periodic_export_and_reload(self, tmp_path):
        model_dir = str(tmp_path / "run")
        export_dir = str(tmp_path / "export")
        builder = AsyncExportHookBuilder(
            export_dir=export_dir, save_secs=0.0, num_versions=3
        )
        train_eval.train_eval_model(
            t2r_model=MockT2RModel(device_type="cpu"),
            input_generator_train=MockInputGenerator(batch_size=4),
            model_dir=model_dir,
            max_train_steps=4,
            save_checkpoints_steps=4,
            log_every_steps=2,
            hook_builders=[builder],
        )
        versions = sorted(os.listdir(export_dir))
        assert versions, "No exports produced"
        assert len(versions) <= 3
        # The exported artifact serves predictions (reference
        # async_export_hook_builder_tpu_test :33-66).
        predictor = ExportedSavedModelPredictor(export_dir=export_dir)
        assert predictor.restore()
        features = {"x": np.zeros((2, 3), np.float32)}
        outputs = predictor.predict(features)
        assert outputs["a_predicted"].shape == (2, 1)

    def test_td3_lagged_dirs(self, tmp_path):
        model_dir = str(tmp_path / "run")
        export_dir = str(tmp_path / "export")
        lagged_dir = str(tmp_path / "lagged")
        builder = TD3Hooks(
            export_dir=export_dir,
            lagged_export_dir=lagged_dir,
            save_secs=0.0,
            num_versions=5,
        )
        train_eval.train_eval_model(
            t2r_model=MockT2RModel(device_type="cpu"),
            input_generator_train=MockInputGenerator(batch_size=4),
            model_dir=model_dir,
            max_train_steps=4,
            save_checkpoints_steps=2,
            log_every_steps=2,
            hook_builders=[builder],
        )
        latest_versions = sorted(os.listdir(export_dir))
        lagged_versions = sorted(os.listdir(lagged_dir))
        assert latest_versions and lagged_versions
        # Lagged holds strictly older-or-equal versions, never the newest
        # when more than one exists.
        if len(latest_versions) > 1:
            assert lagged_versions[-1] <= latest_versions[-2]
        # Both directories hold loadable artifacts.
        lagged_predictor = ExportedSavedModelPredictor(export_dir=lagged_dir)
        assert lagged_predictor.restore()


class TestMiscHooks:
    def test_variable_logger_and_config_logger_run(self, tmp_path, caplog):
        import logging as pylogging

        caplog.set_level(pylogging.INFO)
        train_eval.train_eval_model(
            t2r_model=MockT2RModel(device_type="cpu"),
            input_generator_train=MockInputGenerator(batch_size=4),
            model_dir=str(tmp_path / "run"),
            max_train_steps=2,
            save_checkpoints_steps=2,
            log_every_steps=1,
            hook_builders=[
                VariableLoggerHookBuilder(every_steps=1),
                ConfigLoggerHookBuilder(),
            ],
        )
        messages = " ".join(r.message for r in caplog.records)
        assert "mean=" in messages
        assert "Operative config" in messages


class TestProfilingHooks:
    # ~5s (profiler capture) on 1 cpu: slow slice — tooling smoke.
    @pytest.mark.slow
    def test_profiler_hook_writes_trace(self, tmp_path):
        from tensor2robot_tpu.hooks import ProfilerHookBuilder
        from tensor2robot_tpu.train import train_eval
        from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

        model_dir = str(tmp_path / "run")
        train_eval.train_eval_model(
            t2r_model=MockT2RModel(device_type="cpu"),
            input_generator_train=MockInputGenerator(batch_size=8),
            model_dir=model_dir,
            max_train_steps=10,
            save_checkpoints_steps=10,
            log_every_steps=10,
            hook_builders=[ProfilerHookBuilder(start_step=2, num_steps=3)],
        )
        trace_root = os.path.join(model_dir, "profiling")
        assert os.path.isdir(trace_root)
        # jax writes plugins/profile/<ts>/ under the trace dir.
        found = []
        for root, _, files in os.walk(trace_root):
            found.extend(f for f in files if f.endswith((".xplane.pb", ".trace.json.gz", ".json.gz")))
        assert found, f"no trace artifacts under {trace_root}"

    def test_profiling_hooks_fire_in_multistep_regime(self, tmp_path):
        """ctx.step advances by iterations_per_loop; windows/gates must not
        require exact step multiples."""
        from tensor2robot_tpu.hooks import ProfilerHookBuilder
        from tensor2robot_tpu.train import train_eval
        from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

        model_dir = str(tmp_path / "run")
        train_eval.train_eval_model(
            t2r_model=MockT2RModel(device_type="cpu"),
            input_generator_train=MockInputGenerator(batch_size=8),
            model_dir=model_dir,
            max_train_steps=48,
            save_checkpoints_steps=48,
            log_every_steps=16,
            iterations_per_loop=16,
            hook_builders=[ProfilerHookBuilder(start_step=10, num_steps=5)],
        )
        traces = []
        for root, _, files in os.walk(os.path.join(model_dir, "profiling")):
            traces += [
                os.path.join(root, f) for f in files
                if "xplane" in f or f.endswith(".json.gz")
            ]
        assert traces, "profiler trace missing under scan dispatch"
        # The window's program spans lie beside the xplane, on its clock.
        spans_path = os.path.join(os.path.dirname(traces[0]), "spans.jsonl")
        with open(spans_path) as f:
            spans = [json.loads(line) for line in f]
        # One scanned dispatch lies in the window (steps 16 to 32).
        assert "train.dispatch" in {span["name"] for span in spans}
        assert all(span["end_ns"] >= span["start_ns"] for span in spans)
