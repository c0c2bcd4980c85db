"""Predictor tests: exported-dir serving, checkpoint serving, polling/async
restore, random init — mirroring the reference's predictor test coverage
(checkpoint_predictor + exported_savedmodel_predictor tests against the mock
model / mock SavedModel fixture).
"""

import os
import threading
import time

import jax
import numpy as np
import pytest

from tensor2robot_tpu.export import DefaultExportGenerator, save_exported_model
from tensor2robot_tpu.predictors import (
    CheckpointPredictor,
    ExportedSavedModelPredictor,
)
from tensor2robot_tpu.train.train_eval import CompiledModel
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel


@pytest.fixture(scope="module")
def trained():
    model = MockT2RModel(device_type="cpu")
    generator = MockInputGenerator(batch_size=8)
    generator.set_specification_from_model(model, "train")
    batches = iter(generator.create_dataset("train"))
    compiled = CompiledModel(model, donate_state=False)
    state = compiled.init_state(jax.random.PRNGKey(0), next(batches))
    for _ in range(3):
        batch = compiled.shard_batch(next(batches))
        state, _ = compiled.train_step(state, batch, jax.random.PRNGKey(1))
    return compiled, state


def _export(trained, root, serialize_stablehlo=True):
    compiled, state = trained
    generator = DefaultExportGenerator()
    generator.set_specification_from_model(compiled.model)
    variables = state.export_variables()
    return save_exported_model(
        root,
        variables=variables,
        feature_spec=generator.serving_input_spec(),
        label_spec=generator.label_spec,
        global_step=int(jax.device_get(state.step)),
        predict_fn=generator.create_serving_fn(compiled, variables),
        example_features=generator.create_example_features(),
        serialize_stablehlo=serialize_stablehlo,
    )


class TestExportedSavedModelPredictor:
    def test_restore_and_predict_stablehlo(self, trained, tmp_path):
        root = str(tmp_path)
        _export(trained, root)
        predictor = ExportedSavedModelPredictor(export_dir=root)
        assert predictor.restore()
        x = np.zeros((2, 3), np.float32)
        out = predictor.predict({"x": x})
        assert out["a_predicted"].shape == (2, 1)
        assert predictor.global_step == 3
        assert predictor.model_version > 0
        assert "x" in predictor.get_feature_specification()

    def test_restore_without_stablehlo_needs_model(self, trained, tmp_path):
        root = str(tmp_path)
        _export(trained, root, serialize_stablehlo=False)
        predictor = ExportedSavedModelPredictor(export_dir=root)
        with pytest.raises(ValueError, match="StableHLO"):
            predictor.restore()

    def test_restore_without_stablehlo_model_fallback(self, trained, tmp_path):
        compiled, state = trained
        root = str(tmp_path)
        _export(trained, root, serialize_stablehlo=False)
        predictor = ExportedSavedModelPredictor(
            export_dir=root, t2r_model=MockT2RModel(device_type="cpu")
        )
        assert predictor.restore()
        x = np.random.RandomState(0).uniform(-1, 1, (2, 3)).astype(np.float32)
        out = predictor.predict({"x": x})
        direct = compiled.predict_step(state.export_variables(), {"x": x})
        np.testing.assert_allclose(
            out["a_predicted"], np.asarray(direct["a_predicted"]), rtol=1e-5
        )

    def test_restore_times_out_on_empty_dir(self, tmp_path):
        predictor = ExportedSavedModelPredictor(
            export_dir=str(tmp_path / "nothing"), timeout=0
        )
        assert not predictor.restore()

    def test_restore_picks_up_new_version(self, trained, tmp_path):
        root = str(tmp_path)
        _export(trained, root)
        predictor = ExportedSavedModelPredictor(export_dir=root)
        assert predictor.restore()
        v1 = predictor.model_version
        time.sleep(1.1)  # new unix-second timestamp
        _export(trained, root)
        assert predictor.restore()
        assert predictor.model_version > v1

    def test_async_restore(self, trained, tmp_path):
        root = str(tmp_path)
        _export(trained, root)
        predictor = ExportedSavedModelPredictor(export_dir=root)
        assert predictor.restore(is_async=True)
        deadline = time.time() + 60
        while predictor.model_version < 0 and time.time() < deadline:
            time.sleep(0.1)
        assert predictor.model_version > 0
        predictor.close()

    def test_restore_prewarm_runs_before_swap(self, trained, tmp_path):
        """set_restore_prewarm's fn sees the incoming version's serving
        surface BEFORE the predictor flips to it (the policy server's
        hot-swap continuity hook)."""
        root = str(tmp_path)
        _export(trained, root)
        predictor = ExportedSavedModelPredictor(export_dir=root)
        assert predictor.restore()
        v1 = predictor.model_version
        seen = []

        def prewarm(loaded, serve_fn):
            # At prewarm time the OLD version is still the live one.
            seen.append(
                (predictor.model_version, loaded.export_dir,
                 serve_fn({"x": np.zeros((2, 3), np.float32)}))
            )

        predictor.set_restore_prewarm(prewarm)
        time.sleep(1.1)  # new unix-second timestamp
        path_v2 = _export(trained, root)
        assert predictor.restore()
        assert predictor.model_version > v1
        assert len(seen) == 1
        live_at_prewarm, prewarmed_dir, outputs = seen[0]
        assert live_at_prewarm == v1  # swap had not landed yet
        assert prewarmed_dir == path_v2  # the incoming version compiled
        assert outputs["a_predicted"].shape == (2, 1)

    def test_restore_prewarm_failure_keeps_old_version(self, trained, tmp_path):
        root = str(tmp_path)
        _export(trained, root)
        predictor = ExportedSavedModelPredictor(export_dir=root, timeout=0)
        assert predictor.restore()
        v1 = predictor.model_version

        def broken_prewarm(loaded, serve_fn):
            raise RuntimeError("artifact cannot compile")

        predictor.set_restore_prewarm(broken_prewarm)
        time.sleep(1.1)
        _export(trained, root)
        # The new version fails prewarm -> no swap, old version serves.
        assert not predictor.restore()
        assert predictor.model_version == v1
        out = predictor.predict({"x": np.zeros((1, 3), np.float32)})
        assert out["a_predicted"].shape == (1, 1)

    def test_async_restore_no_duplicate_thread(self, tmp_path):
        """A second restore(is_async=True) while one is scheduled/running
        must not start a second thread — including the window where the
        first thread exists but has not yet reached is_alive()."""
        started = threading.Event()
        release = threading.Event()
        calls = []

        class _Gated(ExportedSavedModelPredictor):
            def _restore_sync(self):
                calls.append(1)
                started.set()
                release.wait(30)
                return False

        predictor = _Gated(export_dir=str(tmp_path / "none"), timeout=0)
        try:
            for _ in range(5):
                assert predictor.restore(is_async=True)
            assert started.wait(10)
            assert predictor._restore_in_flight
            assert len(calls) == 1
            alive = [
                t for t in threading.enumerate()
                if t.name == "t2r-async-restore" and t.is_alive()
            ]
            assert len(alive) == 1
        finally:
            release.set()
        predictor.close()
        # The in-flight flag clears once the thread finishes, so a LATER
        # async restore may start again.
        deadline = time.time() + 10
        while predictor._restore_in_flight and time.time() < deadline:
            time.sleep(0.01)
        assert not predictor._restore_in_flight
        assert not predictor.restore_thread_leaked

    # ~8s (deliberately wedged restore thread) on 1 cpu: slow slice.
    @pytest.mark.slow
    def test_close_surfaces_leaked_restore_thread(self, tmp_path, caplog):
        """close() must flag + log a restore thread that outlives its
        join timeout instead of silently leaking it."""
        import logging as logging_mod

        predictor = ExportedSavedModelPredictor(
            # No export will ever appear: the restore busy-wait polls the
            # empty dir for `timeout` seconds.
            export_dir=str(tmp_path / "none"),
            timeout=3,
        )
        assert predictor.restore(is_async=True)
        with caplog.at_level(logging_mod.WARNING):
            predictor.close(join_timeout=0.2)
        assert predictor.restore_thread_leaked
        assert any(
            "restore thread still alive" in record.message
            for record in caplog.records
        )
        # Bounded cleanup so the polling daemon does not outlive the test.
        predictor._restore_thread.join(timeout=30)
        # Once the leaked thread finally dies, the in-flight latch clears —
        # the predictor is USABLE again (a later async restore may start,
        # and a clean close joins it)...
        deadline = time.time() + 10
        while predictor._restore_in_flight and time.time() < deadline:
            time.sleep(0.01)
        assert not predictor._restore_in_flight
        assert predictor.restore(is_async=True)
        predictor.close(join_timeout=30)
        # ...but the leak flag is STICKY: fleet monitors polling
        # snapshot() must keep seeing the wound after recovery.
        assert predictor.restore_thread_leaked

    def test_init_randomly(self):
        predictor = ExportedSavedModelPredictor(
            export_dir="/nonexistent", t2r_model=MockT2RModel(device_type="cpu")
        )
        predictor.init_randomly()
        out = predictor.predict({"x": np.zeros((2, 3), np.float32)})
        assert out["a_predicted"].shape == (2, 1)

    def test_predict_before_restore_raises(self, tmp_path):
        predictor = ExportedSavedModelPredictor(export_dir=str(tmp_path))
        with pytest.raises(ValueError, match="no model loaded"):
            predictor.predict({"x": np.zeros((1, 3), np.float32)})


class TestCheckpointPredictor:
    def test_init_randomly_and_predict(self):
        predictor = CheckpointPredictor(t2r_model=MockT2RModel(device_type="cpu"))
        predictor.init_randomly()
        out = predictor.predict({"x": np.zeros((4, 3), np.float32)})
        assert out["a_predicted"].shape == (4, 1)

    def test_restore_from_trainer_checkpoint(self, tmp_path):
        from tensor2robot_tpu.train.train_eval import train_eval_model

        model_dir = str(tmp_path / "run")
        train_eval_model(
            MockT2RModel(device_type="cpu"),
            input_generator_train=MockInputGenerator(batch_size=8),
            model_dir=model_dir,
            max_train_steps=4,
            save_checkpoints_steps=2,
            log_every_steps=2,
        )
        predictor = CheckpointPredictor(
            t2r_model=MockT2RModel(device_type="cpu"),
            checkpoint_dir=model_dir,
            timeout=5,
        )
        assert predictor.restore()
        assert predictor.global_step == 4
        out = predictor.predict({"x": np.zeros((2, 3), np.float32)})
        assert out["a_predicted"].shape == (2, 1)
        assert predictor.model_path.endswith("4")

    @staticmethod
    def _train_quant_zero2(model, model_dir):
        """Two steps and a checkpoint in the quantized ZeRO-2 regime, the
        one producer of a flat optimizer state and a flat EMA."""
        from tensor2robot_tpu import flags
        from tensor2robot_tpu.train.train_eval import train_eval_model

        saved = flags.read_raw("T2R_COLLECTIVE_QUANT")
        try:
            flags.write_env("T2R_COLLECTIVE_QUANT", "int8")
            train_eval_model(
                model,
                input_generator_train=MockInputGenerator(batch_size=8),
                model_dir=model_dir,
                max_train_steps=2,
                save_checkpoints_steps=2,
                log_every_steps=2,
                shard_weight_update=True,
            )
        finally:
            flags.restore_env("T2R_COLLECTIVE_QUANT", saved)

    def test_restore_flat_ema_checkpoint(self, tmp_path):
        """A checkpoint from the quantized ZeRO-2 regime stores the EMA
        as ONE concatenated, block-padded vector; every consumer must
        unravel it against the params structure and drop the tail
        (train/state.py ema_as_tree), not serve the raw 1-D vector as
        'params'."""
        from tensor2robot_tpu.models.checkpoint_init import (
            load_checkpoint_variables,
        )
        from tensor2robot_tpu.train.state import checkpoint_metadata_template

        model_dir = str(tmp_path / "run")
        self._train_quant_zero2(
            MockT2RModel(device_type="cpu", use_avg_model_params=True),
            model_dir,
        )
        on_disk = checkpoint_metadata_template(
            os.path.join(model_dir, "checkpoints"), 2
        )
        n_params = sum(
            int(np.prod(leaf.shape))
            for leaf in jax.tree_util.tree_leaves(
                on_disk["variables"]["params"]
            )
        )
        assert len(on_disk["ema_params"].shape) == 1
        assert on_disk["ema_params"].shape[0] > n_params  # padded tail
        predictor = CheckpointPredictor(
            t2r_model=MockT2RModel(
                device_type="cpu", use_avg_model_params=True
            ),
            checkpoint_dir=model_dir,
            timeout=5,
            use_ema=True,
        )
        assert predictor.restore()
        out = predictor.predict({"x": np.zeros((2, 3), np.float32)})
        assert out["a_predicted"].shape == (2, 1)

        # Warm-start consumer: path-based matching must see real
        # per-variable paths, not one flat 'params' leaf.
        variables = load_checkpoint_variables(model_dir, use_ema=True)
        assert "kernel" in variables["params"]["Dense_0"]

    def test_restore_checkpoint_with_different_opt_layout(self, tmp_path):
        """Serving must not care how the TRAINER laid out its optimizer
        state: a checkpoint written in the quantized ZeRO-2 regime (one
        concatenated moment vector) restores into a predictor whose
        model-derived template is per-leaf — the opt_state template comes
        from the checkpoint's own metadata."""
        model_dir = str(tmp_path / "run")
        self._train_quant_zero2(MockT2RModel(device_type="cpu"), model_dir)
        predictor = CheckpointPredictor(
            t2r_model=MockT2RModel(device_type="cpu"),
            checkpoint_dir=model_dir,
            timeout=5,
        )
        assert predictor.restore()
        out = predictor.predict({"x": np.zeros((2, 3), np.float32)})
        assert out["a_predicted"].shape == (2, 1)

        # Cross-topology serving: the same checkpoint (written on this
        # process's 8-device mesh) restores in a ONE-device process — the
        # robot-host-loads-pod-checkpoint workflow. Template leaves carry
        # explicit host shardings, so orbax never consults the
        # checkpoint's topology-specific sharding file.
        import subprocess
        import sys as _sys

        env = {
            k: v
            for k, v in os.environ.items()
            if k not in ("PYTHONPATH", "XLA_FLAGS")
        }
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [
                _sys.executable,
                "-c",
                "import sys; sys.path.insert(0, '/root/repo')\n"
                "import jax, numpy as np\n"
                "assert len(jax.devices()) == 1\n"
                "from tensor2robot_tpu.predictors.checkpoint_predictor "
                "import CheckpointPredictor\n"
                "from tensor2robot_tpu.utils.mocks import MockT2RModel\n"
                "p = CheckpointPredictor(t2r_model=MockT2RModel("
                "device_type='cpu'), checkpoint_dir=%r, timeout=5)\n"
                "assert p.restore()\n"
                "out = p.predict({'x': np.zeros((2, 3), np.float32)})\n"
                "assert out['a_predicted'].shape == (2, 1)\n"
                "print('OK')" % model_dir,
            ],
            capture_output=True,
            text=True,
            timeout=300,
            env=env,
        )
        assert proc.returncode == 0 and "OK" in proc.stdout, proc.stderr[-2000:]

    def test_feature_specification_is_the_raw_in_spec(self):
        """get_feature_specification returns what predict() actually
        validates: the preprocessor's raw in-spec filtered to required
        tensors (reference checkpoint_predictor.py:72-75,118-120) — not
        the model's post-preprocess packing spec."""
        from tensor2robot_tpu.specs.utils import flatten_spec_structure

        predictor = CheckpointPredictor(
            t2r_model=MockT2RModel(device_type="cpu")
        )
        predictor.init_randomly()
        spec = predictor.get_feature_specification()
        for key, item in flatten_spec_structure(spec).items():
            assert not getattr(item, "is_optional", False), key
        # Feeding exactly this spec works end to end.
        from tensor2robot_tpu.specs import make_random_numpy

        out = predictor.predict(make_random_numpy(spec, batch_size=3))
        assert out["a_predicted"].shape == (3, 1)

    def test_restore_times_out(self, tmp_path):
        predictor = CheckpointPredictor(
            t2r_model=MockT2RModel(device_type="cpu"),
            checkpoint_dir=str(tmp_path / "empty"),
            timeout=0,
        )
        assert not predictor.restore()


class TestSavedModelV2Family:
    """Explicit code-path vs signature-path predictors over one export
    (reference saved_model_v2_predictor.py:33-257)."""

    def test_signature_predictor_serves_stablehlo(self, trained, tmp_path):
        from tensor2robot_tpu.predictors import SavedModelSignaturePredictor

        path = _export(trained, str(tmp_path / "export"))
        predictor = SavedModelSignaturePredictor(path)  # specific version dir
        assert predictor.restore()
        x = np.random.RandomState(0).rand(3, 3).astype(np.float32)
        out = predictor.predict({"x": x})
        assert out["a_predicted"].shape == (3, 1)
        assert predictor.global_step >= 3
        assert predictor.model_path == path

    def test_signature_predictor_resolves_latest_from_root(self, trained, tmp_path):
        from tensor2robot_tpu.predictors import SavedModelSignaturePredictor

        root = str(tmp_path / "export")
        _export(trained, root)
        newest = _export(trained, root)
        predictor = SavedModelSignaturePredictor(root)
        assert predictor.restore()
        assert predictor.model_path == newest

    def test_signature_predictor_rejects_codeless_export(self, trained, tmp_path):
        from tensor2robot_tpu.predictors import SavedModelSignaturePredictor

        path = _export(trained, str(tmp_path / "export"), serialize_stablehlo=False)
        predictor = SavedModelSignaturePredictor(path)
        with pytest.raises(ValueError, match="no StableHLO signature"):
            predictor.restore()

    def test_code_predictor_matches_signature_predictor(self, trained, tmp_path):
        from tensor2robot_tpu.predictors import (
            SavedModelCodePredictor,
            SavedModelSignaturePredictor,
        )

        path = _export(trained, str(tmp_path / "export"))
        code = SavedModelCodePredictor(path, t2r_model=MockT2RModel(device_type="cpu"))
        sig = SavedModelSignaturePredictor(path)
        assert code.restore() and sig.restore()
        x = np.random.RandomState(1).rand(4, 3).astype(np.float32)
        np.testing.assert_allclose(
            code.predict({"x": x})["a_predicted"],
            sig.predict({"x": x})["a_predicted"],
            rtol=1e-5,
        )

    def test_code_predictor_serves_codeless_export(self, trained, tmp_path):
        from tensor2robot_tpu.predictors import SavedModelCodePredictor

        path = _export(trained, str(tmp_path / "export"), serialize_stablehlo=False)
        predictor = SavedModelCodePredictor(
            path, t2r_model=MockT2RModel(device_type="cpu")
        )
        assert predictor.restore()
        out = predictor.predict({"x": np.zeros((2, 3), np.float32)})
        assert out["a_predicted"].shape == (2, 1)

    def test_code_predictor_init_randomly(self):
        from tensor2robot_tpu.predictors import SavedModelCodePredictor

        predictor = SavedModelCodePredictor(
            "/nonexistent", t2r_model=MockT2RModel(device_type="cpu")
        )
        predictor.init_randomly()
        out = predictor.predict({"x": np.zeros((2, 3), np.float32)})
        assert out["a_predicted"].shape == (2, 1)

    def test_signature_predictor_restore_false_on_missing(self, tmp_path):
        from tensor2robot_tpu.predictors import SavedModelSignaturePredictor

        predictor = SavedModelSignaturePredictor(str(tmp_path / "nothing"))
        assert predictor.restore() is False
