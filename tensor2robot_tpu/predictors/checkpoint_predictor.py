"""CheckpointPredictor: serve straight from training checkpoints.

Rebuilds the predict fn from model code and polls the trainer's orbax
checkpoint directory for new steps — the robot-side view of a learner that
checkpoints but has not (yet) exported. Parity with the reference
predictors/checkpoint_predictor.py:36-214 (fresh-graph rebuild, polling
`latest_checkpoint` restore with timeout, random init for tests).
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, Mapping, Optional

import jax
import numpy as np

from tensor2robot_tpu.config import configurable
from tensor2robot_tpu.predictors.abstract_predictor import AbstractPredictor
from tensor2robot_tpu.specs import TensorSpecStruct, flatten_spec_structure
from tensor2robot_tpu.train import state as state_lib


@configurable("CheckpointPredictor")
class CheckpointPredictor(AbstractPredictor):
    """Serves a T2RModel from the newest checkpoint under model_dir."""

    def __init__(
        self,
        t2r_model,
        checkpoint_dir: Optional[str] = None,
        timeout: int = 600,
        use_ema: Optional[bool] = None,
    ):
        """Args:
        t2r_model: the model whose predict path to serve.
        checkpoint_dir: the trainer's model_dir (its checkpoints/ subdir is
          polled). Optional when only init_randomly will be used.
        timeout: seconds restore() busy-waits for a first checkpoint.
        use_ema: serve averaged params; defaults to the model's
          use_avg_model_params (swapping-saver parity).
        """
        from tensor2robot_tpu.train.train_eval import CompiledModel, maybe_wrap_for_tpu

        self._model = maybe_wrap_for_tpu(t2r_model)
        self._compiled = CompiledModel(self._model, donate_state=False)
        self._checkpoint_dir = checkpoint_dir
        self._timeout = timeout
        self._use_ema = (
            use_ema
            if use_ema is not None
            else getattr(self._model, "use_avg_model_params", False)
        )
        self._feature_spec = self._compiled.preprocessor.get_in_feature_specification(
            "predict"
        )
        self._variables = None
        self._restored_step = -1
        self._template_state = None

    # -- state template -------------------------------------------------------

    def _example_features(self) -> TensorSpecStruct:
        from tensor2robot_tpu.specs import make_constant_numpy

        flat = make_constant_numpy(self._feature_spec, batch_size=1)
        return TensorSpecStruct(dict(flat.items()))

    def _get_template_state(self):
        """An abstract TrainState matching the trainer's checkpoint layout."""
        if self._template_state is None:
            features, _ = self._compiled.preprocessor.preprocess(
                self._example_features(), None, mode="predict", rng=None
            )
            self._template_state = self._compiled_init_state(features)
        return self._template_state

    def _compiled_init_state(self, features):
        from tensor2robot_tpu.train.state import create_train_state

        return create_train_state(
            self._model, jax.random.PRNGKey(0), features, self._compiled.optimizer
        )

    # -- restore --------------------------------------------------------------

    def restore(self, is_async: bool = False) -> bool:
        del is_async  # Checkpoint reload is fast; always synchronous.
        if self._checkpoint_dir is None:
            raise ValueError("CheckpointPredictor needs checkpoint_dir to restore.")
        import orbax.checkpoint as ocp

        path = os.path.abspath(os.path.join(self._checkpoint_dir, "checkpoints"))
        start = time.time()
        while True:
            latest = None
            if os.path.isdir(path):
                with ocp.CheckpointManager(path) as manager:
                    # Durable steps only (read-only skip, never quarantine):
                    # this predictor polls a LIVE trainer's dir, where
                    # latest_step() can name a torn final-named dir — the
                    # durability contract (docs/RESILIENCE.md) says no
                    # reader ever loads one. durability (not train_eval):
                    # it is orbax/jax-free, so this serving-side poll
                    # does not drag in the training stack.
                    from tensor2robot_tpu.train.durability import (
                        latest_durable_step_in,
                    )

                    latest = latest_durable_step_in(manager)
                    if latest is not None and latest != self._restored_step:
                        # Restore against the checkpoint's OWN metadata with
                        # host-placed leaves (train/state.py): serving must
                        # depend neither on the trainer's topology (whose
                        # sharding file a template-less restore replays) nor
                        # on its optimizer layout (per-leaf vs
                        # optax.flatten). Fall back to the model-derived
                        # template — exact for same-config trainers — only
                        # if metadata probing fails.
                        from tensor2robot_tpu.train.state import (
                            checkpoint_metadata_template,
                        )

                        try:
                            abstract = checkpoint_metadata_template(
                                path, latest
                            )
                        except Exception:  # noqa: BLE001 — best-effort
                            state = self._get_template_state()
                            abstract = jax.tree_util.tree_map(
                                lambda x: jax.ShapeDtypeStruct(
                                    x.shape, x.dtype
                                ),
                                state,
                            )
                        restored = manager.restore(
                            latest, args=ocp.args.StandardRestore(abstract)
                        )
                        # Metadata-derived restore yields the raw on-disk
                        # dict; the model-template fallback yields a
                        # TrainState. Both carry the same fields.
                        if isinstance(restored, dict):
                            variables = dict(restored["variables"])
                            if (
                                self._use_ema
                                and restored.get("ema_params") is not None
                            ):
                                # ema_as_tree: a quantized ZeRO-2
                                # checkpoint stores the EMA as one
                                # block-padded 1-D vector, not a tree.
                                variables["params"] = state_lib.ema_as_tree(
                                    restored["ema_params"],
                                    variables["params"],
                                )
                            self._variables = variables
                        else:
                            self._variables = restored.export_variables(
                                use_ema=self._use_ema
                            )
                        self._restored_step = int(latest)
                        return True
            if latest is not None and latest == self._restored_step:
                return True
            if time.time() - start > self._timeout:
                return False
            time.sleep(2.0)

    def init_randomly(self) -> None:
        features, _ = self._compiled.preprocessor.preprocess(
            self._example_features(), None, mode="predict", rng=None
        )
        variables = self._model.init_variables(jax.random.PRNGKey(0), features)
        self._variables = variables
        self._restored_step = 0

    # -- predict --------------------------------------------------------------

    def predict(self, features: Mapping[str, Any]) -> Dict[str, Any]:
        self.assert_is_loaded()
        struct = TensorSpecStruct(
            {k: np.asarray(v) for k, v in flatten_spec_structure(features).items()}
        )
        preprocessed, _ = self._compiled.preprocessor.preprocess(
            struct, None, mode="predict", rng=None
        )
        outputs = self._compiled.predict_step(self._variables, preprocessed)
        return {
            key: np.asarray(value)
            for key, value in flatten_spec_structure(outputs).items()
        }

    # -- introspection --------------------------------------------------------

    def get_feature_specification(self) -> TensorSpecStruct:
        """The client-facing input contract: the preprocessor's RAW in-spec
        (what predict() itself validates), filtered to required tensors —
        reference predictors/checkpoint_predictor.py:72-75,118-120. The
        model's packed spec describes the post-preprocess network input and
        is NOT what a caller feeds."""
        from tensor2robot_tpu.specs.utils import (
            filter_required_flat_tensor_spec,
        )

        return filter_required_flat_tensor_spec(self._feature_spec)

    @property
    def model_version(self) -> int:
        return self._restored_step

    @property
    def global_step(self) -> int:
        return self._restored_step

    @property
    def model_path(self) -> Optional[str]:
        if self._checkpoint_dir is None or self._restored_step < 0:
            return None
        return os.path.join(
            self._checkpoint_dir, "checkpoints", str(self._restored_step)
        )
