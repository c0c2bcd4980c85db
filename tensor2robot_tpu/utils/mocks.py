"""Mock models and input generators — the backbone of the test suite.

Parity with tensor2robot/utils/mocks.py: `MockT2RModel` is a 3-layer MLP
with batch norm over a 3-vector input predicting one logit;
`MockInputGenerator` emits a deterministic linearly-separable dataset so a
few hundred steps of training must converge (the reference's
train_eval_test gate).
"""

from __future__ import annotations

from typing import Iterator, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.data.input_generators import AbstractInputGenerator
from tensor2robot_tpu.models.base_models import ClassificationModel
from tensor2robot_tpu.specs import ExtendedTensorSpec, TensorSpecStruct

_FEATURE_DIM = 3


class _MockNetwork(nn.Module):
    """3-layer MLP + batch norm (mirrors the mock network's capacity)."""

    use_batch_norm: bool = True

    @nn.compact
    def __call__(self, features, mode: str):
        x = features["x"]
        if x.dtype == jnp.bfloat16:
            x = x.astype(jnp.float32)
        for width in (100, 100):
            x = nn.Dense(width)(x)
            if self.use_batch_norm:
                x = nn.BatchNorm(
                    use_running_average=(mode != "train"), momentum=0.9
                )(x)
            x = nn.relu(x)
        logit = nn.Dense(1)(x)
        out = TensorSpecStruct()
        out["a_predicted"] = logit
        return out


class MockT2RModel(ClassificationModel):
    """Minimal end-to-end-trainable model (reference mocks.py:99-189)."""

    def __init__(self, device_type: str = "cpu", use_batch_norm: bool = True,
                 multi_dataset: bool = False, **kwargs):
        super().__init__(device_type=device_type, **kwargs)
        self._use_batch_norm = use_batch_norm
        self._multi_dataset = multi_dataset

    def create_network(self):
        return _MockNetwork(use_batch_norm=self._use_batch_norm)

    def get_feature_specification(self, mode: str) -> TensorSpecStruct:
        spec = TensorSpecStruct()
        if self._multi_dataset:
            spec["x"] = ExtendedTensorSpec(
                shape=(_FEATURE_DIM,), dtype=np.float32, name="measured_position",
                dataset_key="dataset1",
            )
        else:
            spec["x"] = ExtendedTensorSpec(
                shape=(_FEATURE_DIM,), dtype=np.float32, name="measured_position"
            )
        return spec

    def get_label_specification(self, mode: str) -> TensorSpecStruct:
        spec = TensorSpecStruct()
        if self._multi_dataset:
            spec["a_target"] = ExtendedTensorSpec(
                shape=(1,), dtype=np.float32, name="valid_position",
                dataset_key="dataset2",
            )
        else:
            spec["a_target"] = ExtendedTensorSpec(
                shape=(1,), dtype=np.float32, name="valid_position"
            )
        return spec


class MockInputGenerator(AbstractInputGenerator):
    """Deterministic linearly-separable data: label = x0 + x1 + x2 > 0
    (reference mocks.py:43-96)."""

    def __init__(self, batch_size: int = 32, seed: int = 0):
        super().__init__(batch_size=batch_size)
        self._seed = seed

    def _create_dataset(self, mode: str) -> Iterator[TensorSpecStruct]:
        rng = np.random.RandomState(self._seed)
        while True:
            x = rng.uniform(-1.0, 1.0, size=(self._batch_size, _FEATURE_DIM))
            y = (x.sum(axis=1, keepdims=True) > 0).astype(np.float32)
            batch = TensorSpecStruct()
            batch["features/x"] = x.astype(np.float32)
            batch["labels/a_target"] = y
            yield batch

    def create_numpy_data(self, num_examples: int = 256):
        rng = np.random.RandomState(self._seed)
        x = rng.uniform(-1.0, 1.0, size=(num_examples, _FEATURE_DIM))
        y = (x.sum(axis=1, keepdims=True) > 0).astype(np.float32)
        return x.astype(np.float32), y
