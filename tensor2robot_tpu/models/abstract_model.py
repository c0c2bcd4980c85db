"""Model abstraction: the central T2RModel contract, JAX-native.

A T2RModel declares its tensor specs and provides four pure hooks —
`inference_network_fn`, `model_train_fn`, `model_eval_fn`,
`create_export_outputs_fn` — from which the trainer derives jit/pjit-compiled
`init`/`train_step`/`eval_step`/`predict` functions. Parameters are explicit
pytrees (flax collections), never hidden graph state; device placement comes
from the mesh the trainer compiles against, not from the model.

Contract parity with the reference's AbstractT2RModel / ModelInterface
(tensor2robot/models/abstract_model.py:161-938, model_interface.py:48-146):
spec getters incl. *_for_packing variants, preprocessor ownership, device
typing, optimizer creation, warm-start hooks. What the reference composed in
`model_fn` (validate/pack -> network -> loss -> optimizer -> EstimatorSpec)
lives here as `make_train_model_fn` etc., consumed by train/train_eval.py.
"""

from __future__ import annotations

import abc
from typing import Any, Callable, Dict, Mapping, Optional, Tuple, Union

import flax
import jax
import jax.numpy as jnp
import optax

from tensor2robot_tpu.preprocessors import (
    AbstractPreprocessor,
    NoOpPreprocessor,
)
from tensor2robot_tpu.specs import TensorSpecStruct, validate_and_pack

MODE_TRAIN = "train"
MODE_EVAL = "eval"
MODE_PREDICT = "predict"

# Model variables are a dict of flax collections: {'params': ..., and
# optionally 'batch_stats': ... for batch-norm moving statistics}.
ModelVariables = Mapping[str, Any]


class ModelInterface(abc.ABC):
    """The minimal interface infra relies on (reference
    model_interface.py:48-146)."""

    @abc.abstractmethod
    def get_feature_specification(self, mode: str) -> TensorSpecStruct:
        ...

    @abc.abstractmethod
    def get_label_specification(self, mode: str) -> TensorSpecStruct:
        ...

    def get_feature_specification_for_packing(self, mode: str) -> TensorSpecStruct:
        """Spec used by policies to pack raw observations; defaults to the
        model in-spec (CEM critics override to drop the tiled action)."""
        return self.get_feature_specification(mode)

    def get_label_specification_for_packing(self, mode: str) -> TensorSpecStruct:
        return self.get_label_specification(mode)

    @property
    @abc.abstractmethod
    def preprocessor(self) -> AbstractPreprocessor:
        ...

    @property
    def device_type(self) -> str:
        return "cpu"

    @property
    def is_device_tpu(self) -> bool:
        return self.device_type == "tpu"

    @property
    def is_device_gpu(self) -> bool:
        return self.device_type == "gpu"


class AbstractT2RModel(ModelInterface):
    """Base model: subclass and implement the spec getters plus
    `inference_network_fn` and `model_train_fn`.

    Attributes:
      use_avg_model_params: maintain an EMA of params; checkpoints hold both
        and exports select the EMA (reference MovingAverageOptimizer +
        swapping saver, abstract_model.py:855-863).
      init_checkpoint: optional warm-start source (path or (path, filter_fn)).
    """

    def __init__(
        self,
        preprocessor_cls: Optional[Callable[..., AbstractPreprocessor]] = None,
        create_optimizer_fn: Optional[Callable[[], optax.GradientTransformation]] = None,
        device_type: str = "tpu",
        use_avg_model_params: bool = False,
        avg_model_params_decay: float = 0.9999,
        init_from_checkpoint_fn: Optional[Callable[[ModelVariables], ModelVariables]] = None,
        use_summaries: Optional[bool] = None,
    ):
        self._preprocessor_cls = preprocessor_cls
        self._create_optimizer_fn = create_optimizer_fn
        self._device_type = device_type
        self.use_avg_model_params = use_avg_model_params
        self.avg_model_params_decay = avg_model_params_decay
        self._init_from_checkpoint_fn = init_from_checkpoint_fn
        # Summaries default off on TPU (host transfers in the hot loop;
        # reference :873-893); scalars still flow via train metrics.
        self._use_summaries = (
            use_summaries if use_summaries is not None else device_type != "tpu"
        )

    # -- device / preprocessor ------------------------------------------------

    @property
    def device_type(self) -> str:
        return self._device_type

    @property
    def use_summaries(self) -> bool:
        return self._use_summaries

    @property
    def preprocessor(self) -> AbstractPreprocessor:
        if self._preprocessor_cls is not None:
            return self._preprocessor_cls(self)
        return NoOpPreprocessor(self)

    # -- parameter lifecycle --------------------------------------------------

    @abc.abstractmethod
    def init_variables(
        self, rng: jax.Array, features: TensorSpecStruct, mode: str = MODE_TRAIN
    ) -> ModelVariables:
        """Initializes model variables from example (or ShapeDtypeStruct)
        features. Flax models: `module.init(rng, features, mode)`."""

    def maybe_init_from_checkpoint(self, variables: ModelVariables) -> ModelVariables:
        """Warm-start hook: rewrite freshly-initialized variables from a
        foreign checkpoint (reference default_init_from_checkpoint_fn
        :86-126)."""
        if self._init_from_checkpoint_fn is not None:
            return self._init_from_checkpoint_fn(variables)
        return variables

    # -- the four hooks -------------------------------------------------------

    @abc.abstractmethod
    def inference_network_fn(
        self,
        variables: ModelVariables,
        features: TensorSpecStruct,
        mode: str,
        rng: Optional[jax.Array] = None,
        labels: Optional[TensorSpecStruct] = None,
    ) -> Tuple[TensorSpecStruct, ModelVariables]:
        """Pure forward pass. Returns (outputs, updated_mutable_collections);
        the second element carries e.g. new batch_stats in train mode and is
        {} when the model has no mutable state (reference
        inference_network_fn's optional update_ops tuple, :703-712).

        `labels` mirrors the reference's inference_network_fn(features,
        labels, ...) signature (:703): density-style heads (MDN/MAF decoders)
        emit their negative log-likelihood as an output tensor when labels
        are available, since the loss depends on network-internal params."""

    @abc.abstractmethod
    def model_train_fn(
        self,
        features: TensorSpecStruct,
        labels: TensorSpecStruct,
        inference_outputs: TensorSpecStruct,
        mode: str,
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Returns (scalar loss, {metric_name: scalar}) — the metrics dict
        replaces TF summaries as the observability channel.

        Metric values are normally scalars (or fixed-size vectors),
        averaged across gradient-accumulation microbatches. A metric whose
        value carries a leading BATCH dimension (per-example captures)
        must declare it by key prefix — `golden/` (see add_golden_tensor)
        or `per_example/` — so the trainer concatenates microbatch slices
        back to the full batch instead of averaging them."""

    def model_eval_fn(
        self,
        features: TensorSpecStruct,
        labels: TensorSpecStruct,
        inference_outputs: TensorSpecStruct,
    ) -> Dict[str, jax.Array]:
        """Per-batch eval statistics, averaged across batches by the
        evaluator. Defaults to the train loss/metrics."""
        loss, metrics = self.model_train_fn(
            features, labels, inference_outputs, MODE_EVAL
        )
        out = {"loss": loss}
        out.update(metrics)
        return out

    def create_export_outputs_fn(
        self,
        features: TensorSpecStruct,
        inference_outputs: TensorSpecStruct,
    ) -> TensorSpecStruct:
        """Selects the serving outputs; defaults to all inference outputs."""
        return inference_outputs

    # -- optimizer ------------------------------------------------------------

    def create_optimizer(self) -> optax.GradientTransformation:
        if self._create_optimizer_fn is not None:
            return self._create_optimizer_fn()
        from tensor2robot_tpu.models import optimizers

        return optimizers.create_adam_optimizer()

    # -- composed validated-forward (what model_fn composed in the reference) --

    def packed_inference(
        self,
        variables: ModelVariables,
        features,
        mode: str,
        labels=None,
        rng: Optional[jax.Array] = None,
    ) -> Tuple[TensorSpecStruct, Optional[TensorSpecStruct], TensorSpecStruct, ModelVariables]:
        """validate_and_pack features/labels against the model specs, run the
        network, return (features, labels, outputs, mutable_updates)."""
        packed_features = validate_and_pack(
            self.get_feature_specification(mode), features, ignore_batch=True
        )
        packed_labels = None
        if labels is not None:
            packed_labels = validate_and_pack(
                self.get_label_specification(mode), labels, ignore_batch=True
            )
        outputs, mutable = self.inference_network_fn(
            variables, packed_features, mode, rng, labels=packed_labels
        )
        return packed_features, packed_labels, outputs, mutable


class FlaxT2RModel(AbstractT2RModel):
    """T2RModel over a flax linen module.

    Subclasses implement `create_network() -> nn.Module` whose
    `__call__(features, mode)` consumes the packed feature struct; batch-norm
    moving stats live in the standard 'batch_stats' collection.
    """

    _MUTABLE_COLLECTIONS = ("batch_stats",)
    # Networks whose __call__ accepts (features, mode, labels) — e.g. models
    # with density-decoder heads — set this True to receive packed labels.
    _NETWORK_TAKES_LABELS = False

    @abc.abstractmethod
    def create_network(self) -> "flax.linen.Module":
        ...

    @property
    def network(self) -> "flax.linen.Module":
        # Flax modules are cheap immutable dataclasses; fresh instance per
        # access keeps the model object pickle-free and fork-safe.
        return self.create_network()

    def init_variables(self, rng, features, mode=MODE_TRAIN) -> ModelVariables:
        def make_zero(leaf):
            if isinstance(leaf, jax.ShapeDtypeStruct):
                return jnp.zeros(leaf.shape, leaf.dtype)
            return jnp.asarray(leaf)

        example = jax.tree_util.tree_map(make_zero, features)
        variables = self.network.init(rng, example, mode)
        return flax.core.unfreeze(variables)

    def _extra_mutable_collections(self, mode) -> tuple:
        """Extra flax collections to open during a TRAIN apply (beyond
        _MUTABLE_COLLECTIONS); subclasses whose networks sow auxiliary
        values (e.g. MoE router losses) name the collections here and
        consume them in `_postprocess_network_outputs`."""
        del mode
        return ()

    def _postprocess_network_outputs(self, outputs, updates, mode):
        """Hook between network.apply and the trainer: subclasses may move
        sown collection values from `updates` into `outputs` (anything
        left in `updates` is merged into the train state's variables).
        Receives mutable copies; returns (outputs, updates)."""
        del mode
        return outputs, updates

    def inference_network_fn(
        self, variables, features, mode, rng=None, labels=None
    ):
        mutable = [c for c in self._MUTABLE_COLLECTIONS if c in variables]
        if rng is not None:
            rng_dropout, rng_sample = jax.random.split(rng)
            rngs = {"dropout": rng_dropout, "sample": rng_sample}
        else:
            rngs = {}
        args = (features, mode)
        if self._NETWORK_TAKES_LABELS:
            args = (features, mode, labels)
        if mode == MODE_TRAIN:
            mutable = mutable + [
                c
                for c in self._extra_mutable_collections(mode)
                if c not in mutable
            ]
        if mode == MODE_TRAIN and mutable:
            outputs, updates = self.network.apply(
                variables, *args, mutable=mutable, rngs=rngs
            )
            return self._postprocess_network_outputs(
                dict(outputs), flax.core.unfreeze(updates), mode
            )
        outputs = self.network.apply(variables, *args, rngs=rngs)
        outputs, _ = self._postprocess_network_outputs(
            dict(outputs), {}, mode
        )
        return outputs, {}
