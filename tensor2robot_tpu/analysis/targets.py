"""Registered model/preprocessor pairings the spec-flow pass checks.

Every shipped model family that a pipeline can be configured with should
have one entry here: `t2r-check` then proves its spec contract end to
end on every run. Registration is cheap — a name and a zero-argument
factory returning a constructed model (device_type='cpu' so the check
never wants an accelerator). Factories import lazily inside the lambda
so listing targets does not import every research package.

Contribution rule: a PR adding a model family adds a `register_target`
call (here, or at import time from the model's own module) — the
checker's coverage IS this table.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Sequence, Tuple

__all__ = ["CheckTarget", "register_target", "default_targets"]


@dataclasses.dataclass(frozen=True)
class CheckTarget:
    """One checkable pairing: the factory builds the model (which owns
    its preprocessor); `modes` are the modes to flow."""

    name: str
    factory: Callable[[], object]
    modes: Tuple[str, ...] = ("train", "eval")


_TARGETS: Dict[str, CheckTarget] = {}


def register_target(
    name: str,
    factory: Callable[[], object],
    modes: Sequence[str] = ("train", "eval"),
) -> CheckTarget:
    target = CheckTarget(name, factory, tuple(modes))
    _TARGETS[name] = target
    return target


def _qtopt_grasping44():
    from tensor2robot_tpu.research.qtopt.t2r_models import (
        Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom,
    )

    # Full reference geometry: eval_shape only traces, so the 472x472
    # contract (and its 512x640 jpeg source + decode-ROI crop) is checked
    # at the real production shapes.
    return Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom(
        device_type="cpu"
    )


def _transformer_bc():
    from tensor2robot_tpu.models.transformer_models import TransformerBCModel

    # use_flash=False: the flash kernel is a TPU lowering; the abstract
    # checker must trace on any host.
    return TransformerBCModel(
        action_size=7,
        pose_size=14,
        episode_length=8,
        image_size=(64, 64),
        use_flash=False,
        device_type="cpu",
    )


def _hybrid_sequence_lm():
    from tensor2robot_tpu.models.sequence_lm_models import (
        HybridSequenceLMModel,
    )

    # Small widths, the family's layer pattern: the spec contract (int32
    # tokens and segment ids, no position input) does not depend on size.
    return HybridSequenceLMModel(
        layer_types=("mamba", "attention", "mamba"),
        sequence_length=64,
        mamba_chunk_size=16,
        device_type="cpu",
    )


def _kimi_linear_lm():
    from tensor2robot_tpu.models.sequence_lm_models import KimiLinearLMModel

    # The defaults are a small model of the family's pattern (three KDA
    # layers and a latent-attention one, the first dense, the others with 8
    # experts): the spec contract is the hybrid model's.
    return KimiLinearLMModel(
        sequence_length=64, kda_chunk_size=16, device_type="cpu"
    )


def _deepseek_v3_lm():
    from tensor2robot_tpu.models.sequence_lm_models import DeepseekV3LMModel

    # The defaults are a small model of the family's pattern (three
    # rotary latent-attention layers, the first dense, the others with 8
    # experts): the spec contract is the hybrid model's.
    return DeepseekV3LMModel(sequence_length=64, device_type="cpu")


def _mock_noop():
    from tensor2robot_tpu.utils.mocks import MockT2RModel

    return MockT2RModel()


register_target("qtopt-grasping44", _qtopt_grasping44)
register_target("transformer-bc", _transformer_bc)
register_target("hybrid-sequence-lm", _hybrid_sequence_lm)
register_target("kimi-linear-lm", _kimi_linear_lm)
register_target("deepseek-v3-lm", _deepseek_v3_lm)
register_target("mock-noop", _mock_noop)
# The policy server's request path: predict-mode specs are what the
# server's submit() validates against and what the micro-batcher stacks
# into bucket batches; flowing preprocess -> inference in predict mode
# is the static twin of request -> batch -> predict.
register_target("mock-serving", _mock_noop, modes=("predict",))


def default_targets() -> List[CheckTarget]:
    return [_TARGETS[name] for name in sorted(_TARGETS)]
