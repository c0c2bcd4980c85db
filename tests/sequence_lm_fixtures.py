"""What the files of sequence-model tests share: a packing of documents
into two rows, a tiny model of each family and a batch (ISSUE 28, 32)."""

import importlib.util
import json
import os

import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.models.sequence_lm_models import (
    HybridSequenceLMModel,
    KimiLinearLMModel,
)
from tensor2robot_tpu.specs import TensorSpecStruct

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 64
# Two rows of documents back to back; 0 is padding.
LENGTHS = ((20, 9, 23, 12), (31, 5, 17))


def segments(lengths=LENGTHS, seq=SEQ):
    rows = []
    for row_lengths in lengths:
        row = np.concatenate(
            [np.full(n, i + 1, np.int32) for i, n in enumerate(row_lengths)]
        )
        rows.append(np.pad(row, (0, seq - len(row))))
    return np.stack(rows)


def spans(row):
    """[(start, stop)] of the documents of row `row`."""
    edges = np.cumsum((0,) + LENGTHS[row])
    return list(zip(edges[:-1], edges[1:]))


def one_segment(batch=2, seq=SEQ):
    return jnp.ones((batch, seq), jnp.int32)


def model(**overrides):
    kwargs = dict(
        vocab_size=96, hidden_size=64, shared_intermediate_size=128,
        layer_types=("mamba", "attention", "mamba"), num_attention_heads=4,
        num_key_value_heads=2, mamba_n_heads=4, mamba_d_head=32,
        mamba_d_state=16, mamba_chunk_size=16, sequence_length=SEQ,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=8.0, attention_multiplier=0.0625, device_type="cpu",
    )
    kwargs.update(overrides)
    return HybridSequenceLMModel(**kwargs)


def batch(seed=0):
    rng = np.random.RandomState(seed)
    ids = segments()
    tokens = rng.randint(0, 96, ids.shape).astype(np.int32)
    tokens[ids == 0] = 0
    targets = np.roll(tokens, -1, axis=1)
    ends = np.concatenate(
        [ids[:, 1:] != ids[:, :-1], np.ones((2, 1), bool)], axis=1
    )
    loss_mask = ((ids > 0) & ~ends).astype(np.float32)
    return (
        TensorSpecStruct({"tokens": tokens, "segment_ids": ids}),
        TensorSpecStruct({"targets": targets, "loss_mask": loss_mask}),
    )


KIMI_LINEAR = {
    "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4], "num_heads": 4,
    "head_dim": 16, "short_conv_kernel_size": 4,
}
KIMI_TINY = dict(
    vocab_size=96, hidden_size=64, intermediate_size=128, num_hidden_layers=5,
    num_attention_heads=4, linear_attn_config=KIMI_LINEAR, first_k_dense_replace=1,
    num_experts=4, router_experts=16, first_expert=0, num_experts_per_token=4,
    num_shared_experts=1, moe_intermediate_size=32, routed_scaling_factor=2.446,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    kda_chunk_size=16, sequence_length=SEQ, device_type="cpu",
)


def loss_fn(model, features, labels):
    """params -> the model's training loss on the batch."""
    def loss(params):
        outputs, _ = model.inference_network_fn(
            {"params": params}, features, "train", labels=labels
        )
        return model.model_train_fn(features, labels, outputs, "train")[0]

    return loss


def kimi_model(**overrides):
    return KimiLinearLMModel(**{**KIMI_TINY, **overrides})


def kimi_reference():
    """(the benchmark's plain reference of the Kimi-Linear share, its
    configuration at `KIMI_TINY`'s sizes)."""
    path = os.path.join(REPO, "benchmark", "reference", "kimi_linear_48b_a3b_s1.py")
    spec = importlib.util.spec_from_file_location("kimi_reference_for_tests", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    with open(os.path.join(REPO, "benchmark", "configs", "kimi_linear_48b_a3b_s1.json")) as f:
        config = json.load(f)
    keys = set(config["model"]) | {"kda_chunk_size"}
    config["model"] = {
        **config["model"], **{k: v for k, v in KIMI_TINY.items() if k in keys},
    }
    return module, config
