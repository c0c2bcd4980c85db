"""What the files of sequence-model tests share: a packing of documents
into two rows, a tiny model of each family and a batch."""

import functools
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.models.sequence_lm_models import (
    DeepseekV3LMModel,
    HybridSequenceLMModel,
    KimiLinearLMModel,
)
from tensor2robot_tpu.ops import flash_attention
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


def kernel_path_names():
    """The `checkpoint_name`s in the gradient of the attention kernel path
    (`flash_attention._segment_kernel`), traced at bfloat16 and a length
    every tile divides: the tiny models take the einsum path, which names
    nothing."""
    seq = max(flash_attention.SEGMENT_KERNEL_BLOCKS.values())
    q = jax.ShapeDtypeStruct((1, seq, 2, 128), jnp.bfloat16)
    ids = jnp.ones((1, seq), jnp.int32)
    attend = functools.partial(
        flash_attention._segment_kernel, segment_ids=ids, scale=0.125)
    text = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2),
    ))(q, q, q))
    return set(re.findall(r"name\[name=(\w+)\]", text))


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


def _reference(name, tiny, extra=()):
    """(the benchmark's plain reference of configuration `name`, its
    configuration with the model keys it shares with `tiny` taken from it)."""
    path = os.path.join(REPO, "benchmark", "reference", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"{name}_reference_for_tests", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    with open(os.path.join(REPO, "benchmark", "configs", f"{name}.json")) as f:
        config = json.load(f)
    keys = set(config["model"]) | set(extra)
    config["model"] = {
        **config["model"], **{k: v for k, v in tiny.items() if k in keys},
    }
    return module, config


def kimi_reference():
    """(the benchmark's plain reference of the Kimi-Linear share, its
    configuration at `KIMI_TINY`'s sizes)."""
    return _reference("kimi_linear_48b_a3b_s1", KIMI_TINY, {"kda_chunk_size"})


#: Kanana-2-30B-A3B's pattern at tiny widths: five latent-attention layers,
#: the first dense, 4 of 16 experts held in the others, 4 a token, 2 shared.
DEEPSEEK_TINY = dict(
    vocab_size=96, hidden_size=64, intermediate_size=128, num_hidden_layers=5,
    num_attention_heads=4, first_k_dense_replace=1, n_routed_experts=4,
    router_experts=16, first_expert=0, num_experts_per_tok=4, n_shared_experts=2,
    moe_intermediate_size=32, routed_scaling_factor=2.448, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=16,
    rope_theta=1000000, sequence_length=SEQ, device_type="cpu",
)


def deepseek_model(**overrides):
    return DeepseekV3LMModel(**{**DEEPSEEK_TINY, **overrides})


def kanana_reference():
    """(the benchmark's plain reference of the Kanana-2-30B-A3B share, its
    configuration at `DEEPSEEK_TINY`'s sizes)."""
    return _reference("kanana_2_30b_a3b_s1", DEEPSEEK_TINY)
