"""Token-sequence models trained on packed documents: a hybrid state-space
/ attention language model, and a linear-attention / latent-attention
model with sparse experts.

`HybridSequenceLMModel` is the granite-4.0-h family's decoder
(`model_type: granitemoehybrid` without experts): Mamba-2 layers with a
grouped-query attention layer, without positional encoding, wherever
`layer_types` says "attention"; RMSNorm, a SwiGLU feed-forward in every
layer, a tied embedding and four scalar multipliers. Constructor
arguments carry the names of that family's `config.json` keys.

`KimiLinearLMModel` is the Kimi-Linear family's decoder (`model_type:
kimi_linear`): KDA gated-delta-rule layers (layers/kda.py) with a latent
attention layer without positions wherever `linear_attn_config` says so, a
dense SwiGLU in the leading layers and sigmoid-routed experts with a shared
expert in the others (layers/moe.RoutedExperts, told which experts it
holds), an untied head. Both models share the feature and label specs, the
jitted init, Adam and the packing contract; each has a block and a network
of its own, because nothing of one family's layer arguments is the
other's.

The packing contract (docs/SEQUENCE_MODELS.md): one example is one
sequence of `sequence_length` positions holding whole documents back to
back. `segment_ids` are >= 1 and change where a document starts; padding
after the last document has segment id 0 and `loss_mask` 0. At a document
boundary the recurrent state, the convolution's taps and attention all
stop. `targets` is the next token within the document, and the last
position of a document has `loss_mask` 0.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import flax
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.layers.kda import KDAMixer
from tensor2robot_tpu.layers.moe import RoutedExperts
from tensor2robot_tpu.layers.transformer import (
    HybridBlock,
    LatentAttention,
    RMSNorm,
    SwiGLU,
)
from tensor2robot_tpu.models import optimizers
from tensor2robot_tpu.models.abstract_model import MODE_TRAIN, FlaxT2RModel
from tensor2robot_tpu.specs import ExtendedTensorSpec, TensorSpecStruct


#: Positions a piece of the cross-entropy takes at once.
LOSS_CHUNK = 2048

#: What a block keeps of its forward for the backward pass, by
#: `checkpoint_name`: positions x width x 2 bytes a layer at bfloat16.
KEPT_RESIDUALS = (
    "mlp_gate",  # SwiGLU's W_gate x: width shared_intermediate_size
    "mlp_up",  # SwiGLU's W_up x: the same
    # Mamba-2's [z, xBC, dt]: width 2 x inner + 2 x groups x state + heads
    "mamba2_in_proj",
)

#: A block whose forward is recomputed in the backward pass, but for the
#: three products above: norms, convolution, scans, gated norm and the
#: attention mixer run a second time.
_RematBlock = nn.remat(
    HybridBlock,
    policy=jax.checkpoint_policies.save_only_these_names(*KEPT_RESIDUALS),
)


def chunked_cross_entropy(hidden, embedding, targets, loss_mask, *,
                          logits_scaling: float):
    """(sum of masked token losses, sum of the mask) of the tied head
    `hidden @ embedding^T / logits_scaling`, taken `LOSS_CHUNK` positions at
    a time and recomputed in the backward pass, so that no [S, V] float32
    array and its gradient are alive together."""
    head = embedding.astype(hidden.dtype)

    @jax.checkpoint
    def piece(h, y, m):
        logits = jnp.einsum(
            "bsd,vd->bsv", h, head, preferred_element_type=jnp.float32
        ) / logits_scaling
        picked = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - picked) * m)

    mask = loss_mask.astype(jnp.float32)
    seq = hidden.shape[1]
    total = sum(
        piece(hidden[:, i:i + LOSS_CHUNK], targets[:, i:i + LOSS_CHUNK],
              mask[:, i:i + LOSS_CHUNK])
        for i in range(0, seq, LOSS_CHUNK)
    )
    return total, jnp.sum(mask)


class _HybridLMNet(nn.Module):
    vocab_size: int
    hidden_size: int
    layer_types: Sequence[str]
    block: dict
    embedding_multiplier: float
    logits_scaling: float
    epsilon: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, features, mode, labels=None):
        del mode
        tokens, segment_ids = features["tokens"], features["segment_ids"]
        embedding = self.param(
            "embedding", nn.initializers.normal(0.02),
            (self.vocab_size, self.hidden_size),
        )
        h = (self.embedding_multiplier * embedding[tokens]).astype(self.dtype)
        for index, layer_type in enumerate(self.layer_types):
            h = _RematBlock(
                layer_type=layer_type, dtype=self.dtype, epsilon=self.epsilon,
                name=f"layer_{index}", **self.block,
            )(h, segment_ids)
        with jax.named_scope("lm_head"):
            h = RMSNorm(self.epsilon, name="final_norm")(h)
            if labels is None:
                logits = jnp.einsum(
                    "bsd,vd->bsv", h, embedding.astype(h.dtype),
                    preferred_element_type=jnp.float32,
                ) / self.logits_scaling
                return {"logits": logits, "inference_output": logits}
            total, count = chunked_cross_entropy(
                h, embedding, labels["targets"], labels["loss_mask"],
                logits_scaling=self.logits_scaling,
            )
        return {
            "loss": total / jnp.maximum(count, 1.0),
            "tokens": count,
            "pad_tokens": jnp.sum(segment_ids == 0).astype(jnp.float32),
        }


class _PackedLMModel(FlaxT2RModel):
    """What the packed-document language models share: int32 `tokens` and
    `segment_ids` [S], labels `targets` int32 [S] and `loss_mask` float32
    [S], no position input; a network that takes the labels and returns the
    loss with the step's counts; initialisers run under jit; Adam."""

    _NETWORK_TAKES_LABELS = True
    #: Keys of the network's output that go into a step's metrics.
    _COUNT_KEYS = ("tokens", "pad_tokens")

    def __init__(self, sequence_length, learning_rate, adam_b1, adam_b2,
                 adam_eps, **kwargs):
        super().__init__(**kwargs)
        self._sequence_length = sequence_length
        self._adam = dict(
            learning_rate=learning_rate, beta1=adam_b1, beta2=adam_b2,
            epsilon=adam_eps,
        )
        # bfloat16 where maybe_wrap_for_tpu wraps the model: there is no
        # float input whose dtype the network could follow.
        self._dtype = jnp.bfloat16 if self.is_device_tpu else jnp.float32

    def get_feature_specification(self, mode: str) -> TensorSpecStruct:
        del mode
        shape = (self._sequence_length,)
        return TensorSpecStruct(
            tokens=ExtendedTensorSpec(shape=shape, dtype=np.int32, name="tokens"),
            segment_ids=ExtendedTensorSpec(
                shape=shape, dtype=np.int32, name="segment_ids"
            ),
        )

    def get_label_specification(self, mode: str) -> TensorSpecStruct:
        del mode
        shape = (self._sequence_length,)
        return TensorSpecStruct(
            targets=ExtendedTensorSpec(shape=shape, dtype=np.int32, name="targets"),
            loss_mask=ExtendedTensorSpec(
                shape=shape, dtype=np.float32, name="loss_mask"
            ),
        )

    def init_variables(self, rng, features, mode=MODE_TRAIN):
        # Under jit only the initialisers run: the eager forward that flax's
        # init would make at 8k tokens is dead code to the compiler.
        example = jax.tree_util.tree_map(
            lambda leaf: jnp.zeros(leaf.shape, leaf.dtype), features
        )
        variables = jax.jit(
            lambda key, x: self.network.init(key, x, mode)
        )(rng, example)
        return flax.core.unfreeze(variables)

    def create_optimizer(self):
        if self._create_optimizer_fn is not None:
            return self._create_optimizer_fn()
        return optimizers.create_adam_optimizer(**self._adam)

    def model_train_fn(self, features, labels, inference_outputs, mode):
        del features, labels, mode
        return inference_outputs["loss"], {
            key: inference_outputs[key] for key in self._COUNT_KEYS
        }


class HybridSequenceLMModel(_PackedLMModel):
    """Next-token training of a Mamba-2 / attention hybrid on packed
    documents.

    Features `tokens`, `segment_ids` int32 [S]; labels `targets` int32 [S],
    `loss_mask` float32 [S]. There is no position input: the model has no
    positional encoding. `layer_types[:num_hidden_layers]` picks each
    layer's mixer. Every block is recomputed in the backward pass
    (`nn.remat`) but for `KEPT_RESIDUALS`, which a step keeps: SwiGLU's
    gate and up products in every layer and the Mamba-2 input projection
    (2 x positions x shared_intermediate_size x 2 bytes and positions x
    in_proj width x 2 bytes a layer at bfloat16; 3.9 GB at 8,192 positions
    x ten layers of granite-4.0-h-micro's widths). The scans and the
    attention mixer are still recomputed: the scans' decay matrices are
    four times that, and the attention kernel's residuals wait for a
    roofline that counts them (docs/SEQUENCE_MODELS.md). The loss is taken
    `LOSS_CHUNK` positions at a time.
    The step's metrics carry `tokens` (positions with a loss) and
    `pad_tokens` (positions of segment 0). With labels the network returns
    the loss; without (predict) the logits [S, vocab_size].
    """

    def __init__(
        self,
        vocab_size: int = 256,
        hidden_size: int = 64,
        shared_intermediate_size: int = 128,
        layer_types: Sequence[str] = ("mamba", "attention"),
        num_hidden_layers: Optional[int] = None,
        num_attention_heads: int = 4,
        num_key_value_heads: int = 2,
        attention_multiplier: float = 0.25,
        embedding_multiplier: float = 1.0,
        residual_multiplier: float = 1.0,
        logits_scaling: float = 1.0,
        rms_norm_eps: float = 1e-5,
        mamba_n_heads: int = 4,
        mamba_d_head: int = 32,
        mamba_d_state: int = 16,
        mamba_n_groups: int = 1,
        mamba_d_conv: int = 4,
        mamba_expand: int = 2,
        mamba_chunk_size: int = 256,
        sequence_length: int = 64,
        learning_rate: float = 3e-4,
        adam_b1: float = 0.9,
        adam_b2: float = 0.95,
        adam_eps: float = 1e-8,
        **kwargs,
    ):
        super().__init__(
            sequence_length, learning_rate, adam_b1, adam_b2, adam_eps, **kwargs
        )
        if mamba_n_heads * mamba_d_head != mamba_expand * hidden_size:
            raise ValueError(
                f"mamba_n_heads x mamba_d_head = {mamba_n_heads * mamba_d_head} "
                f"is not mamba_expand x hidden_size = {mamba_expand * hidden_size}"
            )
        depth = len(layer_types) if num_hidden_layers is None else num_hidden_layers
        if depth > len(layer_types):
            raise ValueError(
                f"num_hidden_layers={depth} over {len(layer_types)} layer_types"
            )
        self._vocab_size = vocab_size
        self._hidden_size = hidden_size
        self._layer_types = tuple(layer_types[:depth])
        self._embedding_multiplier = embedding_multiplier
        self._logits_scaling = logits_scaling
        self._epsilon = rms_norm_eps
        self._block = dict(
            num_heads=num_attention_heads,
            num_kv_heads=num_key_value_heads,
            head_dim=hidden_size // num_attention_heads,
            attention_multiplier=attention_multiplier,
            mlp_dim=shared_intermediate_size,
            mamba_heads=mamba_n_heads,
            mamba_head_dim=mamba_d_head,
            mamba_state=mamba_d_state,
            mamba_groups=mamba_n_groups,
            mamba_conv=mamba_d_conv,
            mamba_chunk=mamba_chunk_size,
            residual_multiplier=residual_multiplier,
        )

    def create_network(self) -> nn.Module:
        return _HybridLMNet(
            vocab_size=self._vocab_size,
            hidden_size=self._hidden_size,
            layer_types=self._layer_types,
            block=self._block,
            embedding_multiplier=self._embedding_multiplier,
            logits_scaling=self._logits_scaling,
            epsilon=self._epsilon,
            dtype=self._dtype,
        )


#: What a Kimi-Linear block keeps of its forward for the backward pass, by
#: the kind of its mixer and of its feed-forward (`checkpoint_name`s; the
#: rest of the block is recomputed). "kda_out" is the delta rule's output
#: (positions x 4,096 x 2 bytes a layer): with it kept the rule is not run
#: a third time, since it recomputes itself from its inputs for its own
#: backward (layers/kda.py). The others are wide products that are plain
#: matrix work to recompute, as far as the chip has room beside 7.2 GB of
#: state and a KDA layer's backward: the KDA layers' [q, k, v] product
#: (0.4 GB a layer at 16,384 positions) and latent attention's expanded
#: keys and values (a rank-512 product) do not fit, and carry no name.
KIMI_KEPT_RESIDUALS = {
    "kda": ("kda_out",),
    "mla": ("mla_q_proj",),
    "dense": ("mlp_gate", "mlp_up"),    # the leading layers' SwiGLU
    "moe": ("mlp_gate", "mlp_up"),      # the shared expert's
}


class KimiLinearBlock(nn.Module):
    """Pre-RMSNorm block whose mixer ("kda" or "mla") and feed-forward
    ("dense" or "moe") are both chosen per layer:

        u = h + Mixer(RMSNorm(h));  h_next = u + FFN(RMSNorm(u))

    Returns (h_next, the routed layer's counts or zeros: RoutedExperts).
    """

    mixer: str
    ffn: str
    heads: int
    kda_heads: int
    kda_head_dim: int
    kda_conv: int
    kda_chunk: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    kv_rank: int
    mlp_dim: int
    experts: dict
    epsilon: float = 1e-5
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, h: jax.Array, segment_ids: jax.Array):
        init = nn.initializers.normal(0.02)
        normed = RMSNorm(self.epsilon, name="norm_mixer")(h)
        if self.mixer == "kda":
            mixed = KDAMixer(
                num_heads=self.kda_heads, head_dim=self.kda_head_dim,
                conv_width=self.kda_conv, chunk_size=self.kda_chunk,
                epsilon=self.epsilon, dtype=self.dtype, name="mixer",
            )(normed, segment_ids)
        elif self.mixer == "mla":
            mixed = LatentAttention(
                num_heads=self.heads, qk_nope_dim=self.qk_nope_dim,
                qk_rope_dim=self.qk_rope_dim, v_dim=self.v_dim,
                kv_rank=self.kv_rank, epsilon=self.epsilon, dtype=self.dtype,
                kernel_init=init, name="mixer",
            )(normed, segment_ids)
        else:
            raise ValueError(f"no mixer {self.mixer!r}")
        u = h + mixed
        normed = RMSNorm(self.epsilon, name="norm_mlp")(u)
        counts = jnp.zeros((len(RoutedExperts.COUNT_NAMES),), jnp.float32)
        if self.ffn == "dense":
            out = SwiGLU(
                self.mlp_dim, dtype=self.dtype, kernel_init=init, name="mlp"
            )(normed)
        elif self.ffn == "moe":
            out, counts = RoutedExperts(
                dtype=self.dtype, name="moe", **self.experts
            )(normed)
        else:
            raise ValueError(f"no feed-forward {self.ffn!r}")
        return u + out, counts


@functools.lru_cache(maxsize=None)
def _kept_policy(kept: Tuple[str, ...]):
    """One policy object a set of names: jax keys what it derives from a
    jitted function under a `jax.checkpoint` (the delta rule's forward and
    backward programs, `layers/kda.kda_chunked`) on the policy's identity,
    so blocks that keep the same names must share it to share those."""
    return jax.checkpoint_policies.save_only_these_names(*kept)


@functools.lru_cache(maxsize=None)
def _remat_kimi_block(mixer: str, ffn: str):
    kept = KIMI_KEPT_RESIDUALS[mixer] + KIMI_KEPT_RESIDUALS[ffn]
    return nn.remat(KimiLinearBlock, policy=_kept_policy(kept))


class _KimiLinearLMNet(nn.Module):
    vocab_size: int
    hidden_size: int
    layers: Sequence[Tuple[str, str]]    # (mixer, ffn) of each layer
    block: dict
    epsilon: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, features, mode, labels=None):
        del mode
        tokens, segment_ids = features["tokens"], features["segment_ids"]
        init = nn.initializers.normal(0.02)
        embedding = self.param(
            "embedding", init, (self.vocab_size, self.hidden_size)
        )
        h = embedding[tokens].astype(self.dtype)
        counts = jnp.zeros((len(RoutedExperts.COUNT_NAMES),), jnp.float32)
        for index, (mixer, ffn) in enumerate(self.layers):
            h, routed = _remat_kimi_block(mixer, ffn)(
                mixer=mixer, ffn=ffn, dtype=self.dtype, epsilon=self.epsilon,
                name=f"layer_{index}", **self.block,
            )(h, segment_ids)
            counts = counts + routed
        with jax.named_scope("lm_head"):
            h = RMSNorm(self.epsilon, name="final_norm")(h)
            head = self.param(
                "lm_head", init, (self.vocab_size, self.hidden_size)
            )
            if labels is None:
                logits = jnp.einsum(
                    "bsd,vd->bsv", h, head.astype(h.dtype),
                    preferred_element_type=jnp.float32,
                )
                return {"logits": logits, "inference_output": logits}
            total, count = chunked_cross_entropy(
                h, head, labels["targets"], labels["loss_mask"],
                logits_scaling=1.0,
            )
        return {
            "loss": total / jnp.maximum(count, 1.0),
            "tokens": count,
            "pad_tokens": jnp.sum(segment_ids == 0).astype(jnp.float32),
            **dict(zip(RoutedExperts.COUNT_NAMES, counts)),
        }


class KimiLinearLMModel(_PackedLMModel):
    """Next-token training of a KDA / latent-attention model with routed
    experts on packed documents.

    Constructor arguments are the keys of the family's `config.json`.
    `linear_attn_config` gives the KDA layers' heads, head size and
    convolution width, and which layers (1-indexed) take KDA and which
    latent attention; the first `first_k_dense_replace` layers have a dense
    SwiGLU of `intermediate_size`, the others `num_shared_experts` shared
    experts and routed ones. **`num_experts` is what this chip holds** of a
    layer's `router_experts` (None: all), from `first_expert` on: the router
    scores all of them and takes `num_experts_per_token`, the layer adds the
    chosen experts it holds and leaves the others' part out
    (docs/SEQUENCE_MODELS.md). `kda_chunk_size` is the program's choice; no
    result depends on it.

    Every block is recomputed in the backward pass (`nn.remat`) but for
    `KIMI_KEPT_RESIDUALS`, by the block's kind. The step's metrics carry
    `tokens`, `pad_tokens` and the routed layers' counts summed over layers
    (`RoutedExperts.COUNT_NAMES`).
    """

    _COUNT_KEYS = _PackedLMModel._COUNT_KEYS + RoutedExperts.COUNT_NAMES

    def __init__(
        self,
        vocab_size: int = 256,
        hidden_size: int = 64,
        intermediate_size: int = 128,
        num_hidden_layers: int = 4,
        num_attention_heads: int = 4,
        linear_attn_config: Optional[dict] = None,
        first_k_dense_replace: int = 1,
        num_experts: int = 8,
        router_experts: Optional[int] = None,
        first_expert: int = 0,
        num_experts_per_token: int = 2,
        num_shared_experts: int = 1,
        moe_intermediate_size: int = 32,
        routed_scaling_factor: float = 1.0,
        moe_renormalize: bool = True,
        moe_router_activation_func: str = "sigmoid",
        num_expert_group: int = 1,
        topk_group: int = 1,
        kv_lora_rank: int = 32,
        q_lora_rank: Optional[int] = None,
        qk_nope_head_dim: int = 16,
        qk_rope_head_dim: int = 8,
        v_head_dim: int = 16,
        mla_use_nope: bool = True,
        rms_norm_eps: float = 1e-5,
        tie_word_embeddings: bool = False,
        kda_chunk_size: int = 64,
        sequence_length: int = 64,
        learning_rate: float = 3e-4,
        adam_b1: float = 0.9,
        adam_b2: float = 0.95,
        adam_eps: float = 1e-8,
        **kwargs,
    ):
        super().__init__(
            sequence_length, learning_rate, adam_b1, adam_b2, adam_eps, **kwargs
        )
        unsupported = {
            "q_lora_rank": q_lora_rank is not None,
            "mla_use_nope=False (rotary)": not mla_use_nope,
            "moe_renormalize=False": not moe_renormalize,
            "moe_router_activation_func": moe_router_activation_func != "sigmoid",
            "expert groups": (num_expert_group, topk_group) != (1, 1),
            "tie_word_embeddings": tie_word_embeddings,
        }
        if any(unsupported.values()):
            raise ValueError(
                "not supported: "
                + ", ".join(k for k, bad in unsupported.items() if bad)
            )
        linear = dict(linear_attn_config or {
            "kda_layers": [1, 2, 3], "full_attn_layers": [4], "num_heads": 4,
            "head_dim": 16, "short_conv_kernel_size": 4,
        })
        layers = []
        for number in range(1, num_hidden_layers + 1):
            if number in linear["kda_layers"]:
                mixer = "kda"
            elif number in linear["full_attn_layers"]:
                mixer = "mla"
            else:
                raise ValueError(f"linear_attn_config names no mixer for layer {number}")
            layers.append(
                (mixer, "dense" if number <= first_k_dense_replace else "moe")
            )
        router_experts = router_experts or num_experts
        if first_expert + num_experts > router_experts:
            raise ValueError(
                f"experts {first_expert}..{first_expert + num_experts} held of "
                f"{router_experts}"
            )
        self._vocab_size = vocab_size
        self._hidden_size = hidden_size
        self._layers = tuple(layers)
        self._epsilon = rms_norm_eps
        self._block = dict(
            heads=num_attention_heads,
            kda_heads=linear["num_heads"],
            kda_head_dim=linear["head_dim"],
            kda_conv=linear["short_conv_kernel_size"],
            kda_chunk=kda_chunk_size,
            qk_nope_dim=qk_nope_head_dim,
            qk_rope_dim=qk_rope_head_dim,
            v_dim=v_head_dim,
            kv_rank=kv_lora_rank,
            mlp_dim=intermediate_size,
            experts=flax.core.FrozenDict(
                num_experts=num_experts, router_experts=router_experts,
                first_expert=first_expert, hidden_dim=moe_intermediate_size,
                num_selected=num_experts_per_token,
                shared_experts=num_shared_experts,
                scaling=routed_scaling_factor,
            ),
        )

    def create_network(self) -> nn.Module:
        return _KimiLinearLMNet(
            vocab_size=self._vocab_size,
            hidden_size=self._hidden_size,
            layers=self._layers,
            block=self._block,
            epsilon=self._epsilon,
            dtype=self._dtype,
        )
