"""Token-sequence models: a hybrid state-space / attention language model
trained on packed documents.

`HybridSequenceLMModel` is the granite-4.0-h family's decoder
(`model_type: granitemoehybrid` without experts): Mamba-2 layers with a
grouped-query attention layer, without positional encoding, wherever
`layer_types` says "attention"; RMSNorm, a SwiGLU feed-forward in every
layer, a tied embedding and four scalar multipliers. Constructor
arguments carry the names of that family's `config.json` keys.

The packing contract (docs/SEQUENCE_MODELS.md): one example is one
sequence of `sequence_length` positions holding whole documents back to
back. `segment_ids` are >= 1 and change where a document starts; padding
after the last document has segment id 0 and `loss_mask` 0. At a document
boundary the recurrent state, the convolution's taps and attention all
stop. `targets` is the next token within the document, and the last
position of a document has `loss_mask` 0.
"""

from __future__ import annotations

from typing import Optional, Sequence

import flax
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.layers.transformer import HybridBlock, RMSNorm
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


class HybridSequenceLMModel(FlaxT2RModel):
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

    _NETWORK_TAKES_LABELS = True

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
        super().__init__(**kwargs)
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
        self._sequence_length = sequence_length
        self._embedding_multiplier = embedding_multiplier
        self._logits_scaling = logits_scaling
        self._epsilon = rms_norm_eps
        self._adam = dict(
            learning_rate=learning_rate, beta1=adam_b1, beta2=adam_b2,
            epsilon=adam_eps,
        )
        # bfloat16 where maybe_wrap_for_tpu wraps the model: there is no
        # float input whose dtype the network could follow.
        self._dtype = jnp.bfloat16 if self.is_device_tpu else jnp.float32
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
            "tokens": inference_outputs["tokens"],
            "pad_tokens": inference_outputs["pad_tokens"],
        }
