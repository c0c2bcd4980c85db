"""Vision towers: conv feature extractors + pose heads, flax-native.

Behavioral reference: tensor2robot/layers/vision_layers.py:31-351
(BuildImagesToFeaturesModel / BuildFILMParams /
BuildImagesToFeaturesModelHighRes / BuildImageFeaturesToPoseModel).

Conventions kept from the reference: VALID-padded 3x3 convs, strides (2, 2,
1, 1, ...) over num_blocks, 32 channels per block, optional FiLM with
(1 + gamma) * x + beta applied pre-ReLU, final 1x1 conv to num_output_maps,
optional spatial softmax returning [x1..xN, y1..yN] feature points.
All convs are NHWC and bf16-safe; XLA maps them onto the MXU.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensor2robot_tpu.layers.spatial_softmax import spatial_softmax
from tensor2robot_tpu.ops import pooling


def apply_film(x: jax.Array, film_gamma_beta: Optional[jax.Array]) -> jax.Array:
    """FiLM modulation (1 + gamma) * x + beta with [batch, 2C] params
    (reference film_resnet_model.py:109-120)."""
    if film_gamma_beta is None:
        return x
    gamma, beta = jnp.split(film_gamma_beta[:, None, None, :], 2, axis=-1)
    return (1.0 + gamma) * x + beta


class FilmParams(nn.Module):
    """Linear FiLM generator (reference BuildFILMParams,
    vision_layers.py:163-183)."""

    film_output_size: int = 2 * 5 * 32

    @nn.compact
    def __call__(self, embedding: jax.Array) -> jax.Array:
        return nn.Dense(self.film_output_size, name="film")(embedding)


class ImagesToFeaturesNet(nn.Module):
    """Conv tower: images [B, H, W, C] in [0, 1] -> feature points or maps
    (reference BuildImagesToFeaturesModel, vision_layers.py:31-160).

    Returns (features, extra): with spatial softmax, features is
    [B, 2 * num_output_maps] and extra = {'softmax': maps}; without, features
    is the [B, h, w, num_output_maps] activation and extra = {}.
    """

    filter_size: int = 3
    num_blocks: int = 5
    num_output_maps: int = 32
    num_channels_per_block: int = 32
    use_spatial_softmax: bool = True
    normalizer: str = "layer_norm"  # 'layer_norm' | 'batch_norm' | 'none'

    def _normalize(self, x: jax.Array, train: bool, scale: bool, idx: str) -> jax.Array:
        if self.normalizer == "layer_norm":
            return nn.LayerNorm(use_scale=scale, name=f"norm_{idx}")(x)
        if self.normalizer == "batch_norm":
            return nn.BatchNorm(
                use_running_average=not train,
                momentum=0.99,
                epsilon=1e-4,
                use_scale=scale,
                name=f"norm_{idx}",
            )(x)
        return x

    @nn.compact
    def __call__(
        self,
        images: jax.Array,
        train: bool = False,
        film_output_params: Optional[jax.Array] = None,
    ):
        film_gamma_betas = [None] * self.num_blocks
        if film_output_params is not None:
            expected = 2 * self.num_blocks * self.num_channels_per_block
            if film_output_params.ndim != 2 or film_output_params.shape[-1] != expected:
                raise ValueError(
                    f"FiLM params shape {film_output_params.shape}, expected"
                    f" [batch, {expected}]"
                )
            film_gamma_betas = jnp.split(
                film_output_params, self.num_blocks, axis=-1
            )

        net = images
        for i in range(self.num_blocks):
            stride = 2 if i < 2 else 1
            net = nn.Conv(
                self.num_channels_per_block,
                (self.filter_size, self.filter_size),
                strides=(stride, stride),
                padding="VALID",
                use_bias=True,
                bias_init=nn.initializers.constant(0.01),
                kernel_init=nn.initializers.xavier_uniform(),
                name=f"conv{i + 2}",
            )(net)
            net = self._normalize(net, train, scale=False, idx=f"conv{i + 2}")
            net = apply_film(net, film_gamma_betas[i])
            net = nn.relu(net)

        net = nn.Conv(
            self.num_output_maps,
            (1, 1),
            padding="VALID",
            use_bias=True,
            bias_init=nn.initializers.constant(0.01),
            kernel_init=nn.initializers.xavier_uniform(),
            name="final_conv_1x1",
        )(net)
        net = self._normalize(net, train, scale=True, idx="final")
        net = nn.relu(net)
        if self.use_spatial_softmax:
            points, softmax = spatial_softmax(net)
            return points, {"softmax": softmax}
        return net, {}


class ImagesToFeaturesHighResNet(nn.Module):
    """Multi-resolution conv tower: block outputs at every scale are resized
    to the highest resolution and summed before the spatial softmax
    (reference BuildImagesToFeaturesModelHighRes, vision_layers.py:186-275;
    PI-GPS architecture, arXiv:1610.00529)."""

    filter_size: int = 3
    num_blocks: int = 5
    num_output_maps: int = 32

    @nn.compact
    def __call__(self, images: jax.Array, train: bool = False):
        block_outs = []
        net = nn.avg_pool(images, (2, 2), strides=(2, 2), padding="VALID")
        net = nn.Conv(
            16,
            (self.filter_size, self.filter_size),
            strides=(2, 2),
            padding="VALID",
            name="conv1",
        )(net)
        net = nn.relu(nn.LayerNorm(name="norm1")(net))
        net = nn.Conv(
            32,
            (self.filter_size, self.filter_size),
            padding="VALID",
            name="conv2",
        )(net)
        net = nn.relu(nn.LayerNorm(name="norm2")(net))
        block_outs.append(nn.Conv(32, (1, 1), name="conv2_1x1")(net))
        for i in range(1, self.num_blocks):
            net = pooling.max_pool(net, (2, 2), "VALID")
            net = nn.Conv(
                32,
                (self.filter_size, self.filter_size),
                padding="VALID",
                name=f"conv{i + 2}",
            )(net)
            net = nn.relu(nn.LayerNorm(name=f"norm{i + 2}")(net))
            block_outs.append(
                nn.Conv(32, (1, 1), name=f"conv{i + 2}_1x1")(net)
            )

        target_hw = block_outs[0].shape[1:3]
        resized = [
            jax.image.resize(
                b,
                (b.shape[0], target_hw[0], target_hw[1], b.shape[3]),
                method="nearest",
            )
            for b in block_outs
        ]
        net = sum(resized)
        net = nn.Conv(self.num_output_maps, (1, 1), name="final_conv_1x1")(net)
        points, softmax = spatial_softmax(net)
        return points, {"softmax": softmax}


class ImageFeaturesToPoseNet(nn.Module):
    """FC head mapping feature points (+aux input) to a pose vector, with the
    MAML-friendly learned bias transform (reference
    BuildImageFeaturesToPoseModel, vision_layers.py:278-351)."""

    num_outputs: Optional[int]
    aux_output_dim: int = 0
    hidden_dim: int = 100
    num_layers: int = 2
    bias_transform_size: int = 10

    @nn.compact
    def __call__(
        self,
        expected_feature_points: jax.Array,
        aux_input: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Optional[jax.Array]]:
        net = expected_feature_points
        if aux_input is not None:
            net = jnp.concatenate([net, aux_input], axis=1)
        if self.bias_transform_size > 0:
            bias_transform = self.param(
                "bias_transform",
                nn.initializers.constant(0.01),
                (self.bias_transform_size,),
            )
            tiled = jnp.broadcast_to(
                bias_transform, (net.shape[0], self.bias_transform_size)
            ).astype(net.dtype)
            net = jnp.concatenate([net, tiled], axis=1)
        dense_kwargs = dict(
            bias_init=nn.initializers.constant(0.01),
            kernel_init=nn.initializers.truncated_normal(stddev=0.01),
        )
        for layer_index in range(self.num_layers):
            net = nn.Dense(
                self.hidden_dim, name=f"pose_fc{layer_index}", **dense_kwargs
            )(net)
            net = nn.relu(nn.LayerNorm(name=f"pose_ln{layer_index}")(net))
        if self.num_outputs:
            net = nn.Dense(
                self.num_outputs, name=f"pose_fc{self.num_layers}", **dense_kwargs
            )(net)
        aux_output = None
        if self.aux_output_dim > 0:
            aux_output = nn.Dense(
                self.aux_output_dim, name="pose_fc_aux", **dense_kwargs
            )(expected_feature_points)
        return net, aux_output
