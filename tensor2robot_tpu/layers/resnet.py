"""FiLM-capable ResNet (v1/v2, sizes 18-200), flax-native.

Behavioral reference: tensor2robot/layers/film_resnet_model.py:392-630
(Model) and tensor2robot/layers/resnet.py:99-210 (linear_film_generator,
resnet_model). Structure kept: fixed padding on strided convs, v2
pre-activation by default, FiLM as (1 + gamma) * x + beta applied after the
second batch norm of each block (pre-residual-add for v1, pre-ReLU for v2),
block strides [1, 2, 2, 2], channel widths num_filters * 2^i.

TPU notes: NHWC, bf16-safe; batch-norm stats live in the standard flax
'batch_stats' collection so the trainer's mutable-collection path applies.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensor2robot_tpu.layers.vision_layers import apply_film

_BLOCK_SIZES = {
    18: [2, 2, 2, 2],
    34: [3, 4, 6, 3],
    50: [3, 4, 6, 3],
    101: [3, 4, 23, 3],
    152: [3, 8, 36, 3],
    200: [3, 24, 36, 3],
}


def get_block_sizes(resnet_size: int) -> List[int]:
    if resnet_size not in _BLOCK_SIZES:
        raise ValueError(
            f"resnet_size {resnet_size} not in {sorted(_BLOCK_SIZES)}"
        )
    return _BLOCK_SIZES[resnet_size]


def _fixed_pad(x: jax.Array, kernel_size: int) -> jax.Array:
    """Explicit symmetric padding independent of input size (reference
    film_resnet_model.py:61-88) so strided convs stay shape-deterministic."""
    pad_total = kernel_size - 1
    pad_beg = pad_total // 2
    pad_end = pad_total - pad_beg
    return jnp.pad(x, ((0, 0), (pad_beg, pad_end), (pad_beg, pad_end), (0, 0)))


class _ConvFixedPadding(nn.Module):
    filters: int
    kernel_size: int
    strides: int = 1

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        if self.strides > 1:
            x = _fixed_pad(x, self.kernel_size)
        return nn.Conv(
            self.filters,
            (self.kernel_size, self.kernel_size),
            strides=(self.strides, self.strides),
            padding="SAME" if self.strides == 1 else "VALID",
            use_bias=False,
            kernel_init=nn.initializers.variance_scaling(
                2.0, "fan_out", "truncated_normal"
            ),
        )(x)


class _BatchNorm(nn.Module):
    @nn.compact
    def __call__(self, x: jax.Array, train: bool) -> jax.Array:
        return nn.BatchNorm(
            use_running_average=not train,
            momentum=0.997,
            epsilon=1e-5,
            name="bn",
        )(x)


class _Block(nn.Module):
    """One residual block; v1/v2 and plain/bottleneck variants
    (reference film_resnet_model.py:122-343)."""

    filters: int
    strides: int
    bottleneck: bool
    version: int
    use_projection: bool

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        train: bool,
        film_gamma_beta: Optional[jax.Array] = None,
    ) -> jax.Array:
        out_filters = self.filters * (4 if self.bottleneck else 1)
        shortcut = x

        if self.version == 2:
            x = nn.relu(_BatchNorm(name="preact_bn")(x, train))
            if self.use_projection:
                shortcut = _ConvFixedPadding(
                    out_filters, 1, self.strides, name="proj"
                )(x)
        elif self.use_projection:
            shortcut = _ConvFixedPadding(
                out_filters, 1, self.strides, name="proj"
            )(x)
            shortcut = _BatchNorm(name="proj_bn")(shortcut, train)

        if self.bottleneck:
            x = _ConvFixedPadding(self.filters, 1, 1, name="conv1")(x)
            x = nn.relu(_BatchNorm(name="bn1")(x, train))
            x = _ConvFixedPadding(self.filters, 3, self.strides, name="conv2")(x)
            x = _BatchNorm(name="bn2")(x, train)
            if self.version == 1:
                # FiLM at the filters-wide bn2 point for both versions. (The
                # reference nominally modulates v1-bottleneck after bn3, but
                # validates generator outputs at 2*filters —
                # film_resnet_model.py:600 — so that path could never run;
                # we keep the generator contract uniform instead.)
                x = apply_film(x, film_gamma_beta)
                x = nn.relu(x)
                x = _ConvFixedPadding(out_filters, 1, 1, name="conv3")(x)
                x = _BatchNorm(name="bn3")(x, train)
                return nn.relu(x + shortcut)
            x = apply_film(x, film_gamma_beta)
            x = nn.relu(x)
            x = _ConvFixedPadding(out_filters, 1, 1, name="conv3")(x)
            return x + shortcut

        x = _ConvFixedPadding(self.filters, 3, self.strides, name="conv1")(x)
        x = nn.relu(_BatchNorm(name="bn1")(x, train))
        x = _ConvFixedPadding(self.filters, 3, 1, name="conv2")(x)
        if self.version == 1:
            x = _BatchNorm(name="bn2")(x, train)
            x = apply_film(x, film_gamma_beta)
            return nn.relu(x + shortcut)
        x = _BatchNorm(name="bn2")(x, train)
        x = apply_film(x, film_gamma_beta)
        x = nn.relu(x)
        return x + shortcut


class LinearFilmGenerator(nn.Module):
    """Per-block-layer linear FiLM projections (reference
    layers/resnet.py:99-145). Returns film_gamma_betas[i][j]: [batch, 2C_i]
    or None when a block layer is disabled."""

    block_sizes: Sequence[int]
    filter_sizes: Sequence[int]
    enabled_block_layers: Optional[Sequence[bool]] = None

    @nn.compact
    def __call__(self, embedding: jax.Array) -> List[List[Optional[jax.Array]]]:
        if self.enabled_block_layers and len(self.enabled_block_layers) != len(
            self.block_sizes
        ):
            raise ValueError(
                f"Got {len(self.enabled_block_layers)} bools for"
                f" enabled_block_layers, expected {len(self.block_sizes)}"
            )
        film_gamma_betas: List[List[Optional[jax.Array]]] = []
        for i, num_blocks in enumerate(self.block_sizes):
            if self.enabled_block_layers and not self.enabled_block_layers[i]:
                film_gamma_betas.append([None] * num_blocks)
                continue
            out = nn.Dense(
                num_blocks * self.filter_sizes[i] * 2, name=f"film{i}"
            )(embedding)
            film_gamma_betas.append(list(jnp.split(out, num_blocks, axis=-1)))
        return film_gamma_betas


class ResNet(nn.Module):
    """ResNet with optional FiLM conditioning and intermediate endpoints.

    Call: `logits = model(images, train)` or
    `logits, endpoints = model(images, train, return_intermediate_values=True)`
    where endpoints holds 'initial_conv', 'initial_max_pool',
    'block_layer{1..4}', 'pre_final_pool', 'final_reduce_mean',
    'final_dense' (reference resnet.py:61-95 resnet_endpoints).
    """

    num_classes: int
    resnet_size: int = 50
    num_filters: int = 64
    kernel_size: int = 7
    conv_stride: int = 2
    first_pool_size: int = 3
    first_pool_stride: int = 2
    version: int = 2
    film_enabled_block_layers: Optional[Sequence[bool]] = None

    @property
    def bottleneck(self) -> bool:
        return self.resnet_size >= 50

    @nn.compact
    def __call__(
        self,
        images: jax.Array,
        train: bool = False,
        film_embedding: Optional[jax.Array] = None,
        return_intermediate_values: bool = False,
    ):
        block_sizes = get_block_sizes(self.resnet_size)
        block_strides = [1, 2, 2, 2]
        filter_sizes = [self.num_filters * (2**i) for i in range(len(block_sizes))]

        film_gamma_betas: List[List[Optional[jax.Array]]]
        if film_embedding is not None:
            film_gamma_betas = LinearFilmGenerator(
                block_sizes=block_sizes,
                filter_sizes=filter_sizes,
                enabled_block_layers=self.film_enabled_block_layers,
                name="film_generator",
            )(film_embedding)
        else:
            film_gamma_betas = [[None] * n for n in block_sizes]

        endpoints: Dict[str, jax.Array] = {}
        x = _ConvFixedPadding(
            self.num_filters, self.kernel_size, self.conv_stride,
            name="initial_conv",
        )(images)
        endpoints["initial_conv"] = x
        if self.version == 1:
            x = nn.relu(_BatchNorm(name="initial_bn")(x, train))
        if self.first_pool_size:
            x = nn.max_pool(
                x,
                (self.first_pool_size, self.first_pool_size),
                strides=(self.first_pool_stride, self.first_pool_stride),
                padding="SAME",
            )
        endpoints["initial_max_pool"] = x

        for i, num_blocks in enumerate(block_sizes):
            for j in range(num_blocks):
                x = _Block(
                    filters=filter_sizes[i],
                    strides=block_strides[i] if j == 0 else 1,
                    bottleneck=self.bottleneck,
                    version=self.version,
                    use_projection=(j == 0),
                    name=f"block_layer{i + 1}_block{j}",
                )(x, train, film_gamma_betas[i][j])
            endpoints[f"block_layer{i + 1}"] = x

        if self.version == 2:
            x = nn.relu(_BatchNorm(name="postact_bn")(x, train))
        endpoints["pre_final_pool"] = x
        x = jnp.mean(x, axis=(1, 2))
        endpoints["final_reduce_mean"] = x[:, None, None, :]
        x = nn.Dense(self.num_classes, name="final_dense")(x)
        endpoints["final_dense"] = x
        if return_intermediate_values:
            return x, endpoints
        return x


def get_resnet50_spatial(
    images: jax.Array,
    variables: Any,
    model: Optional[ResNet] = None,
    train: bool = False,
) -> jax.Array:
    """Spatial feature maps from the last block layer of a ResNet50
    (reference grasp2vec/resnet.py:538-559 get_resnet50_spatial)."""
    model = model or ResNet(num_classes=1, resnet_size=50)
    _, endpoints = model.apply(
        variables, images, train, return_intermediate_values=True
    )
    return endpoints["block_layer4"]
