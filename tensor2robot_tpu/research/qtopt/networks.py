"""QT-Opt grasping Q-networks, flax-native.

Behavioral reference: tensor2robot/research/qtopt/networks.py:300-741
(`Grasping44FlexibleGraspParams` and the E2E open/close/terminate variant).
Architecture (the "Grasping44" tower):

  472x472x3 image
    -> conv 64@6x6 /2 (no norm) -> BN(scale=False) -> relu -> maxpool 3x3 /3
    -> 6x [conv 64@5x5 + BN + relu]            -> maxpool 3x3 /3   (pool2)
  grasp params (one Dense(256) per named block, summed)
    -> BN(scale=False) -> relu -> Dense(64)    -> context [B,1,1,64]
  merge: image embedding (+ CEM megabatch tiling) + context broadcast-add
    -> 6x [conv 64@3x3 + BN + relu]            -> maxpool 2x2 /2
    -> 3x [conv 64@3x3 VALID + BN + relu]                        (final_conv)
    -> flatten -> 2x Dense(64) -> Dense(1) logit -> sigmoid

TPU-first notes: the CEM action megabatch is tiled *after* the conv tower
(reference networks.py:412-421 + tile_batch at :522) so the expensive image
convs run once per state, not once per action sample — the tiled add and the
tail convs stay one large MXU-batched program. All convs are NHWC float
(bf16-friendly); batch-norm statistics live in flax's `batch_stats`.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensor2robot_tpu.layers.s2d_conv import SpaceToDepthConv, stem_s2d_enabled
from tensor2robot_tpu.ops import pooling

# Named grasp-param sub-blocks of the E2E variant: {name: (offset, size)}
# (reference networks.py:724-732). Separate per-block input projections.
E2E_GRASP_PARAM_BLOCKS: Dict[str, Tuple[int, int]] = {
    "fcgrasp_wv": (0, 3),
    "fcgrasp_vr": (3, 2),
    "fcgrasp_gripper_close": (5, 1),
    "fcgrasp_gripper_open": (6, 1),
    "fcgrasp_terminate_episode": (7, 1),
    "fcgrasp_gripper_closed": (8, 1),
    "fcgrasp_height_to_bottom": (9, 1),
}

_CONV_INIT = nn.initializers.truncated_normal(stddev=0.01)


class _ConvBNRelu(nn.Module):
    features: int
    kernel: Tuple[int, int]
    strides: Tuple[int, int] = (1, 1)
    padding: str = "SAME"
    momentum: float = 0.9997
    epsilon: float = 0.001
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x: jax.Array, is_training: bool) -> jax.Array:
        # Conv AND BatchNorm compute in `dtype` (bf16 on the TPU forward
        # path: params are cast for the MXU matmul, master copies stay
        # f32). Passing dtype to BN is statistics-safe — flax computes
        # batch mean/var internally in f32 regardless, and the running
        # stats live in f32 param storage — while keeping the normalized
        # activation in the compute dtype, so no f32 copy of the full
        # activation ever needs to reach HBM (at bs64/472px the stage-1
        # activation is 456 MB in bf16; an f32 normalize output doubles
        # the block's write traffic on the usual-bottleneck bandwidth).
        x = nn.Conv(
            self.features,
            self.kernel,
            strides=self.strides,
            padding=self.padding,
            use_bias=False,
            kernel_init=_CONV_INIT,
            dtype=self.dtype,
        )(x)
        x = nn.BatchNorm(
            use_running_average=not is_training,
            momentum=self.momentum,
            epsilon=self.epsilon,
            use_scale=True,
            dtype=self.dtype,
        )(x)
        return nn.relu(x)


class Grasping44(nn.Module):
    """The flexible-grasp-params Grasping44 Q-tower.

    Call with `images` [B, 472, 472, 3] and `grasp_params` either
    [B, P] (train/eval) or [B, N, P] (CEM megabatch; N = action_batch_size).
    Returns (logits, end_points) where end_points['predictions'] is
    sigmoid(logits), reshaped to [B, N] when action-tiled — matching the
    reference contract (networks.py:586-600).
    """

    grasp_param_blocks: Optional[Dict[str, Tuple[int, int]]] = None
    num_convs: Sequence[int] = (6, 6, 3)
    hid_layers: int = 2
    num_classes: int = 1
    # Reference batch_norm_decay=0.9997 (networks.py:45 slim arg_scope).
    batch_norm_momentum: float = 0.9997
    batch_norm_epsilon: float = 0.001
    # Conv-tower channel count. 64 is the reference architecture; 128 is
    # the MXU-width-aligned twin used to settle whether the 64-channel
    # tower (half the 128-lane systolic array width) caps achievable MFU
    # (docs/PERFORMANCE.md ceiling analysis). Not a reference knob.
    width: int = 64

    @nn.compact
    def __call__(
        self,
        images: jax.Array,
        grasp_params: jax.Array,
        is_training: bool = False,
        softmax: bool = False,
        goal_spatial: Optional[jax.Array] = None,
        goal_vector: Optional[jax.Array] = None,
    ):
        end_points: Dict[str, jax.Array] = {}
        tile_batch = grasp_params.ndim == 3
        action_batch_size = grasp_params.shape[1] if tile_batch else 1
        if tile_batch:
            # Collapse [B, N, P] -> [B*N, P] megabatch.
            grasp_params = grasp_params.reshape(-1, grasp_params.shape[-1])

        # Compute dtype follows the infeed: a bf16 image (the TPU wrapper's
        # train_in_bfloat16 policy) makes every conv/dense MXU op compute in
        # bf16 with f32 master params; f32 inputs keep the full-precision
        # path. BatchNorm always promotes to f32 (see _ConvBNRelu).
        dtype = jnp.bfloat16 if images.dtype == jnp.bfloat16 else None

        # BN computes in the network dtype (stats stay f32 inside flax;
        # see _ConvBNRelu) so no f32 copy of a full activation reaches
        # HBM — bn1's output is the largest activation in the network
        # ([B, 236, 236, 64] at 472px) and the round-3 profile showed its
        # f32 spill dominating the stem's bandwidth.
        bn_kwargs = dict(
            use_running_average=not is_training,
            momentum=self.batch_norm_momentum,
            epsilon=self.batch_norm_epsilon,
            dtype=dtype,
        )

        # Stem: conv without norm/activation, then a standalone unscaled BN
        # (reference keeps scale=False on the standalone BNs, :444-458).
        # The stem can lower via space-to-depth (layers/s2d_conv.py) — an
        # exact reformulation that fills the MXU's reduction lanes; both
        # lowerings share the checkpoint layout and the "conv1_1" name.
        if stem_s2d_enabled():
            net = SpaceToDepthConv(
                self.width, (6, 6), strides=(2, 2),
                kernel_init=_CONV_INIT, name="conv1_1", dtype=dtype,
            )(images)
        else:
            net = nn.Conv(
                self.width, (6, 6), strides=(2, 2), padding="SAME",
                use_bias=False, kernel_init=_CONV_INIT, name="conv1_1",
                dtype=dtype,
            )(images)
        net = nn.BatchNorm(use_scale=False, name="bn1", **bn_kwargs)(net)
        net = nn.relu(net)
        net = pooling.max_pool(net, (3, 3))

        for i in range(self.num_convs[0]):
            net = _ConvBNRelu(
                self.width, (5, 5),
                momentum=self.batch_norm_momentum,
                epsilon=self.batch_norm_epsilon,
                name=f"conv{2 + i}",
                dtype=dtype,
            )(net, is_training)
        net = pooling.max_pool(net, (3, 3))
        end_points["pool2"] = net

        # Grasp-param input head: one linear projection per named block,
        # summed (reference :470-502); unnamed params use a single block.
        if self.grasp_param_blocks is None:
            blocks = {"fcgrasp": (0, grasp_params.shape[-1])}
        else:
            blocks = self.grasp_param_blocks
        fcgrasp = None
        for name in sorted(blocks):
            offset, size = blocks[name]
            piece = nn.Dense(256, kernel_init=_CONV_INIT, name=name, dtype=dtype)(
                grasp_params[:, offset : offset + size]
            )
            fcgrasp = piece if fcgrasp is None else fcgrasp + piece
        fcgrasp = nn.BatchNorm(use_scale=False, name="bn_fcgrasp", **bn_kwargs)(
            fcgrasp
        )
        fcgrasp = nn.relu(fcgrasp)
        fcgrasp = nn.Dense(
            self.width, kernel_init=_CONV_INIT, name="fcgrasp2", dtype=dtype
        )(fcgrasp)
        fcgrasp = nn.BatchNorm(name="bn_fcgrasp2", **bn_kwargs)(fcgrasp)
        fcgrasp = nn.relu(fcgrasp)
        end_points["fcgrasp"] = fcgrasp
        context = fcgrasp.reshape(-1, 1, 1, self.width)
        if dtype is not None:
            context = context.astype(dtype)

        if tile_batch:
            # Tile the *embedding* (not the raw image) to the megabatch:
            # [B, h, w, c] -> [B*N, h, w, c] with each state repeated N times.
            net = jnp.repeat(net, action_batch_size, axis=0)
        net = net + context
        end_points["vsum"] = net

        for i in range(self.num_convs[1]):
            net = _ConvBNRelu(
                self.width, (3, 3),
                momentum=self.batch_norm_momentum,
                epsilon=self.batch_norm_epsilon,
                name=f"conv{2 + self.num_convs[0] + i}",
                dtype=dtype,
            )(net, is_training)
        net = pooling.max_pool(net, (2, 2))
        for i in range(self.num_convs[2]):
            net = _ConvBNRelu(
                self.width, (3, 3), padding="VALID",
                momentum=self.batch_norm_momentum,
                epsilon=self.batch_norm_epsilon,
                name=f"conv{2 + sum(self.num_convs[:2]) + i}",
                dtype=dtype,
            )(net, is_training)
        end_points["final_conv"] = net

        if goal_spatial is not None:
            reps = net.shape[0] // goal_spatial.shape[0]
            net = jnp.concatenate(
                [net, jnp.tile(goal_spatial, (reps, 1, 1, 1))], axis=3
            )
        net = net.reshape(net.shape[0], -1)
        if goal_vector is not None:
            reps = net.shape[0] // goal_vector.shape[0]
            net = jnp.concatenate([net, jnp.tile(goal_vector, (reps, 1))], axis=1)

        for i in range(self.hid_layers):
            net = nn.Dense(64, kernel_init=_CONV_INIT, name=f"fc{i}", dtype=dtype)(
                net
            )
            net = nn.BatchNorm(name=f"bn_fc{i}", **bn_kwargs)(net)
            net = nn.relu(net)

        # Logit head computes and emits float32: the loss-bearing scalar
        # (and the sigmoid CEM objective) should not quantize to bf16.
        logits = nn.Dense(
            self.num_classes, kernel_init=_CONV_INIT, name="logit"
        )(net.astype(jnp.float32))
        end_points["logits"] = logits
        predictions = (
            jax.nn.softmax(logits) if softmax else jax.nn.sigmoid(logits)
        )
        if tile_batch:
            if self.num_classes > 1:
                predictions = predictions.reshape(
                    -1, action_batch_size, self.num_classes
                )
            else:
                predictions = predictions.reshape(-1, action_batch_size)
        elif self.num_classes == 1:
            predictions = predictions.reshape(-1)
        end_points["predictions"] = predictions
        return logits, end_points


def concat_e2e_grasp_params(action: Dict[str, jax.Array]) -> jax.Array:
    """Packs the E2E action struct into the flat 10-dim grasp-params layout
    the block table indexes (reference create_grasp_params_input +
    grasp_param_sizes, networks.py:668-676)."""
    keys = (
        "world_vector",            # 3
        "vertical_rotation",       # 2
        "close_gripper",           # 1
        "open_gripper",            # 1
        "terminate_episode",       # 1
        "gripper_closed",          # 1
        "height_to_bottom",        # 1
    )
    return jnp.concatenate([action[k] for k in keys], axis=-1)
