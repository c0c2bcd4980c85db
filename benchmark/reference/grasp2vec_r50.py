"""Plain reference of the Grasp2Vec train step (arXiv:1811.06964).

Written from the description of the model (the reference's
research/grasp2vec/grasp2vec_model.py, networks.py, resnet.py, losses.py),
in float32 `jax.numpy` / `lax.conv_general_dilated` at `highest` matmul
precision. It imports nothing of the program and takes nothing the program
made.

  three uint8 [B, 512, 640, 3] frames: pregrasp, postgrasp (scene), goal
    -> one 472x472 crop offset for the scene pair, one for the goal
       (shared by the whole batch), / 255
    -> left-right and up-down flips, one decision an image; the scene pair
       shares its decisions
  scene tower (pre and post as one batch of 2B) and goal tower, each a
  pre-activation ResNet-50 (`version=2`):
    -> conv 64@7x7 /2 (padded 3+3, VALID), maxpool 3x3 /2 SAME
    -> block layers of [3, 4, 6, 3] bottleneck blocks, widths 64..512 (x4
       out), strides [1, 2, 2, 2] on the first block's 3x3
       block: BN, relu -> (1x1 projection shortcut from here, first block)
              -> 1x1, BN, relu -> 3x3 (/s), BN, relu -> 1x1 -> + shortcut
    -> relu(block_layer4) is the spatial embedding, its mean over space
       the vector embedding [B, 2048]
  loss: bidirectional n-pairs over (pre - post, goal): softmax
  cross-entropy of the similarity matrix against the diagonal, both ways,
  plus 0.25 * 0.002 * (mean |a|^2 + mean |b|^2) each way.

BatchNorm is train-mode: the batch's own mean and (biased) variance,
epsilon 1e-5. Optimizer: Adam 1e-3, b1 0.9, b2 0.999, eps 1e-8.

Departures, each noted: (a) parameters are keyed by the path the program's
checkpoints use (`scene/resnet/block_layer1_block0/conv1/Conv_0/kernel`),
so that the benchmark can hand the same seeded weights to both sides; the
tree also carries the ResNet's `postact_bn` and `final_dense`, which the
embedding never reads (their gradient is exactly zero on both sides);
(b) the random numbers of crops and flips are drawn with `jax.random` in
the order the program's preprocessor documents; (c) the scene pair shares
its flips, as the program does on purpose (the published code flips every
image independently).

`quant`, where given, rounds every operand of every convolution and
matrix product to a lower precision: that is the control of `correct`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5
HIGHEST = lax.Precision.HIGHEST
BLOCKS = {18: (2, 2, 2, 2), 34: (3, 4, 6, 3), 50: (3, 4, 6, 3)}
STRIDES = (1, 2, 2, 2)
TOWERS = ("scene", "goal")
REG_LAMBDA = 0.002


def optimizer(config):
    return {"kind": "adam", "learning_rate": 1e-3, "b1": 0.9, "b2": 0.999,
            "eps": 1e-8}


def _layout(config):
    size = config["model"]["resnet_size"]
    return BLOCKS[size], size >= 50


def _block_shapes(prefix, cin, filters, bottleneck, projection):
    out = filters * (4 if bottleneck else 1)
    shapes = {
        f"{prefix}/preact_bn/bn/scale": ((cin,), "ones"),
        f"{prefix}/preact_bn/bn/bias": ((cin,), "zeros"),
    }
    if projection:
        shapes[f"{prefix}/proj/Conv_0/kernel"] = ((1, 1, cin, out), "normal")
    if bottleneck:
        convs = [("conv1", 1, cin, filters), ("conv2", 3, filters, filters),
                 ("conv3", 1, filters, out)]
        norms = [("bn1", filters), ("bn2", filters)]
    else:
        convs = [("conv1", 3, cin, filters), ("conv2", 3, filters, filters)]
        norms = [("bn1", filters), ("bn2", filters)]
    for name, k, a, b in convs:
        shapes[f"{prefix}/{name}/Conv_0/kernel"] = ((k, k, a, b), "normal")
    for name, width in norms:
        shapes[f"{prefix}/{name}/bn/scale"] = ((width,), "ones")
        shapes[f"{prefix}/{name}/bn/bias"] = ((width,), "zeros")
    return shapes, out


def param_shapes(config):
    """{checkpoint path: (shape, init)}."""
    blocks, bottleneck = _layout(config)
    shapes = {}
    for tower in TOWERS:
        root = f"{tower}/resnet"
        shapes[f"{root}/initial_conv/Conv_0/kernel"] = ((7, 7, 3, 64), "normal")
        cin = 64
        for i, count in enumerate(blocks):
            for j in range(count):
                block, cin = _block_shapes(
                    f"{root}/block_layer{i + 1}_block{j}", cin, 64 * 2 ** i,
                    bottleneck, projection=j == 0,
                )
                shapes.update(block)
        shapes[f"{root}/postact_bn/bn/scale"] = ((cin,), "ones")
        shapes[f"{root}/postact_bn/bn/bias"] = ((cin,), "zeros")
        shapes[f"{root}/final_dense/kernel"] = ((cin, 1), "normal")
        shapes[f"{root}/final_dense/bias"] = ((1,), "zeros")
    return shapes


def init_params(key, config):
    """Seeded weights: normal of variance 2 / fan_out, as the ResNet's
    convolutions are initialised; the last convolution of every block is
    scaled by the configuration's `residual_init_scale`, so that the
    residual stream and the embeddings stay of order one as they do in a
    trained network (at 1 the random towers' embeddings grow with depth,
    the n-pairs softmax saturates and every number compared turns
    chaotic). Traceable: the caller jits it."""
    _, bottleneck = _layout(config)
    last = "/conv3/" if bottleneck else "/conv2/"
    residual = float(config["model"].get("residual_init_scale", 1.0))
    params = {}
    for index, (path, (shape, init)) in enumerate(
        sorted(param_shapes(config).items())
    ):
        if init == "normal":
            fan_out = math.prod(shape[:-2]) * shape[-1]
            scale = residual if last in path else 1.0
            params[path] = scale * math.sqrt(2.0 / fan_out) * jax.random.normal(
                jax.random.fold_in(key, index), shape, jnp.float32
            )
        elif init == "ones":
            params[path] = jnp.ones(shape, jnp.float32)
        else:
            params[path] = jnp.zeros(shape, jnp.float32)
    return params


# -- preprocessing -------------------------------------------------------------


def _crop(images, key, size):
    """One offset for every image of `images`, drawn as the program draws
    it: rows in [0, slack), columns in [0, slack) (at least one choice)."""
    th, tw = size
    h, w = images[0].shape[1:3]
    key_h, key_w = jax.random.split(key)
    off_h = jax.random.randint(key_h, (), 0, max(h - th, 1))
    off_w = jax.random.randint(key_w, (), 0, max(w - tw, 1))
    return [
        lax.dynamic_slice(
            im, (0, off_h, off_w, 0), (im.shape[0], th, tw, im.shape[3])
        )
        for im in images
    ]


def _flips(image, key):
    key_lr, key_ud = jax.random.split(key)
    batch = image.shape[0]
    lr = jax.random.bernoulli(key_lr, shape=(batch,))[:, None, None, None]
    ud = jax.random.bernoulli(key_ud, shape=(batch,))[:, None, None, None]
    image = jnp.where(lr, image[:, :, ::-1, :], image)
    return jnp.where(ud, image[:, ::-1, :, :], image)


def preprocess(features, step_key, config):
    """uint8 frames -> (pre, post, goal) float32 [B, th, tw, 3] in [0, 1]."""
    size = tuple(config["model"]["image_size"])
    key_pre, _ = jax.random.split(step_key)
    key_scene, key_goal, key_flip = jax.random.split(key_pre, 3)
    pre, post = _crop(
        [features["pregrasp_image"], features["postgrasp_image"]],
        key_scene, size,
    )
    (goal,) = _crop([features["goal_image"]], key_goal, size)
    flip_keys = (key_flip, key_flip, jax.random.fold_in(key_flip, 1))
    return tuple(
        _flips(image.astype(jnp.float32) / 255.0, key)
        for image, key in zip((pre, post, goal), flip_keys)
    )


# -- network -------------------------------------------------------------------


def _identity(x):
    return x


def _conv(x, kernel, stride, quant):
    """Fixed padding on strided convolutions: (k - 1) split begin/end, then
    VALID; SAME at stride 1."""
    k = kernel.shape[0]
    if stride > 1:
        total = k - 1
        pad = [(total // 2, total - total // 2)] * 2
    else:
        pad = "SAME"
    return lax.conv_general_dilated(
        quant(x), quant(kernel), (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
    )


def _bn_relu(x, p, prefix):
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axis=axes)
    var = jnp.mean(jnp.square(x - mean), axis=axes)
    y = (x - mean) * lax.rsqrt(var + BN_EPS) * p[f"{prefix}/bn/scale"]
    return jax.nn.relu(y + p[f"{prefix}/bn/bias"])


def _block(x, p, prefix, stride, bottleneck, projection, quant):
    shortcut = x
    x = _bn_relu(x, p, f"{prefix}/preact_bn")
    if projection:
        shortcut = _conv(x, p[f"{prefix}/proj/Conv_0/kernel"], stride, quant)
    if bottleneck:
        x = _conv(x, p[f"{prefix}/conv1/Conv_0/kernel"], 1, quant)
        x = _bn_relu(x, p, f"{prefix}/bn1")
        x = _conv(x, p[f"{prefix}/conv2/Conv_0/kernel"], stride, quant)
        x = _bn_relu(x, p, f"{prefix}/bn2")
        x = _conv(x, p[f"{prefix}/conv3/Conv_0/kernel"], 1, quant)
    else:
        x = _conv(x, p[f"{prefix}/conv1/Conv_0/kernel"], stride, quant)
        x = _bn_relu(x, p, f"{prefix}/bn1")
        x = _conv(x, p[f"{prefix}/conv2/Conv_0/kernel"], 1, quant)
        x = _bn_relu(x, p, f"{prefix}/bn2")
    return x + shortcut


def tower(params, images, name, config, quant=None):
    """Vector embedding [B, C] of one tower."""
    quant = quant or _identity
    blocks, bottleneck = _layout(config)
    root = f"{name}/resnet"
    x = _conv(images, params[f"{root}/initial_conv/Conv_0/kernel"], 2, quant)
    x = lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )
    for i, count in enumerate(blocks):
        for j in range(count):
            x = _block(
                x, params, f"{root}/block_layer{i + 1}_block{j}",
                STRIDES[i] if j == 0 else 1, bottleneck, j == 0, quant,
            )
    return jnp.mean(jax.nn.relu(x), axis=(1, 2))


def _npairs(anchor, positive, quant):
    reg = 0.25 * REG_LAMBDA * (
        jnp.mean(jnp.sum(jnp.square(anchor), axis=1))
        + jnp.mean(jnp.sum(jnp.square(positive), axis=1))
    )
    similarity = jnp.dot(quant(anchor), quant(positive).T, precision=HIGHEST)
    log_p = jax.nn.log_softmax(similarity, axis=1)
    return -jnp.mean(jnp.diagonal(log_p)) + reg


def loss_fn(params, batch, step_key, config, quant=None):
    """Scalar training loss of one raw batch, as the train step sees it."""
    pre, post, goal = preprocess(batch["features"], step_key, config)
    scene = tower(
        params, jnp.concatenate([pre, post], axis=0), "scene", config, quant
    )
    pre_v, post_v = jnp.split(scene, 2, axis=0)
    goal_v = tower(params, goal, "goal", config, quant)
    pair_a, pair_b = pre_v - post_v, goal_v
    quant = quant or _identity
    return _npairs(pair_a, pair_b, quant) + _npairs(pair_b, pair_a, quant)
