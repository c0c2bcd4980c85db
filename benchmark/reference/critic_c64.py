"""Plain reference of the QT-Opt Grasping44 critic's train step.

Written from the description of the network (arXiv:1806.10293 and the
reference's research/qtopt/networks.py + t2r_models.py), in float32
`jax.numpy` / `lax.conv_general_dilated` at `highest` matmul precision.
It imports nothing of the program and takes nothing the program made.

  uint8 [B, 512, 640, 3]
    -> random 472x472 crop (one offset per image), / 255
    -> brightness, saturation, hue, contrast distortion, clip to [0, 1]
    -> conv 64@6x6 /2 SAME, BN (no scale), relu, maxpool 3x3 /3 SAME
    -> 6 x [conv 64@5x5 SAME, BN, relu], maxpool 3x3 /3
  action [B, 10] in 7 named blocks
    -> one dense(256) per block, summed; BN (no scale), relu
    -> dense(64), BN, relu -> context [B, 1, 1, 64]
  image embedding + context (broadcast add)
    -> 6 x [conv 64@3x3 SAME, BN, relu], maxpool 2x2 /2
    -> 3 x [conv 64@3x3 VALID, BN, relu]
    -> flatten -> 2 x [dense(64), BN, relu] -> dense(1) logit
  loss: mean sigmoid cross-entropy of the logit against the reward.

BatchNorm is train-mode: the batch's own mean and (biased) variance,
epsilon 0.001. Optimizer: momentum 0.9, learning rate 1e-4.

Departures, each noted: (a) parameters are keyed by the path the program's
checkpoints use (`grasping44/conv2/Conv_0/kernel`), so that the benchmark
can hand the same seeded weights to both sides; (b) the random numbers of
the crop and the distortion are drawn with `jax.random` in the order the
program's preprocessor documents (fold the step into the key, split into
preprocessor and network keys, ...), because the two sides can only be
compared on the same augmented images; (c) a crop the host already made
(decode-time ROI in the fed cell) is taken as given: an image that arrives
at 472x472 is not cropped again.

`quant`, where given, rounds every operand of every convolution and
matrix product to a lower precision: that is the control of `correct`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

PREFIX = "grasping44"
ACTION_BLOCKS = {
    "fcgrasp_wv": (0, 3),
    "fcgrasp_vr": (3, 2),
    "fcgrasp_gripper_close": (5, 1),
    "fcgrasp_gripper_open": (6, 1),
    "fcgrasp_terminate_episode": (7, 1),
    "fcgrasp_gripper_closed": (8, 1),
    "fcgrasp_height_to_bottom": (9, 1),
}
ACTION_KEYS = (
    "world_vector", "vertical_rotation", "close_gripper", "open_gripper",
    "terminate_episode", "gripper_closed", "height_to_bottom",
)
BN_EPS = 1e-3
HIGHEST = lax.Precision.HIGHEST


def optimizer(config):
    return {"kind": "momentum", "learning_rate": 1e-4, "momentum": 0.9}


def _conv_names(config):
    n1, n2, n3 = config["model"]["num_convs"]
    names = [(f"conv{2 + i}", 5, "SAME") for i in range(n1)]
    names += [(f"conv{2 + n1 + i}", 3, "SAME") for i in range(n2)]
    names += [(f"conv{2 + n1 + n2 + i}", 3, "VALID") for i in range(n3)]
    return names, n1, n2


def _final_hw(config):
    """Spatial size after the tower, from the layer arithmetic."""
    h, w = config["model"]["image_size"]
    names, _, _ = _conv_names(config)

    def down(size):
        size = -(-size // 2)        # stem, stride 2, SAME
        size = -(-size // 3)        # pool 3x3 /3 SAME
        size = -(-size // 3)        # pool 3x3 /3 SAME
        size = -(-size // 2)        # pool 2x2 /2 SAME
        return size - 2 * sum(1 for _, _, pad in names if pad == "VALID")

    return down(h), down(w)


def param_shapes(config):
    """{checkpoint path: (shape, init)}; init is 'normal', 'zeros', 'ones'."""
    width = config["model"]["width"]
    shapes = {
        "conv1_1/kernel": ((6, 6, 3, width), "normal"),
        "bn1/bias": ((width,), "zeros"),
    }
    cin = width
    for name, k, _ in _conv_names(config)[0]:
        shapes[f"{name}/Conv_0/kernel"] = ((k, k, cin, width), "normal")
        shapes[f"{name}/BatchNorm_0/scale"] = ((width,), "ones")
        shapes[f"{name}/BatchNorm_0/bias"] = ((width,), "zeros")
    for name, (_, size) in ACTION_BLOCKS.items():
        shapes[f"{name}/kernel"] = ((size, 256), "normal")
        shapes[f"{name}/bias"] = ((256,), "zeros")
    shapes["bn_fcgrasp/bias"] = ((256,), "zeros")
    shapes["fcgrasp2/kernel"] = ((256, width), "normal")
    shapes["fcgrasp2/bias"] = ((width,), "zeros")
    shapes["bn_fcgrasp2/scale"] = ((width,), "ones")
    shapes["bn_fcgrasp2/bias"] = ((width,), "zeros")
    fh, fw = _final_hw(config)
    fan_in = fh * fw * width
    for i in range(2):
        shapes[f"fc{i}/kernel"] = ((fan_in, 64), "normal")
        shapes[f"fc{i}/bias"] = ((64,), "zeros")
        shapes[f"bn_fc{i}/scale"] = ((64,), "ones")
        shapes[f"bn_fc{i}/bias"] = ((64,), "zeros")
        fan_in = 64
    shapes["logit/kernel"] = ((64, 1), "normal")
    shapes["logit/bias"] = ((1,), "zeros")
    return {f"{PREFIX}/{key}": value for key, value in shapes.items()}


def init_params(key, config):
    """Seeded weights, truncated normal of stddev 0.01 as the reference
    network initialises them. Traceable: the caller jits it."""
    params = {}
    for index, (path, (shape, init)) in enumerate(
        sorted(param_shapes(config).items())
    ):
        if init == "normal":
            params[path] = 0.01 * jax.random.truncated_normal(
                jax.random.fold_in(key, index), -2.0, 2.0, shape, jnp.float32
            )
        elif init == "ones":
            params[path] = jnp.ones(shape, jnp.float32)
        else:
            params[path] = jnp.zeros(shape, jnp.float32)
    return params


# -- preprocessing -------------------------------------------------------------


def _rgb_to_hsv(rgb):
    r, g, b = rgb[..., 0], rgb[..., 1], rgb[..., 2]
    maxc = jnp.maximum(jnp.maximum(r, g), b)
    minc = jnp.minimum(jnp.minimum(r, g), b)
    delta = maxc - minc
    s = jnp.where(maxc > 0, delta / jnp.maximum(maxc, 1e-12), 0.0)
    safe = jnp.maximum(delta, 1e-12)
    rc, gc, bc = (maxc - r) / safe, (maxc - g) / safe, (maxc - b) / safe
    h = jnp.where(
        maxc == r, bc - gc, jnp.where(maxc == g, 2.0 + rc - bc, 4.0 + gc - rc)
    )
    h = jnp.where(delta == 0.0, 0.0, (h / 6.0) % 1.0)
    return h, s, maxc


def _hsv_to_rgb(h, s, v):
    def channel(n):
        k = jnp.mod(n + h * 6.0, 6.0)
        return v - v * s * jnp.clip(jnp.minimum(k, 4.0 - k), 0.0, 1.0)

    return jnp.stack([channel(5.0), channel(3.0), channel(1.0)], axis=-1)


def _distort_one(key, image):
    k_b, k_s, k_h, k_c, _, _ = jax.random.split(key, 6)
    image = image + jax.random.uniform(
        k_b, (), minval=-32.0 / 255.0, maxval=32.0 / 255.0
    )
    gray = jnp.mean(image, axis=-1, keepdims=True)
    image = gray + (image - gray) * jax.random.uniform(
        k_s, (), minval=0.5, maxval=1.5
    )
    h, s, v = _rgb_to_hsv(jnp.clip(image, 0.0, 1.0))
    h = (h + jax.random.uniform(k_h, (), minval=-0.2, maxval=0.2)) % 1.0
    image = _hsv_to_rgb(h, s, v)
    mean = jnp.mean(image, axis=(-3, -2), keepdims=True)
    image = (image - mean) * jax.random.uniform(
        k_c, (), minval=0.5, maxval=1.5
    ) + mean
    return jnp.clip(image, 0.0, 1.0)


def preprocess(features, step_key, config):
    """uint8 source frames -> augmented float32 [B, th, tw, 3] in [0, 1]."""
    image = features["state/image"]
    th, tw = config["model"]["image_size"]
    key_pre, _ = jax.random.split(step_key)
    key_crop, key_distort = jax.random.split(key_pre)
    if tuple(image.shape[1:3]) != (th, tw):
        key_y, key_x = jax.random.split(key_crop)
        batch, h, w = image.shape[:3]
        ys = jax.random.randint(key_y, (batch,), 0, h - th + 1)
        xs = jax.random.randint(key_x, (batch,), 0, w - tw + 1)
        image = jax.vmap(
            lambda im, y, x: lax.dynamic_slice(im, (y, x, 0), (th, tw, 3))
        )(image, ys, xs)
    image = image.astype(jnp.float32) / 255.0
    keys = jax.random.split(key_distort, image.shape[0])
    return jax.vmap(_distort_one)(keys, image)


# -- network -------------------------------------------------------------------


def _identity(x):
    return x


def _conv(x, kernel, stride, padding, quant):
    return lax.conv_general_dilated(
        quant(x), quant(kernel), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
    )


def _dense(x, kernel, bias, quant):
    return jnp.dot(quant(x), quant(kernel), precision=HIGHEST) + bias


def _batch_norm(x, scale, bias):
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axis=axes)
    var = jnp.mean(jnp.square(x - mean), axis=axes)
    y = (x - mean) * lax.rsqrt(var + BN_EPS)
    if scale is not None:
        y = y * scale
    return y + bias


def _max_pool(x, window):
    dims = (1, window, window, 1)
    return lax.reduce_window(x, -jnp.inf, lax.max, dims, dims, "SAME")


def forward(params, images, actions, config, quant=None):
    """Logits [B] of augmented float32 images and [B, 10] actions."""
    quant = quant or _identity
    p = {key[len(PREFIX) + 1:]: value for key, value in params.items()}
    names, n1, n2 = _conv_names(config)

    net = _conv(images, p["conv1_1/kernel"], 2, "SAME", quant)
    net = jax.nn.relu(_batch_norm(net, None, p["bn1/bias"]))
    net = _max_pool(net, 3)

    def block(net, name, padding):
        net = _conv(net, p[f"{name}/Conv_0/kernel"], 1, padding, quant)
        return jax.nn.relu(_batch_norm(
            net, p[f"{name}/BatchNorm_0/scale"], p[f"{name}/BatchNorm_0/bias"]
        ))

    for name, _, padding in names[:n1]:
        net = block(net, name, padding)
    net = _max_pool(net, 3)

    context = 0.0
    for name, (offset, size) in sorted(ACTION_BLOCKS.items()):
        context = context + _dense(
            actions[:, offset:offset + size],
            p[f"{name}/kernel"], p[f"{name}/bias"], quant,
        )
    context = jax.nn.relu(_batch_norm(context, None, p["bn_fcgrasp/bias"]))
    context = _dense(context, p["fcgrasp2/kernel"], p["fcgrasp2/bias"], quant)
    context = jax.nn.relu(_batch_norm(
        context, p["bn_fcgrasp2/scale"], p["bn_fcgrasp2/bias"]
    ))
    net = net + context[:, None, None, :]

    for name, _, padding in names[n1:n1 + n2]:
        net = block(net, name, padding)
    net = _max_pool(net, 2)
    for name, _, padding in names[n1 + n2:]:
        net = block(net, name, padding)

    net = net.reshape(net.shape[0], -1)
    for i in range(2):
        net = _dense(net, p[f"fc{i}/kernel"], p[f"fc{i}/bias"], quant)
        net = jax.nn.relu(_batch_norm(
            net, p[f"bn_fc{i}/scale"], p[f"bn_fc{i}/bias"]
        ))
    logits = _dense(net, p["logit/kernel"], p["logit/bias"], quant)
    return logits.reshape(-1)


def loss_fn(params, batch, step_key, config, quant=None):
    """Scalar training loss of one raw batch, as the train step sees it:
    `batch` is {"features": {...}, "labels": {...}} of the raw in-spec."""
    features = batch["features"]
    images = preprocess(features, step_key, config)
    actions = jnp.concatenate(
        [features[f"action/{key}"].astype(jnp.float32) for key in ACTION_KEYS],
        axis=-1,
    )
    logits = forward(params, images, actions, config, quant)
    reward = batch["labels"]["reward"].astype(jnp.float32).reshape(-1)
    # Sigmoid cross-entropy in its stable form.
    return jnp.mean(
        jnp.maximum(logits, 0.0) - logits * reward
        + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )
