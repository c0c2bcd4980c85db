"""The one general generator of training traffic, driven by a cell's file.

Everything comes from `--seed`; the same seed gives the same inputs. The
cell's `traffic` block says what to make:

  {"kind": "resident_batch", "image_block": 32, "image_noise": 8}
      one raw batch at the model's in-spec, made on the device in one
      jitted call: camera-like images (smooth blocks + mild noise: uniform
      noise is jpeg's worst case and averages to one grey, which leaves a
      network blind to its image; copied in substance from chip_smoke.py),
      floats uniform in [-1, 1), a 0/1 reward.

  {"kind": "jpeg_records", "records": 1536, "base_images": 48,
   "jpeg_quality": 90, ...}
      a tf.Example record file of `records` distinct grasps: each record's
      image is a window of one of `base_images` larger camera-like frames
      at an offset of its own, encoded to jpeg in threads; its scalars are
      seeded; `world_vector[0]` carries the record's number (id / 4096, an
      exact float32; `id_scale` names another power of two where a mix has
      more than 4,095 records), so that the comparison can tell which
      record a parsed row came from.

Specs are read from the model's preprocessor: they are the program's
public contract for what its input must look like.
"""

from __future__ import annotations

import concurrent.futures
import os

import numpy as np

ID_SCALE = 4096.0


def _id_scale(params):
    return float(params.get("id_scale", ID_SCALE))


def _flat_specs(model):
    from tensor2robot_tpu.specs import flatten_spec_structure

    pre = model.preprocessor
    features = flatten_spec_structure(pre.get_in_feature_specification("train"))
    labels = flatten_spec_structure(pre.get_in_label_specification("train"))
    return dict(features.items()), dict(labels.items())


def _is_image(spec):
    return np.dtype(spec.dtype) == np.uint8 and len(spec.shape) == 3


def resident_batch(model, batch_size, seed, params):
    """{"features": {...}, "labels": {...}} of device arrays."""
    import jax
    import jax.numpy as jnp

    features, labels = _flat_specs(model)
    block = int(params.get("image_block", 32))
    noise = int(params.get("image_noise", 8))

    def camera_like(key, shape):
        h, w, c = shape
        k1, k2 = jax.random.split(key)
        coarse = jax.random.randint(
            k1, (batch_size, h // block + 1, w // block + 1, c), 0, 256
        )
        image = jnp.repeat(jnp.repeat(coarse, block, axis=1), block, axis=2)
        image = image[:, :h, :w] + jax.random.randint(
            k2, (batch_size, h, w, c), -noise, noise + 1
        )
        return jnp.clip(image, 0, 255).astype(jnp.uint8)

    def make(key):
        out = {"features": {}, "labels": {}}
        for group, specs in (("features", features), ("labels", labels)):
            for index, (name, spec) in enumerate(sorted(specs.items())):
                sub = jax.random.fold_in(key, index + (1000 if group == "labels" else 0))
                shape = tuple(spec.shape)
                if _is_image(spec):
                    value = camera_like(sub, shape)
                elif name.endswith("reward"):
                    value = jax.random.bernoulli(
                        sub, 0.5, (batch_size,) + shape
                    ).astype(jnp.float32)
                else:
                    value = jax.random.uniform(
                        sub, (batch_size,) + shape, jnp.float32, -1.0, 1.0
                    )
                out[group][name] = value
        if not out["labels"]:
            del out["labels"]
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed % (2**31)))


def _camera_like_host(rng, height, width, block, noise):
    coarse = rng.integers(0, 256, (height // block + 1, width // block + 1, 3))
    image = np.repeat(np.repeat(coarse, block, axis=0), block, axis=1)
    image = image[:height, :width] + rng.integers(-noise, noise + 1, (height, width, 3))
    return np.clip(image, 0, 255).astype(np.uint8)


def jpeg_records(model, seed, params, path):
    """Writes the record file; returns the generator's own knowledge of each
    record: [{"jpeg": bytes, "values": {flat key: float32 array}}]."""
    import cv2

    from tensor2robot_tpu.data import tfrecord
    from tensor2robot_tpu.data.encoder import encode_example

    features, labels = _flat_specs(model)
    specs = {f"features/{k}": v for k, v in features.items()}
    specs.update({f"labels/{k}": v for k, v in labels.items()})
    image_keys = [k for k, v in specs.items() if _is_image(v)]
    if len(image_keys) != 1:
        raise ValueError(f"jpeg_records wants one image field, got {image_keys}")
    image_key = image_keys[0]
    height, width, _ = specs[image_key].shape
    count = int(params["records"])
    id_scale = _id_scale(params)
    if count >= id_scale:
        raise ValueError(
            f"at most {int(id_scale) - 1} records at id_scale {id_scale:g}"
        )
    bases = int(params.get("base_images", 48))
    slack = int(params.get("window_slack", 64))
    quality = int(params.get("jpeg_quality", 90))
    block = int(params.get("image_block", 32))
    noise = int(params.get("image_noise", 8))

    rng = np.random.default_rng(seed)
    frames = [
        _camera_like_host(rng, height + slack, width + slack, block, noise)
        for _ in range(bases)
    ]
    offsets = rng.integers(0, slack + 1, (count, 2))
    flips = rng.integers(0, 2, count)
    values = []
    for index in range(count):
        row = {}
        for key, spec in sorted(specs.items()):
            if key == image_key:
                continue
            shape = tuple(spec.shape)
            if key.endswith("reward"):
                row[key] = rng.integers(0, 2, shape).astype(np.float32)
            else:
                row[key] = rng.uniform(-1.0, 1.0, shape).astype(np.float32)
        id_key = params.get("id_field", "features/action/world_vector")
        row[id_key][0] = np.float32(index / id_scale)
        values.append(row)

    def encode(index):
        dy, dx = offsets[index]
        frame = frames[index % bases][dy:dy + height, dx:dx + width]
        if flips[index]:
            frame = frame[:, ::-1]
        ok, buf = cv2.imencode(
            ".jpg", np.ascontiguousarray(frame[..., ::-1]),
            [cv2.IMWRITE_JPEG_QUALITY, quality],
        )
        if not ok:
            raise RuntimeError("jpeg encode failed")
        return buf.tobytes()

    with concurrent.futures.ThreadPoolExecutor(
        max_workers=min(12, os.cpu_count() or 1)
    ) as pool:
        jpegs = list(pool.map(encode, range(count)))

    records = [
        encode_example(specs, {**values[i], image_key: jpegs[i]})
        for i in range(count)
    ]
    tfrecord.write_tfrecords(path, records)
    return [
        {"jpeg": jpegs[i], "values": values[i]} for i in range(count)
    ], image_key


def _flat_group(struct):
    """A batch group (features or labels) as {flat key: numpy array}."""
    from tensor2robot_tpu.specs import flatten_spec_structure

    return {
        key: np.asarray(value)
        for key, value in flatten_spec_structure(struct).items()
    }


def _find_window(image, frame, probes=48):
    """(dy, dx) at which `image` lies in `frame`: the offset whose pixels at
    a few probe positions agree best. The camera-like frames carry noise in
    every pixel, so one offset stands out."""
    th, tw = image.shape[:2]
    slack_y, slack_x = frame.shape[0] - th, frame.shape[1] - tw
    if slack_y == 0 and slack_x == 0:
        return 0, 0
    rng = np.random.default_rng(0)
    ys = rng.integers(0, th, probes)
    xs = rng.integers(0, tw, probes)
    cost = np.zeros((slack_y + 1, slack_x + 1), np.int64)
    wide = frame.astype(np.int16)
    for y, x in zip(ys, xs):
        patch = wide[y:y + slack_y + 1, x:x + slack_x + 1]
        cost += np.abs(patch - image[y, x].astype(np.int16)).sum(axis=-1)
    dy, dx = np.unravel_index(np.argmin(cost), cost.shape)
    return int(dy), int(dx)


def reference_batches(first_batches, records, image_key, params):
    """The fed cell's first batches as the plain reference reads them, made
    from the generator's own knowledge of the records and a jpeg decode of
    its own (OpenCV), not from what the program parsed.

    Each parsed row names its record in `id_field`. Returns
    ([{"features": {...}, "labels": {...}}], numbers): `parse_max_abs` is
    the largest difference between a parsed scalar and the value written
    (exact: 0), `decode_max_abs` the largest pixel difference between the
    program's decoded window and OpenCV's decode of the same jpeg at the
    window's offset (two libjpeg builds may differ by a level or two).
    """
    import cv2

    id_key = params.get("id_field", "features/action/world_vector")
    id_group, id_name = id_key.split("/", 1)
    image_group, image_name = image_key.split("/", 1)
    decoded = {}
    parse_gap = decode_gap = 0.0
    out = []
    for batch in first_batches:
        program = {
            "features": _flat_group(batch["features"]),
            "labels": _flat_group(batch["labels"]),
        }
        ids = np.rint(
            program[id_group][id_name][:, 0] * _id_scale(params)
        ).astype(int)
        if ids.min() < 0 or ids.max() >= len(records):
            raise ValueError("a parsed row names no record that was written")
        mine = {"features": {}, "labels": {}}
        images = []
        for row, ident in enumerate(ids):
            record = records[ident]
            if ident not in decoded:
                bgr = cv2.imdecode(
                    np.frombuffer(record["jpeg"], np.uint8), cv2.IMREAD_COLOR
                )
                decoded[ident] = np.ascontiguousarray(bgr[..., ::-1])
            frame = decoded[ident]
            theirs = program[image_group][image_name][row]
            dy, dx = _find_window(theirs, frame)
            window = frame[dy:dy + theirs.shape[0], dx:dx + theirs.shape[1]]
            decode_gap = max(decode_gap, float(np.abs(
                window.astype(np.int16) - theirs.astype(np.int16)
            ).max()))
            images.append(window)
            for key, value in record["values"].items():
                group, name = key.split("/", 1)
                parse_gap = max(parse_gap, float(np.abs(
                    program[group][name][row].astype(np.float64) - value
                ).max()))
        for key in records[0]["values"]:
            group, name = key.split("/", 1)
            mine[group][name] = np.stack(
                [records[i]["values"][key] for i in ids]
            )
        mine[image_group][image_name] = np.stack(images)
        out.append(mine)
    return out, {"parse_max_abs": parse_gap, "decode_max_abs": decode_gap}
