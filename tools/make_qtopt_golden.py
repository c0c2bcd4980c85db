"""Regenerates the QT-Opt flagship golden-value fixture.

The reference's strongest regression gate was golden-value training
(reference utils/t2r_test_fixture.py:142-195: train on a checked-in
record, numpy-compare tagged tensors against a stored golden at
decimal=5, catching any data->parse->preprocess->forward->loss drift in
one assert). This applies that gate to the flagship QT-Opt critic at
debug scale: a committed TFRecord of seeded spec-conforming examples +
the q_predicted/loss values from two deterministic train steps.

Run `JAX_PLATFORMS=cpu python tools/make_qtopt_golden.py` ONLY on an
intentional behavior change; commit both regenerated files with that
change.
Fixture caveat (same as the reference's checked-in tfrecord): jpeg BYTES
are pinned by the committed record file, so only decode determinism
matters at test time.
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# The golden contract is "what the TEST environment computes": tests run on
# the 8-virtual-device CPU mesh (tests/conftest.py), and sharded reductions
# accumulate in a different order than single-device ones — enough to move
# decimal=5 comparisons. Pin the same topology here so regeneration from a
# plain shell reproduces the values the suite will check.
if "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

GOLDEN_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tests",
    "golden",
)
RECORD_PATH = os.path.join(GOLDEN_DIR, "qtopt_train.tfrecord")
VALUES_PATH = os.path.join(GOLDEN_DIR, "qtopt_golden_values.npy")

BATCH = 4
STEPS = 2
IMAGE_SIZE = (96, 96)
NUM_CONVS = (2, 2, 1)


def build_model():
    from tensor2robot_tpu.hooks import add_golden_tensor
    from tensor2robot_tpu.research.qtopt.t2r_models import (
        Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom,
    )

    class GoldenGrasping(
        Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom
    ):
        def model_train_fn(self, features, labels, outputs, mode):
            loss, metrics = super().model_train_fn(
                features, labels, outputs, mode
            )
            add_golden_tensor(metrics, outputs["q_predicted"], "q_predicted")
            return loss, metrics

    return GoldenGrasping(
        device_type="cpu", image_size=IMAGE_SIZE, num_convs=NUM_CONVS
    )


def write_records(model) -> None:
    from tensor2robot_tpu.data import tfrecord
    from tensor2robot_tpu.data.encoder import encode_example
    from tensor2robot_tpu.specs import make_random_numpy

    specs = {
        "features": model.preprocessor.get_in_feature_specification("train"),
        "labels": model.preprocessor.get_in_label_specification("train"),
    }
    values = make_random_numpy(specs, batch_size=BATCH * STEPS, seed=7)
    records = [
        encode_example(
            specs, {key: np.asarray(value[i]) for key, value in values.items()}
        )
        for i in range(BATCH * STEPS)
    ]
    os.makedirs(GOLDEN_DIR, exist_ok=True)
    tfrecord.write_tfrecords(RECORD_PATH, records)


def train_and_capture(model):
    """Two deterministic train steps over the committed record; returns
    {step metrics incl. golden/q_predicted and loss} stacked."""
    import jax

    from tensor2robot_tpu.data.dataset import RecordDataset
    from tensor2robot_tpu.train.train_eval import CompiledModel

    specs = {
        "features": model.preprocessor.get_in_feature_specification("train"),
        "labels": model.preprocessor.get_in_label_specification("train"),
    }
    dataset = RecordDataset(
        specs=specs,
        file_patterns=RECORD_PATH,
        batch_size=BATCH,
        mode="train",
        shuffle_buffer_size=0,
        seed=11,
        num_parse_workers=0,
        prefetch_depth=0,
    )
    compiled = CompiledModel(model, donate_state=False)
    it = iter(dataset)
    first = next(it)
    batch0 = {"features": first["features"], "labels": first["labels"]}
    state = compiled.init_state(jax.random.PRNGKey(0), batch0)
    captures = []
    batch = batch0
    for step in range(STEPS):
        state, metrics = compiled.train_step(
            state, compiled.shard_batch(batch), jax.random.PRNGKey(123)
        )
        captures.append(
            {
                "loss": np.asarray(jax.device_get(metrics["loss"])),
                "q_predicted": np.asarray(
                    jax.device_get(metrics["golden/q_predicted"])
                ),
            }
        )
        if step + 1 < STEPS:
            nxt = next(it)
            batch = {"features": nxt["features"], "labels": nxt["labels"]}
    return captures


def main() -> None:
    model = build_model()
    write_records(model)
    captures = train_and_capture(model)
    np.save(VALUES_PATH, np.asarray(captures, dtype=object), allow_pickle=True)
    print(f"wrote {RECORD_PATH}")
    print(f"wrote {VALUES_PATH}")
    for step, cap in enumerate(captures):
        print(
            f"  step {step}: loss={float(cap['loss']):.6f} "
            f"q={cap['q_predicted'].ravel()[:3]}"
        )


if __name__ == "__main__":
    main()
