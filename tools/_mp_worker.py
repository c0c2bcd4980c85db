"""Worker for the multi-process distributed test (tests/test_multiprocess.py).

Each invocation is one "host": it joins the coordinator, builds the global
data mesh, contributes its per-process shard, and verifies the cross-host
collective results. Exits 0 only when every check passes on this process.

Usage: python tools/_mp_worker.py <coordinator> <num_processes> \
    <process_id> [shard_data_dir]
"""

from __future__ import annotations

import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import jax  # noqa: E402  (the caller's environment says JAX_PLATFORMS=cpu)
import numpy as np  # noqa: E402

from tensor2robot_tpu.parallel import mesh as mesh_lib  # noqa: E402


def main(
    coordinator: str,
    num_processes: int,
    process_id: int,
    data_dir: "str | None" = None,
) -> None:
    mesh_lib.initialize_distributed(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    assert jax.process_count() == num_processes, jax.process_count()
    assert jax.process_index() == process_id, jax.process_index()

    # Global data mesh over every process's devices (1 CPU device each).
    mesh = mesh_lib.make_mesh()
    assert mesh.shape[mesh_lib.DATA_AXIS] == num_processes

    # Per-host data sharding: each process contributes its own batch rows
    # (the multi-host infeed path RecordDataset(shard_by_host=True) feeds).
    local = np.full((2, 4), float(process_id + 1), np.float32)
    global_shape = (2 * num_processes, 4)
    arr = jax.make_array_from_process_local_data(
        mesh_lib.data_sharding(mesh), local, global_shape
    )
    assert arr.shape == global_shape

    # A cross-host collective through pjit: the global mean sees BOTH
    # hosts' contributions (mean of 1s and 2s = 1.5 with 2 processes).
    mean = jax.jit(lambda x: x.mean())(arr)
    expected = np.mean([p + 1.0 for p in range(num_processes)])
    np.testing.assert_allclose(float(mean), expected, rtol=1e-6)

    # process_allgather (DCN gather): every host sees every host's shard.
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(
        np.asarray([float(process_id)], np.float32)
    )
    np.testing.assert_array_equal(
        np.sort(gathered.ravel()), np.arange(num_processes, dtype=np.float32)
    )

    # The real thing: a full CompiledModel train step ACROSS processes —
    # identical batches on both (same seed) so the SPMD program sees one
    # global batch, gradients all-reduced over the cross-process data
    # axis; losses/params must agree bit-wise on every host.
    from tensor2robot_tpu.train.train_eval import CompiledModel
    from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

    model = MockT2RModel(device_type="cpu", use_batch_norm=False)
    generator = MockInputGenerator(batch_size=2 * num_processes)
    generator.set_specification_from_model(model, "train")
    batch = next(iter(generator.create_dataset("train")))
    compiled = CompiledModel(model, mesh=mesh, donate_state=False)
    state = compiled.init_state(jax.random.PRNGKey(0), batch)
    losses = []
    for _ in range(3):
        state, metrics = compiled.train_step(
            state, compiled.shard_batch(batch), jax.random.PRNGKey(1)
        )
        losses.append(float(jax.device_get(metrics["loss"])))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    # Every host must hold identical post-step replicated params.
    digest = float(
        sum(
            np.abs(np.asarray(jax.device_get(leaf))).sum()
            for leaf in jax.tree_util.tree_leaves(state.params)
        )
    )
    digests = multihost_utils.process_allgather(
        np.asarray([digest], np.float64)
    )
    np.testing.assert_allclose(digests.ravel(), digest, rtol=0, atol=0)
    # Per-host infeed with REAL processes: shard_by_host slices the file
    # list by jax.process_index(); the union across hosts must be exactly
    # the full record set with no overlap.
    if data_dir:
        from tensor2robot_tpu.data.dataset import RecordDataset
        from tensor2robot_tpu.specs import (
            ExtendedTensorSpec,
            TensorSpecStruct,
        )

        spec = TensorSpecStruct()
        spec["y"] = ExtendedTensorSpec(shape=(), dtype=np.int64, name="y")
        dataset = RecordDataset(
            specs=spec,
            file_patterns=os.path.join(data_dir, "s-*.tfrecord"),
            batch_size=1,
            mode="eval",
            drop_remainder=False,
            shard_by_host=True,
        )
        mine = sorted(int(b["y"][0]) for b in dataset)
        padded = np.full((8,), -1, np.int64)
        padded[: len(mine)] = mine
        all_rows = multihost_utils.process_allgather(padded)
        union = sorted(int(v) for v in all_rows.ravel() if v >= 0)
        assert union == [0, 1, 2, 3], union  # complete AND non-overlapping

    print(
        f"mp_worker {process_id}: OK (mean={float(mean)}, "
        f"train losses={['%.4f' % l for l in losses]})"
    )


if __name__ == "__main__":
    main(
        sys.argv[1],
        int(sys.argv[2]),
        int(sys.argv[3]),
        sys.argv[4] if len(sys.argv) > 4 else None,
    )
