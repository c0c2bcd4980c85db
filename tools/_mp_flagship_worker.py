"""Two-process FLAGSHIP dryrun worker.

Each invocation is one "host" with 2 virtual CPU devices: it joins the
coordinator, builds the 4-device global data mesh, and trains ONE step of
the reduced-block Grasping44 flagship (96px, num_convs=(2,2,1), global
batch 4 — deterministic: seed-0 batch and init). Prints the step loss in
a parseable form so the caller (__graft_entry__.dryrun_multichip) can
check parity against the same model on a single-process 4-device mesh.

Usage: python tools/_mp_flagship_worker.py <coordinator> <num_processes> \
    <process_id>
"""

from __future__ import annotations

import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# 2 virtual devices per process BEFORE jax initializes; the CPU platform
# comes from the caller's environment (JAX_PLATFORMS=cpu).
_FLAGS = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _FLAGS:
    os.environ["XLA_FLAGS"] = (
        _FLAGS + " --xla_force_host_platform_device_count=2"
    ).strip()

import jax  # noqa: E402


def main(coordinator: str, num_processes: int, process_id: int) -> None:
    from tensor2robot_tpu.parallel import mesh as mesh_lib

    mesh_lib.initialize_distributed(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    assert jax.process_count() == num_processes, jax.process_count()
    assert jax.device_count() == 2 * num_processes, jax.device_count()

    from __graft_entry__ import _flagship
    from tensor2robot_tpu.train.train_eval import CompiledModel

    model, batch = _flagship(
        image_size=(96, 96), batch_size=2 * num_processes,
        num_convs=(2, 2, 1),
    )
    mesh = mesh_lib.make_mesh()  # data axis over all global devices
    assert mesh.shape[mesh_lib.DATA_AXIS] == 2 * num_processes
    compiled = CompiledModel(model, mesh=mesh, donate_state=False)
    state = compiled.init_state(jax.random.PRNGKey(0), batch)
    state, metrics = compiled.train_step(
        state, compiled.shard_batch(batch), jax.random.PRNGKey(1)
    )
    loss = float(jax.device_get(metrics["loss"]))
    # Every host must agree on the loss bit-wise (one SPMD program).
    from jax.experimental import multihost_utils
    import numpy as np

    losses = multihost_utils.process_allgather(
        np.asarray([loss], np.float64)
    )
    np.testing.assert_allclose(losses.ravel(), loss, rtol=0, atol=0)
    print(f"mp_flagship {process_id}: OK loss={loss:.8f}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]))
