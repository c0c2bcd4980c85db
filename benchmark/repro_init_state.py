"""The smallest reproducer of what keeps `critic_c64.train_fed_dp4` parked
(PERF.md section 7): `CompiledModel.init_state` on a host batch of ROWS
rows, as `train_eval_model` hands its first batch over, and on the same
batch laid over the mesh first, as the resident driver does.

    chiprun --chips 4 -- python benchmark/repro_init_state.py 1024

On four chips at 1,024 rows the first succeeds (12.2 GB a chip) and the
second dies in the eager preprocessor on chip 0 (my chip runs, PR 35). A
script for the chip, not a test; nothing of the benchmark calls it.
"""

import os
import sys
import traceback

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
for _path in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import jax  # noqa: E402
import numpy as np  # noqa: E402

import manifest  # noqa: E402
import program_side  # noqa: E402
import traffic  # noqa: E402
from tensor2robot_tpu.train.train_eval import CompiledModel  # noqa: E402

rows = int(sys.argv[1])
config = manifest.config("critic_c64")
ref = manifest.reference("critic_c64")
weights = jax.jit(lambda k: ref.init_params(k, config))(jax.random.PRNGKey(0))
model = program_side.build_model(config, weights)
raw = jax.tree_util.tree_map(
    np.asarray, traffic.resident_batch(model, rows, 0, {"kind": "resident_batch"})
)
compiled = CompiledModel(model)
print("devices", len(jax.devices()), "rows", rows, flush=True)
for name, batch in (
    ("laid over the mesh first",
     lambda: compiled.shard_batch(program_side.as_program_batch(raw))),
    ("host batch, as train_eval_model hands it over",
     lambda: program_side.as_program_batch(raw)),
):
    try:
        state = compiled.init_state(jax.random.PRNGKey(0), batch())
        jax.block_until_ready(state)
        peak = [
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) / 1e9
            for d in jax.devices()
        ]
        print(f"init_state, {name}: ok; peak_bytes_in_use by chip, GB: "
              f"{[round(p, 2) for p in peak]}", flush=True)
        del state
    except Exception as e:  # noqa: BLE001 - the fault is what is shown
        print(f"init_state, {name}: {type(e).__name__}: {str(e)[:300]}", flush=True)
        print("".join(traceback.format_tb(e.__traceback__)[-6:]), flush=True)
