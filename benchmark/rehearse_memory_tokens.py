"""`rehearse_memory.py` for a cell of driver `train_resident_tokens`: compile
the cell's real train step for a described `v5e:2x2` device and print the
chip compiler's memory and cost reckoning beside `flops.py`'s count.
Nothing runs and no chip is needed; a compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python benchmark/rehearse_memory_tokens.py <cell> [--sequence N]

(`rehearse_memory.py` draws every input as uniform floats and builds the
model from `arguments` alone; a token model needs integer ids and the
configuration's `model` keys. Its `--reference` has no twin here: the
reference's whole float32 step holds 12.4 GB of arguments and as much
again of results, and is followed layer by layer instead.)
"""

from __future__ import annotations

import argparse
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
for _path in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if _path not in sys.path:
        sys.path.insert(0, _path)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("cell")
    parser.add_argument("--sequence", type=int)
    args = parser.parse_args()

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import flops
    import manifest
    import program_side
    from tensor2robot_tpu.train.train_eval import CompiledModel

    cell = manifest.cell(args.cell)
    config = manifest.config(cell["config"])
    ref = manifest.reference(cell["config"])
    driver = manifest.driver(cell["driver"])
    config = dict(config, arguments=driver.constructor_arguments(config))
    if args.sequence:
        config["arguments"]["sequence_length"] = args.sequence
    seq = config["arguments"]["sequence_length"]
    rows = cell["batch"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree
        )

    weights = jax.jit(lambda k: ref.init_params(k, config))(jax.random.PRNGKey(0))
    model = program_side.build_model(config, weights)
    compiled = CompiledModel(model, donate_state=True)
    packed, _ = driver.packed_documents(
        0, rows, seq, config["model"]["vocab_size"], cell["traffic"]
    )
    raw = {
        "features": {k: jnp.asarray(packed[k]) for k in ("tokens", "segment_ids")},
        "labels": {k: jnp.asarray(packed[k]) for k in ("targets", "loss_mask")},
    }
    batch = program_side.as_program_batch(raw)
    state = compiled.init_state(jax.random.PRNGKey(0), batch)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)

    started = time.perf_counter()
    executable = compiled.train_step.lower(
        described(state), described(batch), key
    ).compile()
    memory = executable.memory_analysis()
    cost = executable.cost_analysis()
    total = (memory.temp_size_in_bytes + memory.argument_size_in_bytes
             + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    print(f"{args.cell} {rows} x {seq} tokens: program step compiled for v5e in "
          f"{time.perf_counter() - started:.1f} s")
    print(f"  temp {memory.temp_size_in_bytes / 1e9:.2f} GB + arguments "
          f"{memory.argument_size_in_bytes / 1e9:.2f} GB + outputs-not-aliased "
          f"{(memory.output_size_in_bytes - memory.alias_size_in_bytes) / 1e9:.3f} GB"
          f" = {total / 1e9:.2f} GB, {100 * total / (16 * 2.0 ** 30):.1f}% of 16 GiB")
    print(f"  XLA cost analysis: {cost.get('flops', 0) / 1e12:.3f} TFLOP a step, "
          f"{cost.get('bytes accessed', 0) / 1e9:.1f} GB a step")
    itemsize = np.dtype(config["compute_dtype"]).itemsize
    counted = flops.count(
        lambda p, b: ref.loss_fn(p, b, jax.random.PRNGKey(0), config),
        flops.abstract(weights), flops.abstract(raw), bytes_per_element=itemsize,
    )
    print(f"  benchmark/flops.py from the reference: {counted['step_flops'] / 1e12:.3f} "
          f"TFLOP a step (forward {counted['forward_flops'] / 1e12:.3f}), "
          f"{counted['step_bytes'] / 1e9:.1f} GB of kernel operands and results, "
          f"{counted['equations']} equations")
    for name, cost in ref.kernel_costs(config, rows, seq, itemsize).items():
        print(f"  {name}: {cost['step_flops'] / 1e12:.3f} TFLOP a step, "
              f"{cost['step_bytes'] / 1e9:.2f} GB of operands and results")


if __name__ == "__main__":
    main()
