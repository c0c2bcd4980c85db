"""Third rehearsal: compile a cell's train step, and the plain reference's
step, for a described `v5e:2x2` device, and print what the chip's compiler
says of memory and cost. Nothing runs and no chip is needed; a compile that
passes is not a chip run.

    JAX_PLATFORMS=cpu python benchmark/rehearse_memory.py <cell> [--batch N] [--reference]

A script, not a test: it loads libtpu when it is run, never at import.
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
    parser.add_argument("--batch", type=int)
    parser.add_argument("--reference", action="store_true",
                        help="also compile the float32 reference step")
    parser.add_argument("--quant", default=None,
                        help="compile the reference step as the control "
                             "runs it (float8_e4m3, bfloat16, ...)")
    args = parser.parse_args()

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import compare
    import flops
    import manifest
    import program_side
    import traffic
    from tensor2robot_tpu.train.train_eval import CompiledModel

    cell = manifest.cell(args.cell)
    config = manifest.config(cell["config"])
    ref = manifest.reference(cell["config"])
    batch_size = args.batch or cell["batch"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree
        )

    weights = jax.jit(lambda k: ref.init_params(k, config))(jax.random.PRNGKey(0))
    model = program_side.build_model(config, weights)
    compiled = CompiledModel(model, donate_state=True)
    # Parameter shapes do not depend on the batch: initialise on one row here
    # and describe the batch at the cell's size.
    one = traffic.resident_batch(model, 1, 0, cell["traffic"])
    state = compiled.init_state(
        jax.random.PRNGKey(0), program_side.as_program_batch(one)
    )
    raw = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((batch_size,) + x.shape[1:], x.dtype, sharding=chip),
        one,
    )
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)

    started = time.perf_counter()
    executable = compiled.train_step.lower(
        described(state), program_side.as_program_batch(raw), key
    ).compile()
    memory = executable.memory_analysis()
    cost = executable.cost_analysis()
    gib = 2.0 ** 30
    total = (memory.temp_size_in_bytes + memory.argument_size_in_bytes
             + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    print(f"{args.cell} batch {batch_size}: program step compiled for v5e in "
          f"{time.perf_counter() - started:.1f} s")
    print(f"  temp {memory.temp_size_in_bytes / 1e9:.2f} GB + arguments "
          f"{memory.argument_size_in_bytes / 1e9:.2f} GB + outputs-not-aliased "
          f"{(memory.output_size_in_bytes - memory.alias_size_in_bytes) / 1e9:.3f} GB"
          f" = {total / 1e9:.2f} GB, {100 * total / (16 * gib):.1f}% of 16 GiB")
    print(f"  XLA cost analysis: {cost.get('flops', 0) / 1e12:.3f} TFLOP a step, "
          f"{cost.get('bytes accessed', 0) / 1e9:.1f} GB a step")
    counted = flops.count(
        lambda p, b: ref.loss_fn(p, b, jax.random.PRNGKey(0), config),
        flops.abstract(weights),
        jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), raw),
    )
    print(f"  benchmark/flops.py from the reference: {counted['step_flops'] / 1e12:.3f} "
          f"TFLOP a step (forward {counted['forward_flops'] / 1e12:.3f}), "
          f"{counted['step_bytes'] / 1e9:.1f} GB of kernel operands and results, "
          f"{counted['equations']} equations")

    if args.reference:
        spec = ref.optimizer(config)
        step = compare._reference_step(ref, config, args.quant)

        params = described(weights)
        opt = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            jax.eval_shape(lambda p: compare._optimizer_init(spec, p), weights),
        )
        started = time.perf_counter()
        executable = step.lower(
            params, opt, raw, key, jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
        ).compile()
        memory = executable.memory_analysis()
        total = memory.temp_size_in_bytes + memory.argument_size_in_bytes
        print(f"  reference step (float32, highest, quant {args.quant}): temp "
              f"{memory.temp_size_in_bytes / 1e9:.2f} GB + arguments "
              f"{memory.argument_size_in_bytes / 1e9:.2f} GB = "
              f"{100 * total / (16 * gib):.1f}% of 16 GiB, compiled in "
              f"{time.perf_counter() - started:.1f} s")


if __name__ == "__main__":
    main()
