"""Seconds of a benchmark cell's train step before anything compiles: tracing
it (Python) and lowering it for a TPU (StableHLO, and the Mosaic lowering of
every Pallas kernel's body), at the cell's real shapes, with what the text
holds: its size, its functions and their call sites, and the kernels'
custom-call bodies by name. No chip, no array of the model's size (the state
is `jax.eval_shape`'s), nothing compiled: it is the part of a cell's first
step that no compile cache answers (ISSUE 34), on this machine's clock.

    JAX_PLATFORMS=cpu python tools/time_step_lowering.py <cell> [--tree DIR] [--repeat N]

`--tree` is another checkout of this repo (the parent's `git archive`) to
read the program and the benchmark from: one process a tree. A cell of
driver `train_resident_tokens*` (`benchmark/rehearse_memory_tokens.py`
builds the same step and compiles it).
"""

from __future__ import annotations

import argparse
import collections
import os
import re
import sys
import time


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("cell")
    parser.add_argument("--tree", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    parser.add_argument("--repeat", type=int, default=3)
    args = parser.parse_args()

    tree = os.path.abspath(args.tree)
    sys.path[:0] = [tree, os.path.join(tree, "benchmark")]
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp

    import manifest
    import program_side
    from tensor2robot_tpu.train.train_eval import CompiledModel

    cell = manifest.cell(args.cell)
    config = manifest.config(cell["config"])
    reference = manifest.reference(cell["config"])
    driver = manifest.driver(cell["driver"])
    config = dict(config, arguments=driver.constructor_arguments(config))
    packed, _ = driver.packed_documents(
        0, cell["batch"], config["arguments"]["sequence_length"],
        config["model"]["vocab_size"], cell["traffic"],
    )
    batch = program_side.as_program_batch({
        "features": {k: jnp.asarray(packed[k]) for k in ("tokens", "segment_ids")},
        "labels": {k: jnp.asarray(packed[k]) for k in ("targets", "loss_mask")},
    })
    # The model's warm-start hook reads `weights` when the state is made:
    # inside `eval_shape`, where the reference's weights are shapes too.
    weights = {}
    compiled = CompiledModel(
        program_side.build_model(config, weights), donate_state=True)

    def state_of(key):
        weights.update(reference.init_params(key, config))
        return compiled.init_state(key, batch)

    shapes = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    state = jax.eval_shape(state_of, jax.random.PRNGKey(0))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    step = compiled.train_step.__wrapped__
    for _ in range(args.repeat):
        jax.clear_caches()
        started = time.perf_counter()
        traced = step.trace(shapes(state), shapes(batch), key)
        traced_at = time.perf_counter()
        text = traced.lower(lowering_platforms=("tpu",)).as_text()
        lowered_at = time.perf_counter()
        kernels = collections.Counter(re.findall(r'kernel_name = "([^"]+)"', text))
        print(f"{args.cell} in {tree}: trace {traced_at - started:.2f} s, lower "
              f"{lowered_at - traced_at:.2f} s; {len(text) / 1e6:.2f} MB of text, "
              f"{text.count('func.func')} functions, {len(re.findall('call @', text))} "
              f"call sites, kernel bodies {dict(kernels)}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
