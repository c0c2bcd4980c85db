"""The race behind `layers/kda._chunk_outputs`'s kernel path (ISSUE 37): the
walk over the chunks of one group of heads of one KDA layer at one shape (the
carry between chunks and the products that read the chunks' states), each
candidate timed forward and as the layer runs it under its checkpoint
(`jax.grad` of a `jax.checkpoint`: forward, recomputed forward, backward),
with its output and its six gradients (w, u, k_end, kept, q_start, scores)
held against the XLA form's; then the whole delta rule (`kda_chunked`) with a
candidate in it. Beside every time on the chip stand the seconds the candidate
costs a step program before its first step: tracing and lowering it, and
compiling it (no compile cache is on here), so that a kernel is sized on both
axes at once (`tools/race_kda_pair_scores.py` says why). Run it on the chip; it
refuses every other platform.

    chiprun -- python tools/race_kda_carry.py [--out chiprun_out/race_kda_carry.jsonl]

Candidates: `xla` (`_chunk_outputs_scan`: a `lax.scan` that stacks the states
and three einsums over them, the path every other platform, dtype and shape
takes), `xla_unroll4` and `xla_unroll8` (the same with `lax.scan(..., unroll=)`:
the cheap yardstick), `kernel_hb8` and `kernel_hb16`
(`ops/kda_carry.chunk_outputs` at 8 and 16 heads a grid step). `--also
module:function` adds a candidate `function(w, u, k_end, kept, q_start,
scores)` from a file that is not in the tree. `--rule` names the candidates
that are also raced inside the whole rule (a minute of compiles each; none
with `--rule ""`). The operands are the ones `_kda_heads` hands the walk, from
seeded q, k, v, g, beta and packed documents. One JSON line a timing goes to
`--out` as it is made; the table is printed at the end.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from tools.race_kda_pair_scores import milliseconds_a_call, staged  # noqa: E402
from tools.race_segment_attention import packed_segment_ids  # noqa: E402

GRADIENTS = ("w", "u", "k_end", "kept", "q_start", "scores")


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seq", type=int, default=16384)
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--dim", type=int, default=128)
    parser.add_argument("--chunk", type=int, default=64)
    parser.add_argument("--strength", type=float, default=0.05,
                        help="mean of -g a step and channel")
    parser.add_argument("--seed", type=int, default=2147483659)
    parser.add_argument("--iters", type=int, default=5)
    parser.add_argument("--rule", default="xla,kernel_hb8,kernel_hb16",
                        help="candidates raced inside the whole rule too")
    parser.add_argument("--also", action="append", default=[])
    parser.add_argument("--out", default=None)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from tensor2robot_tpu.layers import kda
    from tensor2robot_tpu.ops import kda_carry

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("race_kda_carry: a time comes from the chip only")

    seq, heads, dim, chunk = args.seq, args.heads, args.dim, args.chunk
    doc = kda.document_index(jnp.asarray(packed_segment_ids(args.seed, seq)))
    keys = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 7)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    draw = lambda key: jax.random.normal(key, (1, seq, heads, dim), jnp.float32)
    q = (unit(draw(keys[0])) * dim ** -0.5).astype(jnp.bfloat16)
    k = unit(draw(keys[1])).astype(jnp.bfloat16)
    v = draw(keys[2]).astype(jnp.bfloat16)
    g = -2 * args.strength * jax.random.uniform(keys[3], (1, seq, heads, dim))
    beta = jax.random.uniform(keys[4], (1, seq, heads))
    weight = draw(keys[5])
    print(f"{jax.devices()[0].device_kind}: q, k, v {q.shape} bf16, chunk {chunk}, "
          f"{int(doc.max()) + 1} documents", flush=True)

    def with_global(module, name, value, call):
        """`call()` with `module.name` set to `value` while it is traced."""
        saved = getattr(module, name)
        setattr(module, name, value)
        try:
            return call()
        finally:
            setattr(module, name, saved)

    # One group of heads' operands of `_chunk_outputs`, as `_kda_heads` makes them.
    group = heads // kda.head_groups(1, seq, heads, dim)

    def walk_operands(*tensors):
        held = []

        def capture(*operands):
            held.extend(operands)
            return kda._chunk_outputs_scan(*operands)

        with_global(kda, "_chunk_outputs", capture, lambda: kda._kda_heads(
            *(t[:, :, :group] for t in tensors), doc=doc, chunk=chunk))
        return tuple(held)

    operands = jax.jit(walk_operands)(q, k, v, g, beta)
    out_weight = jax.random.normal(
        keys[6], operands[1].shape, jnp.float32)
    print(f"the walk of a group of {group} heads: w {operands[0].shape}, "
          f"scores {operands[5].shape}", flush=True)

    milliseconds = functools.partial(milliseconds_a_call, args.iters)

    def forward_and_both(walk):
        def loss(*operands):
            out = jax.checkpoint(walk)(*operands)
            return jnp.sum(out.astype(jnp.float32) * out_weight)

        return jax.jit(walk), jax.jit(jax.value_and_grad(loss, argnums=range(6)))

    def layer_forward_and_both(walk):
        """The whole rule with `walk` in it, read when it is traced."""
        def rule(*tensors):
            kda.kda_chunked.clear_cache()    # jitted: traced with another walk before
            return with_global(kda, "_chunk_outputs", walk, lambda: kda.kda_chunked(
                *tensors, doc, chunk))

        def loss(*tensors):
            out = jax.checkpoint(rule)(*tensors)
            return jnp.sum(out.astype(jnp.float32) * weight)

        return jax.jit(rule), jax.jit(jax.value_and_grad(loss, argnums=range(5)))

    as_f32 = lambda tree: [np.asarray(t, np.float32) for t in tree]
    # Largest gap to the XLA form over its largest value, per output.
    gap = lambda got, want: [
        float(np.abs(a - b).max() / np.abs(b).max()) for a, b in zip(got, want)
    ]
    want = want_layer = None
    rows = []
    in_the_rule = set(filter(None, args.rule.split(",")))

    def race(name, walk):
        """One line of the table; a candidate the compiler refuses says why."""
        nonlocal want, want_layer
        row = {"candidate": name}
        try:
            jax.clear_caches()       # every candidate traces from nothing
            forward, both = forward_and_both(walk)
            forward, *_ = staged(forward, *operands)
            both, row["lower_s"], row["compile_s"] = staged(both, *operands)
            row["forward_ms"] = milliseconds(forward, *operands)
            row["forward_backward_ms"] = milliseconds(both, *operands)
            got = as_f32((forward(*operands),) + both(*operands)[1])
            row["finite"] = all(bool(np.isfinite(t).all()) for t in got)
            want = got if want is None else want
            row["gap"] = gap(got, want)               # out, then GRADIENTS
            if name in in_the_rule:
                layer, layer_both = layer_forward_and_both(walk)
                layer, *_ = staged(layer, q, k, v, g, beta)
                layer_both, row["layer_lower_s"], row["layer_compile_s"] = staged(
                    layer_both, q, k, v, g, beta)
                row["layer_forward_ms"] = milliseconds(layer, q, k, v, g, beta)
                row["layer_forward_backward_ms"] = milliseconds(
                    layer_both, q, k, v, g, beta)
                got_layer = as_f32(
                    (layer(q, k, v, g, beta),) + layer_both(q, k, v, g, beta)[1])
                row["finite"] &= all(bool(np.isfinite(t).all()) for t in got_layer)
                want_layer = got_layer if want_layer is None else want_layer
                row["layer_gap"] = gap(got_layer, want_layer)   # o, dq, dk, dv, dg, dbeta
        except Exception as error:  # a Mosaic or VMEM refusal: part of the result
            row["refused"] = f"{type(error).__name__}: {str(error)[:600]}"
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as handle:
                handle.write(json.dumps(row) + "\n")

    def unrolled(unroll):
        """The XLA form with its scan unrolled `unroll` trips a loop trip."""
        return lambda *operands: with_global(
            lax, "scan", functools.partial(lax.scan, unroll=unroll),
            lambda: kda._chunk_outputs_scan(*operands))

    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    race("xla", kda._chunk_outputs_scan)
    race("xla_unroll4", unrolled(4))
    race("xla_unroll8", unrolled(8))
    for hb in (8, 16):
        if kda_carry.tiles(operands[0], operands[1], hb):
            race(f"kernel_hb{hb}",
                 lambda *operands, hb=hb: kda_carry.chunk_outputs(*operands, hb))
    for spec in args.also:
        module, _, function = spec.partition(":")
        race(function, getattr(importlib.import_module(module), function))

    # ms on the chip, then the seconds before a first step (`fwd+re+bwd` both).
    print(f"\n{'candidate':<20}{'walk fwd':>10}{'fwd+re+bwd':>11}{'lower s':>9}"
          f"{'compile s':>10}{'rule fwd':>10}{'fwd+re+bwd':>11}{'lower s':>9}"
          f"{'compile s':>10}  worst gap (out, d{', d'.join(GRADIENTS)} | "
          "o, dq, dk, dv, dg, dbeta)")
    columns = (("forward_ms", 10), ("forward_backward_ms", 11), ("lower_s", 9),
               ("compile_s", 10), ("layer_forward_ms", 10),
               ("layer_forward_backward_ms", 11), ("layer_lower_s", 9),
               ("layer_compile_s", 10))
    for row in rows:
        if "refused" in row:
            continue
        print(f"{row['candidate']:<20}"
              + "".join(f"{format(row[key], '.3f') if key in row else '':>{width}}"
                        for key, width in columns) + "  "
              + " ".join(f"{x:.1e}" for x in row["gap"]) + " | "
              + " ".join(f"{x:.1e}" for x in row.get("layer_gap", [])))
    print(f"{sum('refused' in r for r in rows)} candidates refused by the compiler")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
