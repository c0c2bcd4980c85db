"""The race behind `layers/kda._pair_scores`'s kernel path (ISSUE 33, 34):
the pair scores of every chunk of one KDA layer at one shape, each candidate
timed forward and as the layer runs it under its two checkpoints (`jax.grad`
of a `jax.checkpoint`: forward, recomputed forward, backward), with its
scores and its gradients of x, k and cum held against the XLA form's; then
the whole delta rule (`kda_chunked`) with each candidate in it. Beside every
time on the chip stand the seconds the candidate costs a step program before
its first step: tracing and lowering it (Python and, for a kernel, the
Mosaic lowering of its body) and compiling it (XLA and the chip's Mosaic
compiler; no compile cache is on here), so that a kernel is sized on both
axes at once: the step traces and lowers each program once whatever the
number of layers (`kda_chunked` is jitted), and compiles it once a cold
machine. Run it on the chip; it refuses every other platform.

    chiprun -- python tools/race_kda_pair_scores.py \
        [--out chiprun_out/race_kda_pair_scores.jsonl]

Candidates: `xla` (`_pair_scores_slabs`, the path every other platform,
dtype and shape takes) and `kernel` (`ops/kda_pair_scores.pair_scores`: the
whole chunk in one Pallas call, forward and backward, the loop over the 16
shifts unrolled). `--also module:function` adds a candidate `function(x, k,
cum, visible)` from a file that is not in the tree: PR 33 raced the kernels
with a `fori_loop` over the shifts (rotating by its index, and by one lane a
trip), with each diagonal written as a row and skewed once, and with the row
operands as arrays of their own that way (PERF.md section 6 has the table).
`--scores-only` leaves the whole rule out: the kernels alone compile in
seconds. The layer's heads go in groups (`kda_chunked`), so the pair scores
are raced a group at a time, as the layer calls them. One JSON line a timing
goes to `--out` as it is made; the table is printed at the end.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from tools.race_segment_attention import packed_segment_ids  # noqa: E402


def staged(fn, *operands):
    """(the compiled `fn`, seconds to trace and lower it, to compile it)."""
    started = time.perf_counter()
    lowered = fn.lower(*operands)
    lowered_at = time.perf_counter()
    compiled = lowered.compile()
    return compiled, lowered_at - started, time.perf_counter() - lowered_at


def milliseconds_a_call(iters, compiled, *operands):
    """Milliseconds a call of `compiled` over `iters` calls, after one."""
    import jax

    jax.block_until_ready(compiled(*operands))
    started = time.perf_counter()
    for _ in range(iters):
        out = compiled(*operands)
    jax.block_until_ready(out)
    return (time.perf_counter() - started) / iters * 1e3


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
    parser.add_argument("--scores-only", action="store_true",
                        help="leave the whole rule out: kernels alone compile in seconds")
    parser.add_argument("--also", action="append", default=[])
    parser.add_argument("--out", default=None)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensor2robot_tpu.layers import kda
    from tensor2robot_tpu.ops import kda_pair_scores

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("race_kda_pair_scores: a time comes from the chip only")

    seq, heads, dim, chunk = args.seq, args.heads, args.dim, args.chunk
    doc = kda.document_index(jnp.asarray(packed_segment_ids(args.seed, seq)))
    keys = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    draw = lambda key: jax.random.normal(key, (1, seq, heads, dim), jnp.float32)
    q = (unit(draw(keys[0])) * dim ** -0.5).astype(jnp.bfloat16)
    k = unit(draw(keys[1])).astype(jnp.bfloat16)
    v = draw(keys[2]).astype(jnp.bfloat16)
    g = -2 * args.strength * jax.random.uniform(keys[3], (1, seq, heads, dim))
    beta = jax.random.uniform(keys[4], (1, seq, heads))
    weight = draw(keys[5])
    print(f"{jax.devices()[0].device_kind}: q, k, v {q.shape} bf16, chunk {chunk}, "
          f"sub-block {kda.SUB_BLOCK}, {int(doc.max()) + 1} documents", flush=True)

    # One group of heads' operands of `_pair_scores`, as `_kda_heads` makes them.
    group = heads // kda.head_groups(1, seq, heads, dim)
    chunks = seq // chunk
    split = lambda t: jnp.moveaxis(
        t[:, :, :group].reshape(1, chunks, chunk, group, dim), 3, 2)
    q_c, k_c, g_c = split(q), split(k), split(g)
    x_c = jnp.stack([q_c, k_c], axis=3)
    cum = jnp.cumsum(g_c, axis=-2)
    doc_c = doc.reshape(1, chunks, chunk)
    visible = (doc_c[..., :, None] == doc_c[..., None, :])[:, :, None]
    score_weight = jax.random.normal(
        keys[5], (1, chunks, group, 2, chunk, chunk), jnp.float32)
    print(f"pair scores a group of {group} heads: x {x_c.shape}", flush=True)

    milliseconds = functools.partial(milliseconds_a_call, args.iters)

    def forward_and_both(scores):
        def loss(x, k, cum):
            out = jax.checkpoint(lambda *a: scores(*a, visible))(x, k, cum)
            return jnp.sum(out * score_weight)

        return (jax.jit(lambda x, k, cum: scores(x, k, cum, visible)),
                jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))))

    def layer_forward_and_both(scores):
        """The whole rule with `scores` in it, read when it is traced."""
        def rule(q, k, v, g, beta):
            saved = kda._pair_scores
            kda._pair_scores = scores
            kda.kda_chunked.clear_cache()    # jitted: traced with `saved` before
            try:
                return kda.kda_chunked(q, k, v, g, beta, doc, chunk)
            finally:
                kda._pair_scores = saved

        def loss(*operands):
            out = jax.checkpoint(rule)(*operands)
            return jnp.sum(out.astype(jnp.float32) * weight)

        return jax.jit(rule), jax.jit(jax.value_and_grad(loss, argnums=range(5)))

    as_f32 = lambda tree: [np.asarray(t, np.float32) for t in tree]
    want = want_layer = None
    rows = []

    def race(name, scores):
        """One line of the table; a candidate the compiler refuses says why."""
        nonlocal want, want_layer
        row = {"candidate": name}
        try:
            jax.clear_caches()       # every candidate traces from nothing
            forward, both = forward_and_both(scores)
            forward, *_ = staged(forward, x_c, k_c, cum)
            both, row["lower_s"], row["compile_s"] = staged(both, x_c, k_c, cum)
            row["forward_ms"] = milliseconds(forward, x_c, k_c, cum)
            row["forward_backward_ms"] = milliseconds(both, x_c, k_c, cum)
            got = as_f32((forward(x_c, k_c, cum),) + both(x_c, k_c, cum)[1])
            got_layer = []
            if not args.scores_only:
                layer, layer_both = layer_forward_and_both(scores)
                layer, *_ = staged(layer, q, k, v, g, beta)
                layer_both, row["layer_lower_s"], row["layer_compile_s"] = staged(
                    layer_both, q, k, v, g, beta)
                row["layer_forward_ms"] = milliseconds(layer, q, k, v, g, beta)
                row["layer_forward_backward_ms"] = milliseconds(
                    layer_both, q, k, v, g, beta)
                got_layer = as_f32(
                    (layer(q, k, v, g, beta),) + layer_both(q, k, v, g, beta)[1])
            if want is None:
                want, want_layer = got, got_layer
            # Largest gap to the XLA form over its largest value, per output.
            gap = lambda got, want: [
                float(np.abs(a - b).max() / np.abs(b).max()) for a, b in zip(got, want)
            ]
            row["finite"] = all(bool(np.isfinite(t).all()) for t in got + got_layer)
            row["gap"] = gap(got, want)               # scores, dx, dk, dcum
            row["layer_gap"] = gap(got_layer, want_layer)   # o, dq, dk, dv, dg, dbeta
        except Exception as error:  # a Mosaic or VMEM refusal: part of the result
            row["refused"] = f"{type(error).__name__}: {str(error)[:600]}"
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as handle:
                handle.write(json.dumps(row) + "\n")

    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    sub = min(kda.SUB_BLOCK, chunk)
    race("xla", kda._pair_scores_slabs)
    race("kernel", functools.partial(kda_pair_scores.pair_scores, sub=sub))
    for spec in args.also:
        module, _, function = spec.partition(":")
        race(function, getattr(importlib.import_module(module), function))

    # ms on the chip, then the seconds before a first step (`fwd+re+bwd` both).
    print(f"\n{'candidate':<20}{'scores fwd':>11}{'fwd+re+bwd':>11}{'lower s':>9}"
          f"{'compile s':>10}{'rule fwd':>10}{'fwd+re+bwd':>11}{'lower s':>9}"
          f"{'compile s':>10}  worst gap (scores, dx, dk, dcum | o, dq, dk, dv, dg, dbeta)")
    columns = (("forward_ms", 11), ("forward_backward_ms", 11), ("lower_s", 9),
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
              + " ".join(f"{x:.1e}" for x in row["layer_gap"]))
    print(f"{sum('refused' in r for r in rows)} candidates refused by the compiler")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
