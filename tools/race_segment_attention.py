"""The race behind `ops/flash_attention.segment_attention`'s kernel path
(ISSUE 29): causal, same-document, grouped-query attention at one shape,
each candidate timed forward and forward + backward as a layer under
`nn.remat` runs it (`jax.grad` of a `jax.checkpoint`: forward, recomputed
forward, backward), with its outputs and gradients held against the einsum
path's. Run it on the chip; it refuses every other platform.

    chiprun -- python tools/race_segment_attention.py [--sweep] \
        [--out chiprun_out/race_segment_attention.jsonl]

Candidates: `einsum` (`_segment_einsum`, the path every other platform and
shape takes), `splash` (`_segment_kernel` at `SEGMENT_KERNEL_BLOCKS`; with
`--sweep` over `--tiles`, forward first, then the backward at
the forward's winner, fused and with a dq kernel of its own) and
`upstream_flash` (`jax.experimental.pallas.ops.tpu.flash_attention` with
`segment_ids`, keys repeated over their group). `--also module:function`
adds a candidate `function(q, k, v, segment_ids, scale)` from a file that is
not in the tree. One JSON line a timing goes to `--out` as it is made; the
table is printed at the end.
"""

from __future__ import annotations

import argparse
import importlib
import itertools
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

def packed_segment_ids(seed, seq, median=512, sigma=1.25, least=16):
    """[1, seq] ids of log-normal documents packed until the next does not
    fit, 0 for the padding left: the benchmark's token traffic."""
    import numpy as np

    rng = np.random.default_rng(seed)
    ids = np.zeros((1, seq), np.int32)
    used = count = 0
    while True:
        length = int(np.clip(round(rng.lognormal(np.log(median), sigma)), least, seq))
        if used + length > seq:
            return ids
        count += 1
        ids[0, used:used + length] = count
        used += length


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seq", type=int, default=8192)
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--kv-heads", type=int, default=8)
    parser.add_argument("--dim", type=int, default=64)
    parser.add_argument("--scale", type=float, default=1 / 64)
    parser.add_argument("--seed", type=int, default=2147483659)
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--sweep", action="store_true")
    parser.add_argument("--tiles", default="256,512,1024,2048",
                        help="the tile sizes --sweep tries")
    parser.add_argument("--also", action="append", default=[])
    parser.add_argument("--out", default=None)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensor2robot_tpu.ops import flash_attention as fa

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("race_segment_attention: a time comes from the chip only")

    segment_ids = jnp.asarray(packed_segment_ids(args.seed, args.seq))
    keys = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 4)
    q, weight = (
        jax.random.normal(key, (1, args.seq, args.heads, args.dim), jnp.bfloat16)
        for key in keys[:2]
    )
    k, v = (
        jax.random.normal(key, (1, args.seq, args.kv_heads, args.dim), jnp.bfloat16)
        for key in keys[2:]
    )
    print(f"{jax.devices()[0].device_kind}: q {q.shape} k, v {k.shape} bf16, "
          f"scale {args.scale}, {int(segment_ids.max())} documents, "
          f"{int((segment_ids == 0).sum())} positions of padding", flush=True)

    def milliseconds(fn):
        jax.block_until_ready(fn(q, k, v))  # compiles
        jax.block_until_ready(fn(q, k, v))
        started = time.perf_counter()
        for _ in range(args.iters):
            out = fn(q, k, v)
        jax.block_until_ready(out)
        return (time.perf_counter() - started) / args.iters * 1e3

    def forward_and_both(attend):
        """(jitted forward, jitted loss and gradients of q, k, v under a
        checkpoint). The loss is returned so that the first forward stays
        in the program: `jax.grad` alone leaves it dead and XLA drops it."""
        def forward(q, k, v):
            return attend(q, k, v, segment_ids, args.scale)

        def loss(q, k, v):
            out = jax.checkpoint(forward)(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * weight.astype(jnp.float32))

        return jax.jit(forward), jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2))
        )

    einsum_forward, einsum_both = forward_and_both(fa._segment_einsum)
    want = [np.asarray(x, np.float32)
            for x in (einsum_forward(q, k, v),) + einsum_both(q, k, v)[1]]
    rows = []

    def race(name, attend, blocks=None, forward_only=False):
        """One line of the table; a candidate the compiler refuses says why."""
        row = {"candidate": name, "blocks": blocks}
        try:
            forward, both = forward_and_both(attend)
            timed = {"forward_ms": milliseconds(forward)}
            got = [forward(q, k, v)]
            if not forward_only:
                timed["forward_backward_ms"] = milliseconds(both)
                got += both(q, k, v)[1]
            # Largest gap to the einsum path over the largest value, per output.
            timed["gap"] = [
                float(np.abs(np.asarray(g, np.float32) - w).max() / np.abs(w).max())
                for g, w in zip(got, want)
            ]
            row.update(timed)
        except Exception as error:  # a Mosaic or VMEM refusal: part of the result
            row["refused"] = f"{type(error).__name__}: {str(error)[:300]}"
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as handle:
                handle.write(json.dumps(row) + "\n")
        return row

    def splash(blocks):
        def attend(q, k, v, ids, scale):
            saved = fa.SEGMENT_KERNEL_BLOCKS
            fa.SEGMENT_KERNEL_BLOCKS = blocks  # read when the call is traced
            try:
                return fa._segment_kernel(q, k, v, ids, scale)
            finally:
                fa.SEGMENT_KERNEL_BLOCKS = saved
        return attend

    def upstream_flash(blocks):
        from jax.experimental.pallas.ops.tpu import flash_attention as upstream

        def attend(q, k, v, ids, scale):
            group = q.shape[2] // k.shape[2]
            heads_first = lambda x: x.transpose(0, 2, 1, 3)
            out = upstream.flash_attention(
                heads_first(q), heads_first(jnp.repeat(k, group, axis=2)),
                heads_first(jnp.repeat(v, group, axis=2)),
                segment_ids=upstream.SegmentIds(ids, ids), causal=True,
                sm_scale=scale, block_sizes=upstream.BlockSizes(**blocks),
            )
            return heads_first(out)
        return attend

    def upstream_blocks(bq, bk_major, bk):
        return dict(
            block_q=bq, block_k_major=bk_major, block_k=bk, block_b=1,
            block_q_major_dkv=bq, block_k_major_dkv=bk_major, block_k_dkv=bk,
            block_q_dkv=bq, block_k_major_dq=bk_major, block_k_dq=bk, block_q_dq=bq,
        )

    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    race("einsum", fa._segment_einsum)
    chosen = dict(fa.SEGMENT_KERNEL_BLOCKS)
    race("splash", splash(chosen), chosen)

    def fastest(found, key):
        found = [r for r in found if key in r]
        return min(found, key=lambda r: r[key]) if found else None

    if args.sweep:
        tiles_tried = tuple(int(t) for t in args.tiles.split(","))
        forward_names = ("block_q", "block_kv", "block_kv_compute")
        found = [
            race("splash", splash({**chosen, **dict(zip(forward_names, tiles))}),
                 dict(zip(forward_names, tiles)), forward_only=True)
            for tiles in itertools.product(tiles_tried, repeat=3) if tiles[2] <= tiles[1]
        ]
        best = fastest(found, "forward_ms")
        forward_best = best["blocks"] if best else {n: chosen[n] for n in forward_names}
        backward_names = ("block_q_dkv", "block_kv_dkv", "block_kv_dkv_compute")
        found = []
        for tiles in itertools.product(tiles_tried, repeat=3):
            if tiles[2] <= tiles[1]:
                blocks = {**forward_best, **dict(zip(backward_names, tiles)),
                          "use_fused_bwd_kernel": True}
                found.append(race("splash", splash(blocks), blocks))
        best = fastest(found, "forward_backward_ms")
        for tiles in itertools.product(tiles_tried, repeat=2):  # a dq kernel of its own
            blocks = {**(best["blocks"] if best else chosen),
                      "use_fused_bwd_kernel": False,
                      "block_q_dq": tiles[0], "block_kv_dq": tiles[1]}
            found.append(race("splash", splash(blocks), blocks))
    # Its fastest of PR 29's sweep first: the one point raced without --sweep.
    upstream_tiles = ((1024, 1024, 1024), (1024, 1024, 512), (512, 512, 512),
                      (2048, 1024, 512), (512, 2048, 512), (1024, 512, 512),
                      (256, 1024, 256), (2048, 2048, 512))
    for tiles in upstream_tiles if args.sweep else upstream_tiles[:1]:
        blocks = upstream_blocks(*tiles)
        race("upstream_flash", upstream_flash(blocks), blocks)
    for spec in args.also:
        module, _, function = spec.partition(":")
        race(function, getattr(importlib.import_module(module), function))

    print(f"\n{'candidate':<16}{'forward ms':>12}{'fwd+bwd ms':>12}  worst gap  blocks")
    for row in rows:
        if "refused" in row:
            continue
        both = row.get("forward_backward_ms")
        print(f"{row['candidate']:<16}{row['forward_ms']:>12.3f}"
              f"{'' if both is None else format(both, '.3f'):>12}"
              f"  {max(row['gap']):.2e}  {json.dumps(row['blocks'])}")
    print(f"{sum('refused' in r for r in rows)} candidates refused by the compiler")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
