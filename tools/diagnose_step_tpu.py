"""On-chip bisection of the flagship train step: which part is slow?

Times, in one process on the chip, the pieces the QT-Opt critic train
step is made of, so a slow step can be attributed:

  1. dominant conv block alone (fwd / fwd+bwd)      — is the op class slow?
  2. first conv (3->64 @ 472px, stride 2) alone      — thin-channel entry?
  3. image tower forward alone                       — tower vs heads?
  4. full model forward (inference_network_fn)       — fwd vs bwd split?
  5. full train step (the bench's measurement)       — reproduces headline
  6. a reference 8192^3 bf16 matmul                  — re-pins the ceiling

Each timing is the median of windows of calls closed by
`block_until_ready`. Emits one JSON document on stdout. Fails (non-zero
exit, no document) on any platform but `tpu`: these are device timings
and mean nothing on a CPU.

Usage: python tools/diagnose_step_tpu.py
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main() -> None:
    import bench

    import jax
    import jax.numpy as jnp

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"diagnose_step_tpu: platform {device.platform!r} is not 'tpu'; "
            "these are device timings"
        )

    peak = bench._peak_flops(device)
    out = {"metric": "train_step_diagnosis", "ok": True,
           "device_kind": getattr(device, "device_kind", "?"),
           "peak_flops": peak, "cases": {}}

    def timed(fn, args, n_warm=2, n_windows=6, calls=6):
        """Median seconds per call over windows of `calls` dispatches,
        each window closed by block_until_ready."""
        for _ in range(n_warm):
            jax.block_until_ready(fn(*args))
        times = []
        for _ in range(n_windows):
            t0 = time.perf_counter()
            for _ in range(calls):
                out_tree = fn(*args)
            jax.block_until_ready(out_tree)
            times.append((time.perf_counter() - t0) / calls)
        return statistics.median(times)

    def record(name, seconds, flops=None, extra=None):
        row = {"ms": round(seconds * 1e3, 3)}
        if flops:
            row["tflops"] = round(flops / seconds / 1e12, 2)
            row["pct_peak"] = round(100.0 * flops / seconds / peak, 2)
        if extra:
            row.update(extra)
        out["cases"][name] = row
        print(f"diag: {name}: {row}", file=sys.stderr)

    B = 64
    key = jax.random.PRNGKey(0)

    # --- 6. matmul ceiling first (cheap, re-pins the reference point) ---
    n = 8192
    a = jax.random.normal(key, (n, n), jnp.bfloat16)
    b = jax.random.normal(key, (n, n), jnp.bfloat16)
    mm = jax.jit(lambda a, b: a @ b)
    t = timed(mm, (a, b))
    record("matmul_8192_bf16", t, flops=2.0 * n**3)

    # --- 0. per-kernel overhead probe. The compiled train step holds ~700
    # schedulable kernels; a chain of N dependent small matmuls
    # (unfusable, ~us of compute each) measures the fixed cost per kernel
    # execution directly, and two lengths check linearity. ---
    def chain(n):
        def f(y, w):
            for _ in range(n):
                y = y @ w
            return y
        return jax.jit(f)

    y0 = jax.random.normal(key, (128, 128), jnp.bfloat16)
    w0 = jax.random.normal(key, (128, 128), jnp.bfloat16)
    for n in (20, 200):
        t = timed(chain(n), (y0, w0))
        record(f"kernel_chain_{n}", t,
               extra={"ms_per_kernel": round(t * 1e3 / n, 4)})

    # --- 1. dominant conv block: 5x5 64->64 @ 79x79, batch 64 ---
    import flax.linen as nn

    class Block(nn.Module):
        @nn.compact
        def __call__(self, x):
            for _ in range(6):
                x = nn.Conv(64, (5, 5), padding="SAME", use_bias=False,
                            dtype=jnp.bfloat16)(x)
                x = nn.relu(x)
            return x

    x79 = jax.random.normal(key, (B, 79, 79, 64), jnp.bfloat16)
    blk = Block()
    pb = blk.init(key, x79)
    blk_fwd = jax.jit(lambda p, x: blk.apply(p, x))
    flops_blk = 6 * 2.0 * B * 79 * 79 * (5 * 5 * 64) * 64
    t = timed(blk_fwd, (pb, x79))
    record("conv5x5_block6_fwd", t, flops=flops_blk)

    def blk_loss(p, x):
        return jnp.sum(blk.apply(p, x).astype(jnp.float32))

    blk_bwd = jax.jit(jax.grad(blk_loss))
    t = timed(blk_bwd, (pb, x79))
    record("conv5x5_block6_fwd_bwd", t, flops=3.0 * flops_blk)

    # --- controls: is the slowness specific to dtype or kernel size? ---
    # f32 twin of the dominant block: if f32 is ~as fast (or faster), the
    # bf16 conv lowering on this backend is broken, not convs in general.
    class BlockF32(nn.Module):
        @nn.compact
        def __call__(self, x):
            for _ in range(6):
                x = nn.Conv(64, (5, 5), padding="SAME",
                            use_bias=False)(x)
                x = nn.relu(x)
            return x

    blk32 = BlockF32()
    x79_32 = x79.astype(jnp.float32)
    pb32 = blk32.init(key, x79_32)
    t = timed(jax.jit(lambda p, x: blk32.apply(p, x)), (pb32, x79_32))
    record("conv5x5_block6_f32_fwd", t, flops=flops_blk)

    # 1x1-conv block (a pure matmul in conv clothing) at the same tensor
    # shapes: fast 1x1 + slow 5x5 => spatial conv lowering is the problem;
    # both slow => the conv op class (or this backend's conv path) is.
    class Block1x1(nn.Module):
        @nn.compact
        def __call__(self, x):
            for _ in range(6):
                x = nn.Conv(64, (1, 1), padding="SAME", use_bias=False,
                            dtype=jnp.bfloat16)(x)
                x = nn.relu(x)
            return x

    blk1 = Block1x1()
    pb1 = blk1.init(key, x79)
    flops_1x1 = 6 * 2.0 * B * 79 * 79 * 64 * 64
    t = timed(jax.jit(lambda p, x: blk1.apply(p, x)), (pb1, x79))
    record("conv1x1_block6_fwd", t, flops=flops_1x1)

    # --- same block WITH BatchNorm (the real tower's composition) ---
    class BlockBN(nn.Module):
        @nn.compact
        def __call__(self, x):
            for _ in range(6):
                x = nn.Conv(64, (5, 5), padding="SAME", use_bias=False,
                            dtype=jnp.bfloat16)(x)
                x = nn.BatchNorm(use_running_average=False,
                                 momentum=0.9997)(x)
                x = nn.relu(x).astype(jnp.bfloat16)
            return x

    bnblk = BlockBN()
    pbn = bnblk.init(key, x79)

    def bn_loss(p, x):
        y, _ = bnblk.apply(p, x, mutable=["batch_stats"])
        return jnp.sum(y.astype(jnp.float32))

    t = timed(jax.jit(jax.grad(bn_loss)), (pbn, x79))
    record("conv5x5_block6_bn_fwd_bwd", t, flops=3.0 * flops_blk)

    # --- round-4 A/Bs: the BN-compute-dtype fix, the pool backward,
    # and the conv-efficiency hypotheses (odd 79x79 spatial tiling;
    # 64 channels on the 128-lane MXU). Each pairs with a control above
    # so the post-fix chip session decomposes the remaining step time. ---
    class BlockBNFix(nn.Module):
        """The round-4 tower composition: BN in the compute dtype."""

        @nn.compact
        def __call__(self, x):
            for _ in range(6):
                x = nn.Conv(64, (5, 5), padding="SAME", use_bias=False,
                            dtype=jnp.bfloat16)(x)
                x = nn.BatchNorm(use_running_average=False, momentum=0.9997,
                                 dtype=jnp.bfloat16)(x)
                x = nn.relu(x)
            return x

    bnfix = BlockBNFix()
    pbnf = bnfix.init(key, x79)

    def bnfix_loss(p, x):
        y, _ = bnfix.apply(p, x, mutable=["batch_stats"])
        return jnp.sum(y.astype(jnp.float32))

    t = timed(jax.jit(jax.grad(bnfix_loss)), (pbnf, x79))
    record("conv5x5_block6_bnfix_fwd_bwd", t, flops=3.0 * flops_blk)

    # BN-stats A/B: the r05 sync-op profile bills ~18 ms/step to reduce
    # fusions (BN mean/var at 64 channels = half-empty 128-lane tiles).
    # Candidate fix: put the reduction on the MXU as a ones-row matmul
    # (bf16 inputs accumulate f32 on TPU). Three cases: the vector
    # reduce at c64, the dot form at c64, and the vector reduce at c128
    # (isolates the tile-occupancy effect on the reduce itself).
    x79s = jax.random.normal(key, (B, 79, 79, 64), jnp.bfloat16)

    def stats_reduce(x):
        xf = x.astype(jnp.float32)
        m = jnp.mean(xf, axis=(0, 1, 2))
        v = jnp.mean(xf * xf, axis=(0, 1, 2)) - m * m
        return m, v

    t = timed(jax.jit(stats_reduce), (x79s,))
    record("bn_stats_reduce_c64", t)

    def stats_dot(x):
        n = x.shape[0] * x.shape[1] * x.shape[2]
        xf = x.reshape(n, x.shape[3])
        ones = jnp.ones((8, n), jnp.bfloat16)  # 8 rows fill the sublanes
        s = (ones @ xf)[0].astype(jnp.float32) / n
        s2 = (ones @ (xf * xf))[0].astype(jnp.float32) / n
        return s, s2 - s * s

    t = timed(jax.jit(stats_dot), (x79s,))
    record("bn_stats_dot_c64", t)

    t = timed(
        jax.jit(stats_reduce),
        (jax.random.normal(key, (B, 79, 79, 128), jnp.bfloat16),),
    )
    record("bn_stats_reduce_c128", t)

    # Stem-pool backward (SelectAndScatter) at the stem activation size.
    from tensor2robot_tpu.ops.pooling import max_pool

    x236 = jax.random.normal(key, (B, 236, 236, 64), jnp.bfloat16)

    def pool_loss(x):
        return jnp.sum(max_pool(x, (3, 3)).astype(jnp.float32))

    t = timed(jax.jit(jax.grad(pool_loss)), (x236,))
    record("stem_pool_bwd_selectscatter", t)

    # Spatial-tiling hypothesis: same block at 80x80 (8-aligned) vs the
    # tower's 79x79. A large gap would justify padding the tower stages.
    x80 = jax.random.normal(key, (B, 80, 80, 64), jnp.bfloat16)
    t = timed(blk_fwd, (pb, x80))
    record("conv5x5_block6_pad80_fwd", t,
           flops=6 * 2.0 * B * 80 * 80 * (5 * 5 * 64) * 64)

    # Channel-width hypothesis: 64 channels fill half the 128-lane MXU.
    # A 128-channel twin at matched depth shows the achievable pct_peak
    # when the lanes are full — the architecture-ceiling datapoint for
    # the written analysis.
    class Block128(nn.Module):
        @nn.compact
        def __call__(self, x):
            for _ in range(6):
                x = nn.Conv(128, (5, 5), padding="SAME", use_bias=False,
                            dtype=jnp.bfloat16)(x)
                x = nn.relu(x)
            return x

    blk128 = Block128()
    x79c128 = jax.random.normal(key, (B, 79, 79, 128), jnp.bfloat16)
    pb128 = blk128.init(key, x79c128)
    t = timed(jax.jit(lambda p, x: blk128.apply(p, x)), (pb128, x79c128))
    record("conv5x5_block6_c128_fwd", t,
           flops=6 * 2.0 * B * 79 * 79 * (5 * 5 * 128) * 128)

    # --- 2. entry conv: 6x6x3->64 /2 @ 472px ---
    class Entry(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Conv(64, (6, 6), strides=(2, 2), padding="SAME",
                           use_bias=False, dtype=jnp.bfloat16)(x)

    x472 = jax.random.normal(key, (B, 472, 472, 3), jnp.bfloat16)
    ent = Entry()
    pe = ent.init(key, x472)
    flops_ent = 2.0 * B * 236 * 236 * (6 * 6 * 3) * 64
    t = timed(jax.jit(lambda p, x: ent.apply(p, x)), (pe, x472))
    record("entry_conv_472_fwd", t, flops=flops_ent)

    def ent_loss(p, x):
        return jnp.sum(ent.apply(p, x).astype(jnp.float32))

    t = timed(jax.jit(jax.grad(ent_loss)), (pe, x472))
    record("entry_conv_472_fwd_bwd", t, flops=3.0 * flops_ent)

    # Space-to-depth twin of the entry conv: the PRODUCTION lowering
    # (layers/s2d_conv.SpaceToDepthConv, including its traced-in kernel
    # refold from the checkpoint layout), so this A/B measures exactly
    # what flipping stem_s2d_enabled's auto rule would run. Identical
    # output resolution and matched FLOPs; measures whether the classic
    # TPU stem transform fixes the tiny-C_in MXU inefficiency (entry conv
    # measured ~0.6-2% of peak raw).
    from tensor2robot_tpu.layers.s2d_conv import SpaceToDepthConv

    ent2 = SpaceToDepthConv(64, (6, 6), strides=(2, 2), dtype=jnp.bfloat16)
    pe2 = ent2.init(key, x472)
    flops_ent2 = 2.0 * B * 236 * 236 * (3 * 3 * 12) * 64
    t = timed(jax.jit(lambda p, x: ent2.apply(p, x)), (pe2, x472))
    record("entry_conv_472_s2d_fwd", t, flops=flops_ent2)

    def ent2_loss(p, x):
        return jnp.sum(ent2.apply(p, x).astype(jnp.float32))

    t = timed(jax.jit(jax.grad(ent2_loss)), (pe2, x472))
    record("entry_conv_472_s2d_fwd_bwd", t, flops=3.0 * flops_ent2)

    # --- 3/4/5. the real model: tower fwd, full fwd, full train step ---
    from __graft_entry__ import _flagship
    from tensor2robot_tpu.train.train_eval import CompiledModel

    model, batch = _flagship(image_size=(472, 472), batch_size=B,
                             num_convs=(6, 6, 3))
    compiled = CompiledModel(model, donate_state=False)
    state = compiled.init_state(jax.random.PRNGKey(0), batch)
    sharded = compiled.shard_batch(batch)
    rng = jax.random.PRNGKey(1)

    try:
        # Full forward + loss, no grads (already jit with static use_ema).
        t = timed(lambda s, b: compiled.eval_step(s, b, False),
                  (state, sharded))
        record("model_fwd_eval_step", t)
    except Exception as err:  # noqa: BLE001 — recorded per case; one
        # failed case must not discard an otherwise-complete diagnosis.
        out["cases"]["model_fwd_eval_step"] = {"case_error": str(err)[:200]}

    t = timed(compiled.train_step, (state, sharded, rng))
    try:
        cost = compiled.train_step.lower(state, sharded, rng).compile()
        step_flops = float(cost.cost_analysis()["flops"])
    except Exception:  # noqa: BLE001
        step_flops = None
    record("full_train_step", t, flops=step_flops)

    print(json.dumps(out))


if __name__ == "__main__":
    main()
