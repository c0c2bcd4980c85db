"""On-chip validation of the Pallas flash-attention kernels.

Mosaic compilation, tiling constraints and VMEM limits only bite on real
hardware, so this runs every kernel entry point of ops/flash_attention.py
with interpret=False on the TPU and checks numerics against
reference_attention: the public forward and its gradients (full, causal,
sliding-window, non-power-of-two and S=8192 shapes), the ring's tile entry
points `flash_attention_tile` / `flash_attention_bwd_tile` with non-zero
global offsets, and a sequence-length ladder that records where the
kernels' whole-K/V-in-VMEM layout stops fitting — which must be a raised
error naming the shape, never a quiet reference path. Then a steady-state
microbench (calls closed by block_until_ready).

Prints ONE JSON line and exits non-zero if any numerics case fails.

    python tools/validate_flash_tpu.py [--out chiprun_out/flash.json]

The interpreter-mode twin of these cases, at toy shapes on the CPU, is
tests/test_flash_attention.py.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default=None, help="also write the JSON here")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench  # repo-root bench.py: the peaks table
    from tensor2robot_tpu.ops import flash_attention as fa
    from tensor2robot_tpu.parallel.mesh import require_devices

    device = require_devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"validate_flash_tpu: platform {device.platform!r}; the "
            "kernels compile on the chip only"
        )

    rows = []

    def rel_err(a, b):
        a = np.asarray(jax.device_get(a), np.float32)
        b = np.asarray(jax.device_get(b), np.float32)
        return float(np.max(np.abs(a - b))) / max(float(np.max(np.abs(b))), 1e-6)

    def tolerance(dtype):
        # bf16 accumulates in f32 in both paths, but the reference's
        # full softmax and flash's running rescale round differently.
        return 2e-2 if dtype == jnp.bfloat16 else 2e-3

    def inputs(shape_q, shape_k, dtype, seed=0):
        kq, kk, kv, kd = jax.random.split(jax.random.PRNGKey(seed), 4)
        return (
            jax.random.normal(kq, shape_q, dtype),
            jax.random.normal(kk, shape_k, dtype),
            jax.random.normal(kv, shape_k, dtype),
            jax.random.normal(kd, shape_q, dtype),
        )

    def record(name, shape, dtype, errs, extra=None):
        tol = tolerance(dtype)
        row = {
            "case": name, "shape": list(shape),
            "dtype": jnp.dtype(dtype).name,
            "rel_errs": {k: round(v, 6) for k, v in errs.items()},
            "tol": tol, "passed": all(v < tol for v in errs.values()),
            **(extra or {}),
        }
        rows.append(row)
        print(f"flash: {row}", file=sys.stderr, flush=True)

    def check(batch, seq, heads, dim, dtype, causal, window=None):
        """Public entry point: forward and (dq, dk, dv) vs the reference.

        The oracle must be at least as accurate as the kernel under
        test: f32 kernels run HIGHEST-precision dots, so the einsum
        reference must too — at DEFAULT both would be independently
        rounded single-pass bf16 approximations."""
        shape = (batch, seq, heads, dim)
        q, k, v, dout = inputs(shape, shape, dtype)
        prec = fa._dot_precision(dtype)

        def flash(q, k, v):
            return fa.flash_attention(
                q, k, v, causal=causal, window=window, interpret=False
            )

        def ref(q, k, v):
            return fa.reference_attention(
                q, k, v, causal=causal, window=window, precision=prec
            )

        def loss(fn):
            return lambda q, k, v: jnp.sum(
                fn(q, k, v).astype(jnp.float32) * dout.astype(jnp.float32)
            )

        out = jax.jit(flash)(q, k, v)
        grads = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
        out_ref = jax.jit(ref)(q, k, v)
        grads_ref = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
        errs = {"fwd": rel_err(out, out_ref)}
        for name, a, b in zip(("dq", "dk", "dv"), grads, grads_ref):
            errs[name] = rel_err(a, b)
        record("flash_attention", shape, dtype, errs,
               {"causal": causal, "window": window})

    def check_tiles(batch, seq, heads, dim, dtype):
        """The ring's entry points on a (q-shard x k-shard) tile with
        non-zero global offsets: two forward k-tiles merged by the
        online-softmax rule must equal full attention, and the backward
        tile must reproduce the reference's gradients for the shard."""
        half, q0 = seq // 2, seq // 4
        shape_k = (batch, seq, heads, dim)
        shape_q = (batch, half, heads, dim)
        q, k, v, dout = inputs(shape_q, shape_k, dtype, seed=1)
        prec = fa._dot_precision(dtype)
        kwargs = dict(causal=True, q_offset=q0, interpret=False)

        def merged(q, k, v):
            o1, l1, m1 = fa.flash_attention_tile(
                q, k[:, :half], v[:, :half], k_offset=0, **kwargs
            )
            o2, l2, m2 = fa.flash_attention_tile(
                q, k[:, half:], v[:, half:], k_offset=half, **kwargs
            )
            m = jnp.maximum(m1, m2)
            a1, a2 = jnp.exp(m1 - m), jnp.exp(m2 - m)
            l = l1 * a1 + l2 * a2
            t = lambda x: jnp.transpose(x, (0, 2, 1))[..., None]  # noqa: E731
            out = (o1 * t(a1) + o2 * t(a2)) / t(jnp.maximum(l, 1e-30))
            return out, m + jnp.log(jnp.maximum(l, 1e-30))

        def ref(q, k, v):
            return fa.reference_attention(
                q, k, v, causal=True, q_offset=q0, precision=prec
            )

        out, lse = jax.jit(merged)(q, k, v)
        out_ref = jax.jit(ref)(q, k, v)
        record("flash_attention_tile", shape_q, dtype,
               {"merged_fwd": rel_err(out, out_ref)},
               {"k_len": seq, "q_offset": q0, "k_offsets": [0, half]})

        def bwd(q, k, v, dout, out_ref, lse):
            return fa.flash_attention_bwd_tile(
                q, k, v, dout, lse, fa.flash_attention_bwd_delta(dout, out_ref),
                k_offset=0, **kwargs,
            )

        grads = jax.jit(bwd)(q, k, v, dout, out_ref, lse)
        grads_ref = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(
                ref(q, k, v).astype(jnp.float32) * dout.astype(jnp.float32)
            ),
            argnums=(0, 1, 2),
        ))(q, k, v)
        record("flash_attention_bwd_tile", shape_q, dtype,
               {name: rel_err(a, b) for name, a, b in
                zip(("dq", "dk", "dv"), grads, grads_ref)},
               {"k_len": seq, "q_offset": q0})

    check(2, 512, 4, 64, jnp.float32, False)
    check(2, 512, 4, 64, jnp.float32, True)
    check(2, 1024, 4, 128, jnp.bfloat16, False)
    check(2, 1024, 4, 128, jnp.bfloat16, True)
    check(1, 384, 2, 64, jnp.float32, True)  # non-pow2 seq (block picker)
    check(2, 1024, 4, 128, jnp.bfloat16, True, window=256)
    check(1, 512, 2, 64, jnp.float32, True, window=160)  # unaligned window
    check_tiles(2, 1024, 4, 128, jnp.bfloat16)
    check_tiles(1, 512, 2, 64, jnp.float32)
    check(1, 8192, 2, 128, jnp.bfloat16, True)
    # Whole-sequence blocks past the compiler's default VMEM budget: the
    # kernels must ask for more (ops/flash_attention._vmem_kwargs) and
    # still be right.
    check(1, 16384, 1, 128, jnp.float32, True)
    ok = all(row["passed"] for row in rows)

    # The forward keeps a whole (1, S_k, D) K and V block in VMEM and the
    # dk/dv backward a whole (1, S_q, ...) Q, dO and row-stat block: walk
    # S upward and record what each length does. "refused" must be the
    # kernels' own ValueError naming the shape; a Mosaic/XLA failure here
    # means the budget check in ops/flash_attention.py is wrong.
    vmem_ladder = {}
    for seq in (16384, 32768, 65536, 131072):
        shape = (1, seq, 1, 128)
        q, k, v, dout = inputs(shape, shape, jnp.bfloat16, seed=2)
        entry = {}
        for leg, fn in (
            ("fwd", lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, interpret=False)),
            ("fwd_bwd", jax.grad(
                lambda q, k, v: jnp.sum(fa.flash_attention(
                    q, k, v, causal=True, interpret=False,
                ).astype(jnp.float32) * dout.astype(jnp.float32)),
                argnums=(0, 1, 2))),
        ):
            try:
                jax.block_until_ready(jax.jit(fn)(q, k, v))
                entry[leg] = "ok"
            except ValueError as err:
                entry[leg] = f"refused: {str(err)[:300]}"
            except Exception as err:  # noqa: BLE001 — recorded as data
                entry[leg] = f"FAILED {type(err).__name__}: {str(err)[:300]}"
                ok = False
        vmem_ladder[f"s{seq}"] = entry
        print(f"flash: vmem ladder s{seq}: {entry}", file=sys.stderr,
              flush=True)

    payload = {
        "metric": "flash_attention_tpu_validation",
        "ok": ok,
        "platform": device.platform,
        "device_kind": device.device_kind,
        "interpret": False,
        "cases": rows,
        "vmem_ladder_bf16_d128": vmem_ladder,
    }

    if not tiny:
        def timed(fn, fn_args, n_warm=2, n_windows=5, calls=10):
            for _ in range(n_warm):
                jax.block_until_ready(fn(*fn_args))
            times = []
            for _ in range(n_windows):
                t0 = time.perf_counter()
                for _ in range(calls):
                    out = fn(*fn_args)
                jax.block_until_ready(out)
                times.append((time.perf_counter() - t0) / calls)
            return statistics.median(times)

        b, s, h, d = 4, 2048, 8, 128
        shape = (b, s, h, d)
        q, k, v, _ = inputs(shape, shape, jnp.bfloat16, seed=3)

        def loss_of(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32))

        def flash(q, k, v, **kw):
            return fa.flash_attention(q, k, v, causal=True, **kw)

        def ref(q, k, v):
            return fa.reference_attention(q, k, v, causal=True)

        t_fwd = timed(jax.jit(flash), (q, k, v))
        t_fwdbwd = timed(
            jax.jit(jax.grad(loss_of(flash), argnums=(0, 1, 2))), (q, k, v)
        )
        # Block-size sweep: Mosaic tiling sweet spots are hardware facts;
        # a combination the compiler rejects is data, not a failure.
        block_sweep = {}
        for bq, bk in ((128, 128), (256, 128), (128, 256), (256, 256),
                       (512, 128)):
            try:
                block_sweep[f"{bq}x{bk}"] = round(timed(
                    jax.jit(lambda q, k, v, bq=bq, bk=bk: flash(
                        q, k, v, block_q=bq, block_k=bk)),
                    (q, k, v),
                ) * 1e3, 3)
            except Exception as err:  # noqa: BLE001 — recorded as data
                block_sweep[f"{bq}x{bk}"] = (
                    f"{type(err).__name__}: {str(err)[:160]}"
                )
        # A/B vs plain-XLA attention on each side of FLASH_AUTO_SEQ. Each
        # leg has its own try: the reference path running out of memory
        # at long S is itself a result.
        ab_compare = {}
        for ab_b, ab_s in ((4, 1024), (1, 4096), (1, 8192)):
            ab_shape = (ab_b, ab_s, h, d)
            aq, ak, av, _ = inputs(ab_shape, ab_shape, jnp.bfloat16, seed=7)
            entry = {"shape": list(ab_shape)}
            legs = {}
            for name, fn in (
                ("flash_fwd", jax.jit(flash)),
                ("flash_fwd_bwd",
                 jax.jit(jax.grad(loss_of(flash), argnums=(0, 1, 2)))),
                ("ref_fwd", jax.jit(ref)),
                ("ref_fwd_bwd",
                 jax.jit(jax.grad(loss_of(ref), argnums=(0, 1, 2)))),
            ):
                try:
                    legs[name] = timed(fn, (aq, ak, av), n_windows=3)
                    entry[f"{name}_ms"] = round(legs[name] * 1e3, 3)
                except Exception as err:  # noqa: BLE001 — recorded as data
                    entry[f"{name}_error"] = (
                        f"{type(err).__name__}: {str(err)[:200]}"
                    )
            for pair in ("fwd", "fwd_bwd"):
                if f"flash_{pair}" in legs and f"ref_{pair}" in legs:
                    entry[f"{pair}_speedup"] = round(
                        legs[f"ref_{pair}"] / legs[f"flash_{pair}"], 3
                    )
            ab_compare[f"s{ab_s}"] = entry

        # Causal attention FLOPs: 4*B*H*S^2*D (QK^T + PV), halved by the
        # mask; bwd re-does QK^T plus four more S^2 matmuls => ~2.5x fwd.
        fwd_flops = 0.5 * 4.0 * b * h * s * s * d
        peak = bench._peak_flops(device)
        payload["microbench"] = {
            "shape": list(shape), "dtype": "bfloat16", "causal": True,
            "fwd_ms": round(t_fwd * 1e3, 3),
            "fwd_tflops": round(fwd_flops / t_fwd / 1e12, 2),
            "fwd_fraction_of_peak": round(fwd_flops / t_fwd / peak, 4),
            "fwd_bwd_ms": round(t_fwdbwd * 1e3, 3),
            "fwd_bwd_tflops": round(3.5 * fwd_flops / t_fwdbwd / 1e12, 2),
            "block_sweep_fwd_ms": block_sweep,
            "timing": "median of 5 windows of 10 calls, block_until_ready",
        }
        payload["flash_vs_reference"] = ab_compare

    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
    print(json.dumps(payload))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
