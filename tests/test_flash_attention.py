"""Pallas flash-attention kernel: numerics vs the materialized reference.

Runs the kernel in Pallas interpreter mode on CPU (the TPU-emulation test
strategy, SURVEY §4); the same code path compiles natively on TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_tile,
    reference_attention,
)


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.RandomState(0)
    shape = (2, 64, 4, 16)  # [B, S, H, D]
    return tuple(
        jnp.asarray(rng.randn(*shape).astype(np.float32)) for _ in range(3)
    )


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, qkv, causal):
        q, k, v = qkv
        ref = reference_attention(q, k, v, causal=causal)
        out = flash_attention(
            q, k, v, causal=causal, interpret=True, block_q=16, block_k=16
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_single_block(self, qkv):
        q, k, v = qkv
        ref = reference_attention(q, k, v, causal=True)
        out = flash_attention(
            q, k, v, causal=True, interpret=True, block_q=64, block_k=64
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_global_offsets_tile_semantics(self, qkv):
        q, k, v = qkv
        q_shard = q[:, 32:, :, :]
        ref = reference_attention(q_shard, k, v, causal=True, q_offset=32)
        out = flash_attention(
            q_shard, k, v, causal=True, q_offset=32,
            interpret=True, block_q=16, block_k=16,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    @pytest.mark.parametrize("window", [1, 7, 16, 33, 64, 200])
    def test_sliding_window_matches_reference(self, qkv, window):
        """Causal sliding window (q-W < k <= q) for every alignment class:
        sub-block, block-aligned, block-straddling, and wider-than-S (==
        plain causal). Exercises the k-block loop-bound tightening, not
        just the mask."""
        q, k, v = qkv
        ref = reference_attention(q, k, v, causal=True, window=window)
        out = flash_attention(
            q, k, v, causal=True, window=window,
            interpret=True, block_q=16, block_k=16,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )
        if window >= q.shape[1]:
            full = reference_attention(q, k, v, causal=True)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(full), rtol=2e-5, atol=2e-5
            )

    @pytest.mark.parametrize("window", [7, 32])
    def test_sliding_window_gradients(self, qkv, window):
        q, k, v = qkv
        dout = jnp.asarray(
            np.random.RandomState(7).randn(*q.shape).astype(np.float32)
        )

        def loss(fn):
            def f(q, k, v):
                return jnp.sum(fn(q, k, v) * dout)

            return f

        flash_fn = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, causal=True, window=window,
            interpret=True, block_q=16, block_k=16,
        )
        ref_fn = lambda q, k, v: reference_attention(  # noqa: E731
            q, k, v, causal=True, window=window
        )
        grads = jax.grad(loss(flash_fn), argnums=(0, 1, 2))(q, k, v)
        grads_ref = jax.grad(loss(ref_fn), argnums=(0, 1, 2))(q, k, v)
        for g, gr in zip(grads, grads_ref):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(gr), rtol=3e-5, atol=3e-5
            )

    def test_sliding_window_with_offsets(self, qkv):
        """Windowed attention composes with the global-position tile
        semantics (a ring hop whose k shard is partly outside the window)."""
        q, k, v = qkv
        q_shard = q[:, 32:, :, :]
        window = 24
        ref = reference_attention(
            q_shard, k, v, causal=True, q_offset=32, window=window
        )
        out = flash_attention(
            q_shard, k, v, causal=True, q_offset=32, window=window,
            interpret=True, block_q=16, block_k=16,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_k_block_bounds_exact_over_small_grid(self):
        """Exhaustive check of the kernel's visibility bounds: for every
        (q-block, window, block size, offsets) combination on a small
        grid, [j_lo, j_hi) contains EXACTLY the k blocks holding at least
        one visible (q, k) pair — too-narrow breaks numerics, too-wide is
        silent wasted compute; the docstring claims exact."""
        from tensor2robot_tpu.ops.flash_attention import _k_block_bounds

        for block_q in (2, 3, 8):
            for block_k in (2, 4):
                for num_kb in (1, 3):
                    s_k = block_k * num_kb
                    for q_off in (0, 5, -3):
                        for k_off in (0, 7):
                            for qi in range(3):
                                q0 = q_off + qi * block_q
                                for window in (None, 1, 2, 5, 100):
                                    j_lo, j_hi = _k_block_bounds(
                                        q0, block_q, block_k, num_kb,
                                        k_off, True, window,
                                    )
                                    visible_blocks = set()
                                    for dq in range(block_q):
                                        for kk in range(s_k):
                                            q_pos = q0 + dq
                                            k_pos = k_off + kk
                                            vis = q_pos >= k_pos
                                            if window is not None:
                                                vis &= (
                                                    q_pos - k_pos < window
                                                )
                                            if vis:
                                                visible_blocks.add(
                                                    kk // block_k
                                                )
                                    expected = (
                                        set(range(int(j_lo), int(j_hi)))
                                        if visible_blocks
                                        else set()
                                    )
                                    # Exactness when anything is visible;
                                    # an empty visible set allows any
                                    # (possibly empty) range whose blocks
                                    # are all masked.
                                    if visible_blocks:
                                        assert expected == visible_blocks, (
                                            block_q, block_k, num_kb,
                                            q0, k_off, window,
                                            sorted(expected),
                                            sorted(visible_blocks),
                                        )

    def test_window_requires_causal(self, qkv):
        q, k, v = qkv
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, causal=False, window=8, interpret=True)

    def test_gradients_match_reference(self, qkv):
        q, k, v = qkv

        def loss_flash(q, k, v):
            return flash_attention(
                q, k, v, causal=True, interpret=True, block_q=16, block_k=16
            ).sum()

        def loss_ref(q, k, v):
            return reference_attention(q, k, v, causal=True).sum()

        grads_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        grads_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for gf, gr in zip(grads_flash, grads_ref):
            np.testing.assert_allclose(
                np.asarray(gf), np.asarray(gr), rtol=2e-5, atol=2e-5
            )

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_rectangular_with_offsets(self, qkv, causal):
        """The flash backward on a (q-shard x k-shard) tile: s_q != s_k,
        nonzero global offsets — the exact shape a ring hop differentiates."""
        q, k, v = qkv
        q_shard = q[:, 16:48, :, :]

        def loss_flash(q, k, v):
            return (
                flash_attention(
                    q, k, v, causal=causal, q_offset=16,
                    interpret=True, block_q=16, block_k=16,
                )
                ** 2
            ).sum()

        def loss_ref(q, k, v):
            return (
                reference_attention(q, k, v, causal=causal, q_offset=16) ** 2
            ).sum()

        grads_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q_shard, k, v)
        grads_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q_shard, k, v)
        for gf, gr in zip(grads_flash, grads_ref):
            np.testing.assert_allclose(
                np.asarray(gf), np.asarray(gr), rtol=2e-4, atol=2e-4
            )

    def test_gradients_bf16_inputs(self, qkv):
        """bf16 q/k/v (the TPU wrapper's forward dtype): grads keep the
        input dtype and track the reference within bf16 tolerance."""
        q, k, v = (t.astype(jnp.bfloat16) for t in qkv)

        def loss_flash(q, k, v):
            return flash_attention(
                q, k, v, causal=True, interpret=True, block_q=16, block_k=16
            ).astype(jnp.float32).sum()

        def loss_ref(q, k, v):
            return reference_attention(q, k, v, causal=True).astype(
                jnp.float32
            ).sum()

        grads_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        grads_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for gf, gr in zip(grads_flash, grads_ref):
            assert gf.dtype == jnp.bfloat16
            np.testing.assert_allclose(
                np.asarray(gf, np.float32),
                np.asarray(gr, np.float32),
                rtol=0.1,
                atol=0.1,
            )

    def test_cpu_fallback_is_reference(self, qkv):
        q, k, v = qkv
        out = flash_attention(q, k, v, causal=True)  # cpu backend -> fallback
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref))

    def test_tile_residuals_merge_to_full_attention(self, qkv):
        """Two k-shard tiles merged with the online-softmax rule must equal
        full attention — the exact contract a ring hop relies on."""
        q, k, v = qkv
        k1, k2 = k[:, :32], k[:, 32:]
        v1, v2 = v[:, :32], v[:, 32:]
        o1, l1, m1 = flash_attention_tile(
            q, k1, v1, causal=True, k_offset=0, interpret=True,
            block_q=16, block_k=16,
        )
        o2, l2, m2 = flash_attention_tile(
            q, k2, v2, causal=True, k_offset=32, interpret=True,
            block_q=16, block_k=16,
        )
        m = jnp.maximum(m1, m2)
        a1, a2 = jnp.exp(m1 - m), jnp.exp(m2 - m)
        l = l1 * a1 + l2 * a2
        t = lambda x: jnp.transpose(x, (0, 2, 1))[..., None]
        o = o1 * t(a1) + o2 * t(a2)
        out = o / t(jnp.maximum(l, 1e-30))
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out.astype(q.dtype)), np.asarray(ref),
            rtol=2e-5, atol=2e-5,
        )


class TestRingWithFlashTiles:
    # ~12s: pallas-interpret forward over the 4-way ring; the flash tile
    # forward stays fast in TestFlashAttention::test_matches_reference
    # and the plain ring-vs-full parity stays fast in
    # test_ring_attention's 4-shard column — this composition joins its
    # gradients twin on the slow slice.
    @pytest.mark.slow
    def test_ring_flash_matches_reference(self):
        from tensor2robot_tpu.parallel import mesh as mesh_lib
        from tensor2robot_tpu.parallel.ring_attention import ring_attention

        n = min(4, len(jax.devices()))
        mesh = mesh_lib.make_mesh(
            data=1, sequence=n, devices=jax.devices()[:n]
        )
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(2, 16 * n, 2, 8).astype(np.float32))
        ref = reference_attention(q, q, q, causal=True)
        out = ring_attention(
            q, q, q, mesh=mesh, causal=True, use_flash=True, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    # ~35s: pallas-interpret backward over the full ring; the per-tile
    # flash gradients above keep fast-slice coverage of the kernel vjp.
    @pytest.mark.slow
    def test_ring_flash_gradients(self):
        """grad must flow through the flash ring (custom vjp; the TPU
        default path is use_flash=True)."""
        from tensor2robot_tpu.parallel import mesh as mesh_lib
        from tensor2robot_tpu.parallel.ring_attention import ring_attention

        n = min(4, len(jax.devices()))
        mesh = mesh_lib.make_mesh(
            data=1, sequence=n, devices=jax.devices()[:n]
        )
        rng = np.random.RandomState(2)
        q = jnp.asarray(rng.randn(1, 8 * n, 2, 8).astype(np.float32))

        def loss_flash(q):
            return ring_attention(
                q, q, q, mesh=mesh, causal=True, use_flash=True,
                interpret=True,
            ).sum()

        def loss_ref(q):
            return ring_attention(
                q, q, q, mesh=mesh, causal=True, use_flash=False
            ).sum()

        g_flash = jax.grad(loss_flash)(q)
        g_ref = jax.grad(loss_ref)(q)
        np.testing.assert_allclose(
            np.asarray(g_flash), np.asarray(g_ref), rtol=1e-4, atol=1e-4
        )

    def test_explicit_interpret_false_off_tpu_falls_back(self):
        from tensor2robot_tpu.ops.flash_attention import flash_attention

        rng = np.random.RandomState(3)
        q = jnp.asarray(rng.randn(1, 32, 2, 8).astype(np.float32))
        out = flash_attention(q, q, q, causal=True, interpret=False)
        ref = reference_attention(q, q, q, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref))

    def test_prime_length_falls_back_to_reference(self):
        """No MXU-viable block divides a prime length > block size: the
        documented einsum fallback must actually engage."""
        from tensor2robot_tpu.ops.flash_attention import _pick_block

        assert _pick_block(257, 128) is None
        assert _pick_block(64, 128) == 64   # single block
        assert _pick_block(256, 128) == 128
        rng = np.random.RandomState(4)
        q = jnp.asarray(rng.randn(1, 257, 2, 8).astype(np.float32))
        out = flash_attention(q, q, q, causal=True, interpret=True)
        ref = reference_attention(q, q, q, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_tile_raises_clear_error_off_tpu(self):
        with pytest.raises(ValueError, match="interpreter mode"):
            flash_attention_tile(
                jnp.zeros((1, 16, 1, 8)), jnp.zeros((1, 16, 1, 8)),
                jnp.zeros((1, 16, 1, 8)), interpret=False,
            )


def test_whole_sequence_blocks_are_sized_or_refused_by_name(monkeypatch):
    """The kernels keep whole-sequence operands in VMEM: small ones ride
    the compiler's default budget, larger ones raise the scoped limit,
    and a shape the chip cannot hold is a ValueError naming it — never
    an XLA allocation failure inside the enclosing program (budget
    measured on TPU v5 lite; see ops/flash_attention.py)."""
    import types

    from jax.experimental.pallas import tpu as pltpu

    from tensor2robot_tpu.ops import flash_attention as fa

    monkeypatch.setattr(
        pltpu, "get_tpu_info",
        lambda: types.SimpleNamespace(vmem_capacity_bytes=128 << 20),
    )

    def kv(seq, dim=128, dtype=jnp.bfloat16):
        return [(seq, dim, dtype), (seq, dim, dtype)]

    assert fa._vmem_kwargs("k", kv(8192), False, "s") == {}
    raised = fa._vmem_kwargs("k", kv(65536), False, "s")
    assert raised["compiler_params"].vmem_limit_bytes == 100 << 20
    # D=64 pads to the 128-lane tile: same footprint as D=128.
    assert fa._vmem_kwargs("k", kv(65536, dim=64), False, "s")
    with pytest.raises(ValueError, match=r"flash_attention_tile .*128 MiB.*"
                       r"k/v \(1, 262144, 1, 128\)"):
        fa._vmem_kwargs(
            "flash_attention_tile", kv(262144), False,
            "k/v (1, 262144, 1, 128)",
        )
    assert fa._vmem_kwargs("k", kv(262144), True, "s") == {}  # interpreter

