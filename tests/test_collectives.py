"""Parity suite for the quantized gradient collectives.

Covers the registry itself (blockwise quantizers, reduce-scatter /
all-gather vs the exact lax.psum family on the 8-device host mesh) and
the quantized ZeRO-2 trainer integration: error-feedback determinism
across seeds and restarts, `T2R_COLLECTIVE_QUANT=none` exact-equality
with the GSPMD path, and checkpoint round-trip of the residual state.
"""

import numpy as np
import pytest

import jax
import jax.flatten_util
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tensor2robot_tpu import flags
from tensor2robot_tpu.parallel import collectives
from tensor2robot_tpu.parallel import mesh as mesh_lib
from tensor2robot_tpu.train import train_eval
from tensor2robot_tpu.train.state import ema_as_tree
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

DATA = mesh_lib.DATA_AXIS
N = 8  # the virtual host mesh (conftest forces 8 devices)
BLOCK = 64
L = 4 * BLOCK  # per-peer chunk length


def _mesh():
    return mesh_lib.make_mesh(data=N)


def _rows(seed: int, scale: float = 1.0) -> np.ndarray:
    """[N_dev, N_chunk, L]: device d's local gradient rows are [d]."""
    rng = np.random.RandomState(seed)
    return (rng.randn(N, N, L) * scale).astype(np.float32)


def _run_reduce_scatter(coll, rows_global):
    mesh = _mesh()

    def local(rows):
        reduced, sent = coll.reduce_scatter(rows[0], DATA)
        return reduced[None], sent[None]

    fn = collectives.smap(local, mesh, (P(DATA),), (P(DATA), P(DATA)))
    reduced, sent = fn(jnp.asarray(rows_global))
    return np.asarray(reduced), np.asarray(sent)


def _run_all_gather(coll, shards_global):
    mesh = _mesh()

    def local(shard):
        full, sent = coll.all_gather_shard(shard[0], DATA)
        return full[None], sent

    fn = collectives.smap(local, mesh, (P(DATA),), (P(DATA), P(DATA)))
    full, sent = fn(jnp.asarray(shards_global))
    return np.asarray(full), np.asarray(sent)


#: Per-format quantization step as a fraction of the block max-abs:
#: half of each is the worst-case per-element rounding error. int8 is an
#: ABSOLUTE step (scale/127); the float formats round RELATIVE to the
#: value (<= the block max), with 10 mantissa bits for fp16, 3 for
#: fp8_e4m3, 2 for fp8_e5m2.
STEP_FACTORS = {
    "fp16": 2.0 ** -10,
    "int8": 1 / 127.0,
    "fp8_e4m3": 2.0 ** -3,
    "fp8_e5m2": 2.0 ** -2,
}

QUANT_NAMES = sorted(STEP_FACTORS)
ALL_NAMES = ["none"] + QUANT_NAMES


class TestQuantizers:
    @pytest.mark.parametrize("name", QUANT_NAMES)
    def test_roundtrip_error_bound(self, name):
        coll = collectives.get_collective(name, BLOCK)
        x = jnp.asarray(_rows(0)[0])
        decoded = np.asarray(coll.decode(coll.encode(x)))
        blocks = np.asarray(x).reshape(N, L // BLOCK, BLOCK)
        scale = np.abs(blocks).max(axis=-1, keepdims=True)
        step = scale * STEP_FACTORS[name]
        err = np.abs(decoded.reshape(blocks.shape) - blocks)
        assert (err <= step * 0.5 * (1 + 1e-6) + 1e-12).all()

    @pytest.mark.parametrize("name", ["fp8_e4m3", "fp8_e5m2"])
    def test_fp8_encode_is_finite_and_1_byte(self, name):
        """The clip before the fp8 cast is load-bearing: jax fp8 casts
        don't saturate, so a block max landing ABOVE the format max
        after rounding would decode as NaN and poison the reduced
        shard. Large-magnitude rows + payload dtype/size pinned."""
        coll = collectives.get_collective(name, BLOCK)
        x = jnp.asarray(_rows(5, scale=1e4)[0])
        payload = coll.encode(x)
        assert np.asarray(payload["q"]).dtype.itemsize == 1
        decoded = np.asarray(coll.decode(payload))
        assert np.isfinite(decoded).all()
        assert coll.wire_bytes(1 << 20) == (1 << 20) + 4 * ((1 << 20) // BLOCK)

    @pytest.mark.parametrize("name", ALL_NAMES)
    def test_deterministic(self, name):
        coll = collectives.get_collective(name, BLOCK)
        x = jnp.asarray(_rows(3)[0])
        a = jax.device_get(coll.decode(coll.encode(x)))
        b = jax.device_get(coll.decode(coll.encode(x)))
        np.testing.assert_array_equal(a, b)

    def test_zero_blocks_decode_to_zero(self):
        coll = collectives.get_collective("int8", BLOCK)
        x = jnp.zeros((2, L))
        decoded = np.asarray(coll.decode(coll.encode(x)))
        np.testing.assert_array_equal(decoded, np.zeros((2, L)))

    def test_unknown_collective_rejected(self):
        with pytest.raises(KeyError, match="unknown collective"):
            collectives.get_collective("int4", BLOCK)

    def test_unknown_collective_names_flag_and_available_regimes(self):
        """The resolution error is an operator surface: it must name the
        registered regimes AND the flag that selects one, like the
        flags.py getters do."""
        with pytest.raises(KeyError) as err:
            collectives.get_collective("int4", BLOCK)
        message = str(err.value)
        assert "T2R_COLLECTIVE_QUANT" in message
        for name in collectives.available_collectives():
            assert name in message
        assert "fp8_e4m3" in message  # the registry carries the fp8 regimes

    def test_block_divisibility_enforced(self):
        coll = collectives.get_collective("int8", BLOCK)
        with pytest.raises(ValueError, match="not divisible"):
            coll.encode(jnp.zeros((BLOCK + 1,)))


class TestCollectiveParity:
    """Quantized collectives vs the exact lax.psum family on 8 devices."""

    def test_none_reduce_scatter_matches_psum(self):
        rows = _rows(1)
        coll = collectives.get_collective("none", BLOCK)
        reduced, sent = _run_reduce_scatter(coll, rows)
        expected = rows.sum(axis=0)  # chunk d summed over devices
        np.testing.assert_allclose(reduced, expected, rtol=1e-6, atol=1e-5)
        np.testing.assert_array_equal(sent.reshape(rows.shape), rows)

    @pytest.mark.parametrize("name", QUANT_NAMES)
    def test_quantized_reduce_scatter_within_tolerance(self, name):
        tol_steps = STEP_FACTORS[name]
        rows = _rows(2)
        coll = collectives.get_collective(name, BLOCK)
        reduced, sent = _run_reduce_scatter(coll, rows)
        expected = rows.sum(axis=0)
        # Worst case: every sender contributes half a quantization step
        # of its largest block.
        atol = N * 0.5 * np.abs(rows).max() * tol_steps * 1.01 + 1e-9
        np.testing.assert_allclose(reduced, expected, atol=atol, rtol=0)
        # The error channel is exactly what failed to transmit.
        err = rows - sent.reshape(rows.shape)
        assert np.abs(err).max() <= 0.5 * np.abs(rows).max() * tol_steps * 1.01

    @pytest.mark.parametrize("name", ALL_NAMES)
    def test_all_gather_parity(self, name):
        shards = _rows(4)[:, 0, :]  # [N, L]
        coll = collectives.get_collective(name, BLOCK)
        full, sent = _run_all_gather(coll, shards)
        # Every device reconstructs the same concatenation, equal to the
        # dequantized sends in axis order.
        assert full.shape == (N, N * L)
        for d in range(1, N):
            np.testing.assert_array_equal(full[0], full[d])
        np.testing.assert_array_equal(
            full[0].reshape(N, L), sent.reshape(N, L)
        )
        tol = (
            0
            if name == "none"
            else np.abs(shards).max() * 1.01 * 0.5 * STEP_FACTORS[name]
        )
        np.testing.assert_allclose(
            full[0].reshape(N, L), shards, atol=tol + 1e-12, rtol=0
        )


class TestFlatShardLayout:
    def test_padding_math(self):
        layout = collectives.FlatShardLayout(1000, 8, 64)
        assert layout.shard_len == 128  # ceil(1000/8)=125 -> 128
        assert layout.padded == 1024
        flat = jnp.arange(1000, dtype=jnp.float32)
        padded = layout.pad(flat)
        assert padded.shape == (1024,)
        np.testing.assert_array_equal(np.asarray(padded[1000:]), 0)
        np.testing.assert_array_equal(
            np.asarray(layout.unpad(padded)), np.asarray(flat)
        )
        assert layout.rows(padded).shape == (8, 128)

    def test_bad_inputs_rejected(self):
        with pytest.raises(ValueError):
            collectives.FlatShardLayout(0, 8, 64)
        layout = collectives.FlatShardLayout(100, 4, 8)
        with pytest.raises(ValueError, match="expected"):
            layout.pad(jnp.zeros((101,)))

    def test_wire_summary_ratios(self):
        n = 1 << 20
        pre, post = collectives.wire_summary(
            collectives.get_collective("int8", 512), n
        )
        assert pre / post >= 3.5  # the acceptance bar
        pre16, post16 = collectives.wire_summary(
            collectives.get_collective("fp16", 512), n
        )
        assert 1.9 < pre16 / post16 <= 2.0
        pre0, post0 = collectives.wire_summary(
            collectives.get_collective("none", 512), n
        )
        assert pre0 == post0
        for name in ("fp8_e4m3", "fp8_e5m2"):
            pre8, post8 = collectives.wire_summary(
                collectives.get_collective(name, 512), n
            )
            assert pre8 / post8 >= 3.5  # same byte win as int8


def _setup(batch_size=16, seed=0, **kwargs):
    kwargs.setdefault("use_batch_norm", False)
    model_kwargs = {
        k: kwargs.pop(k)
        for k in ("use_batch_norm", "use_avg_model_params")
        if k in kwargs
    }
    model = MockT2RModel(device_type="cpu", **model_kwargs)
    generator = MockInputGenerator(batch_size=batch_size, seed=seed)
    generator.set_specification_from_model(model, "train")
    batch = next(iter(generator.create_dataset("train")))
    compiled = train_eval.CompiledModel(
        model, donate_state=False, shard_weight_update=True, **kwargs
    )
    state = compiled.init_state(jax.random.PRNGKey(0), batch)
    return compiled, state, batch


def _run_steps(compiled, state, batch, steps, rng_seed=7):
    rng = jax.random.PRNGKey(rng_seed)
    metrics = None
    for _ in range(steps):
        state, metrics = compiled.train_step(
            state, compiled.shard_batch(batch), rng
        )
    return state, metrics


def _flat_params(state):
    return jax.flatten_util.ravel_pytree(jax.device_get(state.params))[0]


class TestQuantizedZero2Step:
    """The trainer integration: explicit quantized collectives vs the
    GSPMD ZeRO-2 step."""

    @pytest.mark.parametrize(
        "quant,loss_tol,param_tol",
        [
            # fp16: measured over six seeds (batch and init, 10 steps,
            # CPU): loss gap 6e-8 to 2.1e-6; param drift 2.29e-3, 1.94e-3,
            # 9.8e-4, 2.1e-4, 5.6e-5, 3.0e-5, the two largest each ONE
            # element of 10,601 (the fifth largest under 5e-4 on every
            # seed). That element's replica gradients all but cancel at
            # step 0 (|sum| 6e-5 of its block's largest), fp16 rounding
            # of the eight terms flips the sum's sign, and Adam's first
            # step normalizes any gradient to +-lr: the runs part by
            # 2 * lr = 2e-3 in one step and stay parallel. Error feedback
            # returns the gradient, not that step; the quantized step is
            # sound. param_tol is ~5x the largest drift, as below.
            ("fp16", 2e-4, 1.2e-2),
            ("int8", 2e-3, 2e-2),
            # fp8 wire formats: same 1 byte/element as int8, relative
            # rounding; error feedback keeps the trajectory pinned to
            # the exact path (measured ~3e-4 loss / ~6e-3 param drift
            # over 10 steps — tolerances carry ~5x headroom).
            ("fp8_e4m3", 2e-3, 2e-2),
            ("fp8_e5m2", 5e-3, 5e-2),
        ],
    )
    def test_loss_parity_with_exact(self, quant, loss_tol, param_tol):
        compiled_e, state_e, batch = _setup()
        compiled_q, state_q, _ = _setup(
            collective_quant=quant, collective_block=BLOCK
        )
        assert compiled_q._quant_collective is not None
        state_e, metrics_e = _run_steps(compiled_e, state_e, batch, 10)
        state_q, metrics_q = _run_steps(compiled_q, state_q, batch, 10)
        loss_e = float(jax.device_get(metrics_e["loss"]))
        loss_q = float(jax.device_get(metrics_q["loss"]))
        assert abs(loss_e - loss_q) < loss_tol, (loss_e, loss_q)
        np.testing.assert_allclose(
            _flat_params(state_e), _flat_params(state_q), atol=param_tol
        )

    def test_none_keeps_the_gspmd_path_byte_identical(self):
        """quant='none' must not even engage the manual step — the exact
        GSPMD psum program runs, byte-for-byte."""
        compiled_n, state_n, batch = _setup(collective_quant="none")
        assert compiled_n._quant_collective is None
        assert state_n.collective_residual is None
        compiled_d, state_d, _ = _setup()  # default (flag unset)
        state_n, _ = _run_steps(compiled_n, state_n, batch, 3)
        state_d, _ = _run_steps(compiled_d, state_d, batch, 3)
        np.testing.assert_array_equal(
            _flat_params(state_n), _flat_params(state_d)
        )

    def test_env_flag_selects_collective(self):
        saved_q = flags.read_raw("T2R_COLLECTIVE_QUANT")
        saved_b = flags.read_raw("T2R_COLLECTIVE_BLOCK")
        try:
            flags.write_env("T2R_COLLECTIVE_QUANT", "int8")
            flags.write_env("T2R_COLLECTIVE_BLOCK", 128)
            compiled, state, _ = _setup()
            assert compiled._quant_collective is not None
            assert compiled._quant_collective.name == "int8"
            assert compiled._quant_collective.block == 128
            assert state.collective_residual is not None
        finally:
            flags.restore_env("T2R_COLLECTIVE_QUANT", saved_q)
            flags.restore_env("T2R_COLLECTIVE_BLOCK", saved_b)

    def test_inert_outside_zero2(self):
        """The flag must be safe to export fleet-wide: without
        shard_weight_update (or off the data axis) nothing changes."""
        model = MockT2RModel(device_type="cpu", use_batch_norm=False)
        compiled = train_eval.CompiledModel(
            model, donate_state=False, collective_quant="int8"
        )
        assert compiled._quant_collective is None
        mesh = mesh_lib.make_mesh(data=1, devices=jax.devices()[:1])
        compiled_1 = train_eval.CompiledModel(
            model, mesh=mesh, donate_state=False,
            shard_weight_update=True, collective_quant="int8",
        )
        assert compiled_1._quant_collective is None

    def test_error_feedback_determinism_across_runs(self):
        runs = []
        for _ in range(2):
            compiled, state, batch = _setup(
                collective_quant="int8", collective_block=BLOCK
            )
            state, _ = _run_steps(compiled, state, batch, 5)
            runs.append(state)
        np.testing.assert_array_equal(
            _flat_params(runs[0]), _flat_params(runs[1])
        )
        res0 = jax.device_get(runs[0].collective_residual)
        res1 = jax.device_get(runs[1].collective_residual)
        np.testing.assert_array_equal(res0["grad"], res1["grad"])
        np.testing.assert_array_equal(res0["update"], res1["update"])
        # The residual is live (int8 on real gradients cannot be exact).
        assert np.abs(res0["grad"]).max() > 0

    @pytest.mark.parametrize("quant", ["int8", "fp8_e4m3"])
    def test_checkpoint_roundtrip_of_residual(self, tmp_path, quant):
        """Save mid-run, restore into a FRESH trainer, continue: the
        trajectory must match the uninterrupted run exactly — which can
        only hold if the residual state round-trips the checkpoint."""
        kwargs = dict(collective_quant=quant, collective_block=BLOCK)
        compiled, state, batch = _setup(**kwargs)
        state, _ = _run_steps(compiled, state, batch, 3)
        manager = train_eval.create_checkpoint_manager(
            str(tmp_path), save_interval_steps=1
        )
        manager.save(
            3,
            args=train_eval.ocp.args.StandardSave(state),
            force=True,
        )
        manager.wait_until_finished()

        compiled_r, _, _ = _setup(**kwargs)
        restored = train_eval.restore_or_init_state(
            manager, compiled_r, jax.random.PRNGKey(0), batch
        )
        manager.close()
        assert int(jax.device_get(restored.step)) == 3
        res_saved = jax.device_get(state.collective_residual)
        res_restored = jax.device_get(restored.collective_residual)
        np.testing.assert_array_equal(
            res_saved["grad"], res_restored["grad"]
        )
        # Continue both for 3 more steps: bitwise-identical trajectory.
        state, _ = _run_steps(compiled, state, batch, 3, rng_seed=11)
        restored, _ = _run_steps(compiled_r, restored, batch, 3, rng_seed=11)
        np.testing.assert_array_equal(
            _flat_params(state), _flat_params(restored)
        )

    def test_grad_accum_composes(self):
        compiled, state, batch = _setup(
            collective_quant="int8", collective_block=BLOCK,
            grad_accum_steps=2,
        )
        state, metrics = _run_steps(compiled, state, batch, 2)
        assert np.isfinite(float(jax.device_get(metrics["loss"])))

    def test_ema_mirror_and_export(self):
        compiled, state, batch = _setup(
            collective_quant="int8", collective_block=BLOCK,
            use_avg_model_params=True,
        )
        assert state.ema_params is not None
        assert state.ema_params.ndim == 1  # flat padded layout
        state, _ = _run_steps(compiled, state, batch, 3)
        ema_tree = ema_as_tree(
            jax.device_get(state.ema_params), jax.device_get(state.params)
        )
        jax.tree_util.tree_map(
            lambda e, p: np.testing.assert_array_equal(
                np.asarray(e).shape, np.asarray(p).shape
            ),
            ema_tree,
            jax.device_get(state.params),
        )
        # EMA tracked the params (moved off init).
        variables = state.export_variables(use_ema=True)
        moved = jax.flatten_util.ravel_pytree(
            jax.device_get(variables["params"])
        )[0]
        assert np.abs(moved - _flat_params(state)).max() > 0

    def test_batch_norm_stats_averaged(self):
        compiled, state, batch = _setup(
            use_batch_norm=True,
            collective_quant="fp16", collective_block=BLOCK,
        )
        init_stats = jax.device_get(state.variables["batch_stats"])
        state, _ = _run_steps(compiled, state, batch, 2)
        stats = jax.device_get(state.variables["batch_stats"])
        moved = max(
            float(np.abs(np.asarray(a) - np.asarray(b)).max())
            for a, b in zip(
                jax.tree_util.tree_leaves(init_stats),
                jax.tree_util.tree_leaves(stats),
            )
        )
        assert moved > 0

    def test_eval_step_works_on_quant_state(self):
        compiled, state, batch = _setup(
            collective_quant="int8", collective_block=BLOCK
        )
        state, _ = _run_steps(compiled, state, batch, 2)
        metrics = compiled.eval_step(
            state, compiled.shard_batch(batch), False
        )
        assert np.isfinite(float(jax.device_get(metrics["accuracy"])))

    def test_collective_log_record(self):
        compiled, _, _ = _setup(
            collective_quant="int8", collective_block=512
        )
        record = compiled.collective_log_record(measure=False)
        assert record["collective/compression"] >= 3.5
        assert record["collective/bytes_post"] < record["collective/bytes_pre"]
        wall = compiled.measure_collective_ms(repeats=2)
        assert wall > 0
        compiled_e, _, _ = _setup()
        assert compiled_e.collective_log_record() == {}


class TestTrainEvalModelIntegration:
    def test_end_to_end_with_flag(self, tmp_path):
        saved = flags.read_raw("T2R_COLLECTIVE_QUANT")
        try:
            flags.write_env("T2R_COLLECTIVE_QUANT", "int8")
            final = train_eval.train_eval_model(
                t2r_model=MockT2RModel(
                    device_type="cpu", use_batch_norm=False
                ),
                input_generator_train=MockInputGenerator(batch_size=16),
                input_generator_eval=MockInputGenerator(
                    batch_size=16, seed=5
                ),
                model_dir=str(tmp_path / "run"),
                max_train_steps=60,
                eval_steps=4,
                save_checkpoints_steps=30,
                log_every_steps=20,
                shard_weight_update=True,
            )
            assert final["accuracy"] > 0.7
            from tensor2robot_tpu.train.metrics import read_metrics

            stream = read_metrics(str(tmp_path / "run" / "train"))
            assert stream, "no train metrics written"
            last = stream[-1]
            assert last["collective/compression"] > 3.5
            assert last["collective/wall_ms"] > 0
        finally:
            flags.restore_env("T2R_COLLECTIVE_QUANT", saved)
