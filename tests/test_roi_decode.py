"""Parity suite for decode-time ROI (ISSUE 2 tentpole).

The contract under test: ROI decode is BIT-IDENTICAL to full decode
followed by the same crop — across the native libjpeg path (including
sub-MCU offsets, where the native layer decodes an iMCU-aligned margin
and slices the residual), the PIL fallback, png, the zero-image
fallback, random- and center-crop modes, cache hit/miss (both cache
policies), the SpecParser-oracle fallback (same resolved offsets), the
process backend's shm-ring return of cropped slots, and the
T2R_DECODE_ROI=0 escape hatch that restores full-frame decode exactly.
"""

import io
import os

import numpy as np
import pytest

from tensor2robot_tpu.data import parser as parser_mod
from tensor2robot_tpu.data.encoder import encode_example
from tensor2robot_tpu.data.parser import SpecParser, decode_image, decode_image_roi
from tensor2robot_tpu.data.roi import (
    DecodeROI,
    ResolvedROI,
    apply_roi_to_batch,
    normalize_decode_rois,
    resolve_decode_rois,
)
from tensor2robot_tpu.data.wire import DecodeCache, FastSpecParser
from tensor2robot_tpu.specs import ExtendedTensorSpec, TensorSpecStruct


def _image_specs(h=64, w=80, data_format="jpeg"):
    specs = TensorSpecStruct()
    specs["img"] = ExtendedTensorSpec(
        shape=(h, w, 3), dtype=np.uint8, name="img", data_format=data_format
    )
    specs["a"] = ExtendedTensorSpec(shape=(2,), dtype=np.float32, name="a")
    return specs


def _records(specs, batch, seed=0):
    rng = np.random.RandomState(seed)
    h, w, c = specs["img"].shape
    rows = [
        {
            "img": rng.randint(0, 256, (h, w, c), dtype=np.uint8),
            "a": rng.randn(2).astype(np.float32),
        }
        for _ in range(batch)
    ]
    return [encode_example(specs, r) for r in rows]


def assert_roi_parity(specs, records, resolved, cache=None):
    """Fast ROI decode vs oracle full-decode-then-crop: byte-identical."""
    slow = SpecParser(specs).parse_batch(records, roi=resolved)
    fast_parser = FastSpecParser(specs)
    assert fast_parser.supported, fast_parser.unsupported_reason
    fast = fast_parser.parse_batch(records, cache=cache, roi=resolved)
    assert set(slow.keys()) == set(fast.keys())
    for key in slow.keys():
        want, got = np.asarray(slow[key]), np.asarray(fast[key])
        assert want.dtype == got.dtype, key
        assert want.shape == got.shape, (key, want.shape, got.shape)
        np.testing.assert_array_equal(want, got, err_msg=key)
    return fast


class TestDecodeImageRoi:
    """decode_image_roi == decode_image[crop] — the primitive contract."""

    def _jpeg(self, h=64, w=80, seed=0, quality=92):
        from PIL import Image

        rng = np.random.RandomState(seed)
        arr = rng.randint(0, 256, (h, w, 3), dtype=np.uint8)
        buf = io.BytesIO()
        Image.fromarray(arr).save(buf, format="JPEG", quality=quality)
        return buf.getvalue()

    @pytest.mark.parametrize(
        "rect",
        [
            (0, 0, 64, 80),  # full frame
            (17, 23, 31, 29),  # sub-MCU offsets both axes
            (3, 5, 40, 40),
            (63, 79, 1, 1),  # bottom-right corner pixel
            (0, 72, 64, 8),  # right edge strip
        ],
    )
    def test_jpeg_bit_identical_to_full_then_crop(self, rect):
        spec = _image_specs()["img"]
        data = self._jpeg()
        y, x, th, tw = rect
        roi = np.asarray(decode_image_roi(data, spec, y, x, th, tw))
        full = np.asarray(decode_image(data, spec))
        np.testing.assert_array_equal(roi, full[y : y + th, x : x + tw])

    def test_native_roi_path_is_active_when_canary_passes(self):
        """When the canary certifies this host's libjpeg, the native ROI
        path must actually engage (not silently fall back)."""
        if not parser_mod._roi_native_ok():
            pytest.skip("native ROI decode unavailable on this host")
        spec = _image_specs()["img"]
        data = self._jpeg(seed=3)
        out = np.empty((31, 29, 3), np.uint8)
        assert parser_mod.decode_image_roi_into_native(
            data, out, 17, 23, (64, 80)
        )
        full = np.asarray(decode_image(data, spec))
        np.testing.assert_array_equal(out, full[17:48, 23:52])

    def test_pil_fallback_parity(self, monkeypatch):
        """No-.so path: full PIL decode + crop, still exact."""
        monkeypatch.setattr(parser_mod, "_jpeg_lib", None)
        monkeypatch.setattr(parser_mod, "_jpeg_lib_failed", True)
        spec = _image_specs()["img"]
        data = self._jpeg(seed=5)
        roi = np.asarray(decode_image_roi(data, spec, 17, 23, 31, 29))
        full = np.asarray(decode_image(data, spec))
        np.testing.assert_array_equal(roi, full[17:48, 23:52])

    def test_wrong_source_dimensions_raise_via_fallback(self):
        """A jpeg whose real dims differ from the spec must fail the same
        way full decode does (shape error), not silently crop."""
        spec = _image_specs(h=32, w=32)["img"]
        data = self._jpeg(h=64, w=80)  # real source is 64x80
        with pytest.raises(ValueError, match="does not match spec"):
            decode_image_roi(data, spec, 0, 0, 16, 16)

    def test_empty_bytes_zero_window(self):
        spec = _image_specs()["img"]
        out = np.asarray(decode_image_roi(b"", spec, 10, 10, 20, 24))
        assert out.shape == (20, 24, 3)
        assert not out.any()


class TestParserParity:
    def test_random_mode_parity(self):
        specs = _image_specs()
        records = _records(specs, 5)
        rois = normalize_decode_rois({"img": DecodeROI(31, 29, "random")}, specs)
        resolved = resolve_decode_rois(
            rois, specs, len(records), np.random.default_rng(3)
        )
        fast = assert_roi_parity(specs, records, resolved)
        assert np.asarray(fast["img"]).shape == (5, 31, 29, 3)

    def test_center_and_fixed_mode_parity(self):
        specs = _image_specs()
        records = _records(specs, 3, seed=2)
        for roi in (DecodeROI(40, 40, "center"), DecodeROI(40, 40, "fixed", y=1, x=7)):
            rois = normalize_decode_rois({"img": roi}, specs)
            resolved = resolve_decode_rois(rois, specs, len(records))
            assert_roi_parity(specs, records, resolved)

    def test_png_parity(self):
        specs = _image_specs(data_format="png")
        records = _records(specs, 3, seed=4)
        rois = normalize_decode_rois({"img": DecodeROI(31, 29, "random")}, specs)
        resolved = resolve_decode_rois(
            rois, specs, len(records), np.random.default_rng(0)
        )
        assert_roi_parity(specs, records, resolved)

    def test_zero_image_fallback_parity(self):
        specs = _image_specs()
        records = _records(specs, 2, seed=6)
        records.append(
            encode_example(specs, {"img": b"", "a": np.zeros(2, np.float32)})
        )
        rois = normalize_decode_rois({"img": DecodeROI(31, 29, "random")}, specs)
        resolved = resolve_decode_rois(
            rois, specs, len(records), np.random.default_rng(1)
        )
        fast = assert_roi_parity(specs, records, resolved)
        assert not np.asarray(fast["img"])[2].any()

    def test_pil_fallback_whole_pipeline_parity(self, monkeypatch):
        monkeypatch.setattr(parser_mod, "_jpeg_lib", None)
        monkeypatch.setattr(parser_mod, "_jpeg_lib_failed", True)
        specs = _image_specs()
        records = _records(specs, 3, seed=8)
        rois = normalize_decode_rois({"img": DecodeROI(31, 29, "random")}, specs)
        resolved = resolve_decode_rois(
            rois, specs, len(records), np.random.default_rng(2)
        )
        assert_roi_parity(specs, records, resolved)

    def test_oracle_fallback_reproduces_identical_batch(self):
        """The dataset's fallback path: fast parse and oracle re-parse of
        the SAME payload (same resolved offsets) — identical batches."""
        from tensor2robot_tpu.data.dataset import _FastParseState, _parse_chunk_impl

        specs = _image_specs()
        records = _records(specs, 4, seed=9)
        rois = normalize_decode_rois({"img": DecodeROI(31, 29, "random")}, specs)
        resolved = resolve_decode_rois(
            rois, specs, len(records), np.random.default_rng(5)
        )
        payload = ("roi", records, resolved)
        oracle = SpecParser(specs)
        with_fast = _parse_chunk_impl(
            _FastParseState(specs, enabled=True), oracle, payload
        )
        without_fast = _parse_chunk_impl(
            _FastParseState(specs, enabled=False), oracle, payload
        )
        for key in with_fast.keys():
            np.testing.assert_array_equal(
                np.asarray(with_fast[key]),
                np.asarray(without_fast[key]),
                err_msg=key,
            )


class TestRoiCache:
    def test_static_offsets_cache_cropped_entries(self):
        """Center/fixed ROI: hits serve the cropped slot; entry bytes
        shrink to the window (the ~1.8x-more-frames budget claim)."""
        specs = _image_specs()
        records = _records(specs, 2, seed=11)
        rois = normalize_decode_rois({"img": DecodeROI(40, 40, "center")}, specs)
        resolved = resolve_decode_rois(rois, specs, len(records))
        cache = DecodeCache(64 << 20)
        cold = assert_roi_parity(specs, records, resolved, cache=cache)
        assert cache.misses >= 2 and cache.hits == 0
        # Entries hold the CROPPED window, not the full frame.
        for _, value in cache._entries.values():
            assert value.shape == (40, 40, 3)
        warm = FastSpecParser(specs).parse_batch(
            records, cache=cache, roi=resolved
        )
        assert cache.hits >= 2
        np.testing.assert_array_equal(
            np.asarray(cold["img"]), np.asarray(warm["img"])
        )

    def test_random_offsets_cache_full_frames_and_stay_exact(self):
        """Random ROI: the cache stores the FULL frame (offsets do not
        repeat across epochs) and serves each fresh window as a slice —
        hits must still be bit-identical to the oracle."""
        specs = _image_specs()
        records = _records(specs, 2, seed=12)
        rois = normalize_decode_rois({"img": DecodeROI(31, 29, "random")}, specs)
        cache = DecodeCache(64 << 20)
        g = np.random.default_rng(9)
        first = resolve_decode_rois(rois, specs, len(records), g)
        assert_roi_parity(specs, records, first, cache=cache)
        for _, value in cache._entries.values():
            assert value.shape == (64, 80, 3)  # full frames cached
        misses_after_cold = cache.misses
        second = resolve_decode_rois(rois, specs, len(records), g)
        assert any(
            not np.array_equal(first["img"].ys, second["img"].ys)
            for _ in (0,)
        ) or True  # offsets independent draws; parity is what matters
        assert_roi_parity(specs, records, second, cache=cache)
        assert cache.hits >= 2  # second epoch served from full-frame cache
        assert cache.misses == misses_after_cold


class TestCacheThrashingGuard:
    def test_thrashing_predicate(self):
        """Full cache + negligible hits over a real sample = thrashing;
        a warming or well-hit cache is not."""
        cache = DecodeCache(1 << 20)
        assert not cache.thrashing()  # empty, no lookups
        # Fill to >90% of budget with distinct entries.
        blob = np.zeros((320, 1024), np.uint8)  # ~320 KB each
        for i in range(4):
            cache.put("sig", bytes([i]) * 64, blob.copy())
        cache.misses = 600
        cache.hits = 2
        assert cache.thrashing()
        cache.hits = 200  # healthy hit rate: not thrashing
        assert not cache.thrashing()

    def test_randomized_roi_bypasses_thrashing_cache_and_stays_exact(self):
        """Once the cache thrashes, randomized-ROI decode must stop
        populating it (no more full-frame decodes for doomed entries) and
        keep producing oracle-identical pixels."""
        specs = _image_specs()
        records = _records(specs, 3, seed=31)
        rois = normalize_decode_rois({"img": DecodeROI(31, 29, "random")}, specs)
        resolved = resolve_decode_rois(
            rois, specs, len(records), np.random.default_rng(11)
        )
        cache = DecodeCache(1 << 20)
        blob = np.zeros((320, 1024), np.uint8)
        for i in range(4):
            cache.put("sig", bytes([i]) * 64, blob.copy())
        cache.misses, cache.hits = 600, 0
        assert cache.thrashing()
        entries_before = len(cache._entries)
        assert_roi_parity(specs, records, resolved, cache=cache)
        assert len(cache._entries) == entries_before  # nothing populated


class TestRefusedWindowsFallBack:
    @pytest.mark.parametrize("mode", ["random", "center"])
    def test_a_window_the_native_decoder_refuses_is_decoded_whole(self, mode):
        """A progressive jpeg among baseline ones: the native ROI decoder
        refuses it (-7) and it alone takes the full decode and crop;
        oracle-identical pixels, with the cache keyed by window (static
        offsets) and bypassed (random) alike."""
        import io

        from PIL import Image

        specs = _image_specs()
        rng = np.random.RandomState(4)
        rows = [
            {"img": rng.randint(0, 256, (64, 80, 3), dtype=np.uint8),
             "a": rng.randn(2).astype(np.float32)}
            for _ in range(4)
        ]
        records = [encode_example(specs, row) for row in rows]
        buf = io.BytesIO()
        Image.fromarray(rows[2]["img"]).save(
            buf, format="JPEG", quality=90, progressive=True
        )
        records[2] = encode_example(
            specs, {"img": buf.getvalue(), "a": rows[2]["a"]}
        )
        rois = normalize_decode_rois({"img": DecodeROI(31, 29, mode)}, specs)
        resolved = resolve_decode_rois(
            rois, specs, len(records), np.random.default_rng(5)
        )
        assert_roi_parity(specs, records, resolved, cache=None)
        cache = DecodeCache(1 << 20)
        first = assert_roi_parity(specs, records, resolved, cache=cache)
        again = assert_roi_parity(specs, records, resolved, cache=cache)
        np.testing.assert_array_equal(first["img"], again["img"])


class TestNormalization:
    def test_rejects_unknown_key(self):
        specs = _image_specs()
        with pytest.raises(KeyError):
            normalize_decode_rois({"nope": DecodeROI(8, 8)}, specs)

    def test_rejects_non_image_and_oversize(self):
        specs = _image_specs()
        with pytest.raises(ValueError, match="single-image"):
            normalize_decode_rois({"a": DecodeROI(1, 1)}, specs)
        with pytest.raises(ValueError, match="exceeds source"):
            normalize_decode_rois({"img": DecodeROI(65, 8)}, specs)

    def test_rejects_sequence_and_stack_images(self):
        specs = TensorSpecStruct()
        specs["stack"] = ExtendedTensorSpec(
            shape=(3, 12, 10, 3), dtype=np.uint8, name="stack",
            data_format="png",
        )
        with pytest.raises(ValueError, match="single-image"):
            normalize_decode_rois({"stack": DecodeROI(8, 8)}, specs)

    def test_bad_mode_and_size_fail_fast(self):
        with pytest.raises(ValueError, match="mode"):
            DecodeROI(8, 8, "diagonal")
        with pytest.raises(ValueError, match="positive"):
            DecodeROI(0, 8)
        with pytest.raises(ValueError, match="fixed"):
            DecodeROI(8, 8, "fixed")


class TestDatasetGate:
    def _write(self, tmp_path, specs, n=8):
        from tensor2robot_tpu.data import tfrecord

        path = str(tmp_path / "roi.tfrecord")
        tfrecord.write_tfrecords(path, _records(specs, n, seed=13))
        return path

    def test_roi_dataset_shapes_and_determinism(self, tmp_path):
        from tensor2robot_tpu.data.dataset import RecordDataset

        specs = _image_specs()
        path = self._write(tmp_path, specs)

        def batches(seed):
            ds = RecordDataset(
                specs=specs, file_patterns=path, batch_size=4, mode="train",
                shuffle_buffer_size=0, seed=seed, repeat=False,
                num_parse_workers=0, prefetch_depth=0,
                decode_roi={"img": DecodeROI(31, 29, "random")},
            )
            return [np.asarray(b["img"]) for b in ds]

        a, b = batches(21), batches(21)
        assert a[0].shape == (4, 31, 29, 3)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)  # seeded offsets reproduce
        c = batches(22)
        assert any(
            not np.array_equal(x, y) for x, y in zip(a, c)
        )  # different seed, different crops

    @pytest.mark.parametrize("workers", [1, 3, 8])
    def test_sliced_roi_batches_equal_the_whole_batch_parse(
        self, tmp_path, workers
    ):
        """A batch of 50 decoded in slices (50; 17+17+16; 16+16+16+2) takes
        each record's window at the offset the reader drew for that row of
        the whole batch: bit for bit the synchronous whole-batch parse."""
        from tensor2robot_tpu.data.dataset import RecordDataset
        from tensor2robot_tpu.utils import tracing

        specs = _image_specs()
        path = self._write(tmp_path, specs, n=110)

        def batches(num_workers):
            ds = RecordDataset(
                specs=specs, file_patterns=path, batch_size=50, mode="train",
                shuffle_buffer_size=16, seed=21, repeat=False,
                num_parse_workers=num_workers,
                decode_roi={"img": DecodeROI(31, 29, "random")},
            )
            try:
                return [
                    {k: np.asarray(v).copy() for k, v in b.items()} for b in ds
                ]
            finally:
                ds.close()

        whole = batches(0)
        before = tracing.counters()
        sliced = batches(workers)
        assert tracing.counters()["data.parse_batches_sliced"] - before.get(
            "data.parse_batches_sliced", 0
        ) == 2
        assert len(whole) == len(sliced) == 2
        assert whole[0]["img"].shape == (50, 31, 29, 3)
        # Random offsets: the windows of a batch differ from row to row.
        assert len({w.tobytes() for w in whole[0]["img"][:, :2, :2]}) > 1
        for a, b in zip(whole, sliced):
            assert list(a.keys()) == list(b.keys())
            for key in a:
                np.testing.assert_array_equal(a[key], b[key], err_msg=key)

    def test_env_zero_restores_full_frame_decode(self, tmp_path, monkeypatch):
        from tensor2robot_tpu.data.dataset import RecordDataset

        specs = _image_specs()
        path = self._write(tmp_path, specs)
        monkeypatch.setenv("T2R_DECODE_ROI", "0")
        ds = RecordDataset(
            specs=specs, file_patterns=path, batch_size=4, mode="eval",
            seed=1, repeat=False, num_parse_workers=0, prefetch_depth=0,
            decode_roi={"img": DecodeROI(31, 29, "center")},
        )
        batch = next(iter(ds))
        assert np.asarray(batch["img"]).shape == (4, 64, 80, 3)
        # ... and byte-identical to a dataset that never asked for ROI.
        ds_plain = RecordDataset(
            specs=specs, file_patterns=path, batch_size=4, mode="eval",
            seed=1, repeat=False, num_parse_workers=0, prefetch_depth=0,
        )
        np.testing.assert_array_equal(
            np.asarray(batch["img"]), np.asarray(next(iter(ds_plain))["img"])
        )

    def test_bad_env_value_fails_fast(self, monkeypatch):
        from tensor2robot_tpu.data.dataset import default_decode_roi

        monkeypatch.setenv("T2R_DECODE_ROI", "yes")
        with pytest.raises(ValueError, match="T2R_DECODE_ROI"):
            default_decode_roi()


class TestPreprocessorIntegration:
    def _model(self):
        from tensor2robot_tpu.research.qtopt.t2r_models import (
            Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom,
        )

        return Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom(
            device_type="cpu", image_size=(96, 96), num_convs=(2, 2, 1)
        )

    def test_grasping44_declares_crop_as_roi(self):
        model = self._model()
        rois = model.preprocessor.get_decode_rois("train")
        assert rois["state/image"].mode == "random"
        assert (rois["state/image"].height, rois["state/image"].width) == (96, 96)
        assert model.preprocessor.get_decode_rois("eval")["state/image"].mode == (
            "center"
        )

    def test_preprocess_accepts_source_and_cropped_shapes(self):
        import jax

        model = self._model()
        spec = model.preprocessor.get_in_feature_specification("train")
        src_h, src_w, _ = spec["state/image"].shape
        rng = np.random.RandomState(0)
        base = {
            key: np.asarray(
                rng.randint(0, 2, (2,) + tuple(s.shape)).astype(
                    np.dtype(s.dtype) if s.data_format is None else np.uint8
                )
            )
            for key, s in spec.items()
        }
        for shape in ((src_h, src_w), (96, 96)):
            feats = dict(base)
            feats["state/image"] = rng.randint(
                0, 256, (2,) + shape + (3,), dtype=np.uint8
            )
            out, _ = model.preprocessor.preprocess(
                feats, None, mode="train", rng=jax.random.PRNGKey(0)
            )
            assert np.asarray(out["state/image"]).shape == (2, 96, 96, 3)

    def test_preprocess_still_rejects_wrong_shapes(self):
        """The ROI tolerance is exactly two shapes — anything else keeps
        failing validation loudly."""
        import jax

        model = self._model()
        spec = model.preprocessor.get_in_feature_specification("train")
        rng = np.random.RandomState(0)
        feats = {
            key: np.asarray(
                rng.randint(0, 2, (2,) + tuple(s.shape)).astype(
                    np.dtype(s.dtype) if s.data_format is None else np.uint8
                )
            )
            for key, s in spec.items()
        }
        feats["state/image"] = rng.randint(0, 256, (2, 50, 50, 3), dtype=np.uint8)
        with pytest.raises(ValueError, match="[Ss]hape"):
            model.preprocessor.preprocess(
                feats, None, mode="train", rng=jax.random.PRNGKey(0)
            )


class TestApplyRoi:
    def test_apply_roi_to_batch_matches_manual_slices(self):
        arr = np.arange(2 * 10 * 12 * 3, dtype=np.uint8).reshape(2, 10, 12, 3)
        resolved = {
            "img": ResolvedROI(4, 5, np.array([1, 3]), np.array([2, 6]), True)
        }
        batch = {"img": arr.copy()}
        apply_roi_to_batch(batch, resolved)
        np.testing.assert_array_equal(batch["img"][0], arr[0, 1:5, 2:7])
        np.testing.assert_array_equal(batch["img"][1], arr[1, 3:7, 6:11])

    def test_offset_count_mismatch_raises(self):
        resolved = {"img": ResolvedROI(2, 2, np.zeros(3, np.int64), np.zeros(3, np.int64))}
        with pytest.raises(ValueError, match="offsets"):
            apply_roi_to_batch({"img": np.zeros((2, 8, 8, 3), np.uint8)}, resolved)


@pytest.mark.slow
class TestProcessBackendRoi:
    def test_shm_ring_returns_cropped_slots(self, tmp_path, monkeypatch):
        """Process backend + shm ring with ROI: batches come back through
        shared-memory slots already cropped, pixel-identical to the
        synchronous thread path under the same seed."""
        from tensor2robot_tpu.data.dataset import RecordDataset

        specs = _image_specs(h=128, w=160)
        from tensor2robot_tpu.data import tfrecord

        path = str(tmp_path / "roi.tfrecord")
        tfrecord.write_tfrecords(path, _records(specs, 8, seed=17))
        monkeypatch.setenv("T2R_PARSE_SHM", "1")

        def batches(backend, workers):
            ds = RecordDataset(
                specs=specs, file_patterns=path, batch_size=4, mode="train",
                shuffle_buffer_size=0, seed=23, repeat=False,
                num_parse_workers=workers, parse_backend=backend,
                prefetch_depth=0,
                decode_roi={"img": DecodeROI(100, 120, "random")},
            )
            try:
                return [np.asarray(b["img"]).copy() for b in ds]
            finally:
                ds.close()

        via_process = batches("process", 2)
        via_thread = batches("thread", 0)
        assert via_process[0].shape == (4, 100, 120, 3)
        assert len(via_process) == len(via_thread)
        for p, t in zip(via_process, via_thread):
            np.testing.assert_array_equal(p, t)
