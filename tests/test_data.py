"""Data pipeline tests: TFRecord IO, spec-driven parsing, dataset assembly.

Mirrors the coverage strategy of the reference's utils/tfdata_test.py
(generated records incl. sequences, varlen, images) against the JAX-native
pipeline.
"""

import functools
import os

import numpy as np
import pytest

from tensor2robot_tpu.data import tfrecord
from tensor2robot_tpu.data.dataset import RecordDataset
from tensor2robot_tpu.data.encoder import encode_example, encode_examples_by_dataset
from tensor2robot_tpu.data.input_generators import (
    DefaultConstantInputGenerator,
    DefaultRandomInputGenerator,
    DefaultRecordInputGenerator,
    GeneratorInputGenerator,
)
from tensor2robot_tpu.data.parser import SpecParser, decode_image
from tensor2robot_tpu.specs import ExtendedTensorSpec, TensorSpecStruct
from tensor2robot_tpu.specs import proto_io


class TestTFRecordIO:
    def test_roundtrip(self, tmp_path):
        path = str(tmp_path / "records.tfrecord")
        records = [b"hello", b"", b"x" * 10000]
        tfrecord.write_tfrecords(path, records)
        assert list(tfrecord.read_tfrecords(path)) == records
        assert tfrecord.count_tfrecords(path) == 3

    def test_corruption_detected(self, tmp_path):
        path = str(tmp_path / "bad.tfrecord")
        tfrecord.write_tfrecords(path, [b"payload"])
        data = bytearray(open(path, "rb").read())
        data[14] ^= 0xFF  # flip a payload byte
        with pytest.raises(tfrecord.TFRecordCorruptionError):
            list(tfrecord.read_tfrecords(bytes_path(tmp_path, data)))

    def test_tf_compatibility(self, tmp_path):
        """Our framing must be readable by TensorFlow and vice versa."""
        tf = pytest.importorskip("tensorflow")
        path = str(tmp_path / "ours.tfrecord")
        tfrecord.write_tfrecords(path, [b"abc", b"defg"])
        got = [r.numpy() for r in tf.data.TFRecordDataset(path)]
        assert got == [b"abc", b"defg"]
        theirs = str(tmp_path / "theirs.tfrecord")
        with tf.io.TFRecordWriter(theirs) as w:
            w.write(b"zzz")
        assert list(tfrecord.read_tfrecords(theirs)) == [b"zzz"]

    def test_buffered_reader_matches_streaming(self, tmp_path):
        """The block-buffered native-indexed reader and the per-record
        framing fallback must yield identical record streams, including
        when records straddle block boundaries (tiny buffer_bytes)."""
        from tensor2robot_tpu.data.tfrecord import _read_tfrecords_streaming

        path = str(tmp_path / "blocks.tfrecord")
        rng = np.random.RandomState(0)
        records = [bytes(rng.randint(0, 256, n, np.uint8).tobytes())
                   for n in (0, 1, 100, 5000, 17, 64 << 10)]
        tfrecord.write_tfrecords(path, records)
        assert list(tfrecord.read_tfrecords(path)) == records
        assert list(tfrecord.read_tfrecords(path, buffer_bytes=64)) == records
        assert list(_read_tfrecords_streaming(path, True)) == records
        assert list(tfrecord.read_tfrecords(path, verify_crc=False)) == records

    def test_list_files(self, tmp_path):
        for name in ["a-0.rec", "a-1.rec", "b-0.rec"]:
            tfrecord.write_tfrecords(str(tmp_path / name), [b"r"])
        files = tfrecord.list_files(str(tmp_path / "a-*.rec"))
        assert [os.path.basename(f) for f in files] == ["a-0.rec", "a-1.rec"]
        both = tfrecord.list_files(f"{tmp_path}/a-*.rec,{tmp_path}/b-*.rec")
        assert len(both) == 3
        with pytest.raises(FileNotFoundError):
            tfrecord.list_files(str(tmp_path / "nope-*.rec"))


def bytes_path(tmp_path, data: bytes) -> str:
    path = str(tmp_path / "mutated.tfrecord")
    with open(path, "wb") as f:
        f.write(data)
    return path


def image_bytes(shape=(6, 8, 3), fmt="PNG", value=128):
    import io

    from PIL import Image

    arr = np.full(shape, value, np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format=fmt)
    return buf.getvalue()


class TestParser:
    def spec(self):
        s = TensorSpecStruct()
        s["state"] = ExtendedTensorSpec(shape=(3,), dtype=np.float32, name="s")
        s["action"] = ExtendedTensorSpec(shape=(2,), dtype=np.int64, name="a")
        return s

    def test_roundtrip_fixed(self):
        spec = self.spec()
        values = {"state": np.array([1.0, 2.0, 3.0], np.float32),
                  "action": np.array([4, 5], np.int64)}
        serialized = encode_example(spec, values)
        parsed = SpecParser(spec).parse_single(serialized)
        np.testing.assert_array_equal(parsed["state"], values["state"])
        np.testing.assert_array_equal(parsed["action"], values["action"])

    def test_batch_parse(self):
        spec = self.spec()
        records = [
            encode_example(spec, {"state": np.full((3,), i, np.float32),
                                  "action": np.array([i, i], np.int64)})
            for i in range(4)
        ]
        batch = SpecParser(spec).parse_batch(records)
        assert batch["state"].shape == (4, 3)
        np.testing.assert_array_equal(batch["state"][2], [2.0, 2.0, 2.0])

    def test_missing_required_raises(self):
        spec = self.spec()
        serialized = encode_example(
            {"state": spec["state"]}, {"state": np.zeros(3, np.float32)}
        )
        with pytest.raises(KeyError):
            SpecParser(spec).parse_single(serialized)

    def test_optional_absent_ok(self):
        spec = self.spec()
        spec["extra"] = ExtendedTensorSpec(
            shape=(1,), dtype=np.float32, is_optional=True
        )
        serialized = encode_example(
            self.spec(), {"state": np.zeros(3, np.float32),
                          "action": np.zeros(2, np.int64)}
        )
        parsed = SpecParser(spec).parse_single(serialized)
        assert "extra" not in parsed

    def test_bfloat16_roundtrip(self):
        import jax.numpy as jnp

        spec = {"x": ExtendedTensorSpec(shape=(2,), dtype="bfloat16", name="x")}
        serialized = encode_example(spec, {"x": np.array([1.5, 2.5], np.float32)})
        batch = SpecParser(spec).parse_batch([serialized])
        assert batch["x"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(batch["x"].astype(np.float32), [[1.5, 2.5]])

    def test_varlen_pad_and_clip(self):
        spec = {"v": ExtendedTensorSpec(shape=(4,), dtype=np.float32, name="v",
                                        varlen_default_value=-1.0)}
        short = encode_example(
            {"v": ExtendedTensorSpec(shape=(2,), dtype=np.float32, name="v")},
            {"v": np.array([1.0, 2.0], np.float32)},
        )
        parsed = SpecParser(spec).parse_single(short)
        np.testing.assert_array_equal(parsed["v"], [1.0, 2.0, -1.0, -1.0])
        long = encode_example(
            {"v": ExtendedTensorSpec(shape=(6,), dtype=np.float32, name="v")},
            {"v": np.arange(6, dtype=np.float32)},
        )
        parsed = SpecParser(spec).parse_single(long)
        np.testing.assert_array_equal(parsed["v"], [0.0, 1.0, 2.0, 3.0])

    def test_image_decode_png_roundtrip(self):
        spec = {"img": ExtendedTensorSpec(shape=(6, 8, 3), dtype=np.uint8,
                                          name="img", data_format="png")}
        values = {"img": np.random.RandomState(0).randint(0, 255, (6, 8, 3), np.uint8)}
        serialized = encode_example(spec, values)
        parsed = SpecParser(spec).parse_single(serialized)
        np.testing.assert_array_equal(parsed["img"], values["img"])

    def test_empty_image_zero_fallback(self):
        spec = ExtendedTensorSpec(shape=(4, 4, 3), dtype=np.uint8, data_format="jpeg")
        out = decode_image(b"", spec)
        np.testing.assert_array_equal(out, np.zeros((4, 4, 3), np.uint8))

    def test_native_jpeg_decode_matches_pil(self):
        """The one-shot libjpeg path (native/jpeg_decode.cc) must be
        BIT-IDENTICAL to the PIL fallback — both sit on libjpeg-turbo, so
        any divergence means the wiring (colorspace, stride, channel
        request) is wrong, not the codec."""
        import io as iomod

        from PIL import Image

        from tensor2robot_tpu.data import parser as parser_mod
        from tensor2robot_tpu.data.encoder import encode_image

        if parser_mod._load_jpeg_native() is None:
            pytest.skip("no C++ toolchain / libjpeg dev files on this host")
        img = np.random.RandomState(3).randint(
            0, 256, (96, 128, 3), np.uint8
        )
        data = encode_image(img, "jpeg")
        native = parser_mod._decode_jpeg_native(data, (96, 128, 3))
        assert native is not None
        pil = np.asarray(Image.open(iomod.BytesIO(data)).convert("RGB"))
        np.testing.assert_array_equal(native, pil)

    def test_native_jpeg_decode_rejects_garbage(self):
        """Corrupt buffers must return None (PIL fallback handles the
        error reporting), never crash the process — libjpeg's default
        handler would exit()."""
        from tensor2robot_tpu.data import parser as parser_mod

        assert (
            parser_mod._decode_jpeg_native(
                b"\xff\xd8" + b"not a jpeg" * 10, (8, 8, 3)
            )
            is None
        )
        # Shape mismatch (spec says 4x4, file is bigger) -> None, fallback.
        from tensor2robot_tpu.data.encoder import encode_image

        img = np.zeros((16, 16, 3), np.uint8)
        assert (
            parser_mod._decode_jpeg_native(
                encode_image(img, "jpeg"), (4, 4, 3)
            )
            is None
        )

    def test_sequence_roundtrip_and_lengths(self):
        spec = TensorSpecStruct()
        spec["obs"] = ExtendedTensorSpec(
            shape=(2,), dtype=np.float32, name="obs", is_sequence=True
        )
        spec["goal"] = ExtendedTensorSpec(shape=(1,), dtype=np.float32, name="goal")
        r1 = encode_example(spec, {"obs": np.ones((5, 2), np.float32),
                                   "goal": np.zeros((1,), np.float32)})
        r2 = encode_example(spec, {"obs": np.ones((3, 2), np.float32),
                                   "goal": np.ones((1,), np.float32)})
        batch = SpecParser(spec).parse_batch([r1, r2])
        assert batch["obs"].shape == (2, 5, 2)  # padded to batch max
        np.testing.assert_array_equal(batch["obs_length"], [5, 3])
        np.testing.assert_array_equal(batch["obs"][1, 3:], np.zeros((2, 2)))

    def test_multi_dataset_routing(self):
        spec = TensorSpecStruct()
        spec["a"] = ExtendedTensorSpec(shape=(1,), dtype=np.float32, name="a",
                                       dataset_key="d1")
        spec["b"] = ExtendedTensorSpec(shape=(1,), dtype=np.float32, name="b",
                                       dataset_key="d2")
        values = {"a": np.array([1.0], np.float32), "b": np.array([2.0], np.float32)}
        by_key = encode_examples_by_dataset(spec, values)
        assert set(by_key.keys()) == {"d1", "d2"}
        parsed = SpecParser(spec).parse_single(by_key)
        np.testing.assert_array_equal(parsed["a"], [1.0])
        np.testing.assert_array_equal(parsed["b"], [2.0])


class TestRecordDataset:
    def make_records(self, tmp_path, n=16, shards=2):
        spec = TensorSpecStruct()
        spec["x"] = ExtendedTensorSpec(shape=(2,), dtype=np.float32, name="x")
        spec["y"] = ExtendedTensorSpec(shape=(), dtype=np.int64, name="y")
        idx = 0
        for shard in range(shards):
            records = []
            for _ in range(n // shards):
                records.append(
                    encode_example(spec, {"x": np.full((2,), idx, np.float32),
                                          "y": np.asarray(idx, np.int64)})
                )
                idx += 1
            tfrecord.write_tfrecords(str(tmp_path / f"data-{shard}.tfrecord"), records)
        return spec

    def test_single_epoch_eval(self, tmp_path):
        spec = self.make_records(tmp_path)
        dataset = RecordDataset(
            specs=spec,
            file_patterns=str(tmp_path / "data-*.tfrecord"),
            batch_size=4,
            mode="eval",
        )
        batches = list(dataset)
        assert len(batches) == 4
        all_y = np.concatenate([b["y"] for b in batches])
        assert sorted(all_y.tolist()) == list(range(16))

    def test_process_parse_backend_matches_thread(self, tmp_path):
        """The process-pool decode path must yield the same batches as the
        thread pool (order is deterministic in eval mode)."""
        spec = self.make_records(tmp_path)

        thread_ds = RecordDataset(
            specs=spec,
            file_patterns=str(tmp_path / "data-*.tfrecord"),
            batch_size=4,
            mode="eval",
            num_parse_workers=2,
            parse_backend="thread",
        )
        process_ds = RecordDataset(
            specs=spec,
            file_patterns=str(tmp_path / "data-*.tfrecord"),
            batch_size=4,
            mode="eval",
            num_parse_workers=2,
            parse_backend="process",
        )
        thread_batches = list(thread_ds)
        process_batches = list(process_ds)
        assert len(thread_batches) == len(process_batches) == 4
        for a, b in zip(thread_batches, process_batches):
            assert sorted(a.keys()) == sorted(b.keys())
            for key in a.keys():
                np.testing.assert_array_equal(
                    np.asarray(a[key]), np.asarray(b[key])
                )
        # The spawn pool is cached on the dataset: a second epoch reuses it
        # (no re-spawn) and still yields the same data.
        pool_first = process_ds._process_pool
        assert pool_first is not None
        second_epoch = list(process_ds)
        assert process_ds._process_pool is pool_first
        np.testing.assert_array_equal(
            np.asarray(second_epoch[0]["y"]),
            np.asarray(process_batches[0]["y"]),
        )
        process_ds.close()
        assert process_ds._process_pool is None

    def test_bad_parse_backend_rejected(self, tmp_path):
        spec = self.make_records(tmp_path)
        with pytest.raises(ValueError, match="parse_backend"):
            RecordDataset(
                specs=spec,
                file_patterns=str(tmp_path / "data-*.tfrecord"),
                batch_size=4,
                parse_backend="greenlet",
            )

    def test_train_repeats_and_shuffles(self, tmp_path):
        spec = self.make_records(tmp_path)
        dataset = RecordDataset(
            specs=spec,
            file_patterns=str(tmp_path / "data-*.tfrecord"),
            batch_size=4,
            mode="train",
            seed=42,
            shuffle_buffer_size=16,
        )
        it = iter(dataset)
        seen = [next(it)["y"] for _ in range(8)]  # 2 epochs worth
        flat = np.concatenate(seen).tolist()
        assert len(flat) == 32
        assert sorted(set(flat)) == list(range(16))
        assert flat[:16] != list(range(16))  # shuffled


class TestInputGenerators:
    def spec_pair(self):
        features = TensorSpecStruct()
        features["x"] = ExtendedTensorSpec(shape=(2,), dtype=np.float32, name="x")
        labels = TensorSpecStruct()
        labels["y"] = ExtendedTensorSpec(shape=(1,), dtype=np.float32, name="y")
        return features, labels

    def test_record_generator(self, tmp_path):
        features, labels = self.spec_pair()
        combined = TensorSpecStruct()
        combined.features = features.copy()
        combined.labels = labels.copy()
        records = [
            encode_example(combined, {"features/x": np.full((2,), i, np.float32),
                                      "labels/y": np.array([i], np.float32)})
            for i in range(8)
        ]
        tfrecord.write_tfrecords(str(tmp_path / "r.tfrecord"), records)
        gen = DefaultRecordInputGenerator(
            file_patterns=str(tmp_path / "r.tfrecord"), batch_size=4
        )
        gen.set_specification(features, labels)
        batch = next(iter(gen.create_dataset("eval")))
        assert batch.features.x.shape == (4, 2)
        assert batch.labels.y.shape == (4, 1)

    def test_random_and_constant_generators(self):
        features, labels = self.spec_pair()
        for gen in [DefaultRandomInputGenerator(batch_size=3),
                    DefaultConstantInputGenerator(constant_value=1.0, batch_size=3)]:
            gen.set_specification(features, labels)
            batch = next(iter(gen.create_dataset("train")))
            assert batch.features.x.shape == (3, 2)

    def test_generator_input_generator(self):
        features, labels = self.spec_pair()

        def source():
            while True:
                yield {"features/x": np.zeros(2, np.float32),
                       "labels/y": np.ones(1, np.float32)}

        gen = GeneratorInputGenerator(source, batch_size=2)
        gen.set_specification(features, labels)
        batch = next(iter(gen.create_dataset("train")))
        np.testing.assert_array_equal(batch.labels.y, np.ones((2, 1)))


class TestProtoIO:
    def test_spec_roundtrip(self):
        spec = ExtendedTensorSpec(
            shape=(4, None, 3), dtype="bfloat16", name="n", is_optional=True,
            is_sequence=True, data_format="jpeg", dataset_key="d",
        )
        back = proto_io.spec_from_proto(proto_io.spec_to_proto(spec))
        assert back.shape == (4, None, 3)
        assert back.name == "n"
        assert back.is_optional and back.is_sequence
        assert back.data_format == "jpeg"
        assert back.dataset_key == "d"
        import jax.numpy as jnp
        assert back.dtype == jnp.bfloat16

    def test_varlen_zero_roundtrip(self):
        spec = ExtendedTensorSpec(shape=(4,), dtype=np.float32, varlen_default_value=0.0)
        back = proto_io.spec_from_proto(proto_io.spec_to_proto(spec))
        assert back.varlen_default_value == 0.0

    def test_assets_roundtrip(self, tmp_path):
        features = TensorSpecStruct()
        features["img"] = ExtendedTensorSpec(shape=(8, 8, 3), dtype=np.uint8, name="i")
        labels = TensorSpecStruct()
        labels["y"] = ExtendedTensorSpec(shape=(1,), dtype=np.float32, name="y")
        path = proto_io.write_t2r_assets(str(tmp_path), features, labels, global_step=7)
        assert path.endswith("t2r_assets.pbtxt")
        f, l, step = proto_io.read_t2r_assets(str(tmp_path))
        assert list(f.keys()) == ["img"]
        assert l is not None and list(l.keys()) == ["y"]
        assert step == 7


class TestMultiDatasetZip:
    def test_misalignment_raises(self, tmp_path):
        spec = TensorSpecStruct()
        spec["a"] = ExtendedTensorSpec(shape=(1,), dtype=np.float32, name="a",
                                       dataset_key="d1")
        spec["b"] = ExtendedTensorSpec(shape=(1,), dtype=np.float32, name="b",
                                       dataset_key="d2")
        recs_a = [encode_example({"a": spec["a"]}, {"a": np.array([float(i)], np.float32)})
                  for i in range(4)]
        recs_b = [encode_example({"b": spec["b"]}, {"b": np.array([float(i)], np.float32)})
                  for i in range(3)]  # one short
        tfrecord.write_tfrecords(str(tmp_path / "a.tfrecord"), recs_a)
        tfrecord.write_tfrecords(str(tmp_path / "b.tfrecord"), recs_b)
        dataset = RecordDataset(
            specs=spec,
            file_patterns={"d1": str(tmp_path / "a.tfrecord"),
                           "d2": str(tmp_path / "b.tfrecord")},
            batch_size=1, mode="eval", prefetch_depth=0,
        )
        with pytest.raises(ValueError, match="misalignment"):
            list(dataset)

    def test_aligned_zip(self, tmp_path):
        spec = TensorSpecStruct()
        spec["a"] = ExtendedTensorSpec(shape=(1,), dtype=np.float32, name="a",
                                       dataset_key="d1")
        spec["b"] = ExtendedTensorSpec(shape=(1,), dtype=np.float32, name="b",
                                       dataset_key="d2")
        recs_a = [encode_example({"a": spec["a"]}, {"a": np.array([float(i)], np.float32)})
                  for i in range(4)]
        recs_b = [encode_example({"b": spec["b"]}, {"b": np.array([float(10 + i)], np.float32)})
                  for i in range(4)]
        tfrecord.write_tfrecords(str(tmp_path / "a.tfrecord"), recs_a)
        tfrecord.write_tfrecords(str(tmp_path / "b.tfrecord"), recs_b)
        dataset = RecordDataset(
            specs=spec,
            file_patterns={"d1": str(tmp_path / "a.tfrecord"),
                           "d2": str(tmp_path / "b.tfrecord")},
            batch_size=2, mode="eval", prefetch_depth=0,
        )
        batches = list(dataset)
        assert len(batches) == 2
        np.testing.assert_array_equal(
            batches[0]["b"] - batches[0]["a"], np.full((2, 1), 10.0)
        )


class TestHardening:
    def test_huge_length_field_reports_corruption(self, tmp_path):
        """A crafted length of ~2^64 must raise, not crash (overflow guard)."""
        import struct as structlib

        from tensor2robot_tpu.data.tfrecord import (
            index_tfrecord_buffer, masked_crc32c,
        )
        header = structlib.pack("<Q", (1 << 64) - 16)
        buf = header + structlib.pack("<I", masked_crc32c(header)) + b"x" * 32
        with pytest.raises(tfrecord.TFRecordCorruptionError):
            index_tfrecord_buffer(buf)
        with pytest.raises(tfrecord.TFRecordCorruptionError):
            list(tfrecord.read_tfrecords(bytes_path(tmp_path, buf)))

    def test_image_stack_roundtrip(self):
        spec = {"imgs": ExtendedTensorSpec(shape=(2, 4, 4, 3), dtype=np.uint8,
                                           name="imgs", data_format="png")}
        values = {"imgs": np.random.RandomState(0).randint(
            0, 255, (2, 4, 4, 3), np.uint8)}
        parsed = SpecParser(spec).parse_single(encode_example(spec, values))
        np.testing.assert_array_equal(parsed["imgs"], values["imgs"])

    def test_image_count_mismatch_raises(self):
        one_spec = {"imgs": ExtendedTensorSpec(shape=(4, 4, 3), dtype=np.uint8,
                                               name="imgs", data_format="png")}
        two = {"imgs": ExtendedTensorSpec(shape=(2, 4, 4, 3), dtype=np.uint8,
                                          name="imgs", data_format="png")}
        serialized = encode_example(
            two, {"imgs": np.zeros((2, 4, 4, 3), np.uint8)}
        )
        with pytest.raises(ValueError, match="images"):
            SpecParser(one_spec).parse_single(serialized)


class TestParseOnError:
    """T2R_PARSE_ON_ERROR: graceful degradation on a genuinely corrupt
    record mid-stream. Default (`raise`) keeps the canonical kill-the-
    consumer error; `skip` drops-and-counts the bad record(s) — the
    quarantine counter surfaced in RecordDataset.stats() — and yields
    the surviving (short) batch instead of dying."""

    def _corrupt_fixture(self, tmp_path, n=8, bad=(3,)):
        spec = TensorSpecStruct()
        spec["features/x"] = ExtendedTensorSpec(
            shape=(3,), dtype=np.float32, name="x"
        )
        records = [
            encode_example(spec, {"features/x": np.full(3, i, np.float32)})
            for i in range(n)
        ]
        for index in bad:
            # Forge a LEN frame that overruns the record: both the fast
            # parser (strict framing) and protobuf reject it.
            records[index] = records[index][:4] + b"\xff\xff\xff\xff"
        path = str(tmp_path / "mixed.tfrecord")
        tfrecord.write_tfrecords(path, records)
        return spec, path

    def _dataset(self, spec, path, workers=0, backend="thread"):
        return RecordDataset(
            spec, path, batch_size=4, mode="eval", repeat=False,
            num_parse_workers=workers, parse_backend=backend,
            prefetch_depth=0, drop_remainder=False,
        )

    def test_default_raise_kills_consumer(self, tmp_path, monkeypatch):
        monkeypatch.delenv("T2R_PARSE_ON_ERROR", raising=False)
        spec, path = self._corrupt_fixture(tmp_path)
        dataset = self._dataset(spec, path)
        with pytest.raises(Exception):
            list(dataset)
        dataset.close()

    @pytest.mark.parametrize("workers", [0, 2])
    def test_skip_counts_and_survives(self, tmp_path, monkeypatch, workers):
        monkeypatch.setenv("T2R_PARSE_ON_ERROR", "skip")
        spec, path = self._corrupt_fixture(tmp_path)
        dataset = self._dataset(spec, path, workers=workers)
        batches = list(dataset)
        # Record 3 dropped: its batch comes back short, the stream lives,
        # and the surviving values are exactly the good records in order.
        sizes = [batch["features/x"].shape[0] for batch in batches]
        assert sizes == [3, 4]
        got = np.concatenate([np.asarray(b["features/x"])[:, 0]
                              for b in batches])
        np.testing.assert_array_equal(got, [0, 1, 2, 4, 5, 6, 7])
        stats = dataset.stats()
        assert stats["records_skipped"] == 1
        assert stats["batches_degraded"] == 1
        assert stats["batches_dropped"] == 0
        dataset.close()

    def test_skip_whole_bad_batch_dropped(self, tmp_path, monkeypatch):
        monkeypatch.setenv("T2R_PARSE_ON_ERROR", "skip")
        spec, path = self._corrupt_fixture(
            tmp_path, n=8, bad=(0, 1, 2, 3)
        )
        dataset = self._dataset(spec, path)
        batches = list(dataset)
        assert [b["features/x"].shape[0] for b in batches] == [4]
        stats = dataset.stats()
        assert stats["records_skipped"] == 4
        assert stats["batches_dropped"] == 1
        dataset.close()

    def test_skip_mode_reraises_batch_level_failures(self, monkeypatch):
        """Skip mode is licensed to swallow RECORD corruption only: a
        failure where every record parses individually (stacking/ROI/
        parser bug at batch level) must re-raise the original error
        uncounted, not log 'dropped 0 records' and die on the retry."""
        from tensor2robot_tpu.data.dataset import (
            ParseStats, _parse_chunk_impl,
        )

        monkeypatch.setenv("T2R_PARSE_ON_ERROR", "skip")

        class BatchLevelBroken:
            def parse_single(self, record):
                return {"x": np.zeros(3, np.float32)}

            def parse_batch(self, chunk, roi=None):
                raise RuntimeError("batch-level stacking failure")

        stats = ParseStats()
        with pytest.raises(RuntimeError, match="batch-level"):
            _parse_chunk_impl(None, BatchLevelBroken(), [b"a", b"b"], stats)
        assert stats.snapshot()["records_skipped"] == 0
        assert stats.snapshot()["batches_degraded"] == 0

    def test_skip_counts_worker_fallbacks_in_stats(
        self, tmp_path, monkeypatch
    ):
        """Process backend: worker-side fast-parser fallbacks must fold
        into the parent's stats() (they ride the payload delta)."""
        monkeypatch.setenv("T2R_PARSE_ON_ERROR", "skip")
        spec, path = self._corrupt_fixture(tmp_path)
        dataset = self._dataset(spec, path, workers=2, backend="process")
        batches = list(dataset)
        assert [b["features/x"].shape[0] for b in batches] == [3, 4]
        stats = dataset.stats()
        assert stats["records_skipped"] == 1
        # The corrupt batch forced one worker fast-parse fallback, and
        # it must be visible HERE, not trapped in the worker process.
        assert stats["fast_fallbacks"] >= 1
        dataset.close()

    def test_skip_mode_clean_stream_untouched(self, tmp_path, monkeypatch):
        """With no corruption, skip mode changes nothing: same batches,
        zero counters (the flag is a failure-path policy, not a parser
        variant)."""
        monkeypatch.setenv("T2R_PARSE_ON_ERROR", "skip")
        spec, path = self._corrupt_fixture(tmp_path, bad=())
        dataset = self._dataset(spec, path)
        batches = list(dataset)
        assert [b["features/x"].shape[0] for b in batches] == [4, 4]
        assert dataset.stats()["records_skipped"] == 0
        dataset.close()


class TestParallelParse:
    """The thread-pool parse path must match the synchronous path exactly
    (same batches, same order) — parallelism is an implementation detail."""

    def make_records(self, tmp_path, n=24):
        spec = TensorSpecStruct()
        spec["img"] = ExtendedTensorSpec(
            shape=(8, 10, 3), dtype=np.uint8, name="img", data_format="jpeg"
        )
        spec["y"] = ExtendedTensorSpec(shape=(), dtype=np.int64, name="y")
        records = []
        for i in range(n):
            img = np.full((8, 10, 3), i % 250, np.uint8)
            records.append(
                encode_example(spec, {"img": img, "y": np.asarray(i, np.int64)})
            )
        tfrecord.write_tfrecords(str(tmp_path / "imgs.tfrecord"), records)
        return spec

    def _batches(self, tmp_path, spec, workers):
        dataset = RecordDataset(
            specs=spec,
            file_patterns=str(tmp_path / "imgs.tfrecord"),
            batch_size=4,
            mode="eval",
            num_parse_workers=workers,
        )
        return list(dataset)

    def test_parallel_matches_synchronous(self, tmp_path):
        spec = self.make_records(tmp_path)
        sync = self._batches(tmp_path, spec, workers=0)
        par = self._batches(tmp_path, spec, workers=4)
        assert len(sync) == len(par) == 6
        for a, b in zip(sync, par):
            np.testing.assert_array_equal(a["y"], b["y"])
            np.testing.assert_array_equal(a["img"], b["img"])

    def test_parallel_train_stream(self, tmp_path):
        spec = self.make_records(tmp_path)
        dataset = RecordDataset(
            specs=spec,
            file_patterns=str(tmp_path / "imgs.tfrecord"),
            batch_size=4,
            mode="train",
            seed=1,
            num_parse_workers=2,
        )
        it = iter(dataset)
        batches = [next(it) for _ in range(10)]  # > one epoch; repeats fine
        assert all(b["img"].shape == (4, 8, 10, 3) for b in batches)

    @pytest.mark.slow
    def test_process_backend_shm_ring_roundtrip(self, tmp_path):
        """Batches big enough for the shared-memory return path (>= 1 MB
        of decoded image) must round-trip bit-exact through ring slots,
        across epochs (slot reuse), and slots must recycle rather than
        leak (bounded ring)."""
        spec = TensorSpecStruct()
        spec["img"] = ExtendedTensorSpec(
            shape=(320, 320, 3), dtype=np.uint8, name="img", data_format="png"
        )
        spec["y"] = ExtendedTensorSpec(shape=(), dtype=np.int64, name="y")
        records = []
        for i in range(8):
            img = np.full((320, 320, 3), i * 7 % 250, np.uint8)
            records.append(
                encode_example(spec, {"img": img, "y": np.asarray(i, np.int64)})
            )
        tfrecord.write_tfrecords(str(tmp_path / "shm.tfrecord"), records)
        kwargs = dict(
            specs=spec,
            file_patterns=str(tmp_path / "shm.tfrecord"),
            batch_size=4,
            mode="eval",
            num_parse_workers=2,
        )
        ref = list(RecordDataset(parse_backend="thread", **kwargs))
        ds = RecordDataset(parse_backend="process", **kwargs)
        from tensor2robot_tpu.data.dataset import _ShmArray

        shm_batches = 0
        # Enough epochs that total shm cycles exceed the ring size
        # (max_in_flight + 2 slots): recycling, not just first use.
        num_epochs = 8
        for epoch in range(num_epochs):
            batches = list(ds)
            assert len(batches) == len(ref) == 2
            for a, b in zip(batches, ref):
                if isinstance(a["img"], _ShmArray):
                    shm_batches += 1
                np.testing.assert_array_equal(
                    np.asarray(a["img"]), np.asarray(b["img"])
                )
                np.testing.assert_array_equal(
                    np.asarray(a["y"]), np.asarray(b["y"])
                )
            del a, b, batches  # release views so slots return to the ring
        assert ds._shm_ring is not None
        ring_size = len(ds._shm_ring.slots)
        assert ring_size > 0
        # First batches return inline (they size the ring); after that the
        # shm path must carry the image batches, INCLUDING after every
        # slot has been used once — i.e. released slots really recycle.
        assert shm_batches > ring_size, (shm_batches, ring_size)
        # Early abandonment must not leak ring slots: drop an iterator
        # mid-epoch, then a fresh full epoch must still ride the shm path
        # (completed-but-unconsumed futures return their slots on discard).
        for _ in range(3):
            it = iter(ds)
            next(it)
            del it
        batches = list(ds)
        assert any(isinstance(b["img"], _ShmArray) for b in batches)
        del batches
        ds.close()
        assert ds._shm_ring is None

    def test_parse_error_propagates(self, tmp_path):
        spec = TensorSpecStruct()
        spec["x"] = ExtendedTensorSpec(shape=(2,), dtype=np.float32, name="x")
        # Write records missing the required feature.
        bad_spec = TensorSpecStruct()
        bad_spec["z"] = ExtendedTensorSpec(shape=(2,), dtype=np.float32, name="z")
        records = [
            encode_example(bad_spec, {"z": np.zeros((2,), np.float32)})
            for _ in range(4)
        ]
        tfrecord.write_tfrecords(str(tmp_path / "bad.tfrecord"), records)
        dataset = RecordDataset(
            specs=spec,
            file_patterns=str(tmp_path / "bad.tfrecord"),
            batch_size=4,
            mode="eval",
            num_parse_workers=2,
        )
        with pytest.raises(KeyError):
            list(dataset)


def _counted(name, before):
    from tensor2robot_tpu.utils import tracing

    return tracing.counters().get(name, 0) - before.get(name, 0)


class TestSlicedParse:
    """A batch parsed in slices over the pool is the batch a single job
    parses whole, bit for bit: same records, same order, same offsets for
    a seed. Batches of 50 over 1, 3 and 8 workers are cut into slices of
    50, 17+17+16 and 16+16+16+2 records."""

    BATCH = 50

    def _spec(self, kind):
        spec = TensorSpecStruct()
        if kind == "stack":
            spec["frames"] = ExtendedTensorSpec(
                shape=(3, 12, 16, 3), dtype=np.uint8, name="frames",
                data_format="jpeg",
            )
            spec["pose"] = ExtendedTensorSpec(
                shape=(4,), dtype="bfloat16", name="pose"
            )
            spec["tail"] = ExtendedTensorSpec(
                shape=(5,), dtype=np.int64, name="tail",
                varlen_default_value=-1,
            )
            spec["never_written"] = ExtendedTensorSpec(
                shape=(1,), dtype=np.float32, name="never_written",
                is_optional=True,
            )
        elif kind == "multi":
            spec["img"] = ExtendedTensorSpec(
                shape=(12, 16, 3), dtype=np.uint8, name="img",
                data_format="jpeg", dataset_key="d1",
            )
            spec["a"] = ExtendedTensorSpec(
                shape=(2,), dtype=np.float32, name="a", dataset_key="d1"
            )
            spec["b"] = ExtendedTensorSpec(
                shape=(), dtype=np.int64, name="b", dataset_key="d2"
            )
        elif kind == "sequence":
            spec["obs"] = ExtendedTensorSpec(
                shape=(2,), dtype=np.float32, name="obs", is_sequence=True
            )
            spec["goal"] = ExtendedTensorSpec(
                shape=(1,), dtype=np.float32, name="goal"
            )
        else:
            raise ValueError(kind)
        return spec

    def _values(self, kind, i, rng):
        if kind == "stack":
            return {
                "frames": rng.randint(0, 256, (3, 12, 16, 3), dtype=np.uint8),
                "pose": rng.randn(4).astype(np.float32),
                "tail": np.arange(i % 7, dtype=np.int64),
            }
        if kind == "multi":
            return {
                "img": rng.randint(0, 256, (12, 16, 3), dtype=np.uint8),
                "a": rng.randn(2).astype(np.float32),
                "b": np.asarray(i, np.int64),
            }
        return {
            "obs": rng.randn(1 + i % 5, 2).astype(np.float32),
            "goal": np.full((1,), i, np.float32),
        }

    def _write(self, tmp_path, kind, n):
        spec = self._spec(kind)
        rng = np.random.RandomState(7)
        rows = [self._values(kind, i, rng) for i in range(n)]
        if kind == "multi":
            by_key = [encode_examples_by_dataset(spec, row) for row in rows]
            patterns = {}
            for key in ("d1", "d2"):
                patterns[key] = str(tmp_path / f"{key}.tfrecord")
                tfrecord.write_tfrecords(
                    patterns[key], [record[key] for record in by_key]
                )
            return spec, patterns
        path = str(tmp_path / f"{kind}.tfrecord")
        tfrecord.write_tfrecords(
            path, [encode_example(spec, row) for row in rows]
        )
        return spec, path

    def _batches(self, spec, patterns, workers, **kwargs):
        dataset = RecordDataset(
            specs=spec, file_patterns=patterns, batch_size=self.BATCH,
            mode="train", seed=11, shuffle_buffer_size=32, repeat=False,
            num_parse_workers=workers, **kwargs,
        )
        try:
            return [
                {key: np.asarray(value).copy() for key, value in batch.items()}
                for batch in dataset
            ]
        finally:
            dataset.close()

    @staticmethod
    def _assert_same(got, want):
        assert len(got) == len(want)
        for a, b in zip(got, want):
            assert list(a.keys()) == list(b.keys())
            for key in a:
                assert a[key].dtype == b[key].dtype, key
                np.testing.assert_array_equal(a[key], b[key], err_msg=key)

    @pytest.mark.parametrize("workers", [1, 3, 8])
    @pytest.mark.parametrize("kind", ["stack", "multi"])
    def test_sliced_batches_equal_the_whole_batch_parse(
        self, tmp_path, kind, workers
    ):
        from tensor2robot_tpu.utils import tracing

        spec, patterns = self._write(tmp_path, kind, 3 * self.BATCH + 7)
        whole = self._batches(spec, patterns, workers=0)
        before = tracing.counters()
        sliced = self._batches(spec, patterns, workers=workers)
        assert len(whole) == 3 and whole[0][
            "frames" if kind == "stack" else "img"
        ].shape[0] == self.BATCH
        assert "never_written" not in whole[0]
        self._assert_same(sliced, whole)
        assert _counted("data.parse_batches", before) == 3
        assert _counted("data.parse_batches_sliced", before) == 3

    def test_slices_written_from_more_threads_than_cores(self, tmp_path):
        """Sixteen workers on slices of one record each, the interpreter
        switching threads every 10 microseconds: a row written by two
        slices, or by none, would show against the whole-batch parse."""
        import sys

        from tensor2robot_tpu.data import dataset as dataset_lib

        spec, patterns = self._write(tmp_path, "stack", 6 * self.BATCH)
        whole = self._batches(spec, patterns, workers=0)
        interval = sys.getswitchinterval()
        floor = dataset_lib._MIN_SLICE_RECORDS
        sys.setswitchinterval(1e-5)
        dataset_lib._MIN_SLICE_RECORDS = 1
        try:
            sliced = self._batches(spec, patterns, workers=16)
        finally:
            dataset_lib._MIN_SLICE_RECORDS = floor
            sys.setswitchinterval(interval)
        self._assert_same(sliced, whole)

    @pytest.mark.parametrize("why", ["sequence", "process", "oracle"])
    def test_whole_batch_paths_say_so(self, tmp_path, why):
        """Where slices do not apply the batch is one job, and the counters
        say so: a sequence field pads to its batch's longest record, the
        process backend ships whole batches, the oracle stacks them."""
        from tensor2robot_tpu.utils import tracing

        kind = "sequence" if why == "sequence" else "multi"
        spec, patterns = self._write(tmp_path, kind, 2 * self.BATCH)
        whole = self._batches(spec, patterns, workers=0)
        before = tracing.counters()
        pooled = self._batches(
            spec, patterns, workers=3,
            parse_backend="process" if why == "process" else "thread",
            parse_fast=why != "oracle",
        )
        self._assert_same(pooled, whole)
        assert _counted("data.parse_batches", before) == 2
        assert _counted("data.parse_batches_sliced", before) == 0

    @pytest.mark.parametrize("mode", ["skip", "raise"])
    def test_corrupt_record_in_one_slice(self, tmp_path, monkeypatch, mode):
        """A slice that raises sends the whole batch through the whole-batch
        job: the oracle's error under `raise`; under `skip` the same
        surviving batch and the same ParseStats as a whole-batch parse."""
        monkeypatch.setenv("T2R_PARSE_ON_ERROR", mode)
        spec = self._spec("multi")
        del spec["b"]
        rng = np.random.RandomState(3)
        records = [
            encode_example(spec, {
                "img": rng.randint(0, 256, (12, 16, 3), dtype=np.uint8),
                "a": np.full((2,), i, np.float32),
            })
            for i in range(2 * self.BATCH)
        ]
        # Record 20 lies in the second of the first batch's three slices.
        records[20] = records[20][:4] + b"\xff\xff\xff\xff"
        path = str(tmp_path / "mixed.tfrecord")
        tfrecord.write_tfrecords(path, records)

        def run(workers):
            dataset = RecordDataset(
                spec, {"d1": path}, batch_size=self.BATCH, mode="eval",
                repeat=False, num_parse_workers=workers, prefetch_depth=0,
            )
            try:
                batches = [
                    {k: np.asarray(v).copy() for k, v in batch.items()}
                    for batch in dataset
                ]
                stats = dataset.stats()
            finally:
                dataset.close()
            assert stats.pop("parse_workers") == workers
            return batches, stats

        if mode == "raise":
            with pytest.raises(Exception) as whole_error:
                run(0)
            with pytest.raises(Exception) as sliced_error:
                run(3)
            assert type(sliced_error.value) is type(whole_error.value)
            return
        whole, whole_stats = run(0)
        sliced, sliced_stats = run(3)
        assert [b["a"].shape[0] for b in whole] == [self.BATCH - 1, self.BATCH]
        self._assert_same(sliced, whole)
        assert sliced_stats == whole_stats
        assert whole_stats["records_skipped"] == 1
        assert whole_stats["batches_degraded"] == 1
        assert whole_stats["fast_fallbacks"] == 1

    def test_optional_field_in_only_some_slices_is_refused(self, tmp_path):
        """Every slice of its own agrees (all present, or all absent), the
        batch does not: the join refuses it and the whole-batch job raises
        what it has always raised."""
        spec = self._spec("stack")
        rng = np.random.RandomState(5)
        rows = [self._values("stack", i, rng) for i in range(self.BATCH)]
        for row in rows[:17]:  # the first of three slices, and only it
            row["never_written"] = np.ones((1,), np.float32)
        path = str(tmp_path / "optional.tfrecord")
        tfrecord.write_tfrecords(
            path, [encode_example(spec, row) for row in rows]
        )
        for workers in (0, 3):
            dataset = RecordDataset(
                spec, path, batch_size=self.BATCH, mode="eval", repeat=False,
                num_parse_workers=workers,
            )
            with pytest.raises(ValueError, match="only some batch"):
                list(dataset)
            dataset.close()

    def test_a_batch_nobody_holds_gives_its_arrays_to_a_later_one(
        self, tmp_path, monkeypatch
    ):
        """Thirty batches through a handful of sets of arrays, every batch
        still bit for bit the whole-batch parse; a batch the consumer keeps
        (or keeps a view of a view of) is never written again."""
        from tensor2robot_tpu.data.wire import FastSpecParser

        spec, patterns = self._write(tmp_path, "stack", 30 * self.BATCH)
        whole = self._batches(spec, patterns, workers=0)
        allocate, fresh = FastSpecParser.allocate_batch, []

        def counted(self, n, roi=None):
            fresh.append(n)
            return allocate(self, n, roi)

        monkeypatch.setattr(FastSpecParser, "allocate_batch", counted)
        self._assert_same(self._batches(spec, patterns, workers=3), whole)
        # in flight 1 + 2, prefetch 2, and four: at most nine sets.
        assert 3 <= len(fresh) <= 9
        del fresh[:]
        dataset = RecordDataset(
            specs=spec, file_patterns=patterns, batch_size=self.BATCH,
            mode="train", seed=11, shuffle_buffer_size=32, repeat=False,
            num_parse_workers=3,
        )
        kept = []
        for index, batch in enumerate(dataset):
            # Every third batch stays, by a view of a view of its frames.
            if index % 3 == 0:
                kept.append((index, batch["frames"][1:][:, 0]))
        dataset.close()
        assert len(fresh) >= len(kept) == 10
        for index, frames in kept:
            np.testing.assert_array_equal(
                frames, whole[index]["frames"][1:][:, 0]
            )

    def test_batch_arrays_are_free_when_nobody_refers_to_them(self):
        from tensor2robot_tpu.data.dataset import _BatchArrays

        made = []

        def allocate(n, roi=None):
            made.append(n)
            return {"a": np.empty((n, 4)), "b": np.empty((n,), np.int64)}

        buffers = _BatchArrays(limit=2)
        take = functools.partial(buffers.take, allocate)
        first, second = take(3), take(3)
        assert made == [3, 3] and first["a"].base is not second["a"].base
        third = take(3)  # both sets held: fresh, and not kept
        assert made == [3, 3, 3]
        owner = id(first["a"].base)
        row = first["a"][1:2][0]  # a view of a view of a view
        del first, third
        assert id(take(3)["b"].base) != owner  # `row` still holds it
        assert made == [3, 3, 3, 3]
        del row
        again = take(3)
        assert id(again["a"].base) == owner and made == [3, 3, 3, 3]
        assert take(5)["a"].shape == (5, 4)  # another size: fresh
        assert _BatchArrays(2).take(lambda n, roi: None, 3) is None

    def test_a_batch_on_its_way_to_the_device_is_not_written_again(self):
        """`jax.device_put` may read the host array after it returns (a
        transfer in flight) or alias it for the device array's life (the
        CPU backend): either way it holds a reference for as long, so the
        set is not handed out under it; and once it has let go, writing
        the arrays again leaves what reached the device as it was."""
        import jax

        from tensor2robot_tpu.data.dataset import _BatchArrays

        buffers = _BatchArrays(limit=4)
        placed = []
        for value in range(12):
            arrays = buffers.take(
                lambda n, roi: {"img": np.empty((n, 64, 64, 3), np.uint8)}, 8
            )
            arrays["img"][...] = value
            placed.append(jax.device_put(arrays["img"]))
            del arrays
        assert len(buffers._sets) <= 4
        for value, on_device in enumerate(placed):
            assert (np.asarray(on_device) == value).all(), value

    @pytest.mark.parametrize("prefetch_depth", [0, 2])
    def test_early_close_mid_batch_leaks_no_thread_and_no_batch(
        self, tmp_path, monkeypatch, prefetch_depth
    ):
        import gc
        import threading
        import time
        import weakref

        from tensor2robot_tpu.data.wire import FastSpecParser

        spec, patterns = self._write(tmp_path, "stack", 8 * self.BATCH)
        allocated = []
        allocate = FastSpecParser.allocate_batch

        def watched(self, n, roi=None):
            arrays = allocate(self, n, roi)
            allocated.extend(weakref.ref(a) for a in arrays.values())
            return arrays

        monkeypatch.setattr(FastSpecParser, "allocate_batch", watched)
        mine = lambda: [  # noqa: E731
            t for t in threading.enumerate()
            if t.name.startswith("t2r-parse") and t.is_alive()
        ]
        threads_before = set(mine())
        dataset = RecordDataset(
            specs=spec, file_patterns=patterns, batch_size=self.BATCH,
            mode="train", seed=3, num_parse_workers=3,
            prefetch_depth=prefetch_depth,
        )
        iterator = iter(dataset)
        first = next(iterator)
        assert first["frames"].shape[0] == self.BATCH
        # Batches are in flight behind the first: several arrays live.
        assert len(allocated) > len(first)
        iterator.close()
        del iterator, first
        dataset.close()
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            gc.collect()
            if not (set(mine()) - threads_before) and not any(
                ref() is not None for ref in allocated
            ):
                break
            time.sleep(0.05)
        assert not set(mine()) - threads_before
        assert not any(ref() is not None for ref in allocated)

    def test_width_and_in_flight_follow_cores_and_batch(
        self, tmp_path, monkeypatch
    ):
        from tensor2robot_tpu.data import dataset as dataset_lib

        monkeypatch.delenv("T2R_PARSE_WORKERS", raising=False)
        for cores, want in ((1, 1), (2, 1), (13, 12), (64, 63)):
            monkeypatch.setattr(
                dataset_lib.os, "sched_getaffinity",
                lambda pid, cores=cores: set(range(cores)), raising=False,
            )
            assert dataset_lib.default_parse_workers() == want
        monkeypatch.setenv("T2R_PARSE_WORKERS", "5")
        assert dataset_lib.default_parse_workers() == 5
        bounds = dataset_lib._slice_bounds
        assert bounds(256, 12) == [(a, min(a + 22, 256)) for a in range(0, 256, 22)]
        assert bounds(50, 8) == [(0, 16), (16, 32), (32, 48), (48, 50)]
        assert bounds(4, 8) == [(0, 4)] and bounds(50, 1) == [(0, 50)]
        spec, files = self._write(tmp_path, "multi", 1)

        def in_flight(batch, workers, **kwargs):
            return RecordDataset(
                spec, files, batch_size=batch, num_parse_workers=workers,
                **kwargs,
            )._max_in_flight()

        # A batch of 256 gives 12 workers a slice each: one batch working,
        # prefetch_depth (2) behind it, where whole-batch jobs need 12 + 2.
        assert in_flight(256, 12) == 3
        assert in_flight(256, 12, parse_backend="process") == 14
        assert in_flight(256, 12, parse_fast=False) == 14
        assert in_flight(32, 12) == 8  # two slices a batch: six batches working
        assert in_flight(4, 2, prefetch_depth=0) == 3


class TestCompression:
    def test_compress_decompress_roundtrip_png(self):
        from tensor2robot_tpu.data.compression import (
            create_compress_fn,
            create_decompress_fn,
        )

        spec = TensorSpecStruct()
        spec["img"] = ExtendedTensorSpec(
            shape=(6, 7, 3), dtype=np.uint8, name="img", data_format="png"
        )
        spec["action"] = ExtendedTensorSpec(
            shape=(2,), dtype=np.float32, name="action"
        )
        batch = TensorSpecStruct()
        rng = np.random.RandomState(0)
        batch["img"] = rng.randint(0, 255, (3, 6, 7, 3), np.uint8)
        batch["action"] = rng.randn(3, 2).astype(np.float32)

        compressed = create_compress_fn(spec)(batch)
        assert isinstance(compressed["img"][0], bytes)
        np.testing.assert_array_equal(compressed["action"], batch["action"])
        restored = create_decompress_fn(spec)(compressed)
        # PNG is lossless: exact roundtrip.
        np.testing.assert_array_equal(restored["img"], batch["img"])

    def test_jpeg_compress_is_lossy_but_close(self):
        from tensor2robot_tpu.data.compression import (
            create_compress_fn,
            create_decompress_fn,
        )

        spec = TensorSpecStruct()
        spec["img"] = ExtendedTensorSpec(
            shape=(16, 16, 3), dtype=np.uint8, name="img", data_format="jpeg"
        )
        batch = TensorSpecStruct()
        batch["img"] = np.full((2, 16, 16, 3), 128, np.uint8)
        restored = create_decompress_fn(spec)(create_compress_fn(spec)(batch))
        assert restored["img"].shape == (2, 16, 16, 3)
        assert np.abs(restored["img"].astype(int) - 128).max() <= 4

    def test_image_stack_roundtrip(self):
        from tensor2robot_tpu.data.compression import (
            create_compress_fn,
            create_decompress_fn,
        )

        spec = TensorSpecStruct()
        spec["frames"] = ExtendedTensorSpec(
            shape=(4, 6, 6, 3), dtype=np.uint8, name="frames", data_format="png"
        )
        batch = TensorSpecStruct()
        batch["frames"] = np.random.RandomState(1).randint(
            0, 255, (2, 4, 6, 6, 3), np.uint8
        )
        compressed = create_compress_fn(spec)(batch)
        assert len(compressed["frames"]) == 2
        assert len(compressed["frames"][0]) == 4
        restored = create_decompress_fn(spec)(compressed)
        np.testing.assert_array_equal(restored["frames"], batch["frames"])


class TestHostSharding:
    def _write_shards(self, tmp_path, n=4):
        spec = TensorSpecStruct()
        spec["y"] = ExtendedTensorSpec(shape=(), dtype=np.int64, name="y")
        for shard in range(n):
            tfrecord.write_tfrecords(
                str(tmp_path / f"s-{shard}.tfrecord"),
                [encode_example(spec, {"y": np.asarray(shard, np.int64)})],
            )
        return spec

    def test_hosts_get_disjoint_complete_slices(self, tmp_path, monkeypatch):
        import jax

        spec = self._write_shards(tmp_path)
        seen = []
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        for host in range(2):
            monkeypatch.setattr(jax, "process_index", lambda h=host: h)
            dataset = RecordDataset(
                specs=spec,
                file_patterns=str(tmp_path / "s-*.tfrecord"),
                batch_size=1,
                mode="eval",
                drop_remainder=False,
                shard_by_host=True,
            )
            seen.append(
                sorted(int(b["y"][0]) for b in dataset)
            )
        # Round-robin over the sorted file list: disjoint and complete.
        assert seen[0] == [0, 2] and seen[1] == [1, 3]

    def test_host_without_files_raises(self, tmp_path, monkeypatch):
        import jax

        spec = self._write_shards(tmp_path, n=1)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(jax, "process_index", lambda: 1)
        with pytest.raises(ValueError, match="no files"):
            RecordDataset(
                specs=spec,
                file_patterns=str(tmp_path / "s-*.tfrecord"),
                batch_size=1,
                mode="eval",
                shard_by_host=True,
            )

    def test_single_process_unaffected(self, tmp_path):
        spec = TensorSpecStruct()
        spec["y"] = ExtendedTensorSpec(shape=(), dtype=np.int64, name="y")
        for shard in range(4):
            tfrecord.write_tfrecords(
                str(tmp_path / f"s-{shard}.tfrecord"),
                [encode_example(spec, {"y": np.asarray(shard, np.int64)})],
            )
        dataset = RecordDataset(
            specs=spec,
            file_patterns=str(tmp_path / "s-*.tfrecord"),
            batch_size=2,
            mode="eval",
            shard_by_host=True,  # process_count()==1 -> no-op
        )
        ys = np.concatenate([b["y"] for b in dataset])
        assert sorted(ys.tolist()) == [0, 1, 2, 3]
