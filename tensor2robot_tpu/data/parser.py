"""Spec-driven Example/SequenceExample parsing.

Auto-generates a parse function from tensor specifications, the defining
feature of the framework: a model declares *what* it consumes and the parser
for serialized records is derived, never hand-written.

Feature selection rules (behavioral parity with
tensor2robot/utils/tfdata.py:213-543 and utils/tensorspec_utils.py:1571-1593):
  * `data_format` in {jpeg, png} -> bytes feature decoded to the spec's
    image shape; an empty string decodes to a zero image (replay buffers
    contain empty camera slots).
  * floating dtypes  -> float_list (bfloat16-declared specs are parsed as
    float32 and cast at the end, floats are stored f32 on disk).
  * integer/bool     -> int64_list, cast to the spec dtype.
  * `varlen_default_value` set -> variable-length parse, padded/clipped to
    the spec's static shape.
  * `is_sequence`    -> read from SequenceExample feature_lists (one step per
    list entry); other specs of the same dataset read from `context`. A
    `<key>_length` int64 scalar reports the true length; batching pads to
    the batch max.
  * `dataset_key`    -> specs are routed to named datasets; the parser then
    accepts a dict of serialized buffers, one per key.
"""

from __future__ import annotations

import io
import threading as _threading
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.proto import example_pb2
from tensor2robot_tpu.specs import (
    ExtendedTensorSpec,
    TensorSpecStruct,
    canonical_dtype,
    flatten_spec_structure,
    pad_or_clip_tensor_to_spec_shape,
)

# -- native jpeg decode (one-shot libjpeg into the output array) -------------
# The PIL path feeds the decoder in 64 KB chunks through a Python loop and
# copies the frame twice more (mode convert + numpy export); profiling put
# ~90% of record-parse time there. native/jpeg_decode.cc decodes the whole
# buffer in one call directly into the numpy array. PIL stays as the
# fallback (and the png path).
_jpeg_lib = None
_jpeg_lib_failed = False
_jpeg_lib_lock = _threading.Lock()


def _load_jpeg_native():
    global _jpeg_lib, _jpeg_lib_failed
    if _jpeg_lib is not None or _jpeg_lib_failed:
        return _jpeg_lib
    import ctypes
    import os
    import subprocess

    with _jpeg_lib_lock:
        if _jpeg_lib is not None or _jpeg_lib_failed:
            return _jpeg_lib
        return _load_jpeg_native_locked(ctypes, os, subprocess)


def _load_jpeg_native_locked(ctypes, os, subprocess):
    global _jpeg_lib, _jpeg_lib_failed
    native_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native"
    )
    lib_path = os.path.join(native_dir, "libt2r_jpeg.so")
    try:
        if not os.path.exists(lib_path):
            subprocess.run(
                ["make", "-C", native_dir, "libt2r_jpeg.so"],
                check=True,
                capture_output=True,
            )
        lib = ctypes.CDLL(lib_path)
        lib.t2r_decode_jpeg.restype = ctypes.c_int
        lib.t2r_decode_jpeg.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int),
        ]
        try:
            lib.t2r_decode_jpeg_roi.restype = ctypes.c_int
            lib.t2r_decode_jpeg_roi.argtypes = [
                ctypes.c_char_p,
                ctypes.c_size_t,
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_int,
                ctypes.c_int,
                ctypes.c_int,
                ctypes.c_int,
                ctypes.c_int,
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int),
            ]
        except AttributeError:
            # A stale .so from before the ROI entry point existed: the
            # full-frame path still works; ROI decode falls back.
            pass
        _jpeg_lib = lib
    except Exception:
        _jpeg_lib_failed = True
    return _jpeg_lib


def native_jpeg_loaded() -> bool:
    """True when jpeg decode runs through native/jpeg_decode.cc; False
    means this process decodes with PIL (the build failed or no toolchain
    exists). Loads the decoder if nothing has yet."""
    return _load_jpeg_native() is not None


def decode_image_into_native(data: bytes, out: np.ndarray) -> bool:
    """Decodes a jpeg directly INTO `out` (uint8, HxWx3, C-contiguous).

    The zero-copy half of the fast batch parser (data/wire.py): `out` is a
    record's slot inside a preallocated batch array, so a successful decode
    writes scanlines straight into the batch with no intermediate frame.
    Returns False on any mismatch/failure — the slot contents are then
    undefined and the caller must fall back to `decode_image` (which either
    fills the slot or raises the canonical error).
    """
    lib = _load_jpeg_native()
    if lib is None:
        return False
    import ctypes

    if out.dtype != np.uint8 or out.ndim != 3 or out.shape[-1] != 3:
        # Grayscale requests stay on PIL: libjpeg's JCS_GRAYSCALE takes
        # the Y plane directly while PIL recomputes luma from the
        # reconstructed RGB — different pixels for color sources, and
        # decoded values must not depend on whether the native library
        # built.
        return False
    if not out.flags.c_contiguous:
        return False
    h = ctypes.c_int()
    w = ctypes.c_int()
    rc = lib.t2r_decode_jpeg(
        data,
        len(data),
        ctypes.c_void_p(out.ctypes.data),
        out.nbytes,
        3,
        ctypes.byref(h),
        ctypes.byref(w),
    )
    return rc == 0 and (h.value, w.value) == tuple(out.shape[:2])


# -- ROI (cropped) decode -----------------------------------------------------
# The native ROI entry point (t2r_decode_jpeg_roi) skips rows outside the
# crop window before IDCT/upsampling and trims columns at iMCU granularity;
# the claim that its output is BIT-IDENTICAL to full-decode-then-crop is
# verified empirically, once per process, by `_roi_native_ok` below —
# decoded pixels must never depend on which libjpeg the host happens to
# ship. On canary failure (or no ROI API in the .so) every ROI decode
# falls back to full decode + numpy crop: slower, identical pixels.
_roi_native_state: Optional[bool] = None


def _roi_native_ok() -> bool:
    """One-time self-test: ROI decode == full decode + crop on this host.

    Exercises sub-MCU offsets and window edges on a deterministic
    synthetic image at the default (4:2:0) and 4:4:4 subsamplings — the
    cases where libjpeg's cropped fancy-upsampling could diverge from a
    full decode if the margin handling in jpeg_decode.cc were wrong.
    """
    global _roi_native_state
    if _roi_native_state is not None:
        return _roi_native_state
    lib = _load_jpeg_native()
    if lib is None or not hasattr(lib, "t2r_decode_jpeg_roi"):
        _roi_native_state = False
        return False
    try:
        import io

        from PIL import Image

        rng = np.random.RandomState(0)
        src = rng.randint(0, 256, (48, 64, 3), dtype=np.uint8)
        ok = True
        for subsampling in (2, 0):  # 4:2:0 (PIL default) and 4:4:4
            buf = io.BytesIO()
            Image.fromarray(src).save(
                buf, format="JPEG", quality=90, subsampling=subsampling
            )
            data = buf.getvalue()
            full = np.empty((48, 64, 3), np.uint8)
            if not decode_image_into_native(data, full):
                ok = False
                break
            for rect in ((0, 0, 48, 64), (17, 23, 23, 29), (7, 3, 41, 61)):
                y, x, th, tw = rect
                out = np.empty((th, tw, 3), np.uint8)
                if not _roi_decode_into(lib, data, out, y, x, (48, 64)):
                    ok = False
                    break
                if not np.array_equal(out, full[y : y + th, x : x + tw]):
                    ok = False
                    break
            if not ok:
                break
        _roi_native_state = ok
    except Exception:
        _roi_native_state = False
    return _roi_native_state


def _roi_decode_into(lib, data: bytes, out: np.ndarray, y: int, x: int,
                     expected_hw) -> bool:
    """Raw native ROI call; False on any failure or source-dim mismatch."""
    import ctypes

    fh = ctypes.c_int()
    fw = ctypes.c_int()
    rc = lib.t2r_decode_jpeg_roi(
        data,
        len(data),
        ctypes.c_void_p(out.ctypes.data),
        out.nbytes,
        3,
        y,
        x,
        out.shape[0],
        out.shape[1],
        ctypes.byref(fh),
        ctypes.byref(fw),
    )
    return rc == 0 and (fh.value, fw.value) == tuple(expected_hw)


def decode_image_roi_into_native(
    data: bytes, out: np.ndarray, y: int, x: int, expected_hw
) -> bool:
    """ROI-decodes a jpeg window directly INTO `out` (uint8, th x tw x 3).

    `expected_hw` is the source image's (H, W) from the spec: a source
    whose real dimensions differ must fail here so the caller's fallback
    path raises the canonical shape error instead of silently cropping a
    different geometry. Returns False on any mismatch/failure (slot
    contents then undefined; caller falls back to full decode + crop).
    """
    lib = _load_jpeg_native()
    if lib is None or not _roi_native_ok():
        return False
    if out.dtype != np.uint8 or out.ndim != 3 or out.shape[-1] != 3:
        return False
    if not out.flags.c_contiguous:
        return False
    return _roi_decode_into(lib, data, out, y, x, expected_hw)


def decode_image_roi(
    data: bytes, spec: ExtendedTensorSpec, y: int, x: int, th: int, tw: int
) -> np.ndarray:
    """Decodes only the (y, x, th, tw) window of an encoded image.

    Bit-identical to `decode_image(data, spec)[y:y+th, x:x+tw]` by
    construction: the native path's parity is canary-verified
    (`_roi_native_ok`), and the fallback literally full-decodes and
    crops. Empty data yields a zero window (the zero-image fallback,
    cropped)."""
    shape = tuple(spec.shape[-3:]) if len(spec.shape) >= 3 else tuple(spec.shape)
    if any(d is None for d in shape):
        raise ValueError(f"Image spec {spec.name!r} must have static H/W/C, got {shape}")
    if not data:
        return np.zeros((th, tw) + shape[2:], dtype=canonical_dtype(spec.dtype))
    if (
        len(shape) == 3
        and shape[-1] == 3
        and spec.data_format
        and spec.data_format.lower() in ("jpeg", "jpg")
        and data[:2] == b"\xff\xd8"
        and canonical_dtype(spec.dtype) == np.dtype(np.uint8)
    ):
        out = np.empty((th, tw, 3), np.uint8)
        if decode_image_roi_into_native(data, out, y, x, shape[:2]):
            return out
    return decode_image(data, spec)[y : y + th, x : x + tw]


def _decode_jpeg_native(data: bytes, shape) -> Optional[np.ndarray]:
    """One-shot decode into a fresh uint8 array of `shape`; None on any
    mismatch/failure (caller falls back to PIL)."""
    if len(shape) != 3 or shape[-1] != 3:
        return None
    out = np.empty(shape, np.uint8)
    if not decode_image_into_native(data, out):
        return None
    return out


def decode_image(data: bytes, spec: ExtendedTensorSpec) -> np.ndarray:
    """Decodes a jpeg/png byte string to the spec's image shape.

    Empty strings yield a zero image (reference zero-image fallback,
    utils/tfdata.py:463-475).
    """
    shape = tuple(spec.shape[-3:]) if len(spec.shape) >= 3 else tuple(spec.shape)
    if any(d is None for d in shape):
        raise ValueError(f"Image spec {spec.name!r} must have static H/W/C, got {shape}")
    if not data:
        return np.zeros(shape, dtype=canonical_dtype(spec.dtype))
    if (
        spec.data_format
        and spec.data_format.lower() in ("jpeg", "jpg")
        and data[:2] == b"\xff\xd8"
    ):
        decoded = _decode_jpeg_native(data, shape)
        if decoded is not None:
            return decoded.astype(canonical_dtype(spec.dtype), copy=False)
    from PIL import Image  # deferred: PIL not needed on non-image paths

    img = Image.open(io.BytesIO(data))
    channels = shape[-1] if len(shape) == 3 else 1
    if channels == 3:
        img = img.convert("RGB")
    elif channels == 1:
        img = img.convert("L")
    arr = np.asarray(img)
    if arr.ndim == 2 and len(shape) == 3:
        arr = arr[..., None]
    if arr.shape != tuple(shape):
        raise ValueError(
            f"Decoded image shape {arr.shape} does not match spec "
            f"{spec.name!r} shape {shape}"
        )
    return arr.astype(canonical_dtype(spec.dtype))


def _num_elements(shape: Sequence[Optional[int]]) -> int:
    n = 1
    for d in shape:
        if d is None:
            raise ValueError(f"FixedLen parse requires static shape, got {shape}")
        n *= d
    return n


def _feature_values(feature: example_pb2.Feature) -> Tuple[str, Any]:
    kind = feature.WhichOneof("kind")
    if kind == "bytes_list":
        return kind, list(feature.bytes_list.value)
    if kind == "float_list":
        return kind, np.asarray(feature.float_list.value, dtype=np.float32)
    if kind == "int64_list":
        return kind, np.asarray(feature.int64_list.value, dtype=np.int64)
    return "", None


def _storage_kind(spec: ExtendedTensorSpec) -> str:
    if spec.data_format is not None:
        return "bytes_list"
    dtype = canonical_dtype(spec.dtype)
    if jnp.issubdtype(dtype, np.floating):
        return "float_list"
    if jnp.issubdtype(dtype, np.integer) or dtype == np.dtype(bool):
        return "int64_list"
    if dtype.kind in ("S", "O", "U"):
        return "bytes_list"
    raise ValueError(f"No storage mapping for spec dtype {dtype} ({spec.name!r})")


class _FieldParser:
    """Parses one spec's value out of a Features map or FeatureList."""

    def __init__(self, key: str, spec: ExtendedTensorSpec):
        self.key = key
        self.spec = spec
        self.lookup_name = spec.name or key
        self.kind = _storage_kind(spec)
        self.out_dtype = canonical_dtype(spec.dtype)
        # bfloat16 has no on-disk representation; it travels as float32.
        self.parse_dtype = (
            np.float32 if self.out_dtype == jnp.bfloat16 else self.out_dtype
        )

    def _convert(self, kind: str, values: Any) -> np.ndarray:
        spec = self.spec
        if spec.data_format is not None:
            images = [decode_image(v, spec) for v in values]
            if spec.varlen_default_value is not None and len(spec.shape) >= 4:
                # Varlen image stacks pad (with zero images) or clip to the
                # spec's leading dim; varlen_default_value only selects the
                # varlen parse mode for images — padding is zeros.
                target = int(spec.shape[0])
                images = images[:target]
                zero = np.zeros_like(images[0]) if images else np.zeros(
                    tuple(int(d) for d in spec.shape[1:]), self.out_dtype
                )
                images = images + [zero] * (target - len(images))
                return np.stack(images)
            if len(spec.shape) <= 3:
                if len(images) != 1:
                    raise ValueError(
                        f"Feature {self.lookup_name!r} holds {len(images)} "
                        "images but the spec declares a single image "
                        f"{tuple(spec.shape)}"
                    )
                return images[0]
            if spec.shape[0] is not None and len(images) != spec.shape[0]:
                raise ValueError(
                    f"Feature {self.lookup_name!r} holds {len(images)} images "
                    f"but the spec stack requires {spec.shape[0]}"
                )
            return np.stack(images)
        if kind != self.kind:
            raise ValueError(
                f"Feature {self.lookup_name!r} stored as {kind} but spec "
                f"expects {self.kind}"
            )
        arr = np.asarray(values)
        if spec.varlen_default_value is not None:
            arr = pad_or_clip_tensor_to_spec_shape(arr, spec)
            return arr.astype(self.parse_dtype)
        n = _num_elements(spec.shape)
        if arr.size != n:
            raise ValueError(
                f"Feature {self.lookup_name!r} has {arr.size} elements, spec "
                f"{tuple(spec.shape)} requires {n}"
            )
        return arr.reshape(tuple(spec.shape)).astype(self.parse_dtype)

    def parse_context(self, features: example_pb2.Features) -> Optional[np.ndarray]:
        feature = features.feature.get(self.lookup_name)
        if feature is None:
            if self.spec.is_optional:
                return None
            raise KeyError(
                f"Required feature {self.lookup_name!r} missing from example "
                f"(available: {sorted(features.feature.keys())[:20]})"
            )
        kind, values = _feature_values(feature)
        return self._convert(kind, values)

    def parse_sequence(
        self, feature_lists: example_pb2.FeatureLists
    ) -> Optional[Tuple[np.ndarray, int]]:
        flist = feature_lists.feature_list.get(self.lookup_name)
        if flist is None:
            if self.spec.is_optional:
                return None
            raise KeyError(
                f"Required sequence feature {self.lookup_name!r} missing "
                f"(available: {sorted(feature_lists.feature_list.keys())[:20]})"
            )
        steps = []
        for feature in flist.feature:
            kind, values = _feature_values(feature)
            steps.append(self._convert(kind, values))
        if not steps:
            shape = (0,) + tuple(int(d) for d in self.spec.shape)
            return np.zeros(shape, self.parse_dtype), 0
        return np.stack(steps), len(steps)


class ExampleParser:
    """Parses serialized records into a flat {path: np.ndarray} dict.

    One parser handles one dataset_key group; `SpecParser` (below) composes
    one per dataset for multi-dataset specs.
    """

    def __init__(self, specs: Union[TensorSpecStruct, Mapping]):
        flat = flatten_spec_structure(specs)
        self._fields: List[_FieldParser] = []
        self._sequence_fields: List[_FieldParser] = []
        for key, spec in flat.items():
            if not isinstance(spec, ExtendedTensorSpec):
                continue
            field = _FieldParser(key, spec)
            if spec.is_sequence:
                self._sequence_fields.append(field)
            else:
                self._fields.append(field)
        self.is_sequence_parser = bool(self._sequence_fields)

    def parse(self, serialized: bytes) -> Dict[str, np.ndarray]:
        out: Dict[str, np.ndarray] = {}
        if self.is_sequence_parser:
            proto = example_pb2.SequenceExample.FromString(serialized)
            context = proto.context
            for field in self._sequence_fields:
                parsed = field.parse_sequence(proto.feature_lists)
                if parsed is not None:
                    tensor, length = parsed
                    out[field.key] = tensor
                    out[field.key + "_length"] = np.asarray(length, np.int64)
        else:
            proto = example_pb2.Example.FromString(serialized)
            context = proto.features
        for field in self._fields:
            value = field.parse_context(context)
            if value is not None:
                out[field.key] = value
        return out


def _pad_to(arr: np.ndarray, length: int) -> np.ndarray:
    if arr.shape[0] == length:
        return arr
    pad = np.zeros((length - arr.shape[0],) + arr.shape[1:], arr.dtype)
    return np.concatenate([arr, pad], axis=0)


class SpecParser:
    """Spec-complete parser: multi-dataset routing + batching + bf16 cast.

    parse_batch() is the pipeline hot path: it parses a list of serialized
    records (or a dict of lists for multi-dataset specs), stacks them along a
    new batch axis, pads sequence features to the batch-max length, and
    applies the bfloat16 egress cast for specs declared bf16.
    """

    def __init__(self, specs: Union[TensorSpecStruct, Mapping]):
        self._flat = flatten_spec_structure(specs)
        self._parsers: Dict[str, ExampleParser] = {}
        keys_seen: Dict[str, TensorSpecStruct] = {}
        for key, spec in self._flat.items():
            if not isinstance(spec, ExtendedTensorSpec):
                continue
            group = keys_seen.setdefault(spec.dataset_key, TensorSpecStruct())
            group[key] = spec
        for dataset_key, group in keys_seen.items():
            self._parsers[dataset_key] = ExampleParser(group)
        self._bf16_keys = [
            key
            for key, spec in self._flat.items()
            if isinstance(spec, ExtendedTensorSpec)
            and canonical_dtype(spec.dtype) == jnp.bfloat16
        ]

    @property
    def dataset_keys(self) -> Tuple[str, ...]:
        return tuple(self._parsers.keys())

    def parse_single(
        self, serialized: Union[bytes, Mapping[str, bytes]]
    ) -> Dict[str, np.ndarray]:
        if isinstance(serialized, (bytes, bytearray)):
            if list(self._parsers.keys()) != [""]:
                raise ValueError(
                    "Multi-dataset specs require a dict of serialized records "
                    f"keyed by {sorted(self._parsers.keys())}"
                )
            return self._parsers[""].parse(bytes(serialized))
        out: Dict[str, np.ndarray] = {}
        for dataset_key, parser in self._parsers.items():
            if dataset_key not in serialized:
                raise KeyError(f"Missing serialized record for dataset {dataset_key!r}")
            out.update(parser.parse(serialized[dataset_key]))
        return out

    def parse_batch(
        self,
        serialized_batch: Union[Sequence[bytes], Mapping[str, Sequence[bytes]]],
        roi: Optional[Mapping[str, Any]] = None,
    ) -> TensorSpecStruct:
        """Parses + stacks a batch; `roi` ({key: ResolvedROI}) crops the
        named image fields AFTER the full decode — the ground-truth
        semantics decode-time ROI (data/wire.py) must reproduce bit for
        bit. Offsets are resolved by the caller so a fast-path fallback
        re-parse produces the identical batch."""
        if isinstance(serialized_batch, Mapping):
            n = len(next(iter(serialized_batch.values())))
            rows = [
                self.parse_single({k: v[i] for k, v in serialized_batch.items()})
                for i in range(n)
            ]
        else:
            rows = [self.parse_single(s) for s in serialized_batch]
        if not rows:
            raise ValueError("Cannot parse an empty batch.")
        out = TensorSpecStruct()
        all_keys = list(
            dict.fromkeys(key for row in rows for key in row.keys())
        )
        for key in all_keys:
            values = [row[key] for row in rows if key in row]
            if len(values) != len(rows):
                raise ValueError(
                    f"Optional feature {key!r} present in only some batch "
                    "elements; optional features must be all-present or "
                    "all-absent within a batch."
                )
            spec = self._flat[key] if key in self._flat else None
            if (
                spec is not None
                and isinstance(spec, ExtendedTensorSpec)
                and spec.is_sequence
            ):
                max_len = max(v.shape[0] for v in values)
                values = [_pad_to(v, max_len) for v in values]
            out[key] = np.stack(values)
        for key in self._bf16_keys:
            if key in out:
                out[key] = out[key].astype(jnp.bfloat16)
        if roi:
            from tensor2robot_tpu.data.roi import apply_roi_to_batch

            apply_roi_to_batch(out, roi)
        return out
