"""Wire-format batch parsing: spec-compiled, copy-free Example decoding.

The generated-parser hot path rebuilt at batch granularity. `SpecParser`
(data/parser.py) materializes a python-protobuf object graph per record —
every jpeg string is copied into a `bytes` the moment `FromString` runs,
every field becomes a per-record array, and `parse_batch` pays one more
full copy in `np.stack`. This module parses the TFRecord `tf.Example` /
`tf.SequenceExample` wire format directly from the record buffer:

  * one forward scan per record finds each feature's payload span
    (offset + length into the record bytes) — no protobuf objects;
  * packed `float_list` payloads are read with `np.frombuffer` at their
    wire offset (zero-copy until the write into the batch slot);
  * packed `int64_list` varint runs are decoded vectorized in numpy
    (`decode_packed_varints`), with a fast path for the ubiquitous
    all-single-byte runs;
  * each field's batch array is preallocated ONCE — records parse/decode
    directly into their batch slot (`jpeg_decode.cc` writes scanlines
    straight into the slot), eliminating the per-record array and the
    `np.stack` copy;
  * decoded images are optionally served from a content-keyed LRU
    (`DecodeCache`): replay-style training (the QT-Opt regime) re-reads
    the same records every epoch, and a cache hit is a ~75x cheaper
    memcpy than a 512x640 Huffman decode.

`SpecParser` remains the semantics oracle: the schema compiler
(`FastSpecParser`) refuses specs it cannot prove equivalent
(`supported == False`), and ANY failure while fast-parsing a batch falls
back to `SpecParser` for that batch — a genuinely corrupt record then
raises the canonical error, and a fast-path bug degrades to slow-but-
correct instead of wrong. The parity suite (tests/test_fast_parser.py)
asserts byte-identical outputs across the covered spec families, and
the fuzz suite (tests/test_wire_fuzz.py) pins the REJECTION side: the
scanners below are strict about wire framing (every LEN frame must end
exactly where it claims; skips may not cross EOF) so the fast path
refuses every record protobuf refuses — acceptance leniency here would
silently change pipeline semantics vs. T2R_PARSE_FAST=0.

Wire layout recap (proto3, tensor2robot_tpu/proto/example.proto):
  Example          = { 1: Features }
  SequenceExample  = { 1: Features (context), 2: FeatureLists }
  Features         = { 1: map<string, Feature> }
  FeatureLists     = { 1: map<string, FeatureList> }
  FeatureList      = { 1: repeated Feature }
  Feature          = oneof { 1: BytesList, 2: FloatList, 3: Int64List }
  BytesList.value  = repeated bytes        (one LEN frame per entry)
  FloatList.value  = packed fixed32 run(s) (proto3 default)
  Int64List.value  = packed varint run(s)
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu import flags
from tensor2robot_tpu.data.parser import (
    decode_image,
    decode_image_into_native,
    decode_image_roi_into_native,
)
from tensor2robot_tpu.data.roi import ResolvedROI
from tensor2robot_tpu.specs import (
    ExtendedTensorSpec,
    TensorSpecStruct,
    canonical_dtype,
    flatten_spec_structure,
)
from tensor2robot_tpu.utils import tracing

__all__ = [
    "FastParseError",
    "FastSpecParser",
    "DecodeCache",
    "decode_packed_varints",
    "get_decode_cache",
    "reset_decode_cache",
]


class FastParseError(ValueError):
    """Raised when the fast path cannot parse a record it was compiled for.

    Callers treat this (and any other exception out of the fast path) as
    "fall back to SpecParser for this batch"; it never escapes to users.
    """


# -- varint / wire primitives -------------------------------------------------

_WT_VARINT, _WT_I64, _WT_LEN, _WT_I32 = 0, 1, 2, 5


def _uvarint(data: bytes, pos: int) -> Tuple[int, int]:
    """Reads one unsigned varint; returns (value, next_pos)."""
    result = 0
    shift = 0
    while True:
        b = data[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7
        if shift > 63:
            raise FastParseError("varint longer than 10 bytes")


def _skip_field(data: bytes, pos: int, wire_type: int) -> int:
    if wire_type == _WT_VARINT:
        _, pos = _uvarint(data, pos)
        return pos
    if wire_type == _WT_I64:
        return pos + 8
    if wire_type == _WT_LEN:
        length, pos = _uvarint(data, pos)
        return pos + length
    if wire_type == _WT_I32:
        return pos + 4
    raise FastParseError(f"unsupported wire type {wire_type}")


_SEVEN = np.uint64(7)


def decode_packed_varints(raw: np.ndarray) -> np.ndarray:
    """Vectorized decode of a packed int64 varint run -> int64 array.

    Protobuf int64 varints are little-endian base-128 with the high bit as
    continuation; negatives are 10-byte two's complement. The grouped
    shift/sum runs entirely in numpy: uint64 addition wraps mod 2^64, which
    IS two's-complement reassembly, so a final `.view(int64)` restores
    signs. Small non-negative ints (the overwhelmingly common case for
    action/flag features) are a single `astype` — every byte its own value.
    """
    if raw.size == 0:
        return np.empty(0, np.int64)
    is_end = raw < 0x80
    if is_end.all():  # all single-byte values
        return raw.astype(np.int64)
    if not is_end[-1]:
        raise FastParseError("truncated varint run")
    ends = np.flatnonzero(is_end)
    starts = np.empty_like(ends)
    starts[0] = 0
    starts[1:] = ends[:-1] + 1
    lengths = ends - starts + 1
    if int(lengths.max()) > 10:
        raise FastParseError("varint longer than 10 bytes")
    payload = (raw & 0x7F).astype(np.uint64)
    idx = np.arange(raw.size, dtype=np.int64)
    shifts = (idx - np.repeat(starts, lengths)).astype(np.uint64) * _SEVEN
    return np.add.reduceat(payload << shifts, starts).view(np.int64)


# -- record scanning ----------------------------------------------------------
#
# A scanned Feature is the tuple (kind, spans, scalars):
#   kind:    1 bytes_list | 2 float_list | 3 int64_list | 0 unset
#   spans:   [(offset, length), ...] — bytes entries, or packed runs
#   scalars: values collected from UNPACKED float/int64 entries (rare
#            writers), or None. Mixing packed and unpacked is refused.

_Feature = Tuple[int, List[Tuple[int, int]], Optional[list]]


def _scan_feature(data: bytes, pos: int, end: int) -> _Feature:
    kind = 0
    spans: List[Tuple[int, int]] = []
    scalars: Optional[list] = None
    while pos < end:
        tag, pos = _uvarint(data, pos)
        fnum, wt = tag >> 3, tag & 7
        if fnum in (1, 2, 3) and wt == _WT_LEN:
            if kind and kind != fnum:
                # oneof re-assignment on the wire: last field wins.
                spans, scalars = [], None
            kind = fnum
            length, pos = _uvarint(data, pos)
            inner_end = pos + length
            if inner_end > end:
                raise FastParseError("value list frame exceeds feature")
            while pos < inner_end:
                tag2, pos = _uvarint(data, pos)
                f2, w2 = tag2 >> 3, tag2 & 7
                if f2 == 1 and w2 == _WT_LEN:
                    ln, pos = _uvarint(data, pos)
                    spans.append((pos, ln))
                    pos += ln
                elif f2 == 1 and w2 == _WT_I32 and fnum == 2:
                    if scalars is None:
                        scalars = []
                    scalars.append(
                        np.frombuffer(data, "<f4", count=1, offset=pos)[0]
                    )
                    pos += 4
                elif f2 == 1 and w2 == _WT_VARINT and fnum == 3:
                    value, pos = _uvarint(data, pos)
                    if scalars is None:
                        scalars = []
                    scalars.append(
                        value - (1 << 64) if value >= (1 << 63) else value
                    )
                else:
                    pos = _skip_field(data, pos, w2)
            if pos != inner_end:
                # A value entry claimed bytes past its list frame: the
                # oracle (protobuf) rejects this record; accepting it
                # here would make the fast path MORE lenient than
                # T2R_PARSE_FAST=0 — a silent semantics change.
                raise FastParseError("value list overran its frame")
        else:
            pos = _skip_field(data, pos, wt)
    if pos != end:
        raise FastParseError("feature scan overran its frame")
    return kind, spans, scalars


def _scan_features(
    data: bytes, pos: int, end: int, out: Dict[bytes, _Feature]
) -> None:
    """Scans a Features message (a map<string, Feature>) into `out`."""
    while pos < end:
        tag, pos = _uvarint(data, pos)
        if tag == 0x0A:  # map entry
            length, pos = _uvarint(data, pos)
            entry_end = pos + length
            if entry_end > end:
                raise FastParseError("map entry frame exceeds message")
            key = b""
            feature: Optional[_Feature] = None
            while pos < entry_end:
                tag2, pos = _uvarint(data, pos)
                if tag2 == 0x0A:  # key
                    klen, pos = _uvarint(data, pos)
                    key = data[pos : pos + klen]
                    pos += klen
                elif tag2 == 0x12:  # value Feature
                    flen, pos = _uvarint(data, pos)
                    if pos + flen > entry_end:
                        raise FastParseError("feature frame exceeds entry")
                    feature = _scan_feature(data, pos, pos + flen)
                    pos += flen
                else:
                    pos = _skip_field(data, pos, tag2 & 7)
            if pos != entry_end:
                raise FastParseError("map entry overran its frame")
            if feature is not None:
                out[key] = feature  # map semantics: last entry wins
        else:
            pos = _skip_field(data, pos, tag & 7)
    if pos != end:
        raise FastParseError("features scan overran its frame")


def _scan_feature_lists(
    data: bytes, pos: int, end: int, out: Dict[bytes, List[_Feature]]
) -> None:
    """Scans a FeatureLists message into {key: [per-step Feature, ...]}."""
    while pos < end:
        tag, pos = _uvarint(data, pos)
        if tag == 0x0A:  # map entry
            length, pos = _uvarint(data, pos)
            entry_end = pos + length
            if entry_end > end:
                raise FastParseError("map entry frame exceeds message")
            key = b""
            steps: List[_Feature] = []
            while pos < entry_end:
                tag2, pos = _uvarint(data, pos)
                if tag2 == 0x0A:  # key
                    klen, pos = _uvarint(data, pos)
                    key = data[pos : pos + klen]
                    pos += klen
                elif tag2 == 0x12:  # value FeatureList
                    flen, pos = _uvarint(data, pos)
                    flist_end = pos + flen
                    if flist_end > entry_end:
                        raise FastParseError(
                            "feature list frame exceeds entry"
                        )
                    while pos < flist_end:
                        tag3, pos = _uvarint(data, pos)
                        if tag3 == 0x0A:  # one step's Feature
                            slen, pos = _uvarint(data, pos)
                            if pos + slen > flist_end:
                                raise FastParseError(
                                    "step feature exceeds its list"
                                )
                            steps.append(_scan_feature(data, pos, pos + slen))
                            pos += slen
                        else:
                            pos = _skip_field(data, pos, tag3 & 7)
                    if pos != flist_end:
                        raise FastParseError(
                            "feature list overran its frame"
                        )
                else:
                    pos = _skip_field(data, pos, tag2 & 7)
            if pos != entry_end:
                raise FastParseError("map entry overran its frame")
            out[key] = steps
        else:
            pos = _skip_field(data, pos, tag & 7)
    if pos != end:
        raise FastParseError("feature lists scan overran its frame")


def scan_record(
    data: bytes, want_feature_lists: bool
) -> Tuple[Dict[bytes, _Feature], Dict[bytes, List[_Feature]]]:
    """One forward pass over an Example/SequenceExample record.

    Example.features and SequenceExample.context are both field 1 with the
    same Features payload, so a single scanner serves both message types;
    field 2 (feature_lists) only exists on SequenceExample and is skipped
    unless requested.
    """
    features: Dict[bytes, _Feature] = {}
    feature_lists: Dict[bytes, List[_Feature]] = {}
    pos, end = 0, len(data)
    while pos < end:
        tag, pos = _uvarint(data, pos)
        if tag == 0x0A:  # features / context
            length, pos = _uvarint(data, pos)
            if pos + length > end:
                raise FastParseError("features frame exceeds record")
            _scan_features(data, pos, pos + length, features)
            pos += length
        elif tag == 0x12 and want_feature_lists:
            length, pos = _uvarint(data, pos)
            if pos + length > end:
                raise FastParseError("feature lists frame exceeds record")
            _scan_feature_lists(data, pos, pos + length, feature_lists)
            pos += length
        else:
            pos = _skip_field(data, pos, tag & 7)
    if pos != end:
        # A skipped field claimed bytes past EOF: a truncated record.
        # Protobuf's FromString rejects it; so must the fast scan —
        # otherwise T2R_PARSE_FAST=1 silently ACCEPTS records the
        # T2R_PARSE_FAST=0 pipeline refuses (found by test_wire_fuzz).
        raise FastParseError("record scan overran EOF (truncated record)")
    return features, feature_lists


# -- decoded-image cache ------------------------------------------------------


class DecodeCache:
    """Byte-budgeted cache of decoded images, exact-verified per lookup.

    Replay-style training (infinite `repeat` over a file set — the QT-Opt
    configuration) decodes the SAME encoded images every epoch; tf.data
    answers this with `.cache()` and DALI with its decoder cache. Here the
    cache sits inside the decode-into stage: a hit is one memcpy into the
    batch slot (~0.5 ms for a 512x640 frame on this host) versus a fresh
    Huffman decode (~8 ms).

    Lookup is two-stage for speed WITHOUT giving up bit-exactness: the
    dict key is a cheap sampled fingerprint (length + head/middle/tail
    slices — hashing the full ~400 KB jpeg would cost more than the rest
    of the hit path), and every fingerprint match is then verified by
    comparing the STORED encoded bytes against the query with one memcmp.
    A fingerprint collision therefore degrades to a miss (and replaces the
    entry), never to wrong pixels; parity with `SpecParser` is structural.

    Eviction is insertion-order (FIFO): for the cyclic epoch access
    pattern this equals LRU without per-hit bookkeeping. Gets are lock-free
    (GIL-atomic dict read + bytes compare); puts/evictions take a lock.
    Hit/miss counters are best-effort under concurrency. Sized by
    T2R_DECODE_CACHE_MB (default 512; 0 disables).
    """

    def __init__(self, capacity_bytes: int):
        self.capacity_bytes = int(capacity_bytes)
        # fingerprint -> (encoded bytes, decoded readonly array)
        self._entries: "OrderedDict[Any, Tuple[bytes, np.ndarray]]" = (
            OrderedDict()
        )
        self._bytes = 0
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    @staticmethod
    def fingerprint(sig, data: bytes):
        n = len(data)
        if n <= 96:
            return (sig, data)
        mid = n >> 1
        return (sig, n, data[:32], data[mid : mid + 32], data[-32:])

    def get(self, sig, data: bytes) -> Optional[np.ndarray]:
        entry = self._entries.get(self.fingerprint(sig, data))
        if entry is not None and entry[0] == data:
            self.hits += 1
            return entry[1]
        self.misses += 1
        return None

    def put(self, sig, data: bytes, value: np.ndarray) -> None:
        nbytes = value.nbytes + len(data)
        if nbytes > self.capacity_bytes:
            return
        value = value if value.flags.owndata else value.copy()
        value.setflags(write=False)
        key = self.fingerprint(sig, data)
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[1].nbytes + len(old[0])
            self._entries[key] = (data, value)
            self._bytes += nbytes
            while self._bytes > self.capacity_bytes and self._entries:
                _, (old_data, old_value) = self._entries.popitem(last=False)
                self._bytes -= old_value.nbytes + len(old_data)

    def thrashing(self) -> bool:
        """True when the cache is full and hits are negligible — the
        working set provably does not fit the byte budget (FIFO eviction
        under a cyclic epoch scan then yields ~0 hits forever). Callers
        use this to stop paying population costs for entries that will be
        evicted before they can ever be served: specifically, randomized-
        ROI decode stops full-frame decoding to feed the cache and drops
        to the pure (cheaper) ROI decode. Thresholds: full means >=90% of
        budget, negligible means <5% hit rate over >=512 lookups — a set
        that fits reaches a high hit rate by its second epoch, well
        before a full-at-512-lookups cache can misclassify it (the
        default 512 MB budget holds ~380 full QT-Opt frames)."""
        total = self.hits + self.misses
        return (
            total >= 512
            and self._bytes * 10 >= self.capacity_bytes * 9
            and self.hits * 20 < total
        )

    def stats(self) -> Dict[str, float]:
        total = self.hits + self.misses
        return {
            "entries": len(self._entries),
            "bytes": self._bytes,
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": round(self.hits / total, 4) if total else 0.0,
        }


_decode_cache: Optional[DecodeCache] = None
_decode_cache_lock = threading.Lock()


def default_decode_cache_mb() -> int:
    return flags.get_int("T2R_DECODE_CACHE_MB")


def get_decode_cache() -> Optional[DecodeCache]:
    """Process-wide decode cache, or None when disabled (cache size 0)."""
    global _decode_cache
    if _decode_cache is None:
        with _decode_cache_lock:
            if _decode_cache is None:
                mb = default_decode_cache_mb()
                if mb == 0:
                    return None
                _decode_cache = DecodeCache(mb << 20)
    return _decode_cache


def reset_decode_cache() -> None:
    """Drops the process-wide cache (tests / bench legs)."""
    global _decode_cache
    with _decode_cache_lock:
        _decode_cache = None


# -- spec compilation ---------------------------------------------------------


class _CompiledField:
    """One spec's parse plan: where to look, how to decode, where to write."""

    __slots__ = (
        "key",
        "spec",
        "name_bytes",
        "kind",
        "out_dtype",
        "parse_dtype",
        "shape",
        "n_elements",
        "is_image",
        "image_shape",
        "stack_size",
        "varlen",
        "pad_value",
        "optional",
        "native_image_ok",
        "cache_sig",
    )

    def is_image_field(self) -> bool:
        return self.image_shape is not None

    def __init__(self, key: str, spec: ExtendedTensorSpec):
        self.key = key
        self.spec = spec
        self.name_bytes = (spec.name or key).encode("utf-8")
        self.out_dtype = canonical_dtype(spec.dtype)
        self.parse_dtype = (
            np.float32 if self.out_dtype == jnp.bfloat16 else self.out_dtype
        )
        self.optional = spec.is_optional
        self.varlen = spec.varlen_default_value is not None
        self.shape = tuple(spec.shape)
        if spec.data_format is not None:
            self.kind = 1
            # Mirrors decode_image: the trailing 3 dims are the image.
            self.image_shape = (
                tuple(self.shape[-3:]) if len(self.shape) >= 3 else self.shape
            )
            if any(d is None for d in self.image_shape):
                raise FastParseError(
                    f"image spec {key!r} lacks static H/W/C: {self.shape}"
                )
            self.stack_size = (
                int(self.shape[0]) if len(self.shape) >= 4 else None
            )
            self.native_image_ok = (
                self.out_dtype == np.dtype(np.uint8)
                and len(self.image_shape) == 3
                and self.image_shape[-1] == 3
                and spec.data_format.lower() in ("jpeg", "jpg")
            )
            self.cache_sig = (
                self.image_shape,
                str(self.out_dtype),
                spec.data_format.lower(),
            )
            self.n_elements = None
            self.pad_value = None
            return
        self.image_shape = None
        self.stack_size = None
        self.native_image_ok = False
        self.cache_sig = None
        storage = canonical_dtype(spec.dtype)
        if jnp.issubdtype(storage, np.floating):
            self.kind = 2
        elif jnp.issubdtype(storage, np.integer) or storage == np.dtype(bool):
            self.kind = 3
        else:
            raise FastParseError(
                f"no fast storage mapping for dtype {storage} ({key!r})"
            )
        if self.varlen:
            if len(self.shape) != 1 or self.shape[0] is None:
                # ExtendedTensorSpec already enforces rank-1 varlen; this
                # guards the fill path's flat pad/clip if that constraint
                # is ever relaxed without updating the fast parser.
                raise FastParseError(
                    f"varlen spec {key!r} must be rank-1, got {self.shape}"
                )
            # Match pad_or_clip + astype(parse_dtype): build the pad scalar
            # in STORAGE dtype first so float64 specs see the same f32
            # rounding the slow path applies.
            storage_np = np.float32 if self.kind == 2 else np.int64
            self.pad_value = np.asarray(
                spec.varlen_default_value, dtype=storage_np
            ).astype(self.parse_dtype)[()]
            self.n_elements = None
        else:
            self.pad_value = None
            n = 1
            for dim in self.shape:
                if dim is None:
                    raise FastParseError(
                        f"FixedLen parse requires static shape, got "
                        f"{self.shape} ({key!r})"
                    )
                n *= dim
            self.n_elements = n

    # -- value materialization ------------------------------------------------

    def _values(self, record: bytes, feature: _Feature) -> np.ndarray:
        """Materializes a numeric feature's flat value array (storage dtype)."""
        kind, spans, scalars = feature
        if kind != self.kind:
            raise FastParseError(
                f"feature {self.key!r} stored as kind {kind}, spec expects "
                f"{self.kind}"
            )
        if scalars is not None:
            if spans:
                raise FastParseError("mixed packed/unpacked list encoding")
            dtype = np.float32 if self.kind == 2 else np.int64
            return np.asarray(scalars, dtype=dtype)
        if self.kind == 2:
            chunks = []
            for off, ln in spans:
                if ln % 4:
                    raise FastParseError("packed float run not 4-byte aligned")
                chunks.append(
                    np.frombuffer(record, "<f4", count=ln // 4, offset=off)
                )
        else:
            chunks = [
                decode_packed_varints(
                    np.frombuffer(record, np.uint8, count=ln, offset=off)
                )
                for off, ln in spans
            ]
        if not chunks:
            dtype = np.float32 if self.kind == 2 else np.int64
            return np.empty(0, dtype)
        return chunks[0] if len(chunks) == 1 else np.concatenate(chunks)

    # -- decode-into fill paths ----------------------------------------------

    def _decode_one_image(
        self,
        record: bytes,
        span: Tuple[int, int],
        out_slice: np.ndarray,
        cache: Optional[DecodeCache],
        rect: Optional[Tuple[int, int, int, int]] = None,
        randomized: bool = False,
    ) -> None:
        """One image into its slot, timed: `images` and `decode_ns` (cache
        lookups and copies included) add up on the enclosing
        `data.parse_chunk` span. Two clock reads an image, no span."""
        started = time.perf_counter_ns()
        try:
            self._decode_one_image_untimed(
                record, span, out_slice, cache, rect, randomized
            )
        finally:
            tracing.add(images=1, decode_ns=time.perf_counter_ns() - started)

    def _decode_one_image_untimed(
        self, record, span, out_slice, cache, rect, randomized
    ) -> None:
        off, ln = span
        if ln == 0:
            out_slice[...] = 0
            return
        data = record[off : off + ln]
        if rect is not None:
            self._decode_one_image_roi(data, out_slice, cache, rect, randomized)
            return
        if cache is not None:
            hit = cache.get(self.cache_sig, data)
            if hit is not None:
                out_slice[...] = hit
                return
        if (
            self.native_image_ok
            and data[:2] == b"\xff\xd8"
            and out_slice.flags.c_contiguous
            and decode_image_into_native(data, out_slice)
        ):
            if cache is not None:
                cache.put(self.cache_sig, data, out_slice.copy())
            return
        arr = decode_image(data, self.spec)
        out_slice[...] = arr
        if cache is not None:
            cache.put(self.cache_sig, data, np.ascontiguousarray(arr))

    def _roi_decode(self, data, out_slice, y, x, th, tw) -> None:
        """ROI decode into the slot: native when possible, else full
        decode + crop (bit-identical either way). The fallback goes
        straight to `decode_image` — native eligibility was just decided
        here, and `decode_image_roi`'s own native attempt would re-parse
        the jpeg header a second time on every deterministic failure
        (e.g. a progressive-jpeg dataset)."""
        if (
            self.native_image_ok
            and data[:2] == b"\xff\xd8"
            and out_slice.flags.c_contiguous
            and decode_image_roi_into_native(
                data, out_slice, y, x, self.image_shape[:2]
            )
        ):
            return
        out_slice[...] = decode_image(data, self.spec)[y : y + th, x : x + tw]

    def _decode_one_image_roi(
        self, data, out_slice, cache, rect, randomized
    ) -> None:
        """Cropped decode with an offset-repetition-aware cache policy.

        Static offsets (center/fixed crops — eval) repeat every epoch, so
        the cache keys on (sig, rect) and stores the CROPPED window: the
        same byte budget then holds ~1/(crop fraction) more frames. Random
        offsets (the training crop) almost never repeat — keying on them
        would miss every epoch — so the cache keeps the FULL frame under
        the plain sig (shared with non-ROI decode) and serves each fresh
        window as a slice copy; only the cache-MISS decode pays full price
        (exactly the r06 cost), and hits get cheaper (window-sized copy).

        Scale guard: when the training set exceeds the byte budget, FIFO
        eviction under the cyclic epoch scan means ~every lookup misses —
        paying a full-frame decode per record to populate entries that
        evict before they serve would erase the ROI win entirely. Once the
        cache reports `thrashing()` (full + negligible hits), randomized
        ROI stops feeding it and decodes just the window, recovering the
        cold-path ROI speedup at any dataset scale.
        """
        y, x, th, tw = rect
        if cache is not None and randomized:
            hit = cache.get(self.cache_sig, data)
            if hit is not None:
                out_slice[...] = hit[y : y + th, x : x + tw]
                return
            if cache.thrashing():
                self._roi_decode(data, out_slice, y, x, th, tw)
                return
            arr = decode_image(data, self.spec)
            out_slice[...] = arr[y : y + th, x : x + tw]
            cache.put(self.cache_sig, data, np.ascontiguousarray(arr))
            return
        if cache is not None:
            sig = (self.cache_sig, y, x, th, tw)
            hit = cache.get(sig, data)
            if hit is not None:
                out_slice[...] = hit
                return
            self._roi_decode(data, out_slice, y, x, th, tw)
            cache.put(sig, data, out_slice.copy())
            return
        self._roi_decode(data, out_slice, y, x, th, tw)

    def fill_image(
        self,
        record: bytes,
        feature: _Feature,
        out_slice: np.ndarray,
        cache: Optional[DecodeCache],
        rect: Optional[Tuple[int, int, int, int]] = None,
        randomized: bool = False,
    ) -> None:
        kind, spans, scalars = feature
        if kind != 1 or scalars is not None:
            raise FastParseError(f"image feature {self.key!r} not bytes_list")
        if rect is not None:
            # normalize_decode_rois restricts ROI to single-image specs;
            # this guards the invariant if a caller bypasses it.
            if self.stack_size is not None:
                raise FastParseError(
                    f"ROI decode unsupported for image stack {self.key!r}"
                )
            if len(spans) != 1:
                raise FastParseError(
                    f"feature {self.key!r} holds {len(spans)} images, spec "
                    "declares one"
                )
            self._decode_one_image(
                record, spans[0], out_slice, cache, rect, randomized
            )
            return
        if self.varlen and self.stack_size is not None:
            target = self.stack_size
            keep = min(len(spans), target)
            for j in range(keep):
                self._decode_one_image(record, spans[j], out_slice[j], cache)
            if keep < target:
                out_slice[keep:] = 0
            return
        if self.stack_size is None:
            if len(spans) != 1:
                raise FastParseError(
                    f"feature {self.key!r} holds {len(spans)} images, spec "
                    "declares one"
                )
            self._decode_one_image(record, spans[0], out_slice, cache)
            return
        if len(spans) != self.stack_size:
            raise FastParseError(
                f"feature {self.key!r} holds {len(spans)} images, stack "
                f"requires {self.stack_size}"
            )
        for j, span in enumerate(spans):
            self._decode_one_image(record, span, out_slice[j], cache)

    def fill_numeric(
        self, record: bytes, feature: _Feature, batch: np.ndarray, index
    ) -> None:
        """Writes one record's value into batch[index] (index may be a
        tuple for sequence steps). Assignment goes through setitem so
        scalar-shaped specs — where batch[index] would be a numpy scalar,
        not a view — still land in the batch."""
        values = self._values(record, feature)
        if self.varlen:
            out_slice = batch[index]
            target = int(self.shape[0])
            keep = min(values.size, target)
            out_slice[:keep] = values[:keep]
            if keep < target:
                out_slice[keep:] = self.pad_value
            return
        if values.size != self.n_elements:
            raise FastParseError(
                f"feature {self.key!r} has {values.size} elements, spec "
                f"{self.shape} requires {self.n_elements}"
            )
        batch[index] = values.reshape(self.shape)


class _CompiledGroup:
    """All fields of one dataset_key group + its record scanner."""

    def __init__(self, specs: Mapping[str, ExtendedTensorSpec]):
        self.context_fields: List[_CompiledField] = []
        self.sequence_fields: List[_CompiledField] = []
        for key, spec in specs.items():
            field = _CompiledField(key, spec)
            if spec.is_sequence:
                self.sequence_fields.append(field)
            else:
                self.context_fields.append(field)
        self.is_sequence = bool(self.sequence_fields)

    def allocate(
        self, n: int, roi: Optional[Mapping[str, ResolvedROI]] = None
    ) -> Dict[str, np.ndarray]:
        """One array per context field for a batch of `n` records, its rows
        still to be written (`fill_rows`). A context field's shape is known
        before any record is read: from its spec, or from the resolved ROI
        window of a cropped image field."""
        arrays: Dict[str, np.ndarray] = {}
        for field in self.context_fields:
            shape, dtype = tuple(field.shape), field.parse_dtype
            if field.is_image_field():
                dtype = field.out_dtype
                resolved = roi.get(field.key) if roi else None
                if resolved is not None:
                    if len(resolved.ys) != n:
                        raise FastParseError(
                            f"ResolvedROI for {field.key!r} has "
                            f"{len(resolved.ys)} offsets, batch holds {n}"
                        )
                    shape = (resolved.height, resolved.width) + shape[2:]
            arrays[field.key] = np.empty((n,) + shape, dtype=dtype)
        return arrays

    def fill_rows(
        self,
        records: Sequence[bytes],
        scans: Sequence[Tuple[Dict, Dict]],
        arrays: Mapping[str, np.ndarray],
        start: int,
        cache: Optional[DecodeCache],
        roi: Optional[Mapping[str, ResolvedROI]] = None,
    ) -> List[str]:
        """Parses and decodes the context fields of `records`, which are
        records `start`, `start + 1`, ... of their batch, straight into
        those rows of `arrays` (from `allocate` at the batch's size; `roi`
        holds the whole batch's offsets). Returns the keys of the optional
        fields that none of `records` carries."""
        absent: List[str] = []
        for field in self.context_fields:
            features = [scan[0].get(field.name_bytes) for scan in scans]
            present = [f is not None for f in features]
            if not all(present):
                if field.optional and not any(present):
                    absent.append(field.key)
                    continue
                if not field.optional:
                    missing = present.index(False)
                    raise KeyError(
                        f"Required feature {field.spec.name or field.key!r} "
                        f"missing from example {start + missing}"
                    )
                raise ValueError(
                    f"Optional feature {field.key!r} present in only some "
                    "batch elements; optional features must be all-present "
                    "or all-absent within a batch."
                )
            batch = arrays[field.key]
            if field.is_image_field():
                resolved = roi.get(field.key) if roi else None
                for i, feature in enumerate(features):
                    row = start + i
                    if resolved is None:
                        field.fill_image(records[i], feature, batch[row], cache)
                    else:
                        field.fill_image(
                            records[i], feature, batch[row], cache,
                            rect=resolved.rect(row),
                            randomized=resolved.randomized,
                        )
            else:
                for i, feature in enumerate(features):
                    field.fill_numeric(records[i], feature, batch, start + i)
        return absent

    def scan(self, records: Sequence[bytes]) -> List[Tuple[Dict, Dict]]:
        return [scan_record(bytes(r), self.is_sequence) for r in records]

    def parse_into(
        self,
        records: Sequence[bytes],
        out: Dict[str, np.ndarray],
        cache: Optional[DecodeCache],
        roi: Optional[Mapping[str, ResolvedROI]] = None,
    ) -> None:
        n = len(records)
        scans = self.scan(records)
        arrays = self.allocate(n, roi)
        absent = self.fill_rows(records, scans, arrays, 0, cache, roi)
        for key, batch in arrays.items():
            if key not in absent:
                out[key] = batch
        for field in self.sequence_fields:
            steps = [scan[1].get(field.name_bytes) for scan in scans]
            present = [s is not None for s in steps]
            if not all(present):
                if field.optional and not any(present):
                    continue
                if not field.optional:
                    missing = present.index(False)
                    raise KeyError(
                        f"Required sequence feature "
                        f"{field.spec.name or field.key!r} missing from "
                        f"example {missing}"
                    )
                raise ValueError(
                    f"Optional feature {field.key!r} present in only some "
                    "batch elements; optional features must be all-present "
                    "or all-absent within a batch."
                )
            lengths = np.asarray([len(s) for s in steps], np.int64)
            max_len = int(lengths.max()) if n else 0
            step_shape = tuple(field.shape)
            if field.is_image_field():
                batch = np.zeros(
                    (n, max_len) + step_shape, dtype=field.out_dtype
                )
                for i, record_steps in enumerate(steps):
                    for t, feature in enumerate(record_steps):
                        field.fill_image(
                            records[i], feature, batch[i, t], cache
                        )
            else:
                batch = np.zeros(
                    (n, max_len) + step_shape, dtype=field.parse_dtype
                )
                for i, record_steps in enumerate(steps):
                    for t, feature in enumerate(record_steps):
                        field.fill_numeric(records[i], feature, batch, (i, t))
            out[field.key] = batch
            out[field.key + "_length"] = lengths


class FastSpecParser:
    """Drop-in fast twin of `SpecParser.parse_batch` with compile-time opt-out.

    `supported` is False when the spec structure uses storage the fast path
    does not implement (e.g. raw string features); callers then keep the
    `SpecParser` oracle. At runtime, any per-batch failure raises out of
    `parse_batch` — the dataset layer catches it and re-parses the batch
    with `SpecParser` (counted in `fallbacks`).
    """

    def __init__(self, specs: Union[TensorSpecStruct, Mapping]):
        self._flat = flatten_spec_structure(specs)
        self._groups: Dict[str, _CompiledGroup] = {}
        self.supported = True
        self.unsupported_reason: Optional[str] = None
        self.fallbacks = 0
        grouped: Dict[str, Dict[str, ExtendedTensorSpec]] = {}
        for key, spec in self._flat.items():
            if not isinstance(spec, ExtendedTensorSpec):
                continue
            grouped.setdefault(spec.dataset_key, {})[key] = spec
        try:
            for dataset_key, group in grouped.items():
                self._groups[dataset_key] = _CompiledGroup(group)
        except Exception as err:  # any compile failure -> keep the oracle
            self.supported = False
            self.unsupported_reason = str(err)
        self._bf16_keys = [
            key
            for key, spec in self._flat.items()
            if isinstance(spec, ExtendedTensorSpec)
            and canonical_dtype(spec.dtype) == jnp.bfloat16
        ]

    @property
    def dataset_keys(self) -> Tuple[str, ...]:
        return tuple(self._groups.keys())

    def _by_dataset_key(self, serialized_batch) -> Dict[str, Sequence[bytes]]:
        if not self.supported:
            raise FastParseError(
                f"unsupported spec structure: {self.unsupported_reason}"
            )
        if isinstance(serialized_batch, Mapping):
            by_key = dict(serialized_batch)
        else:
            if list(self._groups.keys()) != [""]:
                raise ValueError(
                    "Multi-dataset specs require a dict of serialized "
                    f"records keyed by {sorted(self._groups.keys())}"
                )
            by_key = {"": list(serialized_batch)}
        sizes = {len(v) for v in by_key.values()}
        if not sizes or sizes == {0}:
            raise ValueError("Cannot parse an empty batch.")
        for dataset_key in self._groups:
            if dataset_key not in by_key:
                raise KeyError(
                    f"Missing serialized record for dataset {dataset_key!r}"
                )
        return by_key

    def _struct(self, flat: Mapping[str, np.ndarray]) -> TensorSpecStruct:
        out = TensorSpecStruct()
        for key, value in flat.items():
            out[key] = value
        for key in self._bf16_keys:
            if key in out:
                out[key] = out[key].astype(jnp.bfloat16)
        return out

    def parse_batch(
        self,
        serialized_batch: Union[Sequence[bytes], Mapping[str, Sequence[bytes]]],
        cache: Optional[DecodeCache] = None,
        roi: Optional[Mapping[str, ResolvedROI]] = None,
    ) -> TensorSpecStruct:
        """Fast parse; `roi` ({flat key: ResolvedROI}) decodes the named
        image fields cropped (decode-time ROI) — bit-identical to
        `SpecParser.parse_batch(..., roi=roi)`'s full-decode-then-crop."""
        by_key = self._by_dataset_key(serialized_batch)
        if cache is None:
            cache = get_decode_cache()
        flat: Dict[str, np.ndarray] = {}
        for dataset_key, group in self._groups.items():
            group.parse_into(by_key[dataset_key], flat, cache, roi)
        return self._struct(flat)

    # -- one batch in slices: allocate once, fill rows from any thread ---------

    @property
    def static_shapes(self) -> bool:
        """Whether a batch's shapes are known before its records are read.
        A sequence field pads to the longest record of its batch, so a spec
        set that holds one is parsed a whole batch at a time."""
        return self.supported and not any(
            group.is_sequence for group in self._groups.values()
        )

    def allocate_batch(
        self, n: int, roi: Optional[Mapping[str, ResolvedROI]] = None
    ) -> Optional[Dict[str, np.ndarray]]:
        """The arrays of a batch of `n` records, for `parse_rows` to fill
        and `finish_batch` to hand out, or None where `static_shapes` is
        not given (`parse_batch` is the way then)."""
        if not self.static_shapes:
            return None
        arrays: Dict[str, np.ndarray] = {}
        for group in self._groups.values():
            arrays.update(group.allocate(n, roi))
        return arrays

    def parse_rows(
        self,
        serialized: Union[Sequence[bytes], Mapping[str, Sequence[bytes]]],
        arrays: Mapping[str, np.ndarray],
        start: int,
        cache: Optional[DecodeCache] = None,
        roi: Optional[Mapping[str, ResolvedROI]] = None,
    ) -> List[str]:
        """Parses `serialized`, records `start`, `start + 1`, ... of a
        batch, into those rows of the batch's `arrays` (`allocate_batch`);
        `roi` is the whole batch's. Slices that do not overlap may run on
        different threads. Returns the optional keys these records lack."""
        by_key = self._by_dataset_key(serialized)
        if cache is None:
            cache = get_decode_cache()
        absent: List[str] = []
        for dataset_key, group in self._groups.items():
            records = by_key[dataset_key]
            absent += group.fill_rows(
                records, group.scan(records), arrays, start, cache, roi
            )
        return absent

    def finish_batch(
        self, arrays: Mapping[str, np.ndarray], absent: Sequence[Sequence[str]]
    ) -> TensorSpecStruct:
        """The batch whose every row `parse_rows` has filled; `absent` holds
        what each of those calls returned. An optional field has to be in
        every record of a batch or in none."""
        lacking = set(absent[0])
        if any(set(keys) != lacking for keys in absent[1:]):
            raise ValueError(
                "An optional feature is present in only some batch "
                "elements; optional features must be all-present or "
                "all-absent within a batch."
            )
        return self._struct(
            {key: value for key, value in arrays.items() if key not in lacking}
        )
