"""Serialized AOT executables inside the export artifact.

The export->serve artery already ships a batch-polymorphic StableHLO
program plus the warmup corpus that names every batch size the fleet
will ever dispatch (`warmup_batch_sizes`). What every consumer still
pays per process is the XLA *compile* of each bucket: replica boots,
autoscaler scale-ups, and every learner-publish rolling swap re-lower
the same program for the same shapes on the same hardware. The
persistent compile cache (utils/compile_cache.py) only amortizes that
across boots on one host; this module removes it from the consumer
entirely, the full-AOT thesis of arXiv:1810.09868 applied to serving:
compile once, at export time, and ship the executables.

Per warmup bucket (and per serve-quant regime) the exporter rehydrates
the just-serialized StableHLO program, specializes it to the bucket's
concrete batch, compiles it, and serializes the compiled executable
(jax.experimental.serialize_executable) into `aot/` in the export dir.
Restore deserializes instead of compiling — but ONLY when the key
matches, because a compiled executable is meaningless off the exact
(program, weights, hardware) triple it was lowered for:

  * **artifact fingerprint** — sha256 over the regime's serving program
    bytes plus its weight payload bytes (the quant msgpack for fp16/
    int8, variables.msgpack for weights-as-arguments exports; the
    closure-style default program embeds its weights, so the program
    bytes alone cover them). A stale or transplanted `aot/` dir can
    never serve another artifact's weights.
  * **device topology** — (platform, device kind, device count),
    following the MLPerf TPU-pod discipline (arXiv:1909.09756) of
    keying compiled artifacts on the mesh they were lowered for: an
    executable never runs on a topology it wasn't compiled against.
  * **jax version** — executable serialization is not stable across
    XLA versions; a mismatch must be a typed fallback, not an
    unpickle crash mid-boot.

Any mismatch falls back LOUDLY (typed error, counted, surfaced per
bucket in `server.snapshot()["prewarm_source"]`) down the ladder:
AOT executable -> persistent compile cache -> fresh trace.

Envelope (one file per (regime, bucket), `aot/exec_<regime>_b<n>.bin`):

    [0:4]   magic b"T2RA"
    [4:8]   u32 LE: byte length of REST
    [8:12]  u32 LE: crc32 of REST
    [12:]   REST = u32 LE header length + header JSON + pickled
            (payload, in_tree, out_tree) from serialize_executable

The 12-byte magic/length/crc header is the same structural shape as the
replay transport frame, so `analysis/corpus.py corrupt_frame_variants`
drives the corruption tests with no new generator. Integrity (magic,
exact length, CRC) is verified before the header is parsed, and the
key (fingerprint/topology/version) before the payload is unpickled — a
truncated, bitflipped, or foreign file is a typed `AOTCorrupt`/
`AOTKeyMismatch`, never a partial deserialize.
"""

from __future__ import annotations

import hashlib
import json
import pickle
import struct
import zlib
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "AOT_DIR",
    "AOT_FORMAT_VERSION",
    "AOT_MAGIC",
    "AOTError",
    "AOTCorrupt",
    "AOTKeyMismatch",
    "aot_relpath",
    "serving_device",
    "device_topology",
    "digest",
    "artifact_fingerprint",
    "feature_signature",
    "build_bucket_executables",
    "load_executable",
]

AOT_DIR = "aot"
AOT_FORMAT_VERSION = 1
AOT_MAGIC = b"T2RA"
_HEADER_SIZE = 12  # magic + length + crc32, the corpus frame shape

#: Hard bound on a single executable file; a forged length field must be
#: rejected before any allocation happens (corpus frame_huge_length).
MAX_EXECUTABLE_BYTES = 1 << 30


class AOTError(RuntimeError):
    """Base class for AOT-executable failures (export or restore side)."""


class AOTCorrupt(AOTError):
    """The envelope failed integrity (magic/length/CRC/unpickle): a
    truncated or bitflipped file. Restore falls back to the next tier."""


class AOTKeyMismatch(AOTError):
    """The envelope is intact but keyed for a different artifact,
    topology, or jax version — loading it would execute the wrong
    program on the wrong data or hardware. Restore falls back LOUDLY."""


def aot_relpath(regime: str, bucket: int) -> str:
    """Artifact-relative path of one bucket's serialized executable."""
    import os

    return os.path.join(AOT_DIR, f"exec_{regime}_b{int(bucket)}.bin")


def serving_device():
    """The ONE device an exported program is compiled for and executes
    on: uncommitted (numpy) request batches land on jax's default
    device, so that is where every bucket executable lives — on a
    multi-chip host too."""
    import jax

    return jax.local_devices()[0]


def device_topology() -> Dict[str, Any]:
    """The topology key of THIS process: an executable lowered here runs
    only on a host presenting the identical triple. Platform and kind
    are those of `serving_device()`, the device the executable runs on."""
    import jax

    device = serving_device()
    return {
        "platform": str(device.platform),
        "device_kind": str(device.device_kind),
        "device_count": int(jax.device_count()),
    }


def digest(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


def artifact_fingerprint(regime: str, chunk_digests: Sequence[bytes]) -> str:
    """Hex fingerprint binding an executable to its (program, weights)
    pair. `chunk_digests` are sha256 digests of the regime's serving
    program bytes and (when weights travel as arguments) its payload
    bytes — both sides hash the same file contents, so export and
    restore agree without re-reading anything twice."""
    h = hashlib.sha256()
    h.update(f"t2r-aot-v{AOT_FORMAT_VERSION}:{regime}".encode())
    for chunk in chunk_digests:
        h.update(chunk)
    return h.hexdigest()


def feature_signature(batch: Mapping[str, Any]) -> Dict[str, Dict[str, Any]]:
    """{key: {shape, dtype}} of a concrete feature batch — the exact
    input contract the executable was specialized to. Restore dispatches
    to the executable only on an exact match; anything else is a novel
    shape for the fresh path, never a TypeError from deep inside XLA."""
    out = {}
    for key, value in batch.items():
        arr = np.asarray(value)
        out[str(key)] = {
            "shape": [int(d) for d in arr.shape],
            "dtype": np.dtype(arr.dtype).name,
        }
    return out


def _pack(header: Dict[str, Any], payload: bytes) -> bytes:
    header_bytes = json.dumps(header, sort_keys=True).encode()
    rest = struct.pack("<I", len(header_bytes)) + header_bytes + payload
    return (
        AOT_MAGIC
        + struct.pack("<I", len(rest))
        + struct.pack("<I", zlib.crc32(rest) & 0xFFFFFFFF)
        + rest
    )


def _unpack(blob: bytes) -> Tuple[Dict[str, Any], bytes]:
    """Envelope -> (header, pickled payload); integrity only, no keys."""
    if len(blob) < _HEADER_SIZE:
        raise AOTCorrupt(f"executable file truncated at {len(blob)} bytes")
    if blob[:4] != AOT_MAGIC:
        raise AOTCorrupt(f"bad magic {blob[:4]!r} (want {AOT_MAGIC!r})")
    (length,) = struct.unpack("<I", blob[4:8])
    (crc,) = struct.unpack("<I", blob[8:12])
    if length > MAX_EXECUTABLE_BYTES:
        raise AOTCorrupt(f"forged length {length} exceeds the format bound")
    rest = blob[_HEADER_SIZE:]
    if len(rest) != length:
        raise AOTCorrupt(
            f"length field says {length} bytes, file carries {len(rest)}"
        )
    if zlib.crc32(rest) & 0xFFFFFFFF != crc:
        raise AOTCorrupt("crc mismatch: executable bytes are corrupt")
    if len(rest) < 4:
        raise AOTCorrupt("envelope too short for a header")
    (hlen,) = struct.unpack("<I", rest[:4])
    if hlen > len(rest) - 4:
        raise AOTCorrupt(f"header length {hlen} overruns the envelope")
    try:
        header = json.loads(rest[4 : 4 + hlen].decode())
    except (UnicodeDecodeError, ValueError) as err:
        raise AOTCorrupt(f"header is not JSON: {err}") from err
    return header, rest[4 + hlen :]


def _check_key(
    header: Mapping[str, Any],
    expect_fingerprint: Optional[str],
    expect_topology: Optional[Mapping[str, Any]],
) -> None:
    import jax

    if header.get("format_version") != AOT_FORMAT_VERSION:
        raise AOTKeyMismatch(
            f"format_version {header.get('format_version')} != "
            f"{AOT_FORMAT_VERSION}"
        )
    if header.get("jax") != jax.__version__:
        raise AOTKeyMismatch(
            f"executable was serialized under jax {header.get('jax')}, "
            f"this process runs {jax.__version__} — executable "
            "serialization is not stable across versions"
        )
    if (
        expect_fingerprint is not None
        and header.get("fingerprint") != expect_fingerprint
    ):
        raise AOTKeyMismatch(
            "artifact fingerprint mismatch: the executable was compiled "
            "from a different (program, weights) pair than this artifact "
            f"carries ({header.get('fingerprint')} != {expect_fingerprint})"
        )
    if expect_topology is not None:
        got = header.get("topology") or {}
        if dict(got) != dict(expect_topology):
            raise AOTKeyMismatch(
                f"device topology mismatch: executable lowered for {got}, "
                f"this host is {dict(expect_topology)}"
            )


def serialize_compiled(compiled, header: Dict[str, Any]) -> bytes:
    """One compiled jax executable -> envelope bytes."""
    from jax.experimental import serialize_executable

    payload, in_tree, out_tree = serialize_executable.serialize(compiled)
    return _pack(header, pickle.dumps((payload, in_tree, out_tree)))


def load_executable(
    blob: bytes,
    expect_fingerprint: Optional[str] = None,
    expect_topology: Optional[Mapping[str, Any]] = None,
):
    """Envelope bytes -> (loaded Compiled, header).

    Order of checks is the contract: integrity (AOTCorrupt) before the
    key (AOTKeyMismatch) before any unpickle — a mismatched executable
    is never deserialized, let alone run.
    """
    from jax.experimental import serialize_executable

    header, payload = _unpack(blob)
    _check_key(header, expect_fingerprint, expect_topology)
    try:
        serialized, in_tree, out_tree = pickle.loads(payload)
        # jax 0.9 defaults execution_devices to EVERY device of the
        # backend; a one-device executable loaded that way expects one
        # argument shard per device and fails its first call on any
        # multi-device host.
        compiled = serialize_executable.deserialize_and_load(
            serialized,
            in_tree,
            out_tree,
            execution_devices=[serving_device()],
        )
    except AOTError:
        raise
    except Exception as err:  # noqa: BLE001 — any unpickle/PJRT rejection
        # of a CRC-clean payload means the file was produced by an
        # incompatible writer; typed so restore can fall back.
        raise AOTCorrupt(
            f"executable payload failed to deserialize: "
            f"{type(err).__name__}: {err}"
        ) from err
    return compiled, header


def build_bucket_executables(
    artifact_bytes: bytes,
    batches: Sequence[Mapping[str, Any]],
    regime: str,
    fingerprint: str,
    prefix_args: Tuple = (),
    timings_ms: Optional[Dict[int, float]] = None,
) -> Dict[int, bytes]:
    """Export-side AOT pass for one regime: rehydrate the serialized
    program once, specialize+compile it per warmup bucket ACROSS A
    THREAD POOL, envelope each executable.

    Compiling the REHYDRATED program (not the original python serving
    fn) makes the executable the compile of exactly what a fresh-trace
    restore would compile — bit-identical serving by construction.
    `prefix_args` are the concrete leading call arguments (the quant
    payload tree, or the weights tree for weights-as-arguments exports);
    the feature batch is always the trailing argument.

    The per-bucket compiles are independent XLA invocations that release
    the GIL, so they run concurrently (one worker per bucket, capped by
    host cores) instead of serially per (regime, bucket); any bucket
    failing fails the whole regime exactly as the serial loop did (the
    caller's best-effort/error-recording contract is unchanged). When
    `timings_ms` is given, each bucket's wall-clock COMPILE milliseconds
    are recorded into it (the envelope serialize + round-trip check run
    after the pool and are not included — they are cheap relative to
    the compile) — the metadata `aot` block carries the timings so
    publish latency is attributable per bucket.
    """
    import concurrent.futures
    import os
    import time

    import jax
    from jax import export as jax_export

    rehydrated = jax_export.deserialize(artifact_bytes)
    topology = device_topology()

    def compile_one(batch) -> Tuple[int, Any, Mapping[str, Any], float]:
        first = next(iter(batch.values()))
        bucket = int(np.asarray(first).shape[0])
        t0 = time.monotonic()
        compiled = (
            jax.jit(rehydrated.call).lower(*prefix_args, batch).compile()
        )
        header = {
            "format_version": AOT_FORMAT_VERSION,
            "regime": str(regime),
            "bucket": bucket,
            "fingerprint": fingerprint,
            "topology": topology,
            "jax": jax.__version__,
            "features": feature_signature(batch),
            "has_prefix_arg": bool(prefix_args),
        }
        return bucket, compiled, header, (time.monotonic() - t0) * 1e3

    out: Dict[int, bytes] = {}
    if not batches:
        return out
    # At least two workers even on one-core hosts: the compile itself
    # releases the GIL, so it overlaps the previous bucket's python-side
    # lowering work.
    workers = min(len(batches), max(2, (os.cpu_count() or 2) - 1))
    # jax's persistent compilation cache MUST NOT serve these compiles:
    # an executable deserialized from that cache serializes WITHOUT its
    # object code, so the shipped blob fails every later
    # deserialize_and_load with "Symbols not found" — even in the
    # process that exported it. A warm cache (any process that compiled
    # this program before, e.g. a bench re-run or a serving replica
    # that re-exports) would corrupt every bucket; the round-trip check
    # below backstops the bypass. No in-tree process serves and exports
    # concurrently: exporters run between legs / in the learner,
    # serving compiles in replicas.
    from tensor2robot_tpu.utils.compile_cache import compile_cache_bypass

    with compile_cache_bypass():
        with concurrent.futures.ThreadPoolExecutor(
            max_workers=workers
        ) as pool:
            compiled_buckets = list(pool.map(compile_one, batches))
    # Serialization runs AFTER the pool drains, sequentially: XLA's
    # executable serialization snapshots process-global compiled-symbol
    # state, and serializing while another bucket's compile is in
    # flight has been observed to emit blobs whose object code misses
    # symbols ("Symbols not found" on a fresh-process deserialize).
    # Compiles are the expensive, GIL-releasing part — they keep the
    # pool; the envelope step is cheap and stays race-free.
    for bucket, compiled, header, elapsed_ms in compiled_buckets:
        blob = serialize_compiled(compiled, header)
        # Round-trip proof before the blob can ship: a blob this process
        # cannot deserialize is corrupt by definition, and shipping it
        # would turn EVERY boot of the artifact into a logged fallback.
        # Raising here routes the regime into the caller's best-effort
        # error-recording path instead (no aot/ entry, reason recorded).
        load_executable(blob)
        out[bucket] = blob
        if timings_ms is not None:
            timings_ms[bucket] = round(elapsed_ms, 3)
    return out
