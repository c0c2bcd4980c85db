"""Dataset assembly: files -> interleave -> shuffle -> batch -> prefetch.

Host-side record pipeline feeding the device. Design point (TPU-first): the
host does only IO + proto parse + image decode; *all* numeric preprocessing
(crops, distortions, casts) runs on-device inside the jitted train step where
XLA fuses it with the model — so the infeed stays small (uint8 images) and
the host CPU stays out of the hot path. This replaces the reference's
tf.data assembly (utils/tfdata.py:630-689 default_input_fn_tmpl) where
preprocessing ran in tf.data on the host.

Pipeline semantics preserved from the reference:
  * file-pattern listing + per-epoch file shuffling when training
  * cyclic interleave across files (non-deterministic reads OK in training)
  * record-level shuffle buffer
  * batch with drop_remainder (static shapes for XLA)
  * multi-dataset zip keyed by dataset_key
  * background prefetch (the AUTOTUNE analogue: a bounded queue + thread)
"""

from __future__ import annotations

import collections
import concurrent.futures
import functools
import itertools
import logging
import os
import queue
import random
import sys
import threading
from typing import Any, Callable, Dict, Iterator, List, Mapping, Optional, Sequence, Tuple, Union

import numpy as np

from tensor2robot_tpu import flags
from tensor2robot_tpu.data import tfrecord
from tensor2robot_tpu.data.parser import SpecParser
from tensor2robot_tpu.data.roi import (
    DecodeROI,
    normalize_decode_rois,
    resolve_decode_rois,
)
from tensor2robot_tpu.data.wire import FastSpecParser
from tensor2robot_tpu.specs import TensorSpecStruct
from tensor2robot_tpu.utils import tracing

_log = logging.getLogger(__name__)


def _interleave_files(
    files: Sequence[str],
    cycle_length: int,
    shuffle_files: bool,
    rng: Optional[random.Random],
    repeat: bool,
) -> Iterator[bytes]:
    """Round-robin record interleave across up to `cycle_length` open files."""
    while True:
        order = list(files)
        if shuffle_files and rng is not None:
            rng.shuffle(order)
        pending = iter(order)
        active: List[Iterator[bytes]] = []
        for path in itertools.islice(pending, cycle_length):
            active.append(tfrecord.read_tfrecords(path))
        while active:
            next_active: List[Iterator[bytes]] = []
            for reader in active:
                try:
                    yield next(reader)
                    next_active.append(reader)
                except StopIteration:
                    try:
                        next_active.append(tfrecord.read_tfrecords(next(pending)))
                    except StopIteration:
                        pass
            active = next_active
        if not repeat:
            return


def _shuffle_records(
    records: Iterator, buffer_size: int, rng: random.Random
) -> Iterator:
    buf: List = []
    for record in records:
        buf.append(record)
        if len(buf) >= buffer_size:
            idx = rng.randrange(len(buf))
            buf[idx], buf[-1] = buf[-1], buf[idx]
            yield buf.pop()
    rng.shuffle(buf)
    yield from buf


class _Prefetcher:
    """Bounded background-thread prefetch queue.

    The producer re-checks a stop flag between bounded put attempts, so an
    abandoned iterator (consumer breaks early, common in eval loops) releases
    its thread and buffers instead of parking forever on a full queue.
    """

    _SENTINEL = object()

    def __init__(self, source: Iterator, depth: int):
        self._queue: "queue.Queue" = queue.Queue(maxsize=depth)
        self._error: Optional[BaseException] = None
        self._stopped = threading.Event()
        self._thread = threading.Thread(
            target=self._fill, args=(source,), daemon=True
        )
        self._thread.start()

    def _put(self, item) -> bool:
        while not self._stopped.is_set():
            try:
                self._queue.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def _fill(self, source: Iterator) -> None:
        try:
            for item in source:
                if not self._put(item):
                    return
        except BaseException as e:  # propagated to the consumer
            self._error = e
        finally:
            self._put(self._SENTINEL)

    def close(self) -> None:
        self._stopped.set()
        # Drain so a producer blocked in put() can observe the stop flag.
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass

    def __del__(self):
        self.close()

    def __iter__(self) -> "_Prefetcher":
        return self

    def __next__(self):
        # Was the queue empty when the consumer came: the share of gets
        # that had to wait for the producer.
        tracing.count("data.prefetch_gets")
        if self._queue.empty():
            tracing.count("data.prefetch_empty")
        item = self._queue.get()
        if item is self._SENTINEL:
            if self._error is not None:
                raise self._error
            raise StopIteration
        return item


#: Cores the pool leaves to the program's other busy threads: the reader
#: (a third to a half of a core of Python a batch), the train thread and
#: the runtime's host-to-device relayout threads, which work in bursts.
_RESERVED_CORES = 1

#: Fewest records a slice job takes: below it a job's fixed cost (a future,
#: a span, a thread's wake-up) starts to show against the decode.
_MIN_SLICE_RECORDS = 16


def _usable_cores() -> int:
    """Cores this process may run on: its affinity mask, which a
    container's cpuset narrows where `os.cpu_count()` counts the machine."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # no affinity call on this platform
        return os.cpu_count() or 1


def default_parse_workers() -> int:
    """Default width of the parse pool: the cores this process may run on
    less `_RESERVED_CORES`, at least 1.

    The AUTOTUNE analogue for the parse/decode stage (reference
    utils/tfdata.py:630-689 used num_parallel_calls=AUTOTUNE). A batch is
    parsed in slices (`_slice_bounds`), so the pool is never wider than a
    few batches can occupy, whatever this returns. Overridable via
    T2R_PARSE_WORKERS; 0 disables the pool (synchronous parse).
    """
    env = flags.get_optional_int("T2R_PARSE_WORKERS")
    if env is not None:
        return env
    return max(1, _usable_cores() - _RESERVED_CORES)


def _slice_bounds(records: int, num_workers: int) -> List[Tuple[int, int]]:
    """[a, b) of each slice job of a batch of `records`: one slice a worker,
    none shorter than `_MIN_SLICE_RECORDS` but the last."""
    length = max(_MIN_SLICE_RECORDS, -(-records // max(num_workers, 1)))
    return [(a, min(a + length, records)) for a in range(0, records, length)]


def default_parse_backend() -> str:
    """'thread' (default) or 'process' (T2R_PARSE_BACKEND).

    Threads carry the pool as wide as the host's cores: a record's parse
    is some 3% python (the protobuf scan, the scalars) and 97% native jpeg
    decode and TFRecord codec, both of which release the GIL
    (tools/measure_gil_release.py), and a batch's slices decode straight
    into one shared set of arrays. The process backend takes the GIL out
    of the parse altogether, at the price of whole-batch jobs: workers
    re-parse in spawned interpreters and ship back parsed numpy batches
    (raw jpeg chunks are cheap to send; the returned uint8 image batch is
    the dominant IPC cost, which the shared-memory ring carries).
    """
    return flags.get_enum("T2R_PARSE_BACKEND")


def default_parse_fast() -> bool:
    """Whether the wire-format fast parser (data/wire.py) is the default.

    T2R_PARSE_FAST=0 disables it (the SpecParser oracle then runs every
    batch). The fast path self-disables per dataset on unsupported specs
    and falls back per batch on any parse failure, so enabling it is
    always semantics-preserving.
    """
    return flags.get_bool("T2R_PARSE_FAST")


def default_decode_roi() -> bool:
    """Whether decode-time ROI cropping (data/roi.py) is honored.

    T2R_DECODE_ROI=0 makes RecordDataset IGNORE any decode_roi request:
    image fields then decode full-frame and the consumer crops, exactly
    the pre-ROI pipeline. The gate sits at the dataset so one env flip
    restores the old path end to end (bench A/Bs, regression bisects).
    """
    return flags.get_bool("T2R_DECODE_ROI")


def default_parse_shm() -> bool:
    """Whether the process backend returns batches via shared memory.

    T2R_PARSE_SHM=0 reverts to pickling parsed batches through the result
    pipe (the decoded uint8 image batch — ~60 MB at batch 64 for the
    QT-Opt spec — then pays serialize + pipe-write + deserialize)."""
    return flags.get_bool("T2R_PARSE_SHM")


class _FastParseState:
    """A FastSpecParser plus its fallback accounting (shared thread/process).

    After `max_fallbacks` failed batches the fast path is switched off for
    the owning dataset/worker: persistent fallback means the data disagrees
    with the compiled schema and re-parsing every batch twice helps nobody.
    """

    max_fallbacks = 8

    def __init__(self, specs, enabled: bool):
        self.parser: Optional[FastSpecParser] = None
        if enabled:
            fast = FastSpecParser(specs)
            if fast.supported:
                self.parser = fast
            else:
                _log.info(
                    "fast parser disabled for this spec structure: %s",
                    fast.unsupported_reason,
                )

    def note_fallback(self) -> None:
        parser = self.parser
        if parser is None:
            return
        parser.fallbacks += 1
        if parser.fallbacks == 1:
            _log.warning(
                "fast parse failed for a batch; re-parsing with SpecParser"
            )
        if parser.fallbacks >= self.max_fallbacks:
            _log.warning(
                "fast parser disabled after %d fallbacks", parser.fallbacks
            )
            self.parser = None


# Per-process parse state for the process-pool backend (set by the pool
# initializer in each worker; module-level so submitted jobs can reach it
# without pickling the parser per chunk).
_PROCESS_PARSER: Optional[SpecParser] = None
_PROCESS_FAST: Optional[_FastParseState] = None
_PROCESS_SHM_FREE = None  # free-slot name queue, or None (inline returns)
_PROCESS_SHM_CACHE: Dict[str, Any] = {}  # name -> attached SharedMemory

# Arrays below this size ride the result pipe; shm slots are for the big
# decoded image batches where pickling is the dominant IPC cost.
_SHM_MIN_SHIP_BYTES = 1 << 20
_SHM_ALIGN = 64


def _process_pool_init(
    specs_blob: bytes, parse_fast: bool, shm_free, decode_cache_mb: int
) -> None:
    import pickle

    global _PROCESS_PARSER, _PROCESS_FAST, _PROCESS_SHM_FREE
    specs = pickle.loads(specs_blob)
    _PROCESS_PARSER = SpecParser(specs)
    _PROCESS_FAST = _FastParseState(specs, parse_fast)
    _PROCESS_SHM_FREE = shm_free
    # The decode cache is per-process: give each worker its share of the
    # configured budget rather than the full budget times the worker
    # count (records land on arbitrary workers, so per-worker hit rates
    # are diluted anyway — the budget must not multiply).
    flags.write_env("T2R_DECODE_CACHE_MB", decode_cache_mb)


def _regroup_chunk(chunk):
    """Multi-dataset chunks arrive as per-record dicts; both parsers want
    {dataset_key: [record, ...]} columns."""
    if isinstance(chunk[0], dict):
        return {k: [row[k] for row in chunk] for k in chunk[0].keys()}
    return chunk


def _split_payload(payload):
    """A parse payload is either a plain chunk (the pre-ROI wire format,
    unchanged) or ("roi", chunk, {key: ResolvedROI}) when decode-time ROI
    is active — the offsets were resolved once in the parent so thread
    and process workers (and a fast-path fallback) all crop identically."""
    if isinstance(payload, tuple) and len(payload) == 3 and payload[0] == "roi":
        return payload[1], payload[2]
    return payload, None


def _parse_with(parser: SpecParser, chunk, roi=None) -> TensorSpecStruct:
    """Parses one chunk (multi-dataset rows regrouped by key) — the single
    implementation both the thread and process backends run."""
    return parser.parse_batch(_regroup_chunk(chunk), roi=roi)


class ParseStats:
    """Degradation counters one dataset's consumers share (thread-safe).

    `records_skipped` is the quarantine counter the T2R_PARSE_ON_ERROR
    =skip mode surfaces: corrupt records dropped from the stream instead
    of killing the consumer. `fast_fallbacks` aggregates WORKER-side
    fast-parser fallbacks (the parent's own fast parser counts on
    itself). Surfaced via RecordDataset.stats()."""

    _FIELDS = (
        "records_skipped", "batches_degraded", "batches_dropped",
        "fast_fallbacks",
    )
    __slots__ = ("_lock",) + _FIELDS

    def __init__(self):
        self._lock = threading.Lock()
        for field in self._FIELDS:
            setattr(self, field, 0)

    def note_skipped(self, records: int, whole_batch: bool) -> None:
        with self._lock:
            self.records_skipped += records
            if whole_batch:
                self.batches_dropped += 1
            else:
                self.batches_degraded += 1

    def merge(self, delta: Dict[str, int]) -> None:
        """Folds a worker's per-chunk snapshot delta into these totals."""
        with self._lock:
            for field in self._FIELDS:
                setattr(
                    self, field,
                    getattr(self, field) + delta.get(field, 0),
                )

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {field: getattr(self, field) for field in self._FIELDS}


def default_parse_on_error() -> str:
    """T2R_PARSE_ON_ERROR: 'raise' (default) kills the consumer on a
    genuinely corrupt record; 'skip' drops-and-counts it."""
    return flags.get_enum("T2R_PARSE_ON_ERROR")


def _slice_roi(roi, keep: List[int]):
    """Per-record ROI offsets restricted to the surviving records."""
    if roi is None:
        return None
    import dataclasses as _dataclasses

    out = {}
    for key, resolved in roi.items():
        out[key] = _dataclasses.replace(
            resolved,
            ys=np.asarray(resolved.ys)[keep],
            xs=np.asarray(resolved.xs)[keep],
        )
    return out


def _skip_and_parse(
    parser: SpecParser, chunk, roi, stats: Optional[ParseStats],
    original_error: BaseException,
) -> Optional[TensorSpecStruct]:
    """T2R_PARSE_ON_ERROR=skip: triage the failed batch record by record
    with the oracle, drop the corrupt ones (counted), parse the rest.

    Returns None when NOTHING in the chunk survives (callers drop the
    batch entirely). The surviving batch is SHORT — graceful degradation
    trades the static batch shape for stream survival, and the counters
    make the trade visible instead of silent.

    When every record parses individually, the failure was BATCH-level
    (stacking, ROI application, a parser bug) — not record corruption,
    which is the only thing skip mode is licensed to swallow: the
    original error re-raises uncounted."""
    keep: List[int] = []
    for index, record in enumerate(chunk):
        try:
            parser.parse_single(record)
        except Exception:
            continue
        keep.append(index)
    skipped = len(chunk) - len(keep)
    if skipped == 0:
        raise original_error
    if stats is not None:
        stats.note_skipped(skipped, whole_batch=not keep)
    _log.warning(
        "T2R_PARSE_ON_ERROR=skip: dropped %d corrupt record(s) from a "
        "batch of %d", skipped, len(chunk),
    )
    if not keep:
        return None
    survivors = [chunk[index] for index in keep]
    return _parse_with(parser, survivors, roi=_slice_roi(roi, keep))


def _parse_chunk_impl(
    fast_state: Optional[_FastParseState],
    parser: SpecParser,
    payload,
    stats: Optional[ParseStats] = None,
) -> Optional[TensorSpecStruct]:
    """Fast wire-format parse with automatic SpecParser fallback.

    Any fast-path failure re-parses the batch with the oracle: genuinely
    bad data then raises the canonical error; a fast-path limitation
    degrades to slow-but-correct. A ROI payload falls back with the SAME
    resolved offsets, so the oracle reproduces the identical batch.
    test_fast_parser.py / test_roi_decode.py pin the parity.

    Under T2R_PARSE_ON_ERROR=skip an oracle failure additionally triages
    per record: corrupt records are dropped-and-counted (`stats`), the
    surviving batch is returned (None when nothing survives)."""
    chunk, roi = _split_payload(payload)
    fast = fast_state.parser if fast_state is not None else None
    if fast is not None:
        try:
            return fast.parse_batch(_regroup_chunk(chunk), roi=roi)
        except Exception:
            fast_state.note_fallback()
            tracing.add(fast_fallback=1)
    try:
        return _parse_with(parser, chunk, roi=roi)
    except Exception as err:
        if default_parse_on_error() != "skip":
            raise
        return _skip_and_parse(parser, chunk, roi, stats, err)


def _traced_parse(fast_state, parser, item, stats):
    """`_parse_chunk_impl` on one `(ordinal, payload)` of `_chunks()` under
    a `data.parse_chunk` span: one worker's work on one whole batch (the
    job of a batch that is not parsed in slices, and the fallback of one
    whose slice failed). The decoder adds `images` and `decode_ns` to it
    (data/wire.py). Returns (parsed, span)."""
    ordinal, payload = item
    records = len(_split_payload(payload)[0])
    with tracing.span("data.parse_chunk", ordinal=ordinal, records=records) as span:
        parsed = _parse_chunk_impl(fast_state, parser, payload, stats)
    return parsed, span


def _parse_slice(fast, ordinal, first_row, records, arrays, roi) -> List[str]:
    """One slice job: `records`, which are records `first_row`,
    `first_row + 1`, ... of batch `ordinal`, parsed and decoded straight
    into those rows of the batch's `arrays`, under a `data.parse_chunk`
    span of its own. Returns the optional keys the slice lacks."""
    with tracing.span(
        "data.parse_chunk", ordinal=ordinal, records=len(records),
        first_row=first_row,
    ):
        return fast.parse_rows(
            _regroup_chunk(records), arrays, first_row, roi=roi
        )


def _shm_attach(name: str):
    shm = _PROCESS_SHM_CACHE.get(name)
    if shm is None:
        from multiprocessing import shared_memory

        shm = shared_memory.SharedMemory(name=name)
        _PROCESS_SHM_CACHE[name] = shm
    return shm


def _shm_align(nbytes: int) -> int:
    return (nbytes + _SHM_ALIGN - 1) // _SHM_ALIGN * _SHM_ALIGN


def _process_parse_chunk(item):
    """Worker-side parse + zero-copy return.

    Large arrays (decoded image batches) are written into a shared-memory
    ring slot and returned as (dtype, shape, offset) descriptors; only
    small arrays ride the pickle pipe. When no slot frees up in time (the
    consumer is holding every in-flight batch) the whole batch falls back
    to the inline pickle path — slower, never stuck.
    """
    parser = _PROCESS_PARSER
    if parser is None:  # pragma: no cover - initializer always runs first
        raise RuntimeError("process pool worker missing parser init")
    # Skip-mode + fallback counters ride each payload back as a
    # per-chunk DELTA, and the chunk's `data.parse_chunk` span with them
    # (worker processes share neither the parent's ParseStats nor its
    # recorder): epoch nanoseconds mean the same in the parent, and the
    # negated pid stands for this worker's thread there.
    stats = ParseStats()
    fast = _PROCESS_FAST.parser if _PROCESS_FAST is not None else None
    fallbacks_before = fast.fallbacks if fast is not None else 0
    parsed, span = _traced_parse(_PROCESS_FAST, parser, item, stats)
    if fast is not None:
        stats.fast_fallbacks = fast.fallbacks - fallbacks_before
    delta = {key: value for key, value in stats.snapshot().items() if value}
    delta["span"] = dict(span.as_dict(), thread=-os.getpid())
    if parsed is None:
        return ("dropped", delta)
    # Ship plain (key, value) pairs; the parent rebuilds the struct (cheap)
    # rather than relying on TensorSpecStruct pickling across versions.
    flat = list(parsed.items())
    free_queue = _PROCESS_SHM_FREE
    if free_queue is None:
        return ("inline", flat, delta)
    large = [(k, v) for k, v in flat if v.nbytes >= _SHM_MIN_SHIP_BYTES]
    if not large:
        return ("inline", flat, delta)
    need = sum(_shm_align(v.nbytes) for _, v in large)
    try:
        # Non-blocking: before the parent seeds the ring (it sizes slots
        # from the first inline batch) the queue is empty and chunks must
        # not stall; after seeding, ring capacity exceeds max in-flight
        # so a slot is normally free the moment a worker wants one.
        name = free_queue.get_nowait()
    except queue.Empty:
        return ("inline", flat, delta)
    shm = _shm_attach(name)
    if need > shm.size:
        free_queue.put(name)
        return ("inline", flat, delta)
    entries = []
    offset = 0
    for key, value in flat:
        if value.nbytes < _SHM_MIN_SHIP_BYTES:
            entries.append((key, None, value))
            continue
        view = np.frombuffer(
            shm.buf, dtype=value.dtype, count=value.size, offset=offset
        ).reshape(value.shape)
        np.copyto(view, value)
        del view
        entries.append((key, (value.dtype, value.shape, offset), None))
        offset += _shm_align(value.nbytes)
    return ("shm", name, entries, delta)


class _ShmSlotToken:
    """Returns a ring slot to the free queue when the last view of the
    batch it carries is garbage-collected."""

    __slots__ = ("_ring", "_name")

    def __init__(self, ring: "_ShmBatchRing", name: str):
        self._ring = ring
        self._name = name

    def __del__(self):
        try:
            self._ring.release(self._name)
        except Exception:
            pass


class _ShmArray(np.ndarray):
    """ndarray view into a shm ring slot; keeps the slot's release token
    alive for as long as the array (or any derived view) exists."""

    _t2r_token: Optional[_ShmSlotToken] = None


class _ShmBatchRing:
    """Fixed set of shared-memory slots cycling worker -> consumer.

    The parent creates the slots and seeds the workers' free queue (the
    SAME queue the pool initializer handed to every worker — release()
    must feed the queue workers actually read); a worker takes a name,
    writes one parsed batch, and returns the name in its result; the
    parent wraps the slot in numpy views whose token releases the name
    back to the queue once the consumer drops the batch. Capacity is
    in-flight-bounded, so a consumer that retains batches only degrades
    workers to the inline path (get_nowait misses), never blocks the
    pipeline.
    """

    def __init__(self, free_queue, slot_bytes: int, num_slots: int):
        from multiprocessing import shared_memory

        self.slot_bytes = slot_bytes
        self.slots: Dict[str, Any] = {}
        self.free_queue = free_queue
        # Create ALL slots before publishing any name: a mid-loop failure
        # (small /dev/shm) must not leave workers holding slot names the
        # parent never registered — the caller catches the error and the
        # pipeline degrades to inline returns, with nothing leaked.
        created: List[Any] = []
        try:
            for _ in range(num_slots):
                created.append(
                    shared_memory.SharedMemory(create=True, size=slot_bytes)
                )
        except Exception:
            for shm in created:
                try:
                    shm.close()
                    shm.unlink()
                except Exception:
                    pass
            raise
        for shm in created:
            self.slots[shm.name] = shm
            self.free_queue.put(shm.name)
        self._closed = False
        self._zombies: List[Any] = []

    def release(self, name: str) -> None:
        if not self._closed:
            try:
                self.free_queue.put_nowait(name)
            except Exception:
                pass

    def close(self) -> None:
        self._closed = True
        for shm in self.slots.values():
            try:
                shm.unlink()
            except FileNotFoundError:
                pass
            try:
                shm.close()
            except BufferError:
                # A consumer still holds views into this slot; the mapping
                # frees when they die. Keep the object so its __del__ does
                # not spam at arbitrary gc time.
                self._zombies.append(shm)
        self.slots = {}


class _BatchArrays:
    """Gives each batch its arrays, the arrays of a batch nobody holds any
    more before fresh ones.

    A batch of 256 decoded 472x472 frames is 171 MB. Fresh from the
    allocator it is an anonymous mapping of its own: every page of it
    faults in at its first write, under whichever thread of the pool
    decodes into it, and the mapping goes back to the kernel when the
    batch is dropped, which interrupts every core the process runs on. On
    the v5e's 13-core host, twelve threads faulting into one address space
    made an image cost its worker 6 ms where the decode takes 1.5 ms, and
    the train thread's `device_put` 42 ms where it takes 12 (PERF.md,
    PR 27). Arrays that have been written once cost none of that.

    `take` hands out views. A view of an array holds a reference
    to it however it was derived (numpy points `.base` at the array that
    owns the memory), and so does whoever reads its buffer (a host-to-
    device transfer in flight), so a set whose arrays nobody but this
    object refers to is free to be written again. At most `limit` sets are
    kept; a consumer that holds on to more batches than that gets fresh
    arrays, as it always did. One thread takes (the one that iterates the
    dataset's batcher).
    """

    def __init__(self, limit: int):
        self._limit = limit
        self._sets: List[Dict[str, np.ndarray]] = []

    @staticmethod
    def _unreferenced(array: np.ndarray) -> bool:
        # The set's dict, the caller's loop variable, this parameter and
        # getrefcount's own argument.
        return sys.getrefcount(array) == 4

    def take(
        self, allocate: Callable, n: int, roi=None
    ) -> Optional[Dict[str, np.ndarray]]:
        """Views of a free set of arrays for a batch of `n` records, else
        of what `allocate(n, roi)` gives (None where it gives None)."""
        arrays = next(
            (
                held for held in self._sets
                if all(
                    len(array) == n and self._unreferenced(array)
                    for array in held.values()
                )
            ),
            None,
        )
        if arrays is None:
            arrays = allocate(n, roi)
            if arrays is None:
                return None
            if len(self._sets) < self._limit:
                self._sets.append(arrays)
        return {key: array[:] for key, array in arrays.items()}


class _ParallelBatcher:
    """Ordered parallel parse over a worker pool.

    Each `(ordinal, payload)` of `chunks` becomes the jobs of one batch:
    the slice jobs `slice_fn(item)` gives, with what joins their results
    into the batch, or, where it gives None (or there is no `slice_fn`),
    the one job `parse_fn(item)`. Up to `max_in_flight` batches are
    submitted ahead of the consumer; the pool takes their jobs in
    submission order, so its workers finish the oldest batch together and
    go on into the next without a gap, and batches are yielded in
    submission order, each once its last job has closed. A batch whose
    slice raised (or whose slices disagree) goes through `parse_fn` whole:
    the oracle fallback and the skip-mode triage see a batch, as ever.

    Default pool: a ThreadPoolExecutor — parsing is jpeg decode in native
    code that releases the GIL, into arrays the slices share, so threads
    scale with the cores and nothing is pickled. Callers may pass any
    Executor instead (the process backend passes a ProcessPoolExecutor,
    which DOES pickle chunks out and parsed batches back, a whole batch a
    job); an externally-passed pool is the caller's to shut down (reused
    across epochs). This is the rebuild of tf.data's parallel parse/decode
    maps (reference utils/tfdata.py:630-689, num_parallel_calls=AUTOTUNE).
    """

    def __init__(
        self,
        chunks: Iterator,
        parse_fn: Callable,
        num_workers: int,
        max_in_flight: Optional[int] = None,
        pool: Optional[concurrent.futures.Executor] = None,
        on_discard: Optional[Callable] = None,
        slice_fn: Optional[Callable] = None,
    ):
        self._chunks = chunks
        self._parse_fn = parse_fn
        self._slice_fn = slice_fn
        self._owns_pool = pool is None
        self._pool = pool or concurrent.futures.ThreadPoolExecutor(
            max_workers=num_workers, thread_name_prefix="t2r-parse"
        )
        # (item, futures, join) of each batch, oldest first.
        self._in_flight: "collections.deque" = collections.deque()
        self._max_in_flight = max_in_flight or num_workers + 2
        self._exhausted = False
        # Called with each completed-but-unconsumed result when iteration
        # is abandoned (consumer breaks early): results may carry
        # resources (shm ring slot names) that must be returned.
        self._on_discard = on_discard

    def _submit_one(self) -> bool:
        try:
            item = next(self._chunks)
        except StopIteration:
            self._exhausted = True
            return False
        sliced = self._slice_fn(item) if self._slice_fn is not None else None
        if sliced is None:
            futures, join = [self._pool.submit(self._parse_fn, item)], None
        else:
            jobs, join = sliced
            futures = [self._pool.submit(job) for job in jobs]
        self._in_flight.append((item, futures, join))
        return True

    def _result(self, item, futures, join):
        if join is None:
            return futures[0].result()
        try:
            return join([future.result() for future in futures])
        except Exception:
            # Let the other slices end (they write into arrays nobody will
            # read), then parse the batch whole.
            concurrent.futures.wait(futures)
            return self._pool.submit(self._parse_fn, item).result()

    def __iter__(self):
        try:
            while not self._exhausted and len(self._in_flight) < self._max_in_flight:
                self._submit_one()
            while self._in_flight:
                item, futures, join = self._in_flight.popleft()
                if not self._exhausted:
                    self._submit_one()
                result = self._result(item, futures, join)
                # The join holds the batch's arrays; nothing of this batch
                # stays behind in this frame while the consumer has it.
                del item, futures, join
                yield result
                del result
        finally:
            if self._owns_pool:
                # Queued jobs are dropped; a running one ends in its own
                # time and its thread with it.
                self._pool.shutdown(wait=False, cancel_futures=True)
            else:
                # External pool (reused across epochs): cancel what we
                # queued but leave the executor alive for the next epoch.
                # Futures past cancellation (running or done) are drained
                # so their results' resources (shm slots) are released
                # instead of leaking with the discarded future.
                for _, futures, join in self._in_flight:
                    for future in futures:
                        if future.cancel():
                            continue
                        try:
                            result = future.result()
                        except Exception:
                            continue
                        if join is None and self._on_discard is not None:
                            self._on_discard(result)
                self._in_flight.clear()


def _delivered(batches: Iterator) -> Iterator[TensorSpecStruct]:
    """Skip-mode whole-batch drops surface as None and stop here: every
    batch that goes on is real, and counted (`data.parse_batches`)."""
    for batch in batches:
        if batch is not None:
            tracing.count("data.parse_batches")
            yield batch


class RecordDataset:
    """Iterable of parsed, batched TensorSpecStruct numpy batches.

    Args:
      specs: feature(+label) spec structure driving the generated parser.
      file_patterns: glob pattern(s), or a {dataset_key: patterns} map for
        multi-dataset specs (zipped element-wise, reference
        utils/tfdata.py:395-422).
      batch_size: per-host batch size; with drop_remainder shapes are static.
      mode: 'train' enables shuffling + infinite repeat by default.
      shuffle_buffer_size: record-level shuffle window.
      repeat: None -> infinite for train, single epoch otherwise.
      seed: deterministic shuffling when set.
      prefetch_depth: parsed batches buffered ahead by a background thread.
      file_fraction: use only the first fraction of files (data-ablation,
        reference FractionalRecordInputGenerator).
      num_parse_workers: worker-pool size for parallel proto-parse and
        jpeg decode; None -> default_parse_workers(), 0 -> synchronous.
      parse_backend: 'thread' (default) or 'process'
        (see default_parse_backend; env T2R_PARSE_BACKEND). The process
        backend removes the GIL ceiling on many-core hosts; parsed image
        batches return through a shared-memory ring (T2R_PARSE_SHM=0
        reverts to pickling them through the result pipe).
      parse_fast: use the wire-format fast parser (data/wire.py) with
        automatic SpecParser fallback; None -> default_parse_fast()
        (env T2R_PARSE_FAST, default on).
      decode_roi: optional {flat spec key: DecodeROI} — decode-time crop
        of the named image fields (data/roi.py): batches then carry the
        cropped shape and the decoder skips the pixels outside the
        window. Offsets resolve per chunk (random mode draws from this
        dataset's seeded RNG BEFORE decode); honored only while
        T2R_DECODE_ROI=1 (the default) — T2R_DECODE_ROI=0 restores
        full-frame decode exactly.
      shard_by_host: in multi-host runs, each process reads only its
        round-robin slice of the file list (the reference's per-host
        infeed, utils/tfdata.py:38-61); batch_size is then the PER-HOST
        batch. Single-process runs are unaffected.
    """

    def __init__(
        self,
        specs,
        file_patterns: Union[str, Sequence[str], Mapping[str, Union[str, Sequence[str]]]],
        batch_size: int,
        mode: str = "train",
        shuffle_buffer_size: int = 512,
        repeat: Optional[bool] = None,
        seed: Optional[int] = None,
        prefetch_depth: int = 2,
        cycle_length: int = 4,
        drop_remainder: bool = True,
        file_fraction: float = 1.0,
        num_parse_workers: Optional[int] = None,
        parse_backend: Optional[str] = None,
        parse_fast: Optional[bool] = None,
        decode_roi: Optional[Mapping[str, DecodeROI]] = None,
        shard_by_host: bool = False,
    ):
        self._specs = specs
        self._decode_roi = (
            normalize_decode_rois(decode_roi, specs)
            if decode_roi and default_decode_roi()
            else None
        )
        self._process_pool: Optional[concurrent.futures.Executor] = None
        self._parse_backend = (
            default_parse_backend() if parse_backend is None else parse_backend
        )
        if self._parse_backend not in ("thread", "process"):
            raise ValueError(
                f"parse_backend must be 'thread' or 'process', got "
                f"{self._parse_backend!r}"
            )
        self._parser = SpecParser(specs)
        self._parse_fast = (
            default_parse_fast() if parse_fast is None else parse_fast
        )
        self._fast_state = _FastParseState(specs, self._parse_fast)
        self._parse_stats = ParseStats()
        self._shm_ring: Optional[_ShmBatchRing] = None
        self._shm_free_queue = None
        self._mp_context = None
        self._batch_size = batch_size
        self._train = mode == "train"
        self._shuffle_buffer_size = shuffle_buffer_size if self._train else 0
        self._repeat = self._train if repeat is None else repeat
        self._seed = seed
        self._prefetch_depth = prefetch_depth
        self._cycle_length = cycle_length
        self._drop_remainder = drop_remainder
        self._num_parse_workers = (
            default_parse_workers()
            if num_parse_workers is None
            else num_parse_workers
        )

        if isinstance(file_patterns, Mapping):
            self._files: Dict[str, List[str]] = {
                k: tfrecord.list_files(v) for k, v in file_patterns.items()
            }
        else:
            self._files = {"": tfrecord.list_files(file_patterns)}
        if file_fraction < 1.0:
            for k, files in self._files.items():
                n = max(1, int(len(files) * file_fraction))
                self._files[k] = files[:n]
        if shard_by_host:
            import jax

            index, count = jax.process_index(), jax.process_count()
            if count > 1:
                for k, files in self._files.items():
                    mine = files[index::count]
                    if not mine:
                        raise ValueError(
                            f"Host {index}/{count} got no files for dataset "
                            f"{k!r} ({len(files)} files total); need at "
                            "least one shard per host."
                        )
                    self._files[k] = mine
        missing = set(self._parser.dataset_keys) - set(self._files.keys())
        if missing:
            raise ValueError(
                f"Specs reference dataset keys {sorted(missing)} with no file "
                f"patterns (got {sorted(self._files.keys())})"
            )

    def _record_stream(self) -> Iterator:
        rng = random.Random(self._seed)
        dataset_keys = list(self._files.keys())
        if dataset_keys == [""]:
            records: Iterator = _interleave_files(
                self._files[""],
                self._cycle_length,
                shuffle_files=self._train,
                rng=rng,
                repeat=self._repeat,
            )
        else:
            # Multi-dataset zip: streams must stay aligned, so files are read
            # in identical (sorted) order per key, interleave is disabled, and
            # epochs are zipped jointly — unequal record counts are an error,
            # not a silent drift (the pairs ARE the training signal).
            def zipped():
                while True:
                    epoch = {
                        k: _interleave_files(
                            self._files[k], 1, shuffle_files=False, rng=None,
                            repeat=False,
                        )
                        for k in dataset_keys
                    }
                    while True:
                        row = {}
                        done = []
                        for k, stream in epoch.items():
                            try:
                                row[k] = next(stream)
                            except StopIteration:
                                done.append(k)
                        if done:
                            if len(done) != len(epoch):
                                raise ValueError(
                                    "Multi-dataset zip misalignment: datasets "
                                    f"{sorted(done)} exhausted before "
                                    f"{sorted(set(epoch) - set(done))}; record "
                                    "counts must match across dataset keys."
                                )
                            break
                        yield row
                    if not self._repeat:
                        return
            records = zipped()
        if self._shuffle_buffer_size > 1:
            records = _shuffle_records(records, self._shuffle_buffer_size, rng)
        return records

    def _chunks(self) -> Iterator:
        stream = self._record_stream()
        roi_rng = (
            np.random.default_rng(self._seed) if self._decode_roi else None
        )
        # Each payload travels with the ordinal of its batch (0, 1, ... of
        # this iterator), which its `data.*` spans carry.
        for ordinal in itertools.count():
            with tracing.span("data.read_chunk", ordinal=ordinal) as span:
                chunk = list(itertools.islice(stream, self._batch_size))
                whole = bool(chunk) and (
                    len(chunk) == self._batch_size or not self._drop_remainder
                )
                payload = chunk
                if whole and self._decode_roi is not None:
                    # Offsets resolve HERE, once per chunk, in the parent:
                    # every consumer of this payload (thread worker, process
                    # worker, oracle fallback after a fast-path failure)
                    # crops with the same rects, so the batch is
                    # reproducible across paths.
                    payload = (
                        "roi",
                        chunk,
                        resolve_decode_rois(
                            self._decode_roi, self._specs, len(chunk), roi_rng
                        ),
                    )
                span.add(
                    records=len(chunk),
                    bytes=sum(
                        sum(map(len, row.values())) if isinstance(row, dict)
                        else len(row)
                        for row in chunk
                    ),
                )
            if not whole:
                return
            yield ordinal, payload

    def _parse_chunk(self, item) -> Optional[TensorSpecStruct]:
        return _traced_parse(
            self._fast_state, self._parser, item, self._parse_stats
        )[0]

    def _slices_per_batch(self) -> int:
        """Jobs the pool gets for one batch: its slices where a batch can
        be parsed in slices, else 1. Slices need the threads' shared
        memory, the fast parser (the oracle stacks whole batches) and
        shapes known before a record is read (a sequence field pads to its
        batch's longest record)."""
        fast = self._fast_state.parser
        if (
            self._parse_backend != "thread"
            or fast is None
            or not fast.static_shapes
        ):
            return 1
        return len(_slice_bounds(self._batch_size, self._num_parse_workers))

    def _max_in_flight(self) -> int:
        """Batches the pool works ahead of the consumer: as many as give
        every worker a job, and `prefetch_depth` behind them."""
        working = -(-self._num_parse_workers // self._slices_per_batch())
        return working + max(self._prefetch_depth, 1)

    def _slice_jobs(self, buffers: _BatchArrays, item):
        """`_ParallelBatcher`'s `slice_fn`: the batch's arrays taken once
        (from `buffers`, which an iterator owns), one job a slice that
        fills its rows of them, and the join that makes the batch of the
        jobs' results. None where the batch has to be parsed whole
        (`_slices_per_batch`)."""
        fast = self._fast_state.parser
        if fast is None:
            return None
        ordinal, payload = item
        chunk, roi = _split_payload(payload)
        try:
            arrays = buffers.take(fast.allocate_batch, len(chunk), roi)
        except Exception:
            return None  # the whole-batch job raises it where it belongs
        if arrays is None:
            return None
        jobs = [
            functools.partial(
                _parse_slice, fast, ordinal, a, chunk[a:b], arrays, roi
            )
            for a, b in _slice_bounds(len(chunk), self._num_parse_workers)
        ]

        def join(absent):
            batch = fast.finish_batch(arrays, absent)
            tracing.count("data.parse_batches_sliced")
            return batch

        return jobs, join

    def _maybe_seed_ring(self, entries) -> None:
        """Creates the shm ring the first time a (large) batch comes back
        inline: slot size must fit a real parsed batch, which is only
        known once one exists (sequence batches size to the batch max)."""
        if self._shm_ring is not None or self._shm_free_queue is None:
            return
        need = sum(
            _shm_align(v.nbytes)
            for _, desc, v in entries
            if v is not None and v.nbytes >= _SHM_MIN_SHIP_BYTES
        )
        if need == 0:
            return
        slot_bytes = need + need // 2 + (1 << 20)
        try:
            self._shm_ring = _ShmBatchRing(
                self._shm_free_queue, slot_bytes, self._max_in_flight() + 2
            )
        except OSError as err:
            _log.warning("shm ring unavailable (%s); using inline returns", err)
            self._shm_free_queue = None

    def _discard_worker_payload(self, payload) -> None:
        """Returns the ring slot of a parsed-but-never-consumed batch
        (consumer abandoned the iterator mid-epoch)."""
        if (
            isinstance(payload, tuple)
            and payload
            and payload[0] == "shm"
            and self._shm_ring is not None
        ):
            self._shm_ring.release(payload[1])

    def _rebuild_struct(self, payload) -> Optional[TensorSpecStruct]:
        """Parent-side batch reassembly for the process-return forms
        (inline / shm / dropped), folding any worker-side skip counters
        into this dataset's ParseStats and the worker's
        `data.parse_chunk` span into this process's recorder."""
        delta = payload[-1]
        tracing.adopt(delta.pop("span"))
        if delta:
            self._parse_stats.merge(delta)
        if payload[0] == "dropped":
            return None
        out = TensorSpecStruct()
        if payload[0] == "inline":
            for key, value in payload[1]:
                out[key] = value
            self._maybe_seed_ring(
                [(key, None, value) for key, value in payload[1]]
            )
            return out
        _, name, entries = payload[0], payload[1], payload[2]
        ring = self._shm_ring
        if ring is None or name not in ring.slots:
            raise RuntimeError(f"worker returned unknown shm slot {name!r}")
        shm = ring.slots[name]
        token = _ShmSlotToken(ring, name)
        for key, desc, value in entries:
            if desc is None:
                out[key] = value
                continue
            dtype, shape, offset = desc
            count = 1
            for dim in shape:
                count *= dim
            view = (
                np.frombuffer(shm.buf, dtype=dtype, count=count, offset=offset)
                .reshape(shape)
                .view(_ShmArray)
            )
            view._t2r_token = token
            out[key] = view
        return out

    def _get_process_pool(self) -> concurrent.futures.Executor:
        """Lazy, cached per-dataset worker pool: spawn cost (each worker
        re-imports jax, ~seconds) is paid once per RecordDataset, not per
        epoch/iterator."""
        if self._process_pool is None:
            import multiprocessing
            import pickle

            # Spawn, not fork: the parent typically holds an initialized
            # XLA backend whose internal threads/locks do not survive a
            # fork (deadlock risk).
            self._mp_context = multiprocessing.get_context("spawn")
            if default_parse_shm():
                # The free-slot queue exists up front (workers learn it at
                # init); the slots themselves are seeded after the first
                # batch returns and sizes are known (_maybe_seed_ring).
                self._shm_free_queue = self._mp_context.Queue()
            from tensor2robot_tpu.data.wire import default_decode_cache_mb

            self._process_pool = concurrent.futures.ProcessPoolExecutor(
                max_workers=self._num_parse_workers,
                mp_context=self._mp_context,
                initializer=_process_pool_init,
                initargs=(
                    pickle.dumps(self._specs),
                    self._parse_fast,
                    self._shm_free_queue,
                    default_decode_cache_mb()
                    // max(self._num_parse_workers, 1),
                ),
            )
        return self._process_pool

    def close(self) -> None:
        """Shuts down the cached process pool and shm ring (no-op for the
        thread backend)."""
        if self._process_pool is not None:
            self._process_pool.shutdown(wait=False, cancel_futures=True)
            self._process_pool = None
        if self._shm_ring is not None:
            self._shm_ring.close()
            self._shm_ring = None
        if self._shm_free_queue is not None:
            try:
                self._shm_free_queue.close()
            except Exception:
                pass
            self._shm_free_queue = None

    def __del__(self):  # best-effort; close() is the explicit path
        try:
            self.close()
        except Exception:
            pass

    def stats(self) -> Dict[str, int]:
        """Degradation counters: skip-mode quarantine (records_skipped,
        batches_degraded/dropped — T2R_PARSE_ON_ERROR=skip) plus the
        fast parser's fallback count. Thread-backend and parent-side
        numbers are live; process-worker skips AND fallbacks fold in as
        their batches arrive (the aggregate ParseStats.fast_fallbacks
        plus the parent's own fast parser)."""
        out = self._parse_stats.snapshot()
        fast = self._fast_state.parser
        out["fast_fallbacks"] += fast.fallbacks if fast is not None else 0
        out["parse_workers"] = self._num_parse_workers
        return out

    def __iter__(self) -> Iterator[TensorSpecStruct]:
        if self._num_parse_workers > 0 and self._parse_backend == "process":
            batches: Iterator[Optional[TensorSpecStruct]] = map(
                self._rebuild_struct,
                _ParallelBatcher(
                    self._chunks(),
                    _process_parse_chunk,
                    num_workers=self._num_parse_workers,
                    max_in_flight=self._max_in_flight(),
                    pool=self._get_process_pool(),
                    on_discard=self._discard_worker_payload,
                ),
            )
        elif self._num_parse_workers > 0:
            in_flight = self._max_in_flight()
            # As many sets of arrays as batches can be on their way at
            # once: in the pool, in the prefetch queue and before it, and
            # the two or three the consumer places on the device.
            buffers = _BatchArrays(in_flight + self._prefetch_depth + 4)
            batches = iter(
                _ParallelBatcher(
                    self._chunks(),
                    self._parse_chunk,
                    num_workers=self._num_parse_workers,
                    max_in_flight=in_flight,
                    slice_fn=functools.partial(self._slice_jobs, buffers),
                )
            )
        else:
            batches = map(self._parse_chunk, self._chunks())
        batches = _delivered(batches)
        if self._prefetch_depth > 0:
            return iter(_Prefetcher(batches, self._prefetch_depth))
        return batches


class GeneratorDataset:
    """Batches from a python generator of per-example numpy dicts
    (reference GeneratorInputGenerator)."""

    def __init__(
        self,
        generator_fn: Callable[[], Iterator[Mapping[str, np.ndarray]]],
        batch_size: int,
        prefetch_depth: int = 1,
    ):
        self._generator_fn = generator_fn
        self._batch_size = batch_size
        self._prefetch_depth = prefetch_depth

    def __iter__(self) -> Iterator[TensorSpecStruct]:
        def batches():
            source = self._generator_fn()
            while True:
                rows = list(itertools.islice(source, self._batch_size))
                if len(rows) < self._batch_size:
                    return
                out = TensorSpecStruct()
                for key in rows[0].keys():
                    out[key] = np.stack([np.asarray(r[key]) for r in rows])
                yield out

        if self._prefetch_depth > 0:
            return iter(_Prefetcher(batches(), self._prefetch_depth))
        return batches()


def weighted_interleave(
    datasets: Sequence[RecordDataset],
    weights: Sequence[float],
    seed: Optional[int] = None,
) -> Iterator[TensorSpecStruct]:
    """Samples batches from datasets proportionally to weights (reference
    WeightedRecordInputGenerator / sample_from_datasets)."""
    rng = random.Random(seed)
    iterators = [iter(d) for d in datasets]
    total = float(sum(weights))
    probs = [w / total for w in weights]
    while iterators:
        idx = rng.choices(range(len(iterators)), weights=probs, k=1)[0]
        try:
            yield next(iterators[idx])
        except StopIteration:
            del iterators[idx], probs[idx]
            if probs:
                s = sum(probs)
                probs = [p / s for p in probs]
