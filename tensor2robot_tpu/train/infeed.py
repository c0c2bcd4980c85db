"""Device infeed: double-buffered transfers + multi-step batch stacking.

The reference hid host->device transfer behind TPUEstimator's infeed queue
(per-host infeed, utils/tfdata.py:38-61) and amortized host round-trips with
TPUConfig.iterations_per_loop (models/abstract_model.py:76-77). The JAX
equivalents here:

  * `device_prefetch` keeps `depth` batches resident on the mesh ahead of
    the consumer. jax.device_put is asynchronous, so enqueueing batch N+1's
    transfer before step N is dispatched overlaps PCIe/ICI transfer with
    compute — the double-buffering the round-1 trainer lacked. device_put
    returns at the enqueue; a watcher thread closes an `infeed.transfer`
    span when the batch's last shard is resident, and `late_at_dispatch`
    says whether a step was dispatched before its batch was.
  * `stack_batches` concatenates K host batches along a new leading axis for
    the lax.scan multi-step train loop (iterations_per_loop equivalent):
    one host dispatch drives K device steps.
"""

from __future__ import annotations

import collections
import itertools
import logging
import queue
import threading
from typing import Callable, Iterator, Optional, Sequence, Tuple

import jax
import numpy as np

from tensor2robot_tpu import flags
from tensor2robot_tpu.parallel import mesh as mesh_lib
from tensor2robot_tpu.utils import tracing


def resolve_depth(depth: Optional[int] = None) -> int:
    """Prefetch depth: an explicit argument wins; None reads the central
    T2R_INFEED_DEPTH gate (default 2 = classic double buffering)."""
    if depth is not None:
        return depth
    return flags.get_int("T2R_INFEED_DEPTH")


def _tree_bytes(tree) -> int:
    return sum(
        getattr(leaf, "nbytes", 0) for leaf in jax.tree_util.tree_leaves(tree)
    )


def _await_arrival(placed) -> Tuple[int, Optional[int]]:
    """Blocks until every shard of every leaf of `placed` is resident.

    Returns the number of devices the tree lies over and the id of the
    device that was waited for last (None where everything had arrived):
    devices are gone through in turn, so the ones after it were ready by
    the time its last shard was.
    """
    by_device = collections.defaultdict(list)
    for leaf in jax.tree_util.tree_leaves(placed):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is not None:
            for shard in shards:
                by_device[shard.device].append(shard.data)
        elif hasattr(leaf, "block_until_ready"):
            by_device[None].append(leaf)
    last = None
    for device, buffers in by_device.items():
        for buffer in buffers:
            if not buffer.is_ready():
                buffer.block_until_ready()
                last = device
    return len(by_device), getattr(last, "id", None)


class _Arrivals:
    """Closes a `<name>.transfer` span for every placed batch when its last
    shard has arrived, on a thread of its own: the consumer never waits
    for an arrival here. A batch is held until it has arrived and no
    longer (its device memory is the step's to free)."""

    def __init__(self, name: str):
        self._name = name
        self._jobs: "queue.SimpleQueue" = queue.SimpleQueue()
        self._thread: Optional[threading.Thread] = None

    def watch(self, placed, ordinal, nbytes: int, start_ns: int) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name=self._name + ".transfer", daemon=True
            )
            self._thread.start()
        self._jobs.put((placed, ordinal, nbytes, start_ns))

    def close(self) -> None:
        """Ends the thread once the batches already handed over have
        arrived."""
        if self._thread is not None:
            self._jobs.put(None)

    def _run(self) -> None:
        while True:
            job = self._jobs.get()
            if job is None:
                return
            placed, ordinal, nbytes, start_ns = job
            del job
            counts = {"bytes": nbytes}
            try:
                counts["devices"], last = _await_arrival(placed)
                if last is not None:
                    counts["last_device"] = last
            except Exception:  # noqa: BLE001
                # The step that takes the batch raises on the train thread;
                # this one has to go on letting batches go.
                logging.warning(
                    "%s.transfer: waiting for batch %s failed", self._name,
                    ordinal, exc_info=True,
                )
            del placed
            tracing.since(
                self._name + ".transfer", start_ns, ordinal=ordinal, **counts
            )


def late_at_dispatch(batch) -> int:
    """1 where a leaf of the device batch had not arrived when its step is
    dispatched (`is_ready()`, no wait), else 0; counted in
    `infeed.late_at_dispatch` of `infeed.dispatched`."""
    late = int(not all(
        leaf.is_ready() for leaf in jax.tree_util.tree_leaves(batch)
        if hasattr(leaf, "is_ready")
    ))
    tracing.count("infeed.dispatched")
    tracing.count("infeed.late_at_dispatch", late)
    return late


def device_prefetch(
    batches: Iterator,
    shard_fn: Callable,
    depth: int = 2,
    ordinals: Optional[Iterator[int]] = None,
    name: str = "infeed",
) -> Iterator:
    """Yields device-resident batches, keeping `depth` transfers in flight.

    `shard_fn` is typically CompiledModel.shard_batch. With depth=2 the
    transfer of batch N+1 is enqueued before the consumer dispatches step N;
    because device_put is async the copy runs while the device computes.

    Each item is fetched under a `<name>.wait` span (the consumer waits for
    the host pipeline) and placed under a `<name>.h2d` span that counts the
    `bytes` handed to `shard_fn`; `<name>.transfer` opens with the latter
    and closes, on a watcher thread that ends with this iterator, when the
    placed item's last shard is resident (`bytes`, `devices`, and
    `last_device` where a shard was waited for). `ordinals` gives each
    item's ordinal (default 0, 1, ...): the train loop passes its global
    step, which is the ordinal the dataset gave the batch.
    """
    buf: collections.deque = collections.deque()
    it = iter(batches)
    ordinals = itertools.count() if ordinals is None else ordinals
    arrivals = _Arrivals(name)

    exhausted = False

    def fetch() -> bool:
        nonlocal exhausted
        if exhausted:
            return False
        ordinal = next(ordinals, None)
        try:
            with tracing.span(name + ".wait", ordinal=ordinal):
                item = next(it)
        except StopIteration:
            exhausted = True
            return False
        nbytes = _tree_bytes(item)
        with tracing.span(name + ".h2d", ordinal=ordinal, bytes=nbytes) as put:
            buf.append(shard_fn(item))
            # Letting go of the host batch (unmapping a batch's worth of
            # pages, where the transfer is through with it) is part of
            # handing it over: inside the span, not at this frame's end.
            del item
            arrivals.watch(buf[-1], ordinal, nbytes, put.start_ns)
        return True

    try:
        while len(buf) < depth and fetch():
            pass
        while buf:
            fetch()
            # Straight from the buffer: a local here would keep the batch
            # alive, and its device memory held, until the consumer comes
            # back.
            yield buf.popleft()
    finally:
        arrivals.close()


def stack_batches(batches: Sequence) -> object:
    """Stacks K host batches leaf-wise along a new leading axis [K, B, ...].

    Each leaf writes straight into its slot of ONE preallocated output
    array — the earlier np.asarray-then-np.stack form materialized every
    leaf twice (a full extra copy of the whole chunk per dispatch, paid
    on the host hot path between device steps).
    """

    def stack(*leaves):
        # np.asarray is a no-copy view for ndarray leaves; the copy this
        # saves is np.stack's gather into a second buffer. Shape/dtype
        # strictness matches np.stack: mismatched shapes raise (instead
        # of broadcasting a short tail batch across the slot) and dtypes
        # promote to the common type (instead of pinning the first
        # leaf's and silently wrapping).
        arrays = [np.asarray(leaf) for leaf in leaves]
        first = arrays[0]
        for arr in arrays[1:]:
            if arr.shape != first.shape:
                raise ValueError(
                    "all input batches must have the same leaf shapes; "
                    f"got {arr.shape} vs {first.shape}"
                )
        out = np.empty(
            (len(arrays),) + first.shape, np.result_type(*arrays)
        )
        for i, arr in enumerate(arrays):
            out[i] = arr
        return out

    return jax.tree_util.tree_map(stack, *batches)


def shard_stacked_batch(stacked, mesh):
    """Places a [K, B, ...] stacked batch: scan axis replicated, batch axis
    (dim 1) split over data×fsdp; non-divisible leaves replicated."""
    sharding = mesh_lib.stacked_batch_sharding(mesh)
    replicated = mesh_lib.replicated(mesh)
    divisor = mesh.shape[mesh_lib.DATA_AXIS] * mesh.shape[mesh_lib.FSDP_AXIS]

    def put(leaf):
        shape = getattr(leaf, "shape", ())
        if len(shape) >= 2 and shape[1] % divisor == 0:
            return jax.device_put(leaf, sharding)
        return jax.device_put(leaf, replicated)

    return jax.tree_util.tree_map(put, stacked)


