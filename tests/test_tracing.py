"""The host path's flight recorder (utils/tracing.py) and the spans the
learner's host path writes into it: dataset, infeed, train loop."""

import concurrent.futures
import gc
import json
import os
import sys
import threading
import time
import weakref

import jax
import numpy as np
import pytest

from tensor2robot_tpu.data import tfrecord
from tensor2robot_tpu.data.dataset import RecordDataset
from tensor2robot_tpu.data.encoder import encode_example
from tensor2robot_tpu.specs import ExtendedTensorSpec, TensorSpecStruct
from tensor2robot_tpu.train import infeed, train_eval
from tensor2robot_tpu.utils import tracing
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

LOOP_SPANS = (
    "infeed.wait", "infeed.h2d", "train.hooks", "train.dispatch",
    "train.log", "train.checkpoint",
)


def by_name(spans, name):
    return [s for s in spans if s["name"] == name]


class TestRecorder:
    def test_nesting_and_parent_on_one_thread(self):
        recorder = tracing.Recorder()
        with recorder.span("outer") as outer:
            with recorder.span("inner"):
                pass
            with recorder.span("inner"):
                pass
        inner_a, inner_b, closed = recorder.snapshot()["spans"]
        assert closed["name"] == "outer" and closed["parent"] is None
        assert inner_a["parent"] == inner_b["parent"] == outer.ident
        assert closed["thread"] == threading.get_ident()
        assert (
            closed["start_ns"] <= inner_a["start_ns"] <= inner_a["end_ns"]
            <= inner_b["start_ns"] <= inner_b["end_ns"] <= closed["end_ns"]
        )
        # The clock is the epoch's, the one the profiler dates a session by.
        assert abs(closed["end_ns"] - time.time_ns()) < 60e9

    def test_ordinal_is_inherited_and_counts_ride_on_the_span(self):
        recorder = tracing.Recorder()
        with recorder.span("batch", ordinal=7, records=4) as batch:
            with recorder.span("part"):
                recorder.add(images=1, decode_ns=10)
                recorder.add(images=1, decode_ns=5)
            with recorder.span("other", ordinal=9):
                pass
            batch.add(bytes=100)
        part, other, outer = recorder.snapshot()["spans"]
        assert part["ordinal"] == 7 and other["ordinal"] == 9
        assert part["counts"] == {"images": 2, "decode_ns": 15}
        assert outer["counts"] == {"records": 4, "bytes": 100}

    def test_add_outside_a_span_does_nothing(self):
        recorder = tracing.Recorder()
        recorder.add(images=1)
        assert recorder.snapshot() == {"spans": [], "counters": {}}

    def test_a_span_closes_when_its_body_raises(self):
        recorder = tracing.Recorder()
        with pytest.raises(KeyError):
            with recorder.span("outer"):
                with recorder.span("inner"):
                    raise KeyError("x")
        assert [s["name"] for s in recorder.snapshot()["spans"]] == [
            "inner", "outer"
        ]
        with recorder.span("next"):
            pass
        assert recorder.snapshot()["spans"][-1]["parent"] is None

    def test_spans_from_pool_threads_keep_their_own_nesting(self):
        recorder = tracing.Recorder()
        barrier = threading.Barrier(4, timeout=30)

        def work(ordinal):
            with recorder.span("job", ordinal=ordinal):
                barrier.wait()  # all four open at once, one a thread
                with recorder.span("step"):
                    pass

        with recorder.span("submit"):
            with concurrent.futures.ThreadPoolExecutor(4) as pool:
                for future in [pool.submit(work, i) for i in range(4)]:
                    future.result(timeout=30)
        spans = recorder.snapshot()["spans"]
        jobs, steps = by_name(spans, "job"), by_name(spans, "step")
        assert len({s["thread"] for s in jobs}) == 4
        assert threading.get_ident() not in {s["thread"] for s in jobs}
        # The enclosing span is the thread's own, never the submitter's.
        assert all(s["parent"] is None for s in jobs)
        job_of = {s["id"]: s for s in jobs}
        for step in steps:
            job = job_of[step["parent"]]
            assert job["thread"] == step["thread"]
            assert job["ordinal"] == step["ordinal"]

    def test_the_ring_stays_at_its_capacity_after_a_million_spans(self):
        recorder = tracing.Recorder()
        for _ in range(10**6):
            with recorder.span("s"):
                pass
        snapshot = recorder.snapshot()
        assert len(snapshot["spans"]) == tracing.CAPACITY
        # Older spans fell out, the newest are kept; the counters saw all.
        assert snapshot["spans"][-1]["id"] == 10**6
        assert snapshot["spans"][0]["id"] == 10**6 - tracing.CAPACITY + 1
        assert snapshot["counters"]["s.n"] == 10**6

    def test_counters_are_cumulative_and_thread_safe(self):
        recorder = tracing.Recorder()
        threads = (os.cpu_count() or 1) + 4
        each = 5000

        def work():
            for _ in range(each):
                recorder.count("gets")
                recorder.count("bytes", 3)
                with recorder.span("w"):
                    pass

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            with concurrent.futures.ThreadPoolExecutor(threads) as pool:
                for future in [pool.submit(work) for _ in range(threads)]:
                    future.result(timeout=120)
        finally:
            sys.setswitchinterval(interval)
        counters = recorder.counters()
        assert counters["gets"] == threads * each
        assert counters["bytes"] == 3 * threads * each
        assert counters["w.n"] == threads * each
        recorder.count("gets", 2)
        assert recorder.counters()["gets"] == threads * each + 2

    def test_a_closed_span_adds_its_duration_to_the_counters(self):
        recorder = tracing.Recorder()
        for _ in range(3):
            with recorder.span("wait"):
                time.sleep(0.002)
        snapshot = recorder.snapshot()
        total = sum(s["end_ns"] - s["start_ns"] for s in snapshot["spans"])
        assert snapshot["counters"] == {"wait.ns": total, "wait.n": 3}
        assert total >= 3 * 2e6

    def test_snapshot_since_and_adopting_a_span_of_another_process(self):
        recorder, other = tracing.Recorder(), tracing.Recorder()
        with recorder.span("early"):
            pass
        mark = time.time_ns()
        with other.span("data.parse_chunk", ordinal=3, records=4) as parse:
            other.add(decode_ns=5)
        recorder.adopt(dict(parse.as_dict(), thread=-1234))
        (adopted,) = recorder.snapshot(since_ns=mark)["spans"]
        assert adopted["thread"] == -1234 and adopted["ordinal"] == 3
        assert adopted["counts"] == {"records": 4, "decode_ns": 5}
        assert adopted["parent"] is None
        assert (adopted["start_ns"], adopted["end_ns"]) == (
            parse.start_ns, parse.end_ns
        )
        assert recorder.counters()["data.parse_chunk.n"] == 1
        json.dumps(recorder.snapshot())  # what spans.jsonl writes


    def test_since_closes_on_the_calling_thread_what_another_opened(self):
        recorder = tracing.Recorder()
        start = time.time_ns()
        with recorder.span("enclosing", ordinal=1):
            worker = threading.Thread(
                target=lambda: recorder.since("flight", start, ordinal=4, bytes=9)
            )
            worker.start()
            worker.join(timeout=30)
        flight, enclosing = recorder.snapshot()["spans"]
        assert flight["name"] == "flight" and flight["start_ns"] == start
        assert start <= flight["end_ns"] <= enclosing["end_ns"]
        assert flight["thread"] == worker.ident != enclosing["thread"]
        # No parent and no inherited ordinal: it is not the thread's nesting.
        assert flight["parent"] is None and flight["ordinal"] == 4
        assert flight["counts"] == {"bytes": 9}
        assert recorder.counters()["flight.n"] == 1


def write_jpeg_records(tmp_path, n=24, first_chunk=4):
    spec = TensorSpecStruct()
    spec["img"] = ExtendedTensorSpec(
        shape=(16, 24, 3), dtype=np.uint8, name="img", data_format="jpeg"
    )
    spec["y"] = ExtendedTensorSpec(shape=(), dtype=np.int64, name="y")
    rng = np.random.RandomState(0)
    records = [
        encode_example(spec, {
            "img": rng.randint(0, 255, (16, 24, 3)).astype(np.uint8),
            "y": np.asarray(i, np.int64),
        })
        for i in range(n)
    ]
    path = str(tmp_path / "imgs.tfrecord")
    tfrecord.write_tfrecords(path, records)
    return spec, path, sum(len(r) for r in records[:first_chunk])


class TestHostInputSpans:
    @pytest.mark.parametrize("workers,backend,batch,slices", [
        (0, "thread", 4, None), (4, "thread", 4, [(0, 4)]),
        (2, "process", 4, None),
        # 40 records over 3 workers: slices of 16, the last one short.
        (3, "thread", 40, [(0, 16), (16, 16), (32, 8)]),
    ])
    def test_one_batch_one_ordinal_from_read_to_h2d(
        self, tmp_path, workers, backend, batch, slices
    ):
        """`slices` is the (first row, records) of each `data.parse_chunk`
        span of a batch, None where the batch is one whole-batch job."""
        n = 6 * batch
        spec, path, first_chunk_bytes = write_jpeg_records(tmp_path, n, batch)
        dataset = RecordDataset(
            specs=spec, file_patterns=path, batch_size=batch, mode="eval",
            num_parse_workers=workers, parse_backend=backend,
        )
        mark = time.time_ns()
        before = tracing.counters()
        try:
            batches = list(
                infeed.device_prefetch(iter(dataset), lambda b: b, depth=2)
            )
        finally:
            dataset.close()
        snapshot = tracing.snapshot(since_ns=mark)
        spans = snapshot["spans"]
        assert len(batches) == 6
        reads = {s["ordinal"]: s for s in by_name(spans, "data.read_chunk")}
        parses = {}
        for s in by_name(spans, "data.parse_chunk"):
            parses.setdefault(s["ordinal"], []).append(s)
        waits = {s["ordinal"]: s for s in by_name(spans, "infeed.wait")}
        puts = {s["ordinal"]: s for s in by_name(spans, "infeed.h2d")}
        assert sorted(parses) == sorted(puts) == list(range(6))
        # The read after the last batch finds the file at its end, and so
        # does the consumer's last wait.
        assert sorted(reads) == sorted(waits) == list(range(7))
        assert reads[6]["counts"]["records"] == 0
        assert reads[0]["counts"] == {
            "records": batch, "bytes": first_chunk_bytes
        }
        for ordinal, got in enumerate(batches):
            # Eval mode reads in order: batch i holds records
            # batch * i .. batch * (i + 1) - 1.
            assert list(got["y"]) == list(
                range(batch * ordinal, batch * (ordinal + 1))
            )
            read, wait, put = reads[ordinal], waits[ordinal], puts[ordinal]
            assert put["counts"]["bytes"] == sum(
                leaf.nbytes for leaf in (got["img"], got["y"])
            )
            # The slices of an ordinal cover its records once each.
            covered = sorted(
                (s["counts"].get("first_row", 0), s["counts"]["records"])
                for s in parses[ordinal]
            )
            assert covered == (slices or [(0, batch)])
            for parse in parses[ordinal]:
                assert ("first_row" in parse["counts"]) == (slices is not None)
                assert parse["counts"]["images"] == parse["counts"]["records"]
                duration = parse["end_ns"] - parse["start_ns"]
                assert 0 < parse["counts"]["decode_ns"] <= duration
                # One batch's way through the pipeline, on one clock.
                assert read["end_ns"] <= parse["start_ns"]
                assert parse["end_ns"] <= wait["end_ns"] <= put["start_ns"]
        parse_threads = {
            s["thread"] for group in parses.values() for s in group
        }
        if backend == "process":
            # A worker's span comes home with its batch; the negated pid
            # stands for the worker.
            assert all(t < 0 and t != -os.getpid() for t in parse_threads)
        elif workers:
            assert threading.get_ident() not in parse_threads

        def since(name):
            return snapshot["counters"].get(name, 0) - before.get(name, 0)

        gets, empty = since("data.prefetch_gets"), since("data.prefetch_empty")
        assert gets == 7 and 0 <= empty <= gets
        # Every batch delivered is counted, and as sliced where it was.
        assert since("data.parse_batches") == 6
        assert since("data.parse_batches_sliced") == (6 if slices else 0)
        assert dataset.stats()["parse_workers"] == workers

    def test_the_fallback_to_the_oracle_is_counted_on_the_span(self, tmp_path):
        from tensor2robot_tpu.data.dataset import _FastParseState, _traced_parse
        from tensor2robot_tpu.data.parser import SpecParser

        spec, _, _ = write_jpeg_records(tmp_path, n=4)
        state = _FastParseState(spec, enabled=True)
        with pytest.raises(Exception):
            _traced_parse(state, SpecParser(spec), (5, [b"\x00garbage"]), None)
        span = [
            s for s in tracing.snapshot()["spans"]
            if s["name"] == "data.parse_chunk" and s["ordinal"] == 5
        ][-1]
        assert span["counts"] == {"records": 1, "fast_fallback": 1}

    def test_device_prefetch_takes_its_ordinals_and_its_name(self):
        mark = time.time_ns()
        out = list(infeed.device_prefetch(
            iter("abc"), str.upper, depth=2, ordinals=iter([10, 26, 42, 58]),
            name="eval_infeed",
        ))
        assert out == ["A", "B", "C"]
        spans = tracing.snapshot(since_ns=mark)["spans"]
        mine = threading.get_ident()
        waits = [
            s["ordinal"] for s in by_name(spans, "eval_infeed.wait")
            if s["thread"] == mine
        ]
        puts = [
            s["ordinal"] for s in by_name(spans, "eval_infeed.h2d")
            if s["thread"] == mine
        ]
        assert waits == [10, 26, 42, 58] and puts == [10, 26, 42]


def _uncovered_share(spans, first_ordinal):
    """Share of the train thread's loop time, from the dispatch of the batch
    `first_ordinal` on, that lies between its top-level spans and under
    none of them."""
    (first,) = [
        s for s in by_name(spans, "train.dispatch")
        if s["ordinal"] == first_ordinal
    ]
    loop = sorted(
        (
            s for s in spans
            if s["thread"] == first["thread"] and s["parent"] is None
            and s["start_ns"] >= first["start_ns"]
        ),
        key=lambda s: s["start_ns"],
    )
    gaps = sum(
        max(0, b["start_ns"] - a["end_ns"]) for a, b in zip(loop, loop[1:])
    )
    return gaps / (loop[-1]["end_ns"] - loop[0]["start_ns"]), loop


class TestTrainLoopSpans:
    @pytest.mark.parametrize("iterations_per_loop", [1, 16])
    def test_the_loop_body_is_covered_and_the_log_record_says_where_time_went(
        self, tmp_path, iterations_per_loop
    ):
        model_dir = str(tmp_path / "run")
        mark = time.time_ns()
        train_eval.train_eval_model(
            t2r_model=MockT2RModel(device_type="cpu"),
            input_generator_train=MockInputGenerator(batch_size=8),
            input_generator_eval=MockInputGenerator(batch_size=8, seed=1),
            model_dir=model_dir,
            max_train_steps=96,
            eval_steps=2,
            save_checkpoints_steps=48,
            log_every_steps=16,
            iterations_per_loop=iterations_per_loop,
        )
        spans = tracing.snapshot(since_ns=mark)["spans"]
        # Past the first dispatches, which compile.
        share, loop = _uncovered_share(spans, first_ordinal=32)
        assert share < 0.05, share
        names = {s["name"] for s in loop}
        assert names == set(LOOP_SPANS)

        dispatches = by_name(spans, "train.dispatch")
        waits = by_name(spans, "infeed.wait")
        if iterations_per_loop == 1:
            assert [s["ordinal"] for s in dispatches] == list(range(96))
        else:
            # A chunk's ordinal is that of its first batch.
            assert [s["ordinal"] for s in dispatches] == list(range(0, 96, 16))
        assert {s["ordinal"] for s in dispatches} <= {s["ordinal"] for s in waits}

        # The evals of the two checkpoints feed under a name of their own,
        # inside the checkpoint's span.
        checkpoints = {s["id"]: s for s in by_name(spans, "train.checkpoint")}
        assert [s["ordinal"] for s in checkpoints.values()] == [47, 95]
        eval_waits = by_name(spans, "eval_infeed.wait")
        assert eval_waits and all(s["parent"] in checkpoints for s in eval_waits)

        (init,) = by_name(spans, "train.init_state")
        children = [s for s in spans if s["parent"] == init["id"]]
        assert [s["name"] for s in children] == [
            "train.init_state.preprocess", "train.init_state.model_init"
        ]

        with open(os.path.join(model_dir, "train", "metrics.jsonl")) as f:
            records = [json.loads(line) for line in f]
        assert [r["step"] for r in records] == [16, 32, 48, 64, 80, 96]
        for record in records:
            for key in (
                "infeed/wait_ms_per_step", "infeed/h2d_ms_per_step",
                "dispatch_ms_per_step", "input/parse_ms_per_batch",
                "input/prefetch_empty_share", "checkpoint/stall_ms",
            ):
                assert record[key] >= 0, key
            assert record["dispatch_ms_per_step"] > 0
        # The save at step 48 stalls the loop after that step's record is
        # written: the next record carries it.
        assert records[3]["checkpoint/stall_ms"] > 0
        assert records[1]["checkpoint/stall_ms"] == 0

    def test_the_log_record_is_a_difference_of_two_counter_reads(self):
        before = {"infeed.wait.ns": 5_000_000, "data.prefetch_gets": 10}
        after = {
            "infeed.wait.ns": 25_000_000, "infeed.h2d.ns": 4_000_000,
            "train.dispatch.ns": 2_000_000, "data.parse_chunk.ns": 90_000_000,
            "data.parse_chunk.n": 36, "data.parse_batches": 3,
            "data.prefetch_gets": 14,
            "data.prefetch_empty": 1, "train.checkpoint.ns": 7_000_000,
            "jit.trace.ns": 300_000_000, "jit.lower.ns": 200_000_000,
            "jit.compile.ns": 1_000_000_000, "jit.compile.n": 2,
        }
        assert train_eval._host_path_record(before, after, steps=4) == {
            "infeed/wait_ms_per_step": 5.0,
            "infeed/h2d_ms_per_step": 1.0,
            "dispatch_ms_per_step": 0.5,
            "input/parse_ms_per_batch": 30.0,
            "input/prefetch_empty_share": 0.25,
            "checkpoint/stall_ms": 7.0,
            "compile/programs_built": 2.0,
            "compile/seconds": 1.5,
        }


class _Placed:
    """What a fake `shard_fn` returns: one leaf that is resident once
    `arrive()` has been called, and says so as a `jax.Array` would."""

    def __init__(self):
        self._arrived = threading.Event()

    def arrive(self):
        self._arrived.set()

    def is_ready(self):
        return self._arrived.is_set()

    def block_until_ready(self):
        assert self._arrived.wait(timeout=30)
        return self


def _wait_for(predicate, seconds=30.0):
    deadline = time.monotonic() + seconds
    while not predicate() and time.monotonic() < deadline:
        time.sleep(0.002)
    return predicate()


def _transfers(mark, name="infeed"):
    return by_name(tracing.snapshot(since_ns=mark)["spans"], name + ".transfer")


def _watchers(name="infeed"):
    return [
        t for t in threading.enumerate() if t.name == name + ".transfer"
    ]


class TestTransferSpans:
    def test_a_transfer_closes_at_the_arrival_and_not_at_the_enqueue(self):
        mark = time.time_ns()
        placed = []

        def shard_fn(item):
            placed.append(_Placed())
            return placed[-1]

        batches = [np.zeros((4, 3), np.uint8), np.ones((4, 3), np.uint8)]
        it = infeed.device_prefetch(
            iter(batches), shard_fn, depth=2, ordinals=iter([7, 8, 9])
        )
        first = next(it)  # both are enqueued; the consumer was not held up
        assert first is placed[0] and len(placed) == 2
        mine = threading.get_ident()
        puts = [
            s for s in by_name(tracing.snapshot(since_ns=mark)["spans"], "infeed.h2d")
            if s["thread"] == mine
        ]
        assert [s["ordinal"] for s in puts] == [7, 8]
        # infeed.h2d is what it was: the enqueue, with the bytes handed over.
        assert all(s["counts"] == {"bytes": 12} for s in puts)
        time.sleep(0.05)
        assert _transfers(mark) == []  # nothing has arrived yet

        placed[0].arrive()
        assert _wait_for(lambda: len(_transfers(mark)) == 1)
        (transfer,) = _transfers(mark)
        assert transfer["ordinal"] == 7
        assert transfer["counts"] == {"bytes": 12, "devices": 1}
        assert transfer["thread"] != mine and transfer["parent"] is None
        # It opens where the enqueue opens and outlasts it.
        assert transfer["start_ns"] == puts[0]["start_ns"]
        assert transfer["end_ns"] >= puts[0]["end_ns"] + 40e6
        placed[1].arrive()
        assert _wait_for(lambda: len(_transfers(mark)) == 2)
        assert [s["ordinal"] for s in _transfers(mark)] == [7, 8]
        assert list(it) == [placed[1]]

    def test_the_watcher_lets_a_batch_go_when_it_has_arrived(self):
        mark = time.time_ns()
        it = infeed.device_prefetch(
            iter([np.zeros(3)]), lambda item: _Placed(), depth=1
        )
        batch = next(it)
        held = weakref.ref(batch)
        batch.arrive()
        assert _wait_for(lambda: len(_transfers(mark)) == 1)
        del batch
        gc.collect()
        assert held() is None

    def test_the_watcher_ends_with_the_iterator(self):
        before = set(_watchers())
        placed = []

        def shard_fn(item):
            placed.append(_Placed())
            return placed[-1]

        it = infeed.device_prefetch(
            iter([np.zeros(3), np.zeros(3), np.zeros(3)]), shard_fn, depth=1
        )
        next(it)
        (watcher,) = set(_watchers()) - before
        assert watcher.daemon and watcher.is_alive()
        it.close()  # the consumer left early, as the train loop's break does
        # It ends once what was handed to it has arrived, and not before.
        watcher.join(timeout=0.05)
        assert watcher.is_alive() and len(placed) == 2
        for batch in placed:
            batch.arrive()
        watcher.join(timeout=30)
        assert not watcher.is_alive()

        drained = infeed.device_prefetch(
            iter([np.zeros(3)]), lambda item: item, depth=2, name="drained"
        )
        assert len(list(drained)) == 1
        assert _wait_for(lambda: not _watchers("drained"))

    def test_a_real_batch_says_how_many_devices_it_lies_over(self):
        from tensor2robot_tpu.parallel import mesh as mesh_lib

        mark = time.time_ns()
        mesh = mesh_lib.make_mesh(devices=jax.devices()[:4])
        batch = {"x": np.arange(32, dtype=np.float32).reshape(8, 4)}
        (placed,) = infeed.device_prefetch(
            iter([batch]), lambda b: mesh_lib.shard_batch(b, mesh), depth=2,
            name="mesh_infeed",
        )
        assert _wait_for(lambda: len(_transfers(mark, "mesh_infeed")) == 1)
        (transfer,) = _transfers(mark, "mesh_infeed")
        assert transfer["counts"]["devices"] == 4
        assert transfer["counts"]["bytes"] == 128
        assert transfer["counts"].get("last_device", 0) in {
            d.id for d in mesh.devices.flat
        }
        np.testing.assert_array_equal(np.asarray(placed["x"]), batch["x"])

    @pytest.mark.parametrize("ready,late", [(True, 0), (False, 1)])
    def test_late_at_dispatch_counts_a_batch_that_is_not_there(self, ready, late):
        before = tracing.counters()
        leaf = _Placed()
        if ready:
            leaf.arrive()
        batch = {"features": {"image": leaf}, "host_side": np.zeros(2)}
        assert infeed.late_at_dispatch(batch) == late
        after = tracing.counters()
        assert after["infeed.dispatched"] - before.get("infeed.dispatched", 0) == 1
        assert (
            after["infeed.late_at_dispatch"]
            - before.get("infeed.late_at_dispatch", 0)
        ) == late

    @pytest.mark.parametrize("iterations_per_loop", [1, 4])
    def test_both_train_loops_count_their_dispatches(
        self, tmp_path, iterations_per_loop
    ):
        mark = time.time_ns()
        before = tracing.counters()
        train_eval.train_eval_model(
            t2r_model=MockT2RModel(device_type="cpu"),
            input_generator_train=MockInputGenerator(batch_size=8),
            model_dir=str(tmp_path / "run"),
            max_train_steps=8,
            save_checkpoints_steps=8,
            iterations_per_loop=iterations_per_loop,
        )
        after = tracing.counters()
        spans = tracing.snapshot(since_ns=mark)["spans"]
        dispatches = by_name(spans, "train.dispatch")
        assert len(dispatches) == 8 // iterations_per_loop
        assert all(s["counts"]["late"] in (0, 1) for s in dispatches)
        assert (
            after["infeed.dispatched"] - before.get("infeed.dispatched", 0)
            == len(dispatches)
        )
        assert (
            after["infeed.late_at_dispatch"]
            - before.get("infeed.late_at_dispatch", 0)
        ) == sum(s["counts"]["late"] for s in dispatches)
        # Every batch the loop took has a transfer of its ordinal, laid
        # over the trainer's mesh, that opened with its enqueue.
        assert _wait_for(lambda: len(_transfers(mark)) >= len(dispatches))
        puts = {
            s["ordinal"]: s for s in by_name(spans, "infeed.h2d")
        }
        for transfer in _transfers(mark):
            assert transfer["start_ns"] == puts[transfer["ordinal"]]["start_ns"]
            assert transfer["counts"]["devices"] == len(jax.devices())
            assert transfer["counts"]["bytes"] == (
                puts[transfer["ordinal"]]["counts"]["bytes"]
            )
        assert {s["ordinal"] for s in dispatches} <= {
            s["ordinal"] for s in _transfers(mark)
        }
