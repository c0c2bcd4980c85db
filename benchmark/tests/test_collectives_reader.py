"""`collectives.exposed_ms_per_step` over a synthetic xplane of two device
planes: one all-reduce and one `all-reduce-start` / `-done` pair on each
synchronous line, read as milliseconds a step, mean over the planes; None
where the trace holds no collective."""

import types

import pytest

import manifest
import xplane
from test_xplane import US, _field, _plane


def _device(index, collectives):
    ops = [(1, 100 * US, 200 * US)]                 # convolution 100..300
    if collectives:
        ops += [
            (2, 300 * US, (40 + 20 * index) * US),  # all-reduce: 40 and 60 us
            (3, 400 * US, 2 * US),                  # its asynchronous form:
            (1, 402 * US, 98 * US),                 #   compute under it,
            (4, 500 * US, 10 * US),                 #   the wait that is left
        ]
    return _plane(
        f"/device:TPU:{index}",
        [
            ("XLA Ops", ops),
            ("XLA Modules", [(5, 100 * US, 420 * US)]),
            # The asynchronous line spans the pair; it is never billed.
            ("Async XLA Ops", [(3, 400 * US, 110 * US)]),
        ],
        {
            1: ("%convolution.1", {7: "convolution"}),
            2: ("%all-reduce.7", {7: "all-reduce"}),
            3: ("%all-reduce-start.9", {7: "all-reduce-start"}),
            4: ("%all-reduce-done.9", {7: "all-reduce-done"}),
            5: ("jit_train_step(1)", {}),
        },
        stat_names=[(7, "hlo_category")],
    )


class _Reporter:
    def __init__(self):
        self.lines = []

    def say(self, text):
        self.lines.append(text)


def _run(tmp_path, collectives):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"".join(
        _field(1, _device(index, collectives)) for index in range(2)
    ))
    summary = xplane.summarize(xplane.load(str(path)), steps=2)
    return types.SimpleNamespace(trace_summary=summary, reporter=_Reporter())


def _reader():
    # Found as the harness will find it once the metric has its entry: by
    # the reader's name in the metric's file.
    return manifest._load_module("readers", "collectives_exposed")


def test_reads_the_synchronous_lines_collectives_a_step(tmp_path):
    run = _run(tmp_path, collectives=True)
    assert run.trace_summary["devices"] == 2
    # all-reduce (40 + 60) / 2, start 2, done 10 microseconds a plane, over
    # two steps; the asynchronous line's 110 us are not exposed.
    assert _reader().read(run) == pytest.approx((50 + 2 + 10) / 2 / 1e3)
    line = run.reporter.lines[-1]
    for category in ("all-reduce 0.025", "all-reduce-start 0.001", "all-reduce-done 0.005"):
        assert category in line, line
    assert "%all-reduce.7 (all-reduce) 0.025 ms x 0.50 a step" in line
    ops = run.trace_summary["collectives"]
    assert ops["%all-reduce-done.9"] == ["all-reduce-done", pytest.approx(10e-6), 1.0]


def test_nothing_to_read_without_a_collective(tmp_path):
    run = _run(tmp_path, collectives=False)
    assert run.trace_summary["collectives"] == {}
    assert _reader().read(run) is None
    assert run.reporter.lines == []
