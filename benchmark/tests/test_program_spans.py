"""The program's spans over a synthetic xplane (test_xplane.py's builder):
idle attribution, the clocks' check, and every new reader returning its
number, or None where its span or the recorder is absent."""

import types

import pytest

import manifest
import program_spans
from test_xplane import EPOCH_NS, US, _field, _plane

NS = 1000  # one microsecond in nanoseconds
MAIN, READER, W1, W2 = 1, 2, 3, 4


def _xplane(tmp_path):
    """Busy 100..350, 600..700, 900..950 us of a 0..1000 us traced part;
    step modules start at 100 and 600 us."""
    device = _plane(
        "/device:TPU:0",
        [
            ("XLA Ops", [
                (1, 100 * US, 200 * US), (2, 250 * US, 100 * US),
                (1, 600 * US, 100 * US), (2, 900 * US, 50 * US),
            ]),
            ("XLA Modules", [(3, 100 * US, 250 * US), (3, 600 * US, 350 * US)]),
        ],
        {1: ("%convolution.1", {}), 2: ("%fusion.2", {}),
         3: ("jit_train_step(1)", {})},
    )
    task = _plane(
        "Task Environment", [], {}, stat_names=[(1, "profile_start_time")],
        plane_stats=[(1, EPOCH_NS)],
    )
    directory = tmp_path / "trace"
    directory.mkdir()
    (directory / "t.xplane.pb").write_bytes(_field(1, device) + _field(1, task))
    return str(directory)


def _span(ident, name, thread, start_us, end_us, parent=None, ordinal=0, **counts):
    return {
        "name": name, "id": ident, "parent": parent, "thread": thread,
        "ordinal": ordinal, "start_ns": EPOCH_NS + start_us * NS,
        "end_ns": EPOCH_NS + end_us * NS, "counts": counts,
    }


def _snapshot():
    spans = [
        # before the traced part: set-up
        _span(1, "train.init_state", MAIN, -5_000_000, -3_000_000),
        _span(2, "train.init_state.preprocess", MAIN, -5_000_000, -4_500_000, parent=1),
        _span(3, "train.init_state.model_init", MAIN, -4_500_000, -3_000_000, parent=1),
        # step 0: dispatched at 60..90, runs on the device from 100
        _span(10, "infeed.wait", MAIN, 0, 40),
        _span(11, "infeed.h2d", MAIN, 40, 50, bytes=1000),
        _span(12, "train.hooks", MAIN, 50, 60),
        _span(13, "train.dispatch", MAIN, 60, 90),
        _span(14, "train.hooks", MAIN, 90, 100),
        # step 1: the wait covers the idle gap 350..600 from 360 on
        _span(20, "infeed.wait", MAIN, 360, 560, ordinal=1),
        _span(21, "infeed.h2d", MAIN, 560, 580, ordinal=1, bytes=1000),
        _span(22, "train.hooks", MAIN, 580, 585, ordinal=1),
        _span(23, "train.dispatch", MAIN, 585, 595, ordinal=1),
        _span(24, "train.log", MAIN, 700, 800, ordinal=1),
        # a span nested in the log call is not counted twice
        _span(25, "eval_infeed.wait", MAIN, 710, 790, parent=24),
        _span(26, "train.hooks", MAIN, 800, 1000, ordinal=1),
        # the dataset's threads
        _span(30, "data.read_chunk", READER, 10, 30, ordinal=2, records=4, bytes=400),
        _span(31, "data.read_chunk", READER, 400, 440, ordinal=3, records=4, bytes=400),
        _span(40, "data.parse_chunk", W1, -100, 400, ordinal=1,
              records=4, images=4, decode_ns=400 * NS),
        _span(41, "data.parse_chunk", W2, 300, 500, ordinal=2,
              records=4, images=4, decode_ns=100 * NS),
        _span(42, "data.parse_chunk", W1, 800, 1200, ordinal=3,
              records=4, images=4, decode_ns=300 * NS),
    ]
    counters = {"data.prefetch_gets": 8, "data.prefetch_empty": 2}
    return {"spans": spans, "counters": counters}


class _Reporter:
    def __init__(self):
        self.lines = []

    def say(self, text):
        self.lines.append(text)


def _run(tmp_path, traced=True):
    window = types.SimpleNamespace(
        spans=[("bench.dispatch", EPOCH_NS + 55 * NS, EPOCH_NS + 95 * NS)],
        trace_steps=2,
    )
    if traced:
        window.spans.append(
            ("bench.trace_window", EPOCH_NS, EPOCH_NS + 1000 * NS)
        )
    return types.SimpleNamespace(
        window=window, trace_dir=_xplane(tmp_path), reporter=_Reporter(),
        trace_summary={"spans": {
            "bench.host_input.next": 239e-6, "bench.dispatch": 50e-6,
        }},
    )


@pytest.fixture
def recorder(monkeypatch):
    monkeypatch.setattr(program_spans, "snapshot", _snapshot)


def test_interval_helpers():
    gaps = program_spans.complement([(100, 350), (600, 700), (900, 950)], 0, 1000)
    assert gaps == [(0, 100), (350, 600), (700, 900), (950, 1000)]
    assert program_spans.complement([(0, 10)], 0, 10) == []
    assert program_spans.complement([], 5, 10) == [(5, 10)]
    cover = program_spans.Cover(gaps)
    assert cover.before[-1] == 600
    assert cover.inside([(0, 1000)]) == 600
    assert cover.inside([(360, 560)]) == 200
    assert cover.inside([(90, 110), (340, 360)]) == 20
    assert cover.inside([(100, 350)]) == 0
    # Overlapping askers each count.
    assert cover.inside([(0, 50), (25, 75)]) == 100


def test_idle_attribution_and_the_clocks(tmp_path, recorder):
    run = _run(tmp_path)
    view = program_spans.view(run)
    assert program_spans.view(run) is view  # made once a run
    assert view["window_ns"] == 1000 * NS and view["steps"] == 2
    # idle: 0..100, 350..600, 700..900, 950..1000 = 600 us
    assert view["idle_ns"] == 600 * NS
    assert view["idle_under_ns"] == {
        "infeed.wait": (40 + 200) * NS,   # 0..40 and 360..560
        "infeed.h2d": (10 + 20) * NS,     # 40..50 and 560..580
        "train.hooks": (10 + 10 + 5 + 100 + 50) * NS,
        "train.dispatch": (30 + 10) * NS,
        "train.log": 100 * NS,            # 700..800; its child is not added
    }
    # 350..360 and 595..600 lie between spans of the train thread.
    assert view["idle_unattributed_ns"] == 15 * NS
    # Workers in the idle time: W1 0..100 + 350..400 + 800..900 + 950..1000,
    # W2 350..500.
    assert view["parse_busy_in_idle_ns"] == (300 + 150) * NS
    assert view["parse_workers"] == 2
    # Both step modules start after their dispatch opened.
    assert view["step_modules"] == 2 and view["clock_violations"] == 0
    said = "\n".join(run.reporter.lines)
    assert "infeed.wait 0.120" in said and "under none 0.007" in said
    assert "parse workers busy while the device was idle 0.75 of 2" in said
    assert "infeed.wait 0.000240 s, bench.host_input.next 0.000239 s" in said
    assert "train.dispatch 0.000040 s, bench.dispatch 0.000050 s" in said
    assert "clocks: 0 of 2" in said


def test_a_module_that_starts_before_its_dispatch_is_a_violation():
    spans = [
        {"name": "train.dispatch", "id": 1, "parent": None, "thread": 1,
         "ordinal": 0, "start_ns": 3_000_000, "end_ns": 3_100_000, "counts": {}},
    ]
    modules = [("jit_step", 1_500_000, 2_500_000)]
    view = program_spans.reduce(spans, (0, 10_000_000), [(1_500_000, 2_500_000)], modules)
    assert view["clock_violations"] == 1
    modules = [("jit_step", 2_500_000, 3_500_000)]  # half a millisecond early
    view = program_spans.reduce(spans, (0, 10_000_000), [(2_500_000, 3_500_000)], modules)
    assert view["clock_violations"] == 0


READINGS = {
    # infeed.wait 0..40 + 360..560 over 2 steps
    "host_input.consumer_wait_ms_per_step": 0.120,
    "host_input.h2d_put_ms_per_step": 0.015,
    # closed in the traced part: 20 and 40 us
    "host_input.read_ms_per_batch": 0.030,
    # closed in it: -100..400 and 300..500 (the third closes after it): 700
    # us of workers' time over 8 records, times 256
    "host_input.parse_ms_per_batch": 0.700 / 8 * 256,
    "host_input.decode_share": 100.0 * 500 / 700,
    # 0..400 + 300..500 + 800..1000 over 2 workers x 1000 us
    "host_input.workers_busy_share": 40.0,
    "host_input.prefetch_empty_share": 25.0,
    "train_step.dispatch_ms_per_step.fed": 0.020,
    "device.idle_unattributed_share.fed": 100.0 * 15 / 600,
    "setup.init_state_s": 2.0,
}
ABSENT = {
    "host_input.consumer_wait_ms_per_step": "infeed.wait",
    "host_input.h2d_put_ms_per_step": "infeed.h2d",
    "host_input.read_ms_per_batch": "data.read_chunk",
    "host_input.parse_ms_per_batch": "data.parse_chunk",
    "host_input.decode_share": "data.parse_chunk",
    "host_input.workers_busy_share": "data.parse_chunk",
    "host_input.prefetch_empty_share": None,
    "train_step.dispatch_ms_per_step.fed": "train.dispatch",
    "device.idle_unattributed_share.fed": "train.dispatch",
    "setup.init_state_s": "train.init_state",
}


def _reader(name):
    entries = {
        entry["name"]: reader
        for cell in ("critic_c64.train_fed", "critic_c64.train_resident")
        for entry, _, reader in manifest.per_layer(cell)
    }
    return entries[name]


def test_the_new_metrics_are_the_fed_cells_and_init_state_is_both():
    fed = {e["name"] for e, _, _ in manifest.per_layer("critic_c64.train_fed")}
    resident = {
        e["name"] for e, _, _ in manifest.per_layer("critic_c64.train_resident")
    }
    assert set(READINGS) <= fed
    assert set(READINGS) & resident == {"setup.init_state_s"}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_reader_gives_its_number(tmp_path, recorder, name):
    assert _reader(name).read(_run(tmp_path)) == pytest.approx(READINGS[name])


@pytest.mark.parametrize("name", sorted(ABSENT))
def test_reader_gives_none_where_its_span_is_absent(tmp_path, monkeypatch, name):
    absent = ABSENT[name]
    snap = _snapshot()
    snap["spans"] = [s for s in snap["spans"] if s["name"] != absent]
    if absent is None:
        snap["counters"] = {}
    monkeypatch.setattr(program_spans, "snapshot", lambda: snap)
    assert _reader(name).read(_run(tmp_path)) is None


@pytest.mark.parametrize("name", sorted(READINGS))
def test_reader_gives_none_where_the_program_has_no_recorder(
    tmp_path, monkeypatch, name
):
    """The parent commit, under this PR's benchmark files."""
    monkeypatch.setattr(program_spans, "snapshot", lambda: None)
    run = _run(tmp_path)
    assert _reader(name).read(run) is None
    assert run.reporter.lines == []


def test_decode_share_is_silent_where_the_decoder_was_not_timed(
    tmp_path, monkeypatch
):
    snap = _snapshot()
    for span in snap["spans"]:
        span["counts"].pop("decode_ns", None)
    monkeypatch.setattr(program_spans, "snapshot", lambda: snap)
    assert _reader("host_input.decode_share").read(_run(tmp_path)) is None


def test_no_traced_part_no_view(tmp_path, recorder):
    run = _run(tmp_path, traced=False)
    assert program_spans.view(run) is None
    assert _reader("host_input.consumer_wait_ms_per_step").read(run) is None
    # The set-up span needs no trace.
    assert _reader("setup.init_state_s").read(run) == pytest.approx(2.0)


def test_snapshot_is_none_without_the_module(monkeypatch):
    import sys

    import tensor2robot_tpu.utils

    monkeypatch.delattr(tensor2robot_tpu.utils, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "tensor2robot_tpu.utils.tracing", None)
    assert program_spans.snapshot() is None
