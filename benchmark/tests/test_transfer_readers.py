"""The readers of a fed batch's way to the chips, over test_program_spans'
synthetic timeline (busy 100..350, 600..700, 900..950 us of a traced part
of 1000 us; step modules from 100 and from 600; idle 600 us): a gap that
awaits its batch, a gap that does not, and no span at all."""

import pytest

import manifest
import program_spans
from test_program_spans import MAIN, NS, _run, _snapshot, _span

WATCHER = 9
CELL = "critic_c64.train_fed"
NAMES = (
    "host_input.h2d_transfer_ms_per_step", "host_input.h2d_late_share",
    "device.idle_awaiting_batch_share.fed",
)


def _reader(name):
    return {e["name"]: r for e, _, r in manifest.per_layer(CELL)}[name]


def _recorded(monkeypatch, transfers, late=(0, 1)):
    """The synthetic snapshot with `infeed.transfer` spans [(ordinal, start,
    end)] of a watcher thread and `late` on the two dispatches."""
    snap = _snapshot()
    dispatches = [s for s in snap["spans"] if s["name"] == "train.dispatch"]
    if late is not None:
        for span, value in zip(dispatches, late):
            span["counts"]["late"] = value
    snap["spans"] += [
        _span(50 + ordinal, "infeed.transfer", WATCHER, start, end,
              ordinal=ordinal, bytes=1000, devices=4, last_device=3)
        for ordinal, start, end in transfers
    ]
    snap["counters"].update({"infeed.dispatched": 12, "infeed.late_at_dispatch": 3})
    monkeypatch.setattr(program_spans, "snapshot", lambda: snap)


def test_the_three_metrics_are_the_fed_cells():
    fed = {e["name"] for e, _, _ in manifest.per_layer(CELL)}
    resident = {
        e["name"] for e, _, _ in manifest.per_layer("critic_c64.train_resident")
    }
    assert set(NAMES) <= fed and not set(NAMES) & resident
    for name in NAMES:
        (entry,) = [
            m for m in manifest.benchmark_json()["per_layer"] if m["name"] == name
        ]
        assert entry["moves"] == "train.examples_per_s.fed"
        assert entry["workloads"][0] == CELL


def test_gaps_that_await_their_batch(tmp_path, monkeypatch):
    # Step 0's batch arrives at 80 (idle 0..100 before its module), step
    # 1's at 590 (idle 350..600): 80 + 240 of 600 us idle awaited a batch;
    # the idle 700..900 inside step 1's module awaits none.
    _recorded(monkeypatch, [(0, 40, 80), (1, 560, 590)])
    run = _run(tmp_path)
    assert _reader(NAMES[2]).read(run) == pytest.approx(100.0 * 320 / 600)
    assert _reader(NAMES[0]).read(run) == pytest.approx((0.040 + 0.030) / 2)
    assert _reader(NAMES[1]).read(run) == pytest.approx(50.0)
    said = "\n".join(run.reporter.lines)
    assert "0.160 ms a step of 0.300 ms idle, in 2 of 2 steps" in said
    assert "1: 0.240, 0: 0.080" in said
    assert "whole run: 3 of 12 steps" in said
    assert "2 closed in the traced part over 4 devices" in said and "3 x 2" in said


def test_gaps_that_do_not(tmp_path, monkeypatch):
    # Both batches were there before their gaps opened: step 0's before the
    # traced part, step 1's while step 0 still ran.
    _recorded(monkeypatch, [(0, -400, -350), (1, 200, 300)], late=(0, 0))
    run = _run(tmp_path)
    assert _reader(NAMES[2]).read(run) == 0.0
    assert _reader(NAMES[1]).read(run) == 0.0
    # Only the transfer that closed in the traced part is in the mean.
    assert _reader(NAMES[0]).read(run) == pytest.approx(0.100)


def test_a_transfer_that_left_the_ring_had_arrived(tmp_path, monkeypatch):
    _recorded(monkeypatch, [(1, 560, 590)])
    assert _reader(NAMES[2]).read(_run(tmp_path)) == pytest.approx(100.0 * 240 / 600)


@pytest.mark.parametrize("name", NAMES)
def test_none_on_a_program_without_the_span_or_the_count(
    tmp_path, monkeypatch, name
):
    """The parent commit under this PR's benchmark files: its recorder has
    neither `infeed.transfer` nor `late` on a dispatch."""
    _recorded(monkeypatch, [], late=None)
    run = _run(tmp_path)
    assert _reader(name).read(run) is None
    # And a commit with no recorder at all.
    monkeypatch.setattr(program_spans, "snapshot", lambda: None)
    del run._program_recorded, run._program_view
    run.reporter.lines.clear()
    assert _reader(name).read(run) is None and run.reporter.lines == []


def test_a_module_that_opens_before_its_first_op_waits_up_to_that_op():
    reader = manifest._load_module("readers", "idle_awaiting_batch")

    def dispatch(ordinal, start):
        return _span(ordinal, "train.dispatch", MAIN, start, start + 5, ordinal=ordinal)

    def shifted(spans):  # _span stamps epoch ns; the reduction takes session ns
        from test_xplane import EPOCH_NS

        return [
            dict(s, start_ns=s["start_ns"] - EPOCH_NS, end_ns=s["end_ns"] - EPOCH_NS)
            for s in spans
        ]

    spans = shifted([
        dispatch(0, 60), dispatch(1, 400),
        _span(8, "infeed.transfer", WATCHER, 40, 50, ordinal=0),
        _span(9, "infeed.transfer", WATCHER, 380, 610, ordinal=1),
    ])
    busy = [(100 * NS, 350 * NS), (620 * NS, 700 * NS)]
    modules = [("jit_step", 100 * NS, 350 * NS), ("jit_step", 600 * NS, 700 * NS)]
    total, idle, waited = reader.awaiting(spans, (0, 1000 * NS), busy, modules)
    assert idle == (100 + 270 + 300) * NS
    # Step 0: there at 50, so 0..50 of its gap; step 1: module from 600,
    # first op at 620, batch there at 610: 350..610.
    assert waited == [(0, 50 * NS), (1, 260 * NS)] and total == 310 * NS
    assert reader.awaiting(spans[:2], (0, 1000 * NS), busy, modules) is None
