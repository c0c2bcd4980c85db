"""The two readers PR 27 adds, over test_program_spans.py's synthetic
traced part: their numbers, and None where the program has nothing for
them (the parent commit under this PR's benchmark files)."""

import itertools

import pytest

import program_spans
import test_program_spans
from test_program_spans import NS, W1, _reader, _snapshot, _span

_runs = itertools.count()


def _run(tmp_path, traced=True):
    """A run with a trace directory of its own: a test reads several."""
    directory = tmp_path / f"run{next(_runs)}"
    directory.mkdir()
    return test_program_spans._run(directory, traced)


def _with(monkeypatch, spans=None, **counters):
    snap = _snapshot()
    if spans is not None:
        snap["spans"] = spans(snap["spans"])
    snap["counters"].update(counters)
    monkeypatch.setattr(program_spans, "snapshot", lambda: snap)


def test_decode_ms_per_image_is_decode_time_over_images(tmp_path, monkeypatch):
    # Closed in the traced part: 400 us over 4 images and 100 us over 4;
    # the span that ends after it is left out.
    _with(monkeypatch)
    read = _reader("host_input.decode_ms_per_image").read
    assert read(_run(tmp_path)) == pytest.approx(0.5 / 8)
    # A slice's span counts like a whole batch's.
    _with(monkeypatch, spans=lambda spans: spans + [_span(
        43, "data.parse_chunk", W1, 500, 700, ordinal=3, first_row=16,
        records=2, images=2, decode_ns=150 * NS,
    )])
    assert read(_run(tmp_path)) == pytest.approx(0.65 / 10)


def test_parse_ms_is_workers_time_per_256_records_slice_or_batch(tmp_path, monkeypatch):
    """One batch of 8 records in 700 us of workers' time, as one span or as
    four slices of 2: the same milliseconds per 256 records."""
    def only(parse_spans):
        return lambda spans: [
            s for s in spans if s["name"] != "data.parse_chunk"
        ] + parse_spans

    read = _reader("host_input.parse_ms_per_batch").read
    _with(monkeypatch, spans=only([
        _span(40, "data.parse_chunk", W1, 100, 800, ordinal=1, records=8),
    ]))
    whole = read(_run(tmp_path))
    _with(monkeypatch, spans=only([
        _span(40 + i, "data.parse_chunk", W1 + i, 100, 275, ordinal=1,
              first_row=2 * i, records=2)
        for i in range(4)
    ]))
    assert read(_run(tmp_path)) == pytest.approx(whole)
    assert whole == pytest.approx(0.700 / 8 * 256)
    # Spans that count no records (a recorder older than PR 26's counts).
    _with(monkeypatch, spans=only([
        _span(40, "data.parse_chunk", W1, 100, 800, ordinal=1),
    ]))
    assert read(_run(tmp_path)) is None


def test_sliced_batch_share_is_a_ratio_of_two_counters(tmp_path, monkeypatch):
    read = _reader("host_input.sliced_batch_share").read
    _with(monkeypatch, **{"data.parse_batches": 8, "data.parse_batches_sliced": 6})
    assert read(_run(tmp_path)) == pytest.approx(75.0)
    _with(monkeypatch, **{"data.parse_batches": 8})
    assert read(_run(tmp_path)) == 0.0
    # No trace needed: the counters are read after the window.
    assert read(_run(tmp_path, traced=False)) == 0.0


@pytest.mark.parametrize("name", [
    "host_input.decode_ms_per_image", "host_input.sliced_batch_share",
])
def test_nothing_to_read_on_a_commit_without_it(tmp_path, monkeypatch, name):
    read = _reader(name).read
    monkeypatch.setattr(program_spans, "snapshot", lambda: None)
    assert read(_run(tmp_path)) is None
    # A recorder that counts no batches and times no image: PR 26's.
    snap = _snapshot()
    for span in snap["spans"]:
        span["counts"].pop("images", None)
    monkeypatch.setattr(program_spans, "snapshot", lambda: snap)
    assert read(_run(tmp_path)) is None
