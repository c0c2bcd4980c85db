"""The five readers of set-up over test_program_spans' synthetic snapshot:
with the program's build spans, without them, and with builds that closed
after the window opened (the plain reference's, in the same process)."""

import time
import types

import pytest

import manifest
import program_spans
from test_program_spans import EPOCH_NS, MAIN, NS, _Reporter, _snapshot, _span

CELLS = [w["name"] for w in manifest.benchmark_json()["workloads"]]
NAMES = (
    "setup.step_build_s", "setup.step_trace_lower_s", "setup.step_compile_s",
    "setup.init_state_programs", "setup.program_import_s",
)
S = 1_000_000  # one second in the snapshot's microseconds
OPENED_US = -1 * S
OTHER = 7


def _readers(cell=CELLS[0]):
    found = {e["name"]: r for e, _, r in manifest.per_layer(cell)}
    return [found[name] for name in NAMES]


def _run(monkeypatch, snap):
    """A run whose window opened a second before the snapshot's traced part,
    on `window.py`'s clock."""
    monkeypatch.setattr(program_spans, "snapshot", lambda: snap)
    offset = time.time_ns() - time.perf_counter() * 1e9
    window = types.SimpleNamespace(
        opened_at=(EPOCH_NS + OPENED_US * NS - offset) / 1e9
    )
    return types.SimpleNamespace(window=window, reporter=_Reporter())


def _build(ident, start_us, end_us, label="train_step", thread=MAIN, **counts):
    span = _span(ident, "train.build", thread, start_us, end_us, **{
        "programs": 1, "trace_ns": 300_000 * NS, "lower_ns": 200_000 * NS,
        "compile_ns": 600_000 * NS, "cache_hits": 1, "cache_misses": 0,
        "retrieval_ns": 500_000 * NS, **counts,
    })
    return dict(span, label=label)


def _jit(ident, name, start_us, end_us, label, thread=MAIN):
    return dict(_span(ident, name, thread, start_us, end_us), label=label)


def _with_spans():
    snap = _snapshot()
    init = next(s for s in snap["spans"] if s["name"] == "train.init_state")
    init["counts"].update(
        programs=152, trace_ns=400_000 * NS, lower_ns=300_000 * NS,
        compile_ns=900_000 * NS, cache_hits=150, cache_misses=2, retrieval_ns=0,
    )
    snap["spans"] = [
        _span(60, "program.import", MAIN, -30 * S, -18 * S, own_ns=9 * S * NS),
        # An eval step's build is no step program's.
        _build(61, -2_990_000, -2_950_000, label="eval_step"),
        _jit(62, "jit.trace", -2_880_000, -2_580_000, "train_step"),
        _jit(63, "jit.lower", -2_570_000, -2_370_000, "jit_train_step"),
        _jit(64, "jit.compile", -2_300_000, -1_700_000, "jit_train_step"),
        _build(65, -2_900_000, -1_500_000),
        # A recompile before the window is not the first build.
        _build(66, -1_400_000, -1_300_000, compile_ns=1 * NS),
    ] + snap["spans"]
    snap["counters"]["process.start_ns"] = EPOCH_NS - 42 * S * NS
    return snap


def test_every_new_metric_has_its_file_and_reader_and_lists_the_four_cells():
    assert len(CELLS) == 4
    entries = {m["name"]: m for m in manifest.benchmark_json()["per_layer"]}
    for cell in CELLS:
        assert all(callable(r.read) for r in _readers(cell))
    for name in NAMES:
        entry = entries[name]
        assert entry["workloads"] == CELLS
        assert (entry["layer"], entry["moves"], entry["better"]) == (
            "Train step", "setup_s", "lower"
        )
        data = manifest._load_json("metrics", f"{name}.json")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert data[key] == entry[key], (name, key)


def test_the_five_read_the_programs_spans(monkeypatch):
    run = _run(monkeypatch, _with_spans())
    build, trace_lower, compile_s, programs, imported = (
        r.read(run) for r in _readers()
    )
    assert build == pytest.approx(1.4)
    assert trace_lower == pytest.approx(0.5)
    assert compile_s == pytest.approx(0.6)
    assert programs == 152
    assert imported == pytest.approx(12.0)
    said = "\n".join(run.reporter.lines)
    # The three readers of the step's build say its split once.
    assert said.count("step build:") == 1
    assert (
        "train.build train_step 1.400 s = trace 0.300 + lower 0.200 + compile "
        "or load 0.600 (programs 1, cache hits 1 misses 0, retrieval 0.500) + "
        "under no jax event 0.300" in said
    )
    assert "before the window: 3, outside their train.build or off its thread: 0" in said
    assert "init_state builds: programs 152" in said and "of 2.000 s" in said
    assert (
        "process start to the package's first line 12.000 s, the training "
        "stack's import 12.000 s, of it train_eval.py's own first line to its "
        "last 9.000 s" in said
    )


def test_none_on_a_program_without_the_spans(monkeypatch):
    # The parent's snapshot has `train.init_state`, with no counts on it.
    run = _run(monkeypatch, _snapshot())
    assert [r.read(run) for r in _readers()] == [None] * 5
    assert run.reporter.lines == []
    # And on a program with no recorder at all.
    run = _run(monkeypatch, None)
    assert [r.read(run) for r in _readers()] == [None] * 5


def test_builds_after_the_window_opened_are_in_no_metric(monkeypatch):
    # The reference's builds: after the opening, on the program's thread.
    later = [
        _jit(70, "jit.compile", -900_000, -500_000, "jit_train_step"),
        _build(71, -950_000, -400_000, compile_ns=400_000 * NS),
        dict(_span(72, "train.init_state", MAIN, -300_000, -200_000, programs=9)),
        _span(73, "program.import", MAIN, -100_000, -50_000),
    ]
    snap = _snapshot()
    snap["spans"] = [
        s for s in snap["spans"] if s["name"] != "train.init_state"
    ] + later
    run = _run(monkeypatch, snap)
    assert [r.read(run) for r in _readers()] == [None] * 5

    snap = _with_spans()
    snap["spans"] += later
    run = _run(monkeypatch, snap)
    values = [r.read(run) for r in _readers()]
    assert values == pytest.approx([1.4, 0.5, 0.6, 152, 12.0])
    assert "before the window: 3, outside" in "\n".join(run.reporter.lines)


def test_a_jit_span_outside_its_build_is_said(monkeypatch):
    snap = _with_spans()
    snap["spans"].insert(
        2, _jit(80, "jit.lower", -2_950_000, -2_940_000, "jit_train_step", OTHER)
    )
    run = _run(monkeypatch, snap)
    assert _readers()[0].read(run) == pytest.approx(1.4)
    assert "before the window: 4, outside their train.build or off its thread: 1" in (
        "\n".join(run.reporter.lines)
    )


@pytest.mark.parametrize("cell_name", [CELLS[0], CELLS[-1]])
def test_the_five_read_a_real_run_with_no_trace(monkeypatch, cell_name):
    """The resident critic, and the sparse-expert cell whose driver loads
    another, at a tiny preset through `run.run_cell`, as tools/setup_split.py
    runs a cell: the readers see the program's own spans, the window's own
    opening, and the reference's builds after it."""
    import jax

    import report
    import run as bench_run
    import tiny
    from tools import setup_split

    lines = []

    class Reporter(report.Reporter):
        def say(self, text):
            lines.append(text)

    monkeypatch.setattr(
        manifest, "driver", setup_split.saying_the_split(manifest.driver)
    )
    # A benchmark process runs one cell; this one runs two, so each sees
    # the recorder from its own start.
    from tensor2robot_tpu.utils import tracing

    mark = time.time_ns()
    monkeypatch.setattr(
        program_spans, "snapshot", lambda: tracing.snapshot(since_ns=mark)
    )
    cell = tiny.tiny_cell(cell_name, batch=8)
    result = bench_run.run_cell(
        cell, tiny.tiny_config(cell["config"]), tiny.args(seed=5, seconds=1.0),
        jax.devices()[:1], Reporter("test"),
    )
    assert result["failed"] == 0  # `correct` is test_drivers' to hold
    said = {
        line.split(": ")[0].split()[-1]: line.split(": ")[1].split()[0]
        for line in lines if line.startswith("metric setup.")
    }
    assert set(said) == set(NAMES), lines
    # The import is the process's: before this test's mark or after it.
    said.pop(NAMES[4])
    build, trace_lower, compile_s, programs = (float(said[name]) for name in NAMES[:4])
    assert 0 < trace_lower + compile_s <= build
    assert programs >= 0  # an earlier test of the process may have built them all
    (split,) = [line for line in lines if line.startswith("step build:")]
    assert "train.build train_step" in split
    assert "outside their train.build or off its thread: 0" in split
