"""jax's build events as spans of the recorder (utils/build_trace.py), the
`train.build` span around a step program's first call, the import span and
the log record's two fields."""

import threading
import time

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring

from tensor2robot_tpu.models.abstract_model import MODE_TRAIN
from tensor2robot_tpu.train import train_eval
from tensor2robot_tpu.utils import build_trace, tracing
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

JIT_SPANS = ("jit.trace", "jit.lower", "jit.compile")


def spans_since(mark, *names):
    return [
        s for s in tracing.snapshot(since_ns=mark)["spans"]
        if s["name"] in names and s["thread"] == threading.get_ident()
    ]


def length(span):
    return span["end_ns"] - span["start_ns"]


def batch_of(model, batch_size):
    generator = MockInputGenerator(batch_size=batch_size)
    train_eval.provide_input_generator_with_model_information(
        generator, model, MODE_TRAIN
    )
    return next(iter(generator.create_dataset(MODE_TRAIN)))


@pytest.fixture(scope="module")
def trained():
    """A compiled mock model, its first two steps taken: (compiled, state,
    the train.init_state span, the spans of the first call, those of the
    second)."""
    model = MockT2RModel(device_type="cpu")
    compiled = train_eval.CompiledModel(model, donate_state=False)
    batch = compiled.shard_batch(batch_of(model, 8))
    # An earlier test of the process may have built these very programs;
    # with jax's in-memory caches emptied, the init and the first step build.
    jax.clear_caches()
    mark = time.time_ns()
    state = compiled.init_state(jax.random.PRNGKey(0), batch)
    (init,) = spans_since(mark, "train.init_state")
    calls = []
    for _ in range(2):
        mark = time.time_ns()
        state, _ = compiled.train_step(state, batch, jax.random.PRNGKey(1))
        calls.append(spans_since(mark, "train.build", *JIT_SPANS))
    return compiled, state, init, calls[0], calls[1]


def test_install_twice_registers_once():
    build_trace.install()
    build_trace.install()
    for listeners, mine in (
        (monitoring.get_event_time_span_listeners(), build_trace._on_span),
        (monitoring.get_event_listeners(), build_trace._on_event),
        (monitoring.get_event_duration_listeners(), build_trace._on_duration),
        (monitoring.get_scalar_listeners(), build_trace._on_enter),
    ):
        assert listeners.count(mine) == 1


def test_the_first_call_of_a_step_program_is_one_train_build(trained):
    _, _, _, first, _ = trained
    (build,) = [s for s in first if s["name"] == "train.build"]
    assert build["label"] == "train_step"
    counts = build["counts"]
    assert counts["programs"] == 1
    assert min(counts["trace_ns"], counts["lower_ns"], counts["compile_ns"]) > 0
    # The three kinds never overlap on a thread, so they fit inside.
    assert (
        counts["trace_ns"] + counts["lower_ns"] + counts["compile_ns"]
        <= length(build)
    )


def test_the_jit_spans_of_a_build_lie_inside_it(trained):
    _, _, _, first, _ = trained
    (build,) = [s for s in first if s["name"] == "train.build"]
    inside = [s for s in first if s["name"] in JIT_SPANS]
    assert sorted(s["name"] for s in inside) == sorted(JIT_SPANS)
    for span in inside:
        assert build["start_ns"] <= span["start_ns"] <= span["end_ns"] <= build["end_ns"]
        assert span["thread"] == build["thread"]
        assert "train_step" in span["label"]
    # The build's counts are the spans' lengths: one clock, one sum.
    for span in inside:
        key = {"jit.trace": "trace_ns", "jit.lower": "lower_ns",
               "jit.compile": "compile_ns"}[span["name"]]
        assert build["counts"][key] == length(span)


def test_a_call_that_builds_nothing_records_nothing(trained):
    assert trained[4] == []


def test_another_batch_shape_is_a_recompile_and_the_log_record_says_so(trained):
    compiled, state, _, _, _ = trained
    batch = compiled.shard_batch(batch_of(compiled.model, 16))
    before = tracing.counters()
    mark = time.time_ns()
    with tracing.span("train.dispatch", ordinal=41) as dispatch:
        compiled.train_step(state, batch, jax.random.PRNGKey(1))
    (build,) = spans_since(mark, "train.build")
    assert build["label"] == "train_step" and build["counts"]["programs"] == 1
    # Inside the loop it is the dispatch's child and has its ordinal, as
    # the jit spans have.
    assert build["parent"] == dispatch.ident and build["ordinal"] == 41
    assert {s["parent"] for s in spans_since(mark, *JIT_SPANS)} == {dispatch.ident}
    record = train_eval._host_path_record(before, tracing.counters(), steps=1)
    assert record["compile/programs_built"] >= 1
    assert 0 < record["compile/seconds"] <= length(build) / 1e9


def test_init_state_counts_the_programs_it_built(trained):
    init = trained[2]
    counts = init["counts"]
    assert counts["programs"] >= 1
    assert 0 < counts["trace_ns"] + counts["lower_ns"] + counts["compile_ns"] <= length(init)
    children = [
        s for s in tracing.snapshot()["spans"]
        if s["parent"] == init["id"] and s["name"].startswith("train.init_state.")
    ]
    assert [s["name"] for s in children] == [
        "train.init_state.preprocess", "train.init_state.model_init"
    ]
    # What the children built is part of what the whole call built.
    assert sum(s["counts"]["programs"] for s in children) <= counts["programs"]


def test_a_build_inside_a_build_is_part_of_the_outermost():
    build_trace.install()
    inner = jax.jit(lambda x: jnp.sin(x) + 1)
    outer = jax.jit(lambda x: inner(x) * 2)
    x = jnp.ones((3,))
    before = build_trace.totals()
    mark, started = time.time_ns(), time.perf_counter_ns()
    outer(x).block_until_ready()
    wall = time.perf_counter_ns() - started
    built = build_trace.totals().since(before)
    spans = spans_since(mark, *JIT_SPANS)
    # One of each: the inner function's trace is inside the outer's.
    assert sorted(s["name"] for s in spans) == sorted(JIT_SPANS)
    assert built["programs"] == 1
    assert built["trace_ns"] + built["lower_ns"] + built["compile_ns"] <= wall
    assert build_trace._thread.depth == 0


def test_totals_are_the_building_threads_own():
    build_trace.install()
    x = jnp.ones((5,))
    before = build_trace.totals()
    seen = {}

    def build():
        start = build_trace.totals()
        jax.jit(lambda x: x * 3 - 1)(x).block_until_ready()
        seen["built"] = build_trace.totals().since(start)

    thread = threading.Thread(target=build)
    thread.start()
    thread.join(timeout=120)
    assert not thread.is_alive()
    assert seen["built"]["programs"] == 1
    assert build_trace.totals() is before


@pytest.fixture
def compile_cache(tmp_path):
    """jax's persistent cache in a directory of the test's own, every
    program cacheable; the process's own setting comes back afterwards."""
    from jax._src import compilation_cache

    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    kept = {name: getattr(jax.config, name) for name in names}
    jax.config.update(names[0], str(tmp_path / "cache"))
    jax.config.update(names[1], 0.0)
    jax.config.update(names[2], -1)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for name, value in kept.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()


def test_a_build_the_compile_cache_answers_says_so(compile_cache):
    build_trace.install()

    def program():
        # Two functions with one module: the second misses every cache of
        # the process and hits the directory.
        return jax.jit(lambda x: jnp.cos(x) * 7 + 5)

    x = jnp.ones((11,))
    counters = tracing.counters()
    mark = time.time_ns()
    program()(x).block_until_ready()
    (miss,) = spans_since(mark, "jit.compile")
    assert miss["counts"] == {"cache_hit": 0, "retrieval_ns": 0, "saved_ns": 0}

    before = build_trace.totals()
    mark = time.time_ns()
    program()(x).block_until_ready()
    built = build_trace.totals().since(before)
    (hit,) = spans_since(mark, "jit.compile")
    assert hit["counts"]["cache_hit"] == 1
    assert 0 < hit["counts"]["retrieval_ns"] <= length(hit)
    assert built["cache_hits"] == 1 and built["cache_misses"] == 0
    assert built["retrieval_ns"] == hit["counts"]["retrieval_ns"]
    now = tracing.counters()
    assert now["jit.cache_hits"] - counters.get("jit.cache_hits", 0) == 1
    assert now["jit.cache_misses"] - counters.get("jit.cache_misses", 0) == 1


def test_a_label_survives_the_dict_and_the_adoption():
    recorder = tracing.Recorder()
    with recorder.span("outer", ordinal=3) as outer:
        recorder.between("built", 10, 20, label="f", programs=1)
    recorder.since("closed", 5, label="g")
    built, _, closed = recorder.snapshot()["spans"]
    assert (built["label"], built["start_ns"], built["end_ns"]) == ("f", 10, 20)
    assert built["parent"] == outer.ident and built["ordinal"] == 3
    assert built["counts"] == {"programs": 1}
    assert closed["label"] == "g" and closed["parent"] is None
    assert recorder.snapshot()["spans"][1]["label"] is None

    home = tracing.Recorder()
    home.adopt(built)
    # A span shipped by a program from before the field has none.
    home.adopt({k: v for k, v in closed.items() if k != "label"})
    adopted, old = home.snapshot()["spans"]
    assert adopted["label"] == "f" and old["label"] is None
    # One pair of counters a name, however many labels.
    assert sorted(home.counters()) == ["built.n", "built.ns", "closed.n", "closed.ns"]


def test_the_import_span_and_the_process_start():
    snap = tracing.snapshot()
    counters = snap["counters"]
    assert counters["program.import.n"] == 1
    start_ns = counters["process.start_ns"]
    # Boot time is a difference of two clocks, read again at each call.
    assert abs(start_ns - build_trace.process_start_ns()) < 10**8
    assert start_ns < time.time_ns()
    imports = [s for s in snap["spans"] if s["name"] == "program.import"]
    if imports:  # the ring of a long session may have let it go
        (span,) = imports
        assert start_ns < span["start_ns"] < span["end_ns"]
        assert length(span) == counters["program.import.ns"]
