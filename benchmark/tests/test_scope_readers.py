"""The two readers PR 31 adds, over a synthetic xplane whose operations
carry the sequence model's scopes (test_xplane.py's builder): milliseconds
a traced step under `mlp` and under `mamba2/in_proj`, and None where the
trace names no such scope (a critic's step) or there is no trace."""

import types

import pytest

import manifest
from test_xplane import EPOCH_NS, US, _field, _plane

NS = 1000  # one microsecond in nanoseconds
NET = "jit(train_step)/jit(main)/_HybridLMNet"
BACK = "jit(train_step)/jit(main)/transpose(jvp(_HybridLMNet))"
SEQUENCE_MODEL = {
    1: f"{NET}/layer_0/mlp/mlp/gate/dot_general",
    2: f"{BACK}/layer_0/mlp/mlp/up/dot_general",
    3: f"{NET}/layer_0/mixer/mamba2/in_proj/in_proj/dot_general",
    4: f"{BACK}/layer_0/mixer/mamba2/ssd/dot_general",
}
CRITIC = {
    ident: "jit(train_step)/jit(main)/grasping44/conv2/conv_general_dilated"
    for ident in SEQUENCE_MODEL
}


def _run(tmp_path, labels, traced=True):
    """Two traced steps of 0..1000 us: operations 1 to 4 run 200, 100, 60
    and 300 us; the second `mlp/gate` straddles the window's end and counts
    with the 50 us that lie inside."""
    device = _plane(
        "/device:TPU:0",
        [("XLA Ops", [
            (1, 100 * US, 200 * US), (2, 300 * US, 100 * US),
            (3, 400 * US, 60 * US), (4, 500 * US, 300 * US),
            (1, 950 * US, 200 * US),
        ])],
        {ident: (f"%fusion.{ident}", {8: label}) for ident, label in labels.items()},
        stat_names=[(8, "tf_op")],
    )
    task = _plane(
        "Task Environment", [], {}, stat_names=[(1, "profile_start_time")],
        plane_stats=[(1, EPOCH_NS)],
    )
    directory = tmp_path / ("traced" if traced else "untraced")
    directory.mkdir()
    (directory / "t.xplane.pb").write_bytes(_field(1, device) + _field(1, task))
    spans = [("bench.trace_window", EPOCH_NS, EPOCH_NS + 1000 * NS)] if traced else []
    return types.SimpleNamespace(
        window=types.SimpleNamespace(spans=spans),
        trace_dir=str(directory) if traced else None,
        trace_summary={"steps": 2} if traced else None,
        reporter=types.SimpleNamespace(say=lambda text: None),
    )


def _reader(metric):
    readers = {
        entry["name"]: reader for entry, _, reader in manifest.per_layer(
            "granite_4_0_h_micro_p1.train_packed_8k"
        )
    }
    return readers[metric]


@pytest.mark.parametrize("metric,milliseconds", [
    ("train_step.mlp_ms_per_step", (200 + 100 + 50) / 2 / 1e3),
    ("train_step.in_proj_ms_per_step", 60 / 2 / 1e3),
])
def test_scope_reader_gives_milliseconds_a_traced_step(tmp_path, metric, milliseconds):
    assert _reader(metric).read(_run(tmp_path, SEQUENCE_MODEL)) == pytest.approx(
        milliseconds
    )


@pytest.mark.parametrize("metric", [
    "train_step.mlp_ms_per_step", "train_step.in_proj_ms_per_step",
])
def test_scope_reader_finds_nothing_where_the_scope_is_absent(tmp_path, metric):
    read = _reader(metric).read
    assert read(_run(tmp_path, CRITIC)) is None
    assert read(_run(tmp_path, CRITIC, traced=False)) is None
