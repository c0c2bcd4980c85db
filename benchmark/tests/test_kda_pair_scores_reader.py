"""The reader PR 33 adds, over test_scope_readers.py's synthetic xplane with
the Kimi program's scopes: an operation under `kda/delta_rule/../kda/
pair_scores` is billed to `train_step.kda_pair_scores_ms_per_step` and still
to `train_step.kda_ms_per_step`; a program that names no such scope (the
parent of PR 33, a critic) reads None there and the older metric as before."""

import pytest

import manifest
from test_scope_readers import _run

NET = "jit(train_step)/jit(main)/_KimiLinearNet/layer_1/mixer"
BACK = "jit(train_step)/jit(main)/transpose(jvp(_KimiLinearNet))/layer_1/mixer"
WITH_THE_SCOPE = {
    1: f"{NET}/kda/delta_rule/checkpoint/kda/pair_scores/platform_index/pallas_call",
    2: f"{BACK}/kda/delta_rule/checkpoint/kda/pair_scores/pallas_call",
    3: f"{NET}/kda/delta_rule/checkpoint/while/body/dot_general",
    4: f"{NET}/kda/conv/mul",
}
WITHOUT = {
    ident: label.replace("kda/pair_scores/", "")
    for ident, label in WITH_THE_SCOPE.items()
}
CELL = "kimi_linear_48b_a3b_s1.train_packed_16k"
PAIR_SCORES = "train_step.kda_pair_scores_ms_per_step"
RULE = "train_step.kda_ms_per_step"


def _read(metric, run):
    readers = {entry["name"]: reader for entry, _, reader in manifest.per_layer(CELL)}
    return readers[metric].read(run)


# Operations 1 to 4 run 200, 100, 60 and 300 us in two traced steps, and 50 us
# of a second operation 1 lie inside the window (test_scope_readers._run).
@pytest.mark.parametrize("labels,metric,milliseconds", [
    (WITH_THE_SCOPE, PAIR_SCORES, (200 + 100 + 50) / 2 / 1e3),
    (WITH_THE_SCOPE, RULE, (200 + 100 + 50 + 60) / 2 / 1e3),
    (WITHOUT, RULE, (200 + 100 + 50 + 60) / 2 / 1e3),
])
def test_pair_scores_are_billed_to_both_metrics(tmp_path, labels, metric, milliseconds):
    assert _read(metric, _run(tmp_path, labels)) == pytest.approx(milliseconds)


@pytest.mark.parametrize("traced", [True, False])
def test_the_reader_finds_nothing_where_the_scope_is_absent(tmp_path, traced):
    assert _read(PAIR_SCORES, _run(tmp_path, WITHOUT, traced=traced)) is None


def test_the_metric_is_the_cells_alone():
    assert [w for w in manifest.benchmark_json()["workloads"]
            if any(e["name"] == PAIR_SCORES for e, _, _ in manifest.per_layer(w["name"]))
            ] == [w for w in manifest.benchmark_json()["workloads"] if w["name"] == CELL]
