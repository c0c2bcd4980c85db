"""The reader PR 37 adds, over test_scope_readers.py's synthetic xplane with
the Kimi program's scopes: an operation under `kda/delta_rule/../kda/carry` is
billed to `train_step.kda_carry_ms_per_step` and still to
`train_step.kda_ms_per_step`, and not to the pair scores' metric; a program
that names no such scope (the parent of PR 37, a critic) reads None there and
the older metrics as before."""

import pytest

import manifest
from test_scope_readers import _run

NET = "jit(train_step)/jit(main)/_KimiLinearNet/layer_1/mixer"
BACK = "jit(train_step)/jit(main)/transpose(jvp(_KimiLinearNet))/layer_1/mixer"
WITH_THE_SCOPE = {
    1: f"{NET}/kda/delta_rule/checkpoint/kda/carry/platform_index/pallas_call",
    2: f"{BACK}/kda/delta_rule/checkpoint/kda/carry/pallas_call",
    3: f"{NET}/kda/delta_rule/checkpoint/kda/pair_scores/pallas_call",
    4: f"{NET}/kda/conv/mul",
}
# The parent's program: the carry's loop and products under the rule's scope alone.
WITHOUT = {
    1: f"{NET}/kda/delta_rule/checkpoint/while/body/dot_general",
    2: f"{BACK}/kda/delta_rule/checkpoint/while/body/dot_general",
    3: WITH_THE_SCOPE[3],
    4: WITH_THE_SCOPE[4],
}
CELL = "kimi_linear_48b_a3b_s1.train_packed_16k"
CARRY = "train_step.kda_carry_ms_per_step"
PAIR_SCORES = "train_step.kda_pair_scores_ms_per_step"
RULE = "train_step.kda_ms_per_step"


def _read(metric, run):
    readers = {entry["name"]: reader for entry, _, reader in manifest.per_layer(CELL)}
    return readers[metric].read(run)


# Operations 1 to 4 run 200, 100, 60 and 300 us in two traced steps, and 50 us
# of a second operation 1 lie inside the window (test_scope_readers._run).
@pytest.mark.parametrize("labels,metric,milliseconds", [
    (WITH_THE_SCOPE, CARRY, (200 + 100 + 50) / 2 / 1e3),
    (WITH_THE_SCOPE, PAIR_SCORES, 60 / 2 / 1e3),
    (WITH_THE_SCOPE, RULE, (200 + 100 + 50 + 60) / 2 / 1e3),
    (WITHOUT, PAIR_SCORES, 60 / 2 / 1e3),
    (WITHOUT, RULE, (200 + 100 + 50 + 60) / 2 / 1e3),
])
def test_the_walk_is_billed_to_its_metric_and_to_the_rules(
    tmp_path, labels, metric, milliseconds
):
    assert _read(metric, _run(tmp_path, labels)) == pytest.approx(milliseconds)


@pytest.mark.parametrize("traced", [True, False])
def test_the_reader_finds_nothing_where_the_scope_is_absent(tmp_path, traced):
    assert _read(CARRY, _run(tmp_path, WITHOUT, traced=traced)) is None


def test_the_metric_is_the_cells_alone():
    assert [w for w in manifest.benchmark_json()["workloads"]
            if any(e["name"] == CARRY for e, _, _ in manifest.per_layer(w["name"]))
            ] == [w for w in manifest.benchmark_json()["workloads"] if w["name"] == CELL]
