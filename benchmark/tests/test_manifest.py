"""BENCHMARK.json and the files it names: names, units, and that every
file is where the harness will look for it."""

import json
import os

import manifest

B = manifest.benchmark_json()


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in B["configs"]] + [w["name"] for w in B["workloads"]]
    names += [w["traffic"] for w in B["workloads"]]
    metrics = B["end_to_end"] + B["per_layer"]
    names += [m["name"] for m in metrics]
    for name in names:
        assert manifest.NAME_RE.match(name), name
    for metric in metrics:
        assert manifest.UNIT_RE.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
    assert len(set(m["name"] for m in metrics)) == len(metrics)
    assert len(set(w["name"] for w in B["workloads"])) == len(B["workloads"])
    assert "setup_s" in [m["name"] for m in B["end_to_end"]]


def test_per_layer_cells_report_the_metric_they_move():
    cells = [w["name"] for w in B["workloads"]]
    end_to_end = {m["name"]: m.get("workloads", cells) for m in B["end_to_end"]}
    for metric in B["per_layer"]:
        assert metric["moves"] in end_to_end, metric
        # No list: every cell that reports the metric it moves.
        listed = metric.get("workloads", end_to_end[metric["moves"]])
        assert set(listed) <= set(end_to_end[metric["moves"]]), metric
        assert set(listed) <= set(cells), metric


def test_every_cell_has_its_files():
    for entry in B["workloads"]:
        cell = manifest.cell(entry["name"])
        assert cell["chips"] in (1, 4)
        manifest.config(cell["config"])
        assert hasattr(manifest.reference(cell["config"]), "loss_fn")
        assert hasattr(manifest.driver(cell["driver"]), "run")
        assert manifest.end_to_end(entry["name"])
        readers = manifest.per_layer(entry["name"])
        assert readers
        for _, data, reader in readers:
            assert callable(reader.read)
    for config in B["configs"]:
        assert os.path.exists(os.path.join(manifest.ROOT, config["file"]))
        assert config["file"].startswith(tuple(p + "/" for p in B["paths"]))


def test_at_most_a_quarter_of_the_cells_ask_for_four_chips():
    # A four-chip cell costs four times the chip time in every later check:
    # a quarter of the cells at most, rounded down, and one always may.
    names = [w["name"] for w in B["workloads"]]
    four = [w["name"] for w in B["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(names) // 4)
    assert all(len(w["why"]) <= 200 for w in B["workloads"])


def test_the_parked_four_chip_cell_is_the_fed_cell_on_four_chips():
    """`critic_c64.train_fed_dp4` has its files and no entry (PERF.md
    section 7: the program's init_state does not survive its first batch).
    It comes back by entries alone, so the file has to stay the cell that
    ISSUE 35 describes."""
    name = "critic_c64.train_fed_dp4"
    assert name not in [w["name"] for w in B["workloads"]]
    cell = manifest.cell(name, listed=False)
    one = manifest.cell("critic_c64.train_fed")
    assert cell["chips"] == 4 and cell["config"] == one["config"] == "critic_c64"
    assert cell["driver"] == one["driver"] and cell["batch"] == one["batch"] == 256
    # Four times the records (six steps an epoch, as on one chip) and an id
    # scale that holds them; nothing else of the traffic differs.
    assert cell["traffic"] == dict(
        one["traffic"], records=4 * one["traffic"]["records"], id_scale=8192
    )
    assert cell["trainer"] == {} and cell["environment"] == one["environment"]
    assert cell["warmup_steps"] == one["warmup_steps"]
    assert cell["end_to_end"] == one["end_to_end"]
    # Its own per-layer metric waits with it: file and reader, no entry.
    with open(os.path.join(
        manifest.BENCH_DIR, "metrics", "collectives.exposed_ms_per_step.json"
    )) as f:
        metric = json.load(f)
    assert metric["layer"] == "Device" and metric["source"] == "device_trace"
    assert callable(manifest._load_module("readers", metric["reader"]).read)
    assert "collectives.exposed_ms_per_step" not in [
        m["name"] for m in B["per_layer"]
    ]


def test_metric_files_agree_with_the_manifest():
    for entry in B["per_layer"]:
        with open(os.path.join(
            manifest.BENCH_DIR, "metrics", entry["name"] + ".json"
        )) as f:
            data = json.load(f)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert data[key] == entry[key], (entry["name"], key)


def test_limits_name_every_cell():
    for entry in B["workloads"]:
        limits = manifest.cell(entry["name"])["limits"]
        assert any(v is not None for v in limits.values()), entry["name"]


def test_peaks_refuse_an_unknown_device():
    import pytest

    assert manifest.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        manifest.peaks("TPU v9")
