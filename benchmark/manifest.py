"""Finds every file of the benchmark by the names in BENCHMARK.json.

A cell, a configuration, a per-layer metric, a driver, a reader and a plain
reference each sit in a file of their own; nothing here lists them. A
later PR adds an entry to BENCHMARK.json and the files it names, and edits
nothing that is there (README.md walks through it).

  benchmark/workloads/<cell>.json     config, chips, driver, batch, traffic
  benchmark/configs/<config>.json     what defines the model
  benchmark/reference/<config>.py     its plain reference
  benchmark/drivers/<driver>.py       run(ctx) -> drivers.result fields
  benchmark/metrics/<metric>.json     unit, layer, moves, workloads, reader
  benchmark/readers/<reader>.py       read(run) -> number or None
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _load_json(*parts):
    path = os.path.join(BENCH_DIR, *parts)
    with open(path) as f:
        return json.load(f)


def _load_module(kind, name):
    """benchmark/<kind>/<name>.py as a module, found by name alone."""
    if not NAME_RE.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.lru_cache(maxsize=None)
def benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name, listed=True):
    """The cell's entry of BENCHMARK.json merged over its own file.
    `listed=False` reads the file of a cell that BENCHMARK.json does not
    (or not yet) name: the tests keep a parked cell's reference honest."""
    entries = {w["name"]: w for w in benchmark_json()["workloads"]}
    if not listed and name not in entries:
        data = _load_json("workloads", f"{name}.json")
        return dict(data, name=name, traffic_name=data["driver"])
    if name not in entries:
        raise KeyError(
            f"workload {name!r} is not in BENCHMARK.json "
            f"(has {sorted(entries)})"
        )
    entry = entries[name]
    data = _load_json("workloads", f"{name}.json")
    for key in ("config", "chips"):
        if data.get(key) != entry[key]:
            raise ValueError(
                f"{name}: {key} is {data.get(key)!r} in the cell file and "
                f"{entry[key]!r} in BENCHMARK.json"
            )
    return dict(data, name=name, traffic_name=entry["traffic"])


def config(name):
    return dict(_load_json("configs", f"{name}.json"), name=name)


def reference(config_name):
    return _load_module("reference", config_name)


def driver(name):
    return _load_module("drivers", name)


def end_to_end(cell_name):
    """End-to-end metric entries this cell reports."""
    return [
        m for m in benchmark_json()["end_to_end"]
        if cell_name in m.get("workloads", [cell_name])
    ]


def per_layer(cell_name):
    """[(entry, metric file, reader module)] of the cell's per-layer metrics."""
    reported = {m["name"] for m in end_to_end(cell_name)}
    out = []
    for entry in benchmark_json()["per_layer"]:
        if cell_name not in entry.get("workloads", [cell_name]):
            continue
        if entry["moves"] not in reported:
            continue  # it moves a metric this cell does not report
        data = _load_json("metrics", f"{entry['name']}.json")
        out.append((entry, data, _load_module("readers", data["reader"])))
    return out


def peaks(device_kind):
    table = _load_json("peaks.json")["device_kinds"]
    if device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in benchmark/peaks.json "
            f"(has {sorted(table)}): add it with its source, do not default"
        )
    return table[device_kind]
