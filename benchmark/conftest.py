"""Tiny presets found as files, for every test under benchmark/.

`tests/tiny.py` keeps the presets of the cells it was written with in two
dicts. A configuration added since brings
`tests/presets/<config>.json` ({"arguments", "model", "limits"}: sizes
only, as tiny.py's own) and this file puts it into those dicts before any
test module is collected, so that the tests over every cell of
BENCHMARK.json find it whichever files are run.
"""

import glob
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
for _path in (BENCH_DIR, os.path.join(BENCH_DIR, "tests")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import tiny  # noqa: E402

for _path in sorted(glob.glob(os.path.join(BENCH_DIR, "tests", "presets", "*.json"))):
    _name = os.path.splitext(os.path.basename(_path))[0]
    with open(_path) as _file:
        _preset = json.load(_file)
    tiny.TINY_MODEL[_name] = {
        "arguments": _preset["arguments"], "model": _preset["model"],
    }
    tiny.TINY_LIMITS[_name] = _preset["limits"]
