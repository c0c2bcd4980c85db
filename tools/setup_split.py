"""One run of a benchmark cell that also says the set-up split.

    chiprun -- python tools/setup_split.py --workload <cell> --seed <n> --seconds 30

`benchmark/run.py` reads per-layer metrics in a `--trace 1` run only. The
five that read set-up (`setup.step_build_s`, `.step_trace_lower_s`,
`.step_compile_s`, `setup.init_state_programs`, `setup.program_import_s`)
need no trace: this runs the cell exactly as `benchmark/run.py` does, with
whatever `--trace` says, and has the readers say their earlier lines and
their values as soon as the driver returns, beside the driver's own
`set-up:` stage marks, and whether the process imported the checkpointer
before its window (the recorder's `checkpoint.import` counters: 0 where the
cell never checkpoints). The result line is the harness's own.
"""

from __future__ import annotations

import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]

import manifest  # noqa: E402
import program_spans  # noqa: E402
import run as bench_run  # noqa: E402
import setup_spans  # noqa: E402

METRICS = (
    "setup.step_build_s", "setup.step_trace_lower_s", "setup.step_compile_s",
    "setup.init_state_programs", "setup.program_import_s",
)


def saying_the_split(find_driver):
    """`manifest.driver` for one run: the first driver looked up, which is
    the cell's own (`run_cell` asks for it before it can load another, as
    the sparse-expert driver loads the token driver and sets names on it),
    gets the five readers behind its `run`; every later lookup is plain."""
    asked = []

    def driver(name):
        if asked:
            return find_driver(name)
        asked.append(name)  # before the lookup: loading a driver may load another
        found = find_driver(name)

        def run(run):
            found.run(run)
            for metric in METRICS:
                data = manifest._load_json("metrics", f"{metric}.json")
                value = manifest._load_module("readers", data["reader"]).read(run)
                run.reporter.say(f"metric {metric}: {value!r} {data['unit']}")
            imports = [
                s for s in setup_spans.before_window(run)
                if s["name"] == "checkpoint.import"
            ]
            counters = (program_spans.recorded(run) or {}).get("counters", {})
            run.reporter.say(
                f"checkpoint.import: {len(imports)} before the window, "
                f"{sum(s['end_ns'] - s['start_ns'] for s in imports) / 1e9:.3f} s; "
                f"n={counters.get('checkpoint.import.n', 0)} in the process"
            )

        return types.SimpleNamespace(run=run)

    return driver


def main(argv=None):
    manifest.driver = saying_the_split(manifest.driver)
    return bench_run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
