"""tools/read_trace.py parses a real jax.profiler capture.

The tool is the offline half of the on-chip profiling loop (bench.py's
BENCH_PROFILE_DIR capture -> top-ops summary); this pins its parser
against the installed jaxlib so an API drift fails here, not in the one
serialized chip window where the capture is expensive. (It did exactly
that: the installed jax 0.4.37 exports no jax.profiler.ProfileData — the
root cause of this test's long red streak — so the tool now falls back
to its own pure-python XSpace wire parser, exercised by this capture.)
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

# The tool only ever needs CPU jax.
_CLEAN_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


# ~12s (profiler capture + jit) on 1 cpu: slow slice — tooling smoke,
# not a trainer contract.
@pytest.mark.slow
def test_read_trace_summarizes_a_capture(tmp_path):
    trace_dir = tmp_path / "trace"
    a = jnp.ones((256, 256))
    f = jax.jit(lambda a: (a @ a).sum())
    f(a)  # compile outside the capture
    with jax.profiler.trace(str(trace_dir)):
        out = f(a)
        float(out)

    proc = subprocess.run(
        [sys.executable, "tools/read_trace.py", str(trace_dir), "12"],
        capture_output=True,
        text=True,
        timeout=120,
        cwd="/root/repo",
        env=_CLEAN_ENV,
    )
    assert proc.returncode == 0, proc.stderr
    summary = json.loads(proc.stdout)
    assert "error" not in summary, summary
    assert summary["total_device_ms"] > 0
    assert summary["top_ops"], summary
    row = summary["top_ops"][0]
    assert set(row) == {"name", "total_ms", "count"}
    assert row["total_ms"] >= 0 and row["count"] >= 1
    # Category attribution: totals exist and every value is non-negative.
    assert summary["category_ms"], summary
    assert all(v >= 0 for v in summary["category_ms"].values())
    # The jitted module span is detected and normalized per step.
    if "category_ms_per_step" in summary:
        assert summary["step_count"] >= 1
        assert "module" not in summary["category_ms_per_step"]


def test_read_trace_reports_missing_dir(tmp_path):
    proc = subprocess.run(
        [sys.executable, "tools/read_trace.py", str(tmp_path / "none")],
        capture_output=True,
        text=True,
        timeout=60,
        cwd="/root/repo",
        env=_CLEAN_ENV,
    )
    assert proc.returncode == 0
    assert "no .xplane.pb" in json.loads(proc.stdout)["error"]
