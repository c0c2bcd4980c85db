"""Where the persistent compile cache lives (utils/compile_cache.py).

jax reads JAX_COMPILATION_CACHE_DIR at import, so both cases run in fresh
interpreters: with the variable set, nothing the program does — an entry
point engaging the cache, an export with its AOT build, a server start, a
plan-probe bypass — may move jax off that directory or write a cache
anywhere else; without it the directory is one fixed path inside the
checkout, the same in every process.
"""

import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PLACED_FROM_OUTSIDE = """
import os, sys
import jax
import numpy as np

want = os.environ["JAX_COMPILATION_CACHE_DIR"]

def check(where):
    got = jax.config.jax_compilation_cache_dir
    assert got == want, (where, got, want)

from tensor2robot_tpu.utils import compile_cache
from tensor2robot_tpu.export.exporters import LatestExporter
from tensor2robot_tpu.predictors.exported_savedmodel_predictor import (
    ExportedSavedModelPredictor,
)
from tensor2robot_tpu.serving import PolicyServer
from tensor2robot_tpu.train.train_eval import CompiledModel
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

check("import")
assert compile_cache.enable_compile_cache() == want
check("entry point")

model = MockT2RModel(device_type="cpu")
generator = MockInputGenerator(batch_size=8)
generator.set_specification_from_model(model, "train")
compiled = CompiledModel(model, donate_state=False)
state = compiled.init_state(
    jax.random.PRNGKey(0), next(iter(generator.create_dataset("train")))
)
exporter = LatestExporter(name="latest", warmup_batch_sizes=(1, 2))
path = exporter.maybe_export(
    step=1, state=state, eval_metrics={"loss": 1.0}, compiled=compiled,
    model_dir=sys.argv[1],
)
assert os.path.isdir(os.path.join(path, "aot")), "export built no AOT"
check("AOT build")

with compile_cache.compile_cache_bypass():
    check("inside the plan-probe bypass")
    assert not jax.config.jax_enable_compilation_cache
check("after the plan-probe bypass")
assert jax.config.jax_enable_compilation_cache

predictor = ExportedSavedModelPredictor(
    export_dir=exporter.export_root(sys.argv[1])
)
assert predictor.restore()
# A ladder wider than the AOT table: bucket 4 rides the compile tier.
with PolicyServer(predictor, batch_buckets=(1, 2, 4), max_wait_ms=1).start() as server:
    sources = server.snapshot()["prewarm_source"]
    server.call({"x": np.zeros((3,), np.float32)}, timeout=60)
assert sources == {"1": "aot", "2": "aot", "4": "cache"}, sources
check("server start")
assert os.listdir(want), "nothing was cached under the placed directory"
print("PLACED_OK")
"""


def test_directory_placed_from_outside_is_never_moved(tmp_path):
    cache_dir = tmp_path / "outside"
    checkout_cache = os.path.join(REPO_ROOT, ".jax_cache")
    before = (
        sorted(os.listdir(checkout_cache))
        if os.path.isdir(checkout_cache) else None
    )
    proc = subprocess.run(
        [sys.executable, "-c", _PLACED_FROM_OUTSIDE, str(tmp_path / "model")],
        capture_output=True, text=True, timeout=600, cwd=REPO_ROOT,
        env={**os.environ, "JAX_COMPILATION_CACHE_DIR": str(cache_dir)},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "PLACED_OK" in proc.stdout
    after = (
        sorted(os.listdir(checkout_cache))
        if os.path.isdir(checkout_cache) else None
    )
    assert after == before, "the in-checkout cache was written to"


def test_unset_resolves_to_one_fixed_path_inside_the_checkout():
    """Two processes — this one and a fresh interpreter started from
    another working directory — resolve the same path, from the
    package's location."""
    from tensor2robot_tpu.utils.compile_cache import CHECKOUT_CACHE_DIR

    env = {
        k: v for k, v in os.environ.items()
        if k != "JAX_COMPILATION_CACHE_DIR"
    }
    env["PYTHONPATH"] = REPO_ROOT
    script = (
        "from tensor2robot_tpu.utils.compile_cache import "
        "enable_compile_cache; import jax; d = enable_compile_cache(); "
        "assert jax.config.jax_compilation_cache_dir == d; print(d)"
    )
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=120, env=env, check=True,
        cwd=os.path.join(REPO_ROOT, "tests"),
    )
    assert (
        proc.stdout.strip().splitlines()[-1]
        == CHECKOUT_CACHE_DIR
        == os.path.join(REPO_ROOT, ".jax_cache")
    )
