"""The trainer's import closure leaves the checkpointer out.

`orbax.checkpoint` (and the `google.cloud.logging` it imports, most of its
seconds) is imported where a checkpoint is made or read, through
`durability.checkpointer()`, which records the import that actually runs as
the span `checkpoint.import`. What a process has imported is the process's,
so each case runs in a fresh interpreter and says what it saw as JSON.
"""

import json
import os
import subprocess
import sys

import pytest

from tensor2robot_tpu.train import durability, train_eval

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHECKPOINTER = ("orbax.checkpoint", "google.cloud.logging")

_CLOSURE = """
import importlib, json, sys
for name in sys.argv[1:]:
    importlib.import_module(name)
before = [m for m in {checkpointer!r} if m in sys.modules]
from tensor2robot_tpu.train import train_eval
from tensor2robot_tpu.utils import tracing
save = train_eval.ocp.args.StandardSave
print(json.dumps({{
    "before": before,
    "after": [m for m in {checkpointer!r} if m in sys.modules],
    "save": save.__module__,
    "imports": tracing.counters().get("checkpoint.import.n", 0),
}}))
""".format(checkpointer=CHECKPOINTER)

_TRAINER = """
import json, sys, tempfile
import jax
from tensor2robot_tpu.models.abstract_model import MODE_TRAIN
from tensor2robot_tpu.train import train_eval
from tensor2robot_tpu.utils import tracing
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

def imports():
    return tracing.counters().get("checkpoint.import.n", 0)

model = MockT2RModel(device_type="cpu")
generator = MockInputGenerator(batch_size=16)
train_eval.provide_input_generator_with_model_information(
    generator, model, MODE_TRAIN
)
compiled = train_eval.CompiledModel(model, donate_state=False)
batch = compiled.shard_batch(next(iter(generator.create_dataset(MODE_TRAIN))))
state = compiled.init_state(jax.random.PRNGKey(0), batch)
state, _ = compiled.train_step(state, batch, jax.random.PRNGKey(1))
jax.block_until_ready(state)
alone = {"imports": imports(), "loaded": "orbax.checkpoint" in sys.modules}

with tempfile.TemporaryDirectory() as model_dir:
    train_eval.train_eval_model(
        t2r_model=MockT2RModel(device_type="cpu"),
        input_generator_train=MockInputGenerator(batch_size=16),
        model_dir=model_dir, max_train_steps=4, save_checkpoints_steps=2,
        log_every_steps=2,
    )
    trained = imports()
    spans = tracing.snapshot()["spans"]
    for _ in range(2):
        train_eval.create_checkpoint_manager(model_dir, save_interval_steps=2).close()

dispatches = [s["start_ns"] for s in spans if s["name"] == "train.dispatch"]
print(json.dumps({
    "alone": alone,
    "trained": trained,
    "import_spans": [s for s in spans if s["name"] == "checkpoint.import"],
    "first_dispatch_ns": min(dispatches) if dispatches else None,
    "ns": tracing.counters().get("checkpoint.import.ns", 0),
    "again": imports(),
}))
"""


def _run(script, *args):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    done = subprocess.run(
        [sys.executable, "-c", script, *args], cwd=REPO_ROOT, env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize(
    "modules",
    [
        (
            "tensor2robot_tpu.parallel.mesh",
            "tensor2robot_tpu.train.train_eval",
            "tensor2robot_tpu.research.qtopt.t2r_models",
            "tensor2robot_tpu.models.sequence_lm_models",
        ),
        ("tensor2robot_tpu.config.defaults",),
    ],
    ids=["trainer_and_models", "config_defaults"],
)
def test_the_closure_leaves_the_checkpointer_out_until_it_is_read(modules):
    seen = _run(_CLOSURE, *modules)
    assert seen["before"] == []
    # `train_eval.ocp` still resolves for its importers, and only then loads.
    assert seen["save"].startswith("orbax.checkpoint")
    assert "orbax.checkpoint" in seen["after"]
    assert seen["imports"] == 1


@pytest.fixture(scope="module")
def trainer():
    return _run(_TRAINER)


def test_a_compiled_model_alone_imports_no_checkpointer(trainer):
    assert trainer["alone"] == {"imports": 0, "loaded": False}


def test_a_trainer_imports_it_once_before_its_first_dispatch(trainer):
    assert trainer["trained"] == 1
    (span,) = trainer["import_spans"]
    assert span["end_ns"] - span["start_ns"] == trainer["ns"] > 0
    assert trainer["first_dispatch_ns"] is not None
    assert span["start_ns"] < trainer["first_dispatch_ns"]


def test_a_second_manager_records_no_second_import(trainer):
    assert trainer["again"] == 1


def test_the_helper_and_the_attribute_are_the_module():
    ocp = durability.checkpointer()
    assert ocp is sys.modules["orbax.checkpoint"]
    assert train_eval.ocp is ocp
    with pytest.raises(AttributeError):
        train_eval.no_such_attribute  # noqa: B018
