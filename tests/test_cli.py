"""The three CLI binaries as real OS processes (reference bin/ parity).

Library-level config execution is covered elsewhere (test_pose_env runs
every shipped gin config through train_eval_model); these tests close the
last gap between "the function works" and "the shipped command works":
each binary runs as `python -m tensor2robot_tpu.bin.<name>` in a fresh
interpreter with real flags, and the test asserts the artifacts the
reference topology relies on (README:44-51: collect writes shards, the
trainer writes checkpoints, continuous-eval writes eval events).

The children run on the CPU because the environment says so
(JAX_PLATFORMS=cpu, exported by tests/conftest.py), and keep their
compile cache out of the checkout.
"""

import glob
import os
import subprocess
import sys

import pytest


def _run_cli(module, args, cache_dir, timeout=420):
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    proc = subprocess.run(
        [sys.executable, "-m", module, *args],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, (
        f"{module} failed rc={proc.returncode}\n"
        f"stdout: {proc.stdout[-2000:]}\nstderr: {proc.stderr[-2000:]}"
    )
    return proc


def _config_dir():
    from tensor2robot_tpu.research import pose_env

    return os.path.join(os.path.dirname(pose_env.__file__), "configs")


@pytest.mark.slow
def test_collect_then_train_then_eval_clis(tmp_path):
    """The full process topology, one CLI at a time: random collect ->
    trainer -> continuous eval, each a separate OS process exchanging
    data only through the filesystem (the reference's message bus)."""
    collect_dir = tmp_path / "collect"
    cache_dir = tmp_path / "jax_cache"
    _run_cli(
        "tensor2robot_tpu.bin.run_collect_eval",
        [
            f"--root_dir={collect_dir}",
            f"--gin_configs={os.path.join(_config_dir(), 'run_random_collect.gin')}",
            "--gin_bindings=collect_eval_loop.num_collect = 12",
        ],
        cache_dir,
    )
    shards = glob.glob(str(collect_dir / "policy_collect" / "*.tfrecord"))
    if not shards:  # layout fallback: any shard under the root
        shards = glob.glob(str(collect_dir / "**" / "*.tfrecord"), recursive=True)
    assert shards, f"collect CLI wrote no shards under {collect_dir}"

    run_dir = tmp_path / "run"
    _run_cli(
        "tensor2robot_tpu.bin.run_t2r_trainer",
        [
            f"--gin_configs={os.path.join(_config_dir(), 'run_train_reg.gin')}",
            f"--gin_bindings=TRAIN_DATA = {shards!r}",
            f"--gin_bindings=EVAL_DATA = {shards!r}",
            "--gin_bindings=train_eval_model.max_train_steps = 2",
            "--gin_bindings=train_eval_model.eval_steps = 1",
            "--gin_bindings=train_input_generator/DefaultRecordInputGenerator.batch_size = 4",
            "--gin_bindings=eval_input_generator/DefaultRecordInputGenerator.batch_size = 4",
            "--gin_bindings=PoseEnvRegressionModel.device_type = 'cpu'",
            f"--gin_bindings=train_eval_model.model_dir = {str(run_dir)!r}",
        ],
        cache_dir,
    )
    assert os.listdir(cache_dir), "trainer CLI engaged no compile cache"
    assert os.path.isdir(run_dir / "checkpoints"), "trainer CLI wrote no checkpoints"
    operative = glob.glob(str(run_dir / "operative_config*"))
    assert operative, "trainer CLI wrote no operative config artifact"

    _run_cli(
        "tensor2robot_tpu.bin.run_continuous_eval",
        [
            f"--gin_configs={os.path.join(_config_dir(), 'run_train_reg.gin')}",
            f"--gin_bindings=EVAL_DATA = {shards!r}",
            "--gin_bindings=eval_input_generator/DefaultRecordInputGenerator.batch_size = 4",
            "--gin_bindings=PoseEnvRegressionModel.device_type = 'cpu'",
            "--gin_bindings=continuous_eval.t2r_model = @PoseEnvRegressionModel()",
            "--gin_bindings=continuous_eval.input_generator_eval = %EVAL_INPUT_GENERATOR",
            f"--gin_bindings=continuous_eval.model_dir = {str(run_dir)!r}",
            "--gin_bindings=continuous_eval.eval_steps = 1",
            "--gin_bindings=continuous_eval.max_train_steps = 2",
            "--gin_bindings=continuous_eval.timeout = 60.0",
        ],
        cache_dir,
    )
    eval_artifacts = glob.glob(str(run_dir / "eval*")) + glob.glob(
        str(run_dir / "*" / "eval*")
    )
    assert eval_artifacts, f"continuous-eval CLI wrote nothing under {run_dir}"
