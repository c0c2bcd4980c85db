"""Continuous evaluation: a standalone process tailing a trainer's output.

The reference ran eval as its own job ("continuous_eval" mode): loop over
checkpoints_iterator(model_dir), back each checkpoint up against the
trainer's GC, evaluate every named eval dataset, and drive exporters
manually (utils/train_eval.py:584-610; backup machinery :615-683). This is
the learner/eval process topology from the reference README:44-51 — the two
jobs communicate only through the model_dir filesystem.

JAX rebuild: orbax checkpoints are the bus. `wait_for_new_checkpoint` polls
the trainer's checkpoint root; each new step is copied into
`current_eval_checkpoint/` (with retries — the trainer's max_to_keep GC can
delete a version mid-copy), restored onto this process's mesh, evaluated on
every named dataset (per-name metric streams under eval_<name>/), and handed
to the exporters.
"""

from __future__ import annotations

import os
import shutil
import time
from typing import Any, Callable, Dict, List, Optional, Union

import jax

from tensor2robot_tpu.models.abstract_model import MODE_EVAL, AbstractT2RModel
from tensor2robot_tpu.train import durability
from tensor2robot_tpu.train.metrics import MetricsWriter
from tensor2robot_tpu.train.train_eval import (
    CompiledModel,
    eval_dir_name,
    maybe_wrap_for_tpu,
    normalize_eval_generators,
    provide_input_generator_with_model_information,
    run_named_evals,
)


def _checkpoint_root(model_dir: str) -> str:
    return os.path.abspath(os.path.join(model_dir, "checkpoints"))


# Steps that already validated durable, per checkpoint root. A durable
# verdict is immutable (the manifest blesses a finalized checkpoint), so
# the poll loop only pays full manifest validation — a json parse plus a
# stat per checkpoint file — once per NEW step instead of for every step
# on every tick; on a network filesystem the difference is a sustained
# metadata storm. Bounded by keep_checkpoint_max per live root.
_DURABLE_SEEN: set = set()


def _committed_steps(checkpoint_root: str) -> List[int]:
    """DURABLE step dirs on disk, newest last. Orbax tmp dirs
    (uncommitted writes) and torn final-named dirs are excluded — this
    is the read-only side of the durability contract (train/durability):
    an eval tail must never copy or restore a torn checkpoint, but it
    also must not quarantine anything, because the trainer writing this
    dir is alive."""
    if not os.path.isdir(checkpoint_root):
        return []
    steps = []
    for entry in os.listdir(checkpoint_root):
        path = os.path.join(checkpoint_root, entry)
        if not (entry.isdigit() and os.path.isdir(path)):
            continue
        key = (checkpoint_root, int(entry))
        if key not in _DURABLE_SEEN:
            if durability.validate_step_dir(path) is not None:
                continue
            _DURABLE_SEEN.add(key)
        steps.append(int(entry))
    return sorted(steps)


def wait_for_new_checkpoint(
    model_dir: str,
    last_step: Optional[int] = None,
    timeout: float = 600.0,
    poll_interval: float = 2.0,
) -> Optional[int]:
    """Blocks until a checkpoint newer than last_step exists; returns its
    step, or None on timeout (reference checkpoints_iterator semantics)."""
    root = _checkpoint_root(model_dir)
    deadline = time.time() + timeout
    while True:
        steps = _committed_steps(root)
        fresh = [s for s in steps if last_step is None or s > last_step]
        if fresh:
            return fresh[-1]
        if time.time() >= deadline:
            return None
        time.sleep(poll_interval)


def backup_checkpoint_for_eval(
    model_dir: str,
    step: int,
    backup_name: str = "current_eval_checkpoint",
    retries: int = 3,
) -> Optional[str]:
    """Copies checkpoint `step` into model_dir/<backup_name>/<step>.

    Returns the backup ROOT (a valid orbax root holding exactly this step),
    or None if the checkpoint vanished (GC won the race) — callers then move
    on to a newer step. Reference create_backup_checkpoint_for_eval
    (utils/train_eval.py:615-683) with its retry/tmp-file behavior.
    """
    source = os.path.join(_checkpoint_root(model_dir), str(step))
    backup_root = os.path.join(os.path.abspath(model_dir), backup_name)
    dest = os.path.join(backup_root, str(step))
    for attempt in range(retries):
        if not os.path.isdir(source):
            return None
        # One backup at a time: drop older backups first (the eval job is
        # the only consumer).
        if os.path.isdir(backup_root):
            for entry in os.listdir(backup_root):
                if entry != str(step):
                    shutil.rmtree(
                        os.path.join(backup_root, entry), ignore_errors=True
                    )
        tmp = dest + ".tmp"
        shutil.rmtree(tmp, ignore_errors=True)
        try:
            shutil.copytree(source, tmp)
            # The copy only counts if the source survived it (otherwise some
            # files may be partial deletions).
            if not os.path.isdir(source):
                shutil.rmtree(tmp, ignore_errors=True)
                return None
            if os.path.isdir(dest):
                shutil.rmtree(dest, ignore_errors=True)
            os.replace(tmp, dest)
            return backup_root
        except (OSError, shutil.Error):
            shutil.rmtree(tmp, ignore_errors=True)
            time.sleep(0.5 * (attempt + 1))
    return None


def abstract_state_template(compiled: CompiledModel, example_batch):
    """ShapeDtypeStruct template of the TrainState (with shardings) — built
    once; checkpoint restores reuse it across the tail loop."""
    state = compiled.init_state(jax.random.PRNGKey(0), example_batch)
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        state,
    )


def restore_state_from_backup(
    backup_root: str, step: int, compiled: CompiledModel, example_batch=None,
    abstract=None,
):
    """Restores a TrainState from a backed-up checkpoint root."""
    if abstract is None:
        abstract = abstract_state_template(compiled, example_batch)
    ocp = durability.checkpointer()
    manager = ocp.CheckpointManager(backup_root)
    try:
        return manager.restore(step, args=ocp.args.StandardRestore(abstract))
    finally:
        manager.close()


def continuous_eval(
    t2r_model: AbstractT2RModel,
    model_dir: str,
    input_generator_eval: Union[Any, Dict[str, Any], None] = None,
    eval_steps: Optional[int] = 100,
    max_train_steps: Optional[int] = None,
    create_exporters_fn: Optional[Callable] = None,
    timeout: float = 600.0,
    poll_interval: float = 2.0,
    mesh=None,
    use_ema_for_eval: Optional[bool] = None,
    use_backup: bool = True,
) -> Dict[str, float]:
    """Tails model_dir checkpoints, evaluating (and exporting) each one.

    Runs until the evaluated step reaches max_train_steps or no new
    checkpoint appears within `timeout`. Returns the last eval metrics.
    `input_generator_eval` may be a {name: generator} map — each name gets
    its own metric stream under model_dir/eval_<name>/ (multi-eval parity).
    """
    model = maybe_wrap_for_tpu(t2r_model)
    compiled = CompiledModel(model, mesh=mesh, donate_state=False)
    if use_ema_for_eval is None:
        use_ema_for_eval = getattr(model, "use_avg_model_params", False)

    eval_generators = normalize_eval_generators(input_generator_eval)
    if not eval_generators:
        raise ValueError("continuous_eval requires at least one eval generator.")
    for generator in eval_generators.values():
        provide_input_generator_with_model_information(
            generator, model, MODE_EVAL
        )
    first_name = next(iter(eval_generators))
    example_batch = next(
        iter(eval_generators[first_name].create_dataset(MODE_EVAL))
    )

    writers = {
        name: MetricsWriter(
            os.path.join(model_dir, eval_dir_name(name)), use_tensorboard=False
        )
        for name in eval_generators
    }
    exporters = (
        create_exporters_fn(model) if create_exporters_fn is not None else []
    )

    abstract = abstract_state_template(compiled, example_batch)
    last_step: Optional[int] = None
    last_metrics: Dict[str, float] = {}
    try:
        while True:
            step = wait_for_new_checkpoint(
                model_dir, last_step, timeout=timeout, poll_interval=poll_interval
            )
            if step is None:
                break  # trainer stopped producing checkpoints
            if use_backup:
                restore_root = backup_checkpoint_for_eval(model_dir, step)
                if restore_root is None:
                    last_step = step  # GC raced us; wait for a newer one
                    continue
            else:
                restore_root = _checkpoint_root(model_dir)
            state = restore_state_from_backup(
                restore_root, step, compiled, abstract=abstract
            )
            metrics = run_named_evals(
                compiled,
                state,
                eval_generators,
                eval_steps=eval_steps,
                use_ema=use_ema_for_eval,
                step=step,
                writers=writers,
            )
            for exporter in exporters:
                exporter.maybe_export(
                    step=step,
                    state=state,
                    eval_metrics=metrics,
                    compiled=compiled,
                    model_dir=model_dir,
                )
            last_metrics = metrics
            last_step = step
            if max_train_steps is not None and step >= max_train_steps:
                break
    finally:
        for writer in writers.values():
            writer.close()
    return last_metrics
