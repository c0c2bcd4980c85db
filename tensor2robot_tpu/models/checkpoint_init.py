"""Default warm-start: partial restore from a foreign orbax checkpoint.

The reference shipped default_init_from_checkpoint_fn — assignment-map
restore with allow_partial_restore and a filter_restorables_fn so a model
can warm-start from a checkpoint of a *different* model
(models/abstract_model.py:86-126, exercised by train_eval_test.py:204). The
JAX rebuild matches leaves by '/'-joined tree path over orbax checkpoints:

    model = MyModel(init_from_checkpoint_fn=default_init_from_checkpoint_fn(
        "/path/to/other/model_dir",
        assignment_map={"encoder/": "tower/"},   # dest prefix -> src prefix
        allow_partial_restore=True,
    ))

Leaves present in both trees (after prefix rewriting) with matching shapes
are taken from the checkpoint (cast to the destination dtype); everything
else keeps its fresh initialization. Missing leaves raise unless
allow_partial_restore; shape mismatches always raise (silently keeping a
mis-shaped leaf would corrupt the warm start).
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, Mapping, Optional

import jax
import numpy as np

from tensor2robot_tpu.train import durability


def path_str(key_path) -> str:
    """'/'-joined string form of a jax tree key path (DictKey/GetAttrKey/
    SequenceKey all reduce to their key/name/index)."""
    parts = []
    for entry in key_path:
        if hasattr(entry, "key"):
            parts.append(str(entry.key))
        elif hasattr(entry, "name"):
            parts.append(str(entry.name))
        elif hasattr(entry, "idx"):
            parts.append(str(entry.idx))
        else:
            parts.append(str(entry))
    return "/".join(parts)


def flatten_with_paths(tree) -> Dict[str, Any]:
    """Flattens a pytree to {'/'.joined/path: leaf}."""
    return {
        path_str(key_path): leaf
        for key_path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def _checkpoint_root_and_step(
    checkpoint_path: str, step: Optional[int]
) -> tuple[str, int]:
    """Accepts a model_dir, a checkpoints root, or a specific step dir."""
    path = os.path.abspath(checkpoint_path)
    nested = os.path.join(path, "checkpoints")
    if os.path.isdir(nested):
        path = nested
    base = os.path.basename(path)
    if base.isdigit():
        if step is not None and step != int(base):
            raise FileNotFoundError(
                f"Requested step {step} but {checkpoint_path!r} is the "
                f"step-{base} directory."
            )
        return os.path.dirname(path), int(base)
    steps = [
        int(entry)
        for entry in (os.listdir(path) if os.path.isdir(path) else [])
        if entry.isdigit() and os.path.isdir(os.path.join(path, entry))
    ]
    if not steps:
        raise FileNotFoundError(
            f"No checkpoint steps under {checkpoint_path!r}"
        )
    if step is None:
        return path, max(steps)
    if step not in steps:
        raise FileNotFoundError(
            f"Step {step} not in {sorted(steps)} under {checkpoint_path!r}"
        )
    return path, step


def load_checkpoint_variables(
    checkpoint_path: str,
    step: Optional[int] = None,
    use_ema: bool = False,
) -> Dict[str, Any]:
    """Loads a TrainState checkpoint's variables as a raw pytree.

    use_ema swaps the averaged params in as 'params' (the reference's
    swapping-saver semantics: warm starts consume the averaged weights).
    """
    root, resolved = _checkpoint_root_and_step(checkpoint_path, step)
    ocp = durability.checkpointer()
    manager = ocp.CheckpointManager(root)
    try:
        # Restore against the checkpoint's own metadata with host-placed
        # leaves: a bare StandardRestore() replays the TRAINER topology's
        # sharding file and fails whenever the warm-starting job runs on a
        # different device count (pod checkpoint -> single-host finetune).
        from tensor2robot_tpu.train.state import checkpoint_metadata_template

        try:
            abstract = checkpoint_metadata_template(root, resolved)
        except Exception:  # noqa: BLE001 — metadata probing is best-effort
            abstract = None
        tree = manager.restore(
            resolved, args=ocp.args.StandardRestore(abstract)
        )
    finally:
        manager.close()
    variables = tree.get("variables", tree) if isinstance(tree, dict) else tree
    if use_ema:
        if not isinstance(tree, dict) or tree.get("ema_params") is None:
            raise ValueError(
                f"use_ema=True but checkpoint {checkpoint_path!r} holds no "
                "ema_params (trained without use_avg_model_params)."
            )
        variables = dict(variables)
        # ema_as_tree: a quantized ZeRO-2 checkpoint stores the EMA as
        # one block-padded 1-D vector; unravel it against the checkpoint's
        # own params structure before path-based matching sees it.
        from tensor2robot_tpu.train.state import ema_as_tree

        variables["params"] = ema_as_tree(
            tree["ema_params"], variables["params"]
        )
    return variables


def _rewrite(path: str, assignment_map: Optional[Mapping[str, str]]) -> Optional[str]:
    """Maps a destination path to its source path. Longest-prefix match;
    mapping a prefix to None drops the leaf from restoring."""
    if not assignment_map:
        return path
    best = None
    for dest_prefix in sorted(assignment_map, key=len, reverse=True):
        if path.startswith(dest_prefix) or dest_prefix == "":
            best = dest_prefix
            break
    if best is None:
        return path
    src_prefix = assignment_map[best]
    if src_prefix is None:
        return None
    return src_prefix + path[len(best):]


def default_init_from_checkpoint_fn(
    checkpoint_path: str,
    step: Optional[int] = None,
    assignment_map: Optional[Mapping[str, str]] = None,
    filter_restorables_fn: Optional[Callable[[str], bool]] = None,
    allow_partial_restore: bool = False,
    use_ema: bool = False,
) -> Callable[[Dict[str, Any]], Dict[str, Any]]:
    """Builds an init_from_checkpoint_fn for AbstractT2RModel.

    Args mirror the reference (models/abstract_model.py:86-126):
      checkpoint_path: foreign model_dir / checkpoints root / step dir.
      step: specific step (default latest).
      assignment_map: destination-prefix -> source-prefix rewrites applied to
        '/'-joined variable paths ('params/dense/kernel'); a None source
        drops the subtree from restoring.
      filter_restorables_fn: path -> bool; False keeps the fresh init (the
        reference's filter_restorables_fn).
      allow_partial_restore: tolerate leaves missing from the checkpoint.
      use_ema: restore averaged params as 'params'.
    """

    def init_fn(variables: Dict[str, Any]) -> Dict[str, Any]:
        source_flat = flatten_with_paths(
            load_checkpoint_variables(checkpoint_path, step=step, use_ema=use_ema)
        )
        paths_and_leaves, treedef = jax.tree_util.tree_flatten_with_path(variables)
        new_leaves = []
        missing = []
        for key_path, leaf in paths_and_leaves:
            path = path_str(key_path)
            if filter_restorables_fn is not None and not filter_restorables_fn(path):
                new_leaves.append(leaf)
                continue
            source_path = _rewrite(path, assignment_map)
            if source_path is None:
                new_leaves.append(leaf)
                continue
            if source_path not in source_flat:
                missing.append(f"{path} (from {source_path})")
                new_leaves.append(leaf)
                continue
            value = source_flat[source_path]
            dest_shape = tuple(getattr(leaf, "shape", ()))
            if tuple(np.shape(value)) != dest_shape:
                raise ValueError(
                    f"Warm-start shape mismatch for {path!r}: checkpoint "
                    f"{tuple(np.shape(value))} vs model {dest_shape}"
                )
            dtype = getattr(leaf, "dtype", None)
            new_leaves.append(
                np.asarray(value, dtype=dtype) if dtype is not None else value
            )
        if missing and not allow_partial_restore:
            raise KeyError(
                "Warm-start leaves missing from checkpoint (pass "
                f"allow_partial_restore=True to keep their init): {missing[:10]}"
            )
        return jax.tree_util.tree_unflatten(treedef, new_leaves)

    return init_fn
