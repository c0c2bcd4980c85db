"""Crash-consistent checkpoint durability: detect, skip, and quarantine
torn checkpoints; publish a verifiable durability manifest per step.

Why orbax's atomic rename is not enough
---------------------------------------
Orbax writes each step into `<step>.orbax-checkpoint-tmp-<ts>/` and
commits it with one atomic rename to `<step>/`, so a SIGKILL mid-save
normally leaves only a tmp dir that `CheckpointManager` excludes from
its step listing. But the *final-named* form carries no integrity
evidence: a partially copied backup, a crashed filesystem without
fsync, or a half-deleted GC victim all present as `<step>/` with files
missing — and `CheckpointManager.latest_step()` happily returns such a
directory (verified against orbax 0.7.0: an empty `4/` wins
`latest_step` and the restore dies with an unrelated error). A trainer
that trusts `latest_step()` therefore cannot promise "resume from the
last durable checkpoint".

The barrier
-----------
After orbax *finalizes* step S (rename done — saves are serialized, so
issuing save S+1 or calling `wait_until_finished()` is the barrier),
the trainer writes `<step>/t2r_durable.json`: a manifest of every file
in the checkpoint with its size, written tmp-then-`os.replace` so the
manifest itself is atomic. Validation is then:

  * name carries the orbax tmp suffix        -> torn (uncommitted)
  * manifest present, inventory verifies     -> durable
  * manifest present, any file missing/short -> torn
  * no manifest: structural fallback — the orbax step metadata and the
    item's `_METADATA`/`manifest.ocdbt` must exist (covers the window
    between orbax's rename and our manifest write, and checkpoints
    written before this module existed)

Writers (the trainer owns `checkpoints/`) additionally *quarantine*
torn directories into `<model_dir>/checkpoints.quarantine/` at startup:
leaving a torn `<step>/` in place would collide with the re-save of
that step after the replayed window. Readers (continuous_eval, serving)
only ever *skip* — a tmp dir they see may be a live write.

Chaos hooks: `train_eval.checkpoint_and_eval` fires the `save` site
right after the async save is issued (a `kill` clause there is the
SIGKILL-mid-orbax-save fault) and `restore_or_init_state` fires
`restore` before reading (slow-restore / exception injection).
"""

from __future__ import annotations

import json
import logging
import os
import shutil
import sys
import threading
import time
from typing import List, Optional, Tuple

from tensor2robot_tpu.utils import tracing

MANIFEST_NAME = "t2r_durable.json"
QUARANTINE_DIRNAME = "checkpoints.quarantine"
# Mirrors orbax.checkpoint.utils.TMP_DIR_SUFFIX (0.7.0); inlined so
# validation stays importable without pulling in orbax (readers such as
# fleet health probes run in slim processes).
_ORBAX_TMP_MARKER = ".orbax-checkpoint-tmp-"
_STEP_METADATA = "_CHECKPOINT_METADATA"

_CHECKPOINTER_LOCK = threading.Lock()


def checkpointer():
    """`orbax.checkpoint`, imported where a checkpoint is made or read.

    The import costs seconds (its `google.cloud.logging` most of them), so
    a process that never checkpoints never pays it. The process's one
    import that actually runs is the span `checkpoint.import`, a child of
    the calling thread's open span: its counters say whether and where the
    checkpointer was paid.
    """
    with _CHECKPOINTER_LOCK:
        if "orbax.checkpoint" not in sys.modules:
            with tracing.span("checkpoint.import"):
                import orbax.checkpoint  # noqa: F401
    import orbax.checkpoint as ocp

    return ocp


def checkpoint_root(model_dir: str) -> str:
    return os.path.abspath(os.path.join(model_dir, "checkpoints"))


def quarantine_root(model_dir: str) -> str:
    return os.path.abspath(os.path.join(model_dir, QUARANTINE_DIRNAME))


def _inventory(step_dir: str) -> List[Tuple[str, int]]:
    """(relpath, size) for every regular file under step_dir, sorted,
    excluding the manifest itself."""
    entries: List[Tuple[str, int]] = []
    for dirpath, _, filenames in os.walk(step_dir):
        for name in filenames:
            full = os.path.join(dirpath, name)
            rel = os.path.relpath(full, step_dir)
            if rel == MANIFEST_NAME:
                continue
            entries.append((rel, os.path.getsize(full)))
    entries.sort()
    return entries


def write_manifest(step_dir: str) -> None:
    """Publishes the durability manifest for a FINALIZED step dir.

    Must only be called after the orbax commit barrier for this step
    (save of the next step issued, or wait_until_finished returned);
    writing earlier would bless a checkpoint that is still streaming.
    """
    files = _inventory(step_dir)
    payload = {
        "version": 1,
        "files": [{"path": p, "size": s} for p, s in files],
    }
    tmp = os.path.join(step_dir, MANIFEST_NAME + ".tmp")
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, os.path.join(step_dir, MANIFEST_NAME))


def validate_step_dir(step_dir: str) -> Optional[str]:
    """Returns None when the directory is a durable checkpoint, else a
    human-readable torn-reason. Read-only (safe on live trees)."""
    name = os.path.basename(step_dir.rstrip(os.sep))
    if _ORBAX_TMP_MARKER in name:
        return "orbax tmp dir (uncommitted write)"
    if not os.path.isdir(step_dir):
        return "not a directory"
    manifest_path = os.path.join(step_dir, MANIFEST_NAME)
    if os.path.exists(manifest_path):
        try:
            with open(manifest_path) as f:
                manifest = json.load(f)
            declared = manifest["files"]
        except (OSError, ValueError, KeyError) as err:
            return f"unreadable durability manifest: {err}"
        for entry in declared:
            path = os.path.join(step_dir, entry["path"])
            if not os.path.isfile(path):
                return f"manifest file missing: {entry['path']}"
            actual = os.path.getsize(path)
            if actual != entry["size"]:
                return (
                    f"manifest size mismatch: {entry['path']} is {actual} "
                    f"bytes, manifest says {entry['size']}"
                )
        return None
    # No manifest (pre-manifest checkpoint, or crash landed between
    # orbax's rename and the manifest write): structural fallback.
    if not os.path.isfile(os.path.join(step_dir, _STEP_METADATA)):
        return f"no {_STEP_METADATA} (incomplete step directory)"
    items = [
        entry
        for entry in os.listdir(step_dir)
        if os.path.isdir(os.path.join(step_dir, entry))
    ]
    if not items:
        return "no checkpoint items in step directory"
    for item in items:
        item_dir = os.path.join(step_dir, item)
        if not os.path.isfile(os.path.join(item_dir, "_METADATA")):
            return f"item {item!r} missing _METADATA"
    return None


def _step_entries(root: str) -> List[Tuple[int, str]]:
    """(step, dirname) for every final-named step dir under root."""
    if not os.path.isdir(root):
        return []
    out = []
    for entry in os.listdir(root):
        if entry.isdigit() and os.path.isdir(os.path.join(root, entry)):
            out.append((int(entry), entry))
    out.sort()
    return out


def durable_steps(model_dir: str) -> List[int]:
    """Steps under model_dir/checkpoints that validate as durable,
    ascending. Read-only — safe for concurrent readers of a live dir."""
    root = checkpoint_root(model_dir)
    return [
        step
        for step, name in _step_entries(root)
        if validate_step_dir(os.path.join(root, name)) is None
    ]


def latest_durable_step(model_dir: str) -> Optional[int]:
    steps = durable_steps(model_dir)
    return steps[-1] if steps else None


def latest_durable_step_in(manager) -> Optional[int]:
    """Newest step in an orbax CheckpointManager's root that validates
    as DURABLE.

    `manager.latest_step()` trusts directory names: a torn final-named
    dir (partial copy, fsync-less crash) wins it and the restore dies —
    or loads garbage. Walk newest-first, skip anything torn (read-only:
    never quarantines, so concurrent readers are safe on a live dir).

    The manager is duck-typed (`all_steps()` + `directory`) so this
    module stays importable without orbax — serving-side readers
    (checkpoint_predictor) call it from slim processes.
    """
    root = str(manager.directory)
    for step in sorted(manager.all_steps(), reverse=True):
        reason = validate_step_dir(os.path.join(root, str(step)))
        if reason is None:
            return int(step)
        logging.warning(
            "Skipping torn checkpoint %s/%s: %s", root, step, reason
        )
    return None


def sweep_torn_checkpoints(model_dir: str) -> List[Tuple[str, str]]:
    """WRITER-ONLY startup sweep: moves torn step dirs (and stale orbax
    tmp dirs) into model_dir/checkpoints.quarantine/, so a resumed run
    can re-save the replayed steps without colliding with the wreckage.
    Never deletes — the quarantined tree is the crash forensics.

    Returns [(dirname, reason)] for everything quarantined. Must only be
    called by the process that OWNS the checkpoint dir (the trainer,
    before it opens its CheckpointManager): a reader sweeping a live dir
    would quarantine the write in progress.
    """
    root = checkpoint_root(model_dir)
    if not os.path.isdir(root):
        return []
    report: List[Tuple[str, str]] = []
    for entry in sorted(os.listdir(root)):
        path = os.path.join(root, entry)
        if not os.path.isdir(path):
            continue
        if entry.isdigit():
            reason = validate_step_dir(path)
        elif _ORBAX_TMP_MARKER in entry:
            reason = "orbax tmp dir (uncommitted write)"
        else:
            continue  # not checkpoint-shaped; leave it alone
        if reason is None:
            continue
        quarantine = quarantine_root(model_dir)
        os.makedirs(quarantine, exist_ok=True)
        # Monotonic-ish unique destination; collisions only matter for
        # repeated crashes at the same step, where the suffix saves us.
        dest = os.path.join(quarantine, f"{entry}.{int(time.time() * 1e3)}")
        while os.path.exists(dest):
            dest += "x"
        shutil.move(path, dest)
        logging.warning(
            "Quarantined torn checkpoint %s -> %s (%s)", path, dest, reason
        )
        report.append((entry, reason))
    return report


def publish_durable(model_dir: str, step: int) -> bool:
    """Writes the manifest for `step` if its dir exists, validates
    structurally, and does not already carry one. Returns True when a
    manifest is present after the call. Call only past the orbax commit
    barrier for this step."""
    step_dir = os.path.join(checkpoint_root(model_dir), str(step))
    if not os.path.isdir(step_dir):
        return False
    if os.path.exists(os.path.join(step_dir, MANIFEST_NAME)):
        return True
    if validate_step_dir(step_dir) is not None:
        # Structurally torn even though finalized-named: refuse to bless.
        return False
    write_manifest(step_dir)
    return True
