"""Timer-based async export during training.

Behavioral reference: tensor2robot/hooks/async_export_hook_builder.py:41-133
(`default_create_export_fn` + `AsyncExportHookBuilder`): every `save_secs`
the current weights are exported as a serving artifact (with t2r_assets)
without blocking the device step loop — the reference used
AsyncCheckpointSaverHook; here the export runs on a single worker thread
off the host loop, snapshotting the (immutable) jax arrays. If a previous
export is still running, the tick is skipped rather than queued, so a slow
filesystem can never build a backlog.
"""

from __future__ import annotations

import concurrent.futures
import logging
import time
from typing import Callable, Optional, Sequence

from tensor2robot_tpu.config import configurable
from tensor2robot_tpu.export.export_generators import DefaultExportGenerator
from tensor2robot_tpu.export.saved_model import save_exported_model
from tensor2robot_tpu.hooks.checkpoint_hooks import CheckpointExportListener
from tensor2robot_tpu.hooks.hook_builder import Hook, HookBuilder


def default_create_export_fn(
    model,
    compiled,
    export_generator=None,
    warmup_batch_sizes: Sequence[int] = (),
    quantize_weights: bool = False,
    quantize_bits: int = 8,
) -> Callable:
    """Builds fn(state, export_dir, global_step) -> path exporting a serving
    artifact with the t2r-assets spec contract (reference
    default_create_export_fn :41-82). quantize_weights selects int8
    weight-only artifacts (export/quantization.py), matching the Exporter
    policies' flag."""
    generator = export_generator or DefaultExportGenerator()
    generator.set_specification_from_model(model)

    def export_fn(state, export_dir: str, global_step: int) -> str:
        use_ema = getattr(model, "use_avg_model_params", False)
        variables = state.export_variables(use_ema=use_ema)
        serving_fn = generator.create_serving_fn(
            compiled, variables, quantize_weights=quantize_weights,
            quantize_bits=quantize_bits,
        )
        path = save_exported_model(
            export_dir,
            variables=variables,
            feature_spec=generator.serving_input_spec(),
            label_spec=generator.label_spec,
            global_step=global_step,
            predict_fn=serving_fn,
            example_features=generator.create_example_features(),
            quantize_weights=quantize_weights,
            quantize_bits=quantize_bits,
            # Bucket contract for the policy server (serving/buckets.py).
            metadata={"warmup_batch_sizes": list(warmup_batch_sizes)},
        )
        if warmup_batch_sizes:
            generator.create_warmup_requests_numpy(warmup_batch_sizes, path)
        return path

    return export_fn


class AsyncExportHook(Hook):
    """Exports every `save_secs` seconds via a listener, off the host loop."""

    def __init__(
        self,
        listener: CheckpointExportListener,
        state_export_fn: Callable,
        save_secs: float,
    ):
        self._listener = listener
        self._state_export_fn = state_export_fn
        self._save_secs = save_secs
        self._last_export_time: Optional[float] = None
        self._executor = concurrent.futures.ThreadPoolExecutor(max_workers=1)
        self._pending: Optional[concurrent.futures.Future] = None

    def _submit(self, state, step: int) -> None:
        if self._pending is not None and not self._pending.done():
            logging.warning(
                "Skipping export at step %d: previous export still running.",
                step,
            )
            return
        if self._pending is not None:
            exc = self._pending.exception()
            if exc is not None:
                logging.error("Previous async export failed: %s", exc)
        # Snapshot with fresh device buffers: train_step donates the state's
        # arrays, so the worker thread must not reference buffers the next
        # step will free ("Array has been deleted" otherwise). jnp.copy is
        # an on-device copy — cheap, no host sync.
        import jax
        import jax.numpy as jnp

        state = jax.tree_util.tree_map(jnp.copy, state)
        self._bind_state(state)
        self._pending = self._executor.submit(
            self._listener.after_save, step
        )

    def _bind_state(self, state) -> None:
        # The listener's export_fn needs the state; bind the snapshot via
        # the closure the builder installed.
        self._state_export_fn.state = state

    def on_train_begin(self, ctx) -> None:
        self._last_export_time = time.time()

    def after_step(self, ctx) -> None:
        now = time.time()
        if (
            self._last_export_time is None
            or now - self._last_export_time >= self._save_secs
        ):
            self._last_export_time = now
            self._submit(ctx.state, ctx.step)

    def on_train_end(self, ctx) -> None:
        # Final synchronous export with the terminal weights.
        if self._pending is not None:
            concurrent.futures.wait([self._pending])
        self._bind_state(ctx.state)
        self._listener.after_save(ctx.step)
        self._executor.shutdown(wait=True)


@configurable("AsyncExportHookBuilder")
class AsyncExportHookBuilder(HookBuilder):
    """Periodic async serving export (reference AsyncExportHookBuilder
    :86-133)."""

    def __init__(
        self,
        export_dir: str,
        save_secs: float = 90.0,
        num_versions: Optional[int] = 3,
        export_generator=None,
        warmup_batch_sizes: Sequence[int] = (),
        quantize_weights: bool = False,
    ):
        self._export_dir = export_dir
        self._save_secs = save_secs
        self._num_versions = num_versions
        self._export_generator = export_generator
        self._warmup_batch_sizes = tuple(warmup_batch_sizes)
        self._quantize_weights = quantize_weights

    def _make_listener_and_state_fn(self, t2r_model, trainer):
        export_fn = default_create_export_fn(
            t2r_model,
            trainer,
            export_generator=self._export_generator,
            warmup_batch_sizes=self._warmup_batch_sizes,
            quantize_weights=self._quantize_weights,
        )

        def state_export_fn(export_dir: str, global_step: int) -> str:
            return export_fn(state_export_fn.state, export_dir, global_step)

        state_export_fn.state = None
        return state_export_fn

    def create_hooks(self, t2r_model, trainer=None):
        if not self._export_dir:
            return []
        state_export_fn = self._make_listener_and_state_fn(t2r_model, trainer)
        listener = CheckpointExportListener(
            export_fn=state_export_fn,
            export_dir=self._export_dir,
            num_versions=self._num_versions,
        )
        return [
            AsyncExportHook(listener, state_export_fn, self._save_secs)
        ]
