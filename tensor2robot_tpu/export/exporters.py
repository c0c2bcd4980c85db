"""Train-time exporters: Latest/Best export policies + version GC.

Parity with the reference's exporter factory (utils/train_eval.py:295-385):
LatestExporter writes every eval's weights; BestExporter gates on a metric
compare fn (`create_valid_result_smaller/larger`, train_eval.py:206-291) and
persists its best-seen value so resume keeps the gate. Old versions are
garbage-collected deque-style (hooks/checkpoint_hooks.py:31-48).

The trainer calls `exporter.maybe_export(step=, state=, eval_metrics=,
compiled=)` after each evaluation (train/train_eval.py run_eval_and_export).
"""

from __future__ import annotations

import json
import os
import shutil
from typing import Any, Callable, Dict, List, Optional, Sequence

from tensor2robot_tpu.config import configurable
from tensor2robot_tpu.export.export_generators import (
    AbstractExportGenerator,
    DefaultExportGenerator,
)
from tensor2robot_tpu.export.saved_model import (
    list_export_dirs,
    save_exported_model,
)

DEFAULT_METRIC = "loss"


def _native_pre_gate(
    fn,
    rebuild_dequant: Callable[[], Any],
    fp32_outputs,
    warmup_batches,
    tolerance: float,
):
    """Per-regime parity triage for native low-precision matmuls.

    The parity gate is the arbiter of WHERE a regime computes: a
    native-lowered serving fn that misses the regime's tolerance on the
    warmup corpus is demoted wholesale to the dequant path (blockwise
    payload, f32 contractions) and re-measured by the final gate in
    save_exported_model — the artifact either computes natively within
    parity, or dequantizes within parity, or does not exist. Returns
    (fn, demoted); a demoted fn carries `.quant_native_demoted = True`
    so the metadata records that the eligibility map was overridden by
    measurement, not configuration.
    """
    import numpy as np

    from tensor2robot_tpu.export import serve_quant as sq

    quant_outputs = [
        {k: np.asarray(v) for k, v in fn(fn.quant_payload, batch).items()}
        for batch in warmup_batches
    ]
    divergence = sq.measure_parity(fp32_outputs, quant_outputs)
    if all(value <= tolerance for value in divergence.values()):
        # Hand the measurement to the final gate: the fn is saved
        # unchanged, so save_exported_model need not replay the corpus
        # through the (deliberately un-jitted, slow) native forward a
        # second time. A demoted fn carries no measurement — the final
        # gate measures the dequant path it actually saves.
        fn.quant_measured_divergence = divergence
        return fn, False
    demoted = rebuild_dequant()
    demoted.quant_native_demoted = True
    return demoted, True


def create_valid_result_smaller(metric_key: str = DEFAULT_METRIC):
    """Best = strictly smaller metric (reference train_eval.py:206-248)."""

    def compare_fn(best: Optional[Dict[str, float]], current: Dict[str, float]) -> bool:
        if metric_key not in current:
            return False
        if best is None or metric_key not in best:
            return True
        return current[metric_key] < best[metric_key]

    return compare_fn


def create_valid_result_larger(metric_key: str = DEFAULT_METRIC):
    """Best = strictly larger metric (reference train_eval.py:251-291)."""

    def compare_fn(best: Optional[Dict[str, float]], current: Dict[str, float]) -> bool:
        if metric_key not in current:
            return False
        if best is None or metric_key not in best:
            return True
        return current[metric_key] > best[metric_key]

    return compare_fn


class DirectoryVersionGC:
    """Keeps the newest `keep` timestamped versions under a root
    (reference _DirectoryVersionGC, hooks/checkpoint_hooks.py:31-48)."""

    def __init__(self, keep: int):
        self._keep = keep

    def collect(self, export_root: str) -> List[str]:
        removed = []
        if self._keep <= 0:
            return removed
        dirs = list_export_dirs(export_root)
        while len(dirs) > self._keep:
            victim = dirs.pop(0)
            shutil.rmtree(victim, ignore_errors=True)
            removed.append(victim)
        return removed


class Exporter:
    """Base exporter: owns an export generator + destination + GC."""

    def __init__(
        self,
        name: str,
        export_generator: Optional[AbstractExportGenerator] = None,
        exports_to_keep: int = 5,
        serialize_stablehlo: bool = True,
        warmup_batch_sizes: Sequence[int] = (),
        quantize_weights: bool = False,
        quantize_bits: int = 8,
        serve_quant: Sequence[str] = (),
        quant_block: Optional[int] = None,
        quant_min_size: Optional[int] = None,
        quant_parity_tol: Optional[Dict[str, float]] = None,
        serve_calib: Optional[str] = None,
        aot_executables: Optional[bool] = None,
    ):
        self.name = name
        self._export_generator = export_generator or DefaultExportGenerator()
        self._gc = DirectoryVersionGC(exports_to_keep)
        self._serialize_stablehlo = serialize_stablehlo
        self._warmup_batch_sizes = tuple(warmup_batch_sizes)
        # int8 weight-only exports (export/quantization.py): ~4x smaller
        # artifacts for the robots polling this export root.
        if quantize_bits not in (4, 8):
            # Fail at CONFIG time, not on the first export tick mid-run.
            raise ValueError(
                f"quantize_bits must be 4 or 8, got {quantize_bits}"
            )
        self._quantize_weights = quantize_weights
        self._quantize_bits = quantize_bits
        # Low-precision SERVING regimes (export/serve_quant.py): each
        # export also carries blockwise fp16/int8 payloads + per-regime
        # serving programs, calibrated and parity-gated against the
        # artifact's own warmup corpus. Config-time validation: a typo'd
        # regime or a missing calibration corpus must fail here, not on
        # the first export tick minutes into a run.
        from tensor2robot_tpu.export.serve_quant import SERVE_QUANT_REGIMES

        self._serve_quant = tuple(serve_quant)
        for regime in self._serve_quant:
            if regime not in SERVE_QUANT_REGIMES:
                raise ValueError(
                    f"serve_quant regimes must be among "
                    f"{SERVE_QUANT_REGIMES}, got {regime!r}"
                )
        if self._serve_quant and not self._warmup_batch_sizes:
            raise ValueError(
                "serve_quant exports need warmup_batch_sizes: the warmup "
                "corpus is the calibration set and the parity-gate corpus."
            )
        if self._serve_quant and quantize_weights:
            raise ValueError(
                "serve_quant cannot combine with quantize_weights: the "
                "parity gate needs the fp32 forward as its baseline."
            )
        if self._serve_quant and not serialize_stablehlo:
            raise ValueError(
                "serve_quant requires serialize_stablehlo=True: without "
                "the per-regime serving programs the quantized payloads "
                "can never be served (every T2R_SERVE_QUANT restore "
                "would fail fleet-wide at deploy time)."
            )
        self._quant_block = quant_block
        self._quant_min_size = quant_min_size
        self._quant_parity_tol = dict(quant_parity_tol or {})
        # Activation-calibration mode for the native regimes: None
        # defers to T2R_SERVE_CALIB at export time; an explicit value is
        # validated HERE (config time) with the flag-naming error the
        # registry getters produce for a bad env value.
        if serve_calib is not None:
            from tensor2robot_tpu.export.serve_quant import (
                resolve_calib_mode,
            )

            resolve_calib_mode(serve_calib)
        self._serve_calib = serve_calib
        # Serialized AOT executables per warmup bucket (export/aot.py):
        # None defers to the T2R_AOT_EXPORT flag at export time. An
        # EXPLICIT request without a warmup ladder is a config error —
        # there is no bucket contract to compile against — and must
        # fail here, not silently produce artifacts with no aot/ dir.
        if aot_executables and not self._warmup_batch_sizes:
            raise ValueError(
                "aot_executables=True needs warmup_batch_sizes: the "
                "warmup ladder is the set of batch shapes the AOT "
                "executables are compiled for."
            )
        if aot_executables and not serialize_stablehlo:
            raise ValueError(
                "aot_executables=True requires serialize_stablehlo=True: "
                "each executable is compiled from the serialized serving "
                "program so AOT boots serve bit-identically to fresh ones."
            )
        self._aot_executables = aot_executables

    def export_root(self, model_dir: str) -> str:
        return os.path.join(model_dir, "export", self.name)

    def _should_export(self, step, eval_metrics, export_root) -> bool:
        return True

    def maybe_export(
        self,
        step: int,
        state,
        eval_metrics: Dict[str, float],
        compiled,
        model_dir: Optional[str] = None,
    ) -> Optional[str]:
        """Exports the current weights if the policy approves; returns the
        export path (or None)."""
        model = compiled.model
        if model_dir is None:
            model_dir = getattr(compiled, "model_dir", None)
        if model_dir is None:
            raise ValueError("maybe_export requires model_dir (pass it explicitly).")
        root = self.export_root(model_dir)
        if not self._should_export(step, eval_metrics, root):
            return None
        generator = self._export_generator
        generator.set_specification_from_model(model)
        use_ema = getattr(model, "use_avg_model_params", False)
        variables = state.export_variables(use_ema=use_ema)
        serving_fn = generator.create_serving_fn(
            compiled, variables, quantize_weights=self._quantize_weights,
            quantize_bits=self._quantize_bits,
        )
        # The warmup corpus is generated BEFORE the export so the quant
        # calibration + parity gate run over the exact batches the
        # artifact will ship as warmup_requests.tfrecord.
        warmup_batches = (
            generator.generate_warmup_batches(self._warmup_batch_sizes)
            if self._warmup_batch_sizes
            else []
        )
        serve_quant_fns = None
        if self._serve_quant:
            import numpy as np

            from tensor2robot_tpu.export import serve_quant as sq

            calibration = sq.calibrate_activations(warmup_batches)
            calib_mode = sq.resolve_calib_mode(self._serve_calib)
            static_scales: Dict[str, float] = {}
            static_demoted: Dict[str, float] = {}
            layer_calibration: Dict[str, Dict[str, float]] = {}
            native_regimes = tuple(
                regime for regime in self._serve_quant
                if regime in sq.NATIVE_DOT_REGIMES
            )
            # The eager capture replay is slow (un-jitted fp32 forward
            # over the whole corpus) — it runs only when something can
            # CONSUME a clip: an eligible kernel in some native regime,
            # or attention lowering left on (whether the model has
            # einsum-path attention is only discoverable by the capture
            # itself, so a non-empty attn spec keeps the replay).
            capture_can_pay_off = any(
                sq.resolve_native_eligibility(
                    variables, regime,
                    min_size=(
                        sq.DEFAULT_MIN_SIZE
                        if self._quant_min_size is None
                        else int(self._quant_min_size)
                    ),
                )
                for regime in native_regimes
            ) or sq.resolve_native_attention(None) != ()
            if calib_mode == "static" and native_regimes and (
                capture_can_pay_off
            ):
                # Static activation calibration: the capture interceptor
                # rides the UN-JITTED fp32 forward over the SAME corpus
                # the parity gate replays, so the per-layer clips are
                # measured on exactly the batches the artifact ships as
                # warmup. Layers whose observed max overshoots the clip
                # are demoted BACK to dynamic per-row quant here, per
                # layer, before any regime is built.
                eager_fn = generator.create_eager_serving_fn(
                    compiled, variables
                )
                records: Dict[str, list] = {}
                with sq.capture_activations(records):
                    for batch in warmup_batches:
                        eager_fn(batch)
                layer_calibration = sq.calibrate_layer_activations(records)
                static_scales, static_demoted = sq.resolve_static_scales(
                    layer_calibration
                )
            tolerance = dict(sq.DEFAULT_PARITY_TOL)
            tolerance.update(self._quant_parity_tol)
            serve_quant_fns = {}
            fp32_outputs = None
            for regime in self._serve_quant:

                def make(native=None, attn=None, static=True, regime=regime):
                    return generator.create_quant_serving_fn(
                        compiled,
                        variables,
                        regime=regime,
                        block=self._quant_block,
                        min_size=self._quant_min_size,
                        calibration=calibration,
                        native=native,
                        static_scales=static_scales if static else None,
                        attn=attn,
                    )

                fn = make()
                # The (deliberately un-jitted, slow) pre-gate replay
                # runs only when the program can actually carry native
                # contractions: eligible kernels, or attention modules
                # the capture OBSERVED on the einsum path. An
                # attention-only model under dynamic calib (no capture
                # ran) skips the triage — the final gate in
                # save_exported_model still measures it and
                # fails-writes-nothing applies; it just cannot
                # auto-demote wholesale.
                capture_saw_attention = any(
                    key.startswith("attn/") for key in layer_calibration
                )
                if fn.quant_native or (
                    fn.quant_attn != () and capture_saw_attention
                ):
                    # Native contractions ride only where measurement
                    # allows: the fp32 forward (computed once, shared
                    # across regimes) is the baseline for the demotion
                    # triage. The rebuild disables EVERY native leg —
                    # kernels, attention, and static scales alike.
                    if fp32_outputs is None:
                        fp32_outputs = [
                            {
                                k: np.asarray(v)
                                for k, v in serving_fn(batch).items()
                            }
                            for batch in warmup_batches
                        ]
                    fn, _ = _native_pre_gate(
                        fn,
                        lambda: make(native=(), attn=(), static=False),
                        fp32_outputs,
                        warmup_batches,
                        tolerance[regime],
                    )
                # The per-layer static-demotion record rides the fn so
                # the metadata can say which layers still pay a
                # per-dispatch reduce, and why. Native regimes only —
                # a cast regime has no contraction the record applies
                # to (and the shared calibration table is recorded
                # once, not per regime).
                if regime in sq.NATIVE_DOT_REGIMES:
                    fn.quant_static_demoted = dict(static_demoted)
                    fn.quant_layer_calibration = layer_calibration
                serve_quant_fns[regime] = fn
        path = save_exported_model(
            root,
            variables=variables,
            feature_spec=generator.serving_input_spec(),
            label_spec=generator.label_spec,
            global_step=step,
            predict_fn=serving_fn,
            example_features=generator.create_example_features(),
            serialize_stablehlo=self._serialize_stablehlo,
            metadata={
                "exporter": self.name,
                "eval_metrics": eval_metrics,
                # The serving bucket contract: the policy server
                # (tensor2robot_tpu/serving) pads every dispatched batch
                # to one of these pre-warmed sizes.
                "warmup_batch_sizes": list(self._warmup_batch_sizes),
            },
            quantize_weights=self._quantize_weights,
            quantize_bits=self._quantize_bits,
            serve_quant_fns=serve_quant_fns,
            quant_parity_tol=self._quant_parity_tol,
            calibration_batches=warmup_batches,
            aot_executables=self._aot_executables,
        )
        if warmup_batches:
            generator.write_warmup_requests(warmup_batches, path)
        self._after_export(step, eval_metrics, root, path)
        self._gc.collect(root)
        return path

    def _after_export(self, step, eval_metrics, export_root, path) -> None:
        pass


@configurable("LatestExporter")
class LatestExporter(Exporter):
    """Exports after every eval (reference LatestExporter wiring,
    train_eval.py:347-366)."""


@configurable("BestExporter")
class BestExporter(Exporter):
    """Exports only when `compare_fn(best, current)` approves; best-seen
    metrics persist in best_metrics.json so resume keeps the gate
    (reference BestExporter + compare fns, train_eval.py:330-346)."""

    def __init__(
        self,
        name: str = "best",
        compare_fn: Optional[Callable] = None,
        **kwargs,
    ):
        super().__init__(name=name, **kwargs)
        self._compare_fn = compare_fn or create_valid_result_smaller()

    def _best_path(self, export_root: str) -> str:
        return os.path.join(export_root, "best_metrics.json")

    def _read_best(self, export_root: str) -> Optional[Dict[str, float]]:
        try:
            with open(self._best_path(export_root)) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def _should_export(self, step, eval_metrics, export_root) -> bool:
        if not eval_metrics:
            return False
        return self._compare_fn(self._read_best(export_root), eval_metrics)

    def _after_export(self, step, eval_metrics, export_root, path) -> None:
        os.makedirs(export_root, exist_ok=True)
        tmp = self._best_path(export_root) + ".tmp"
        with open(tmp, "w") as f:
            json.dump(dict(eval_metrics), f)
        os.replace(tmp, self._best_path(export_root))


@configurable("create_default_exporters")
def create_default_exporters(
    t2r_model,
    export_generator: Optional[AbstractExportGenerator] = None,
    compare_fn: Optional[Callable] = None,
    exports_to_keep: int = 5,
    serialize_stablehlo: bool = True,
    warmup_batch_sizes: Sequence[int] = (),
    quantize_weights: bool = False,
    quantize_bits: int = 8,
    serve_quant: Sequence[str] = (),
    quant_parity_tol: Optional[Dict[str, float]] = None,
    serve_calib: Optional[str] = None,
    aot_executables: Optional[bool] = None,
) -> List[Exporter]:
    """latest + best exporter pair (reference create_default_exporters,
    train_eval.py:295-385; one artifact serves both the numpy and tf.Example
    interfaces here, so the four receiver variants collapse to two dirs)."""
    del t2r_model  # Specs are bound at export time from the trained model.
    make_gen = (lambda: export_generator) if export_generator else DefaultExportGenerator
    return [
        LatestExporter(
            name="latest",
            export_generator=make_gen(),
            exports_to_keep=exports_to_keep,
            serialize_stablehlo=serialize_stablehlo,
            warmup_batch_sizes=warmup_batch_sizes,
            quantize_weights=quantize_weights,
            quantize_bits=quantize_bits,
            serve_quant=serve_quant,
            quant_parity_tol=quant_parity_tol,
            serve_calib=serve_calib,
            aot_executables=aot_executables,
        ),
        BestExporter(
            name="best",
            export_generator=make_gen(),
            compare_fn=compare_fn,
            exports_to_keep=exports_to_keep,
            serialize_stablehlo=serialize_stablehlo,
            warmup_batch_sizes=warmup_batch_sizes,
            quantize_weights=quantize_weights,
            quantize_bits=quantize_bits,
            serve_quant=serve_quant,
            quant_parity_tol=quant_parity_tol,
            serve_calib=serve_calib,
            aot_executables=aot_executables,
        ),
    ]
