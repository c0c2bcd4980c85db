"""train_eval_model: the orchestration entry point.

Compiles the model's hooks into pjit train/eval steps over a device mesh,
runs the host loop with checkpointing (orbax), metrics, hooks, periodic
evaluation and exporting. The JAX re-architecture of the reference's
utils/train_eval.py:423-612 (TPUEstimator + train_and_evaluate):

  reference                        | here
  ---------------------------------+----------------------------------------
  TPUT2RModelWrapper auto-wrap     | same decision, same wrapper (:476-479)
  Estimator input_fn               | input generator batch iterator
  model_fn(TRAIN) traced by TF     | jitted train_step over the mesh
  CrossShardOptimizer all-reduce   | psum inserted by GSPMD sharded autodiff
  iterations_per_loop infeed       | host loop w/ async dispatch (XLA queues
                                   | steps; host never blocks except on logs)
  Saver/checkpoint listeners       | orbax CheckpointManager + hook protocol
  train_and_evaluate + exporters   | periodic eval + create_exporters_fn
"""

from __future__ import annotations

import time

_IMPORT_BEGAN_NS = time.time_ns()

import contextlib
import itertools
import os
import threading

from tensor2robot_tpu.testing import locksmith
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence

import jax
import jax.flatten_util  # registers jax.flatten_util.ravel_pytree
import jax.numpy as jnp
import numpy as np
import optax
import orbax.checkpoint as ocp

import tensor2robot_tpu
from tensor2robot_tpu import flags
from tensor2robot_tpu.hooks.golden_values_hook_builder import GOLDEN_PREFIX
from tensor2robot_tpu.hooks.hook_builder import Hook, HookBuilder, HookContext
from tensor2robot_tpu.models.abstract_model import (
    MODE_EVAL,
    MODE_PREDICT,
    MODE_TRAIN,
    AbstractT2RModel,
)
from tensor2robot_tpu.models.tpu_model_wrapper import TPUT2RModelWrapper
from tensor2robot_tpu.parallel import collectives
from tensor2robot_tpu.parallel import mesh as mesh_lib
from tensor2robot_tpu.parallel import planner as planner_lib
from tensor2robot_tpu.utils.compile_cache import compile_cache_bypass
from tensor2robot_tpu.specs import TensorSpecStruct, make_example_args
from tensor2robot_tpu.testing import chaos
from tensor2robot_tpu.train import durability, infeed
from tensor2robot_tpu.train.metrics import (
    DeferredFetch,
    MetricsWriter,
    collective_record,
)
from tensor2robot_tpu.train.state import TrainState, create_train_state, update_ema
from tensor2robot_tpu.utils import build_trace, tracing


#: Metric-key prefixes whose values carry a leading batch dimension
#: (concatenated, not averaged, when recombining grad-accum microbatches).
BATCH_CARRYING_METRIC_PREFIXES = (GOLDEN_PREFIX, "per_example/")

#: One process-wide ENQUEUE lock for multi-device (mesh-spanning) jitted
#: programs. XLA runs each device's queue in order; two host threads
#: enqueueing collective programs concurrently can interleave so the
#: device queues disagree on program order — then each program sits at
#: its collective rendezvous waiting for participants queued behind the
#: OTHER program (queue-order inversion: a deadlock, observed between a
#: threaded trainer and an in-process continuous_eval job). Dispatch is
#: asynchronous, so the lock is held for the microseconds of enqueue,
#: never for execution — trainer/eval overlap is preserved; only the
#: ORDER every device sees becomes consistent. Production trainer and
#: eval jobs live in separate processes and never contend here.
_DISPATCH_LOCK = locksmith.make_lock("train_eval._DISPATCH_LOCK", budget_ms=0)


def _serialize_dispatch(fn):
    """Routes calls to a jitted mesh program through _DISPATCH_LOCK; jit
    introspection (`lower`) passes through for AOT/census tests.

    A call inside which the thread built something (the program's first
    call, or a recompile: utils/build_trace.py) is recorded whole as a
    `train.build` span labelled with the program's name, a sibling of the
    `jit.*` spans inside it (all are children of the thread's open span,
    a `train.dispatch` say), with what those account for of it as counts.
    What is left of its length is what no jax event covers: sharding
    inference, the compile cache's key over the module, pjit's
    bookkeeping, the enqueue of the first execution. A call that builds nothing pays a clock read and two
    thread-local reads."""
    label = fn.__name__

    def locked(*args, **kwargs):
        start_ns = time.time_ns()
        built = build_trace.totals()
        with _DISPATCH_LOCK:
            out = fn(*args, **kwargs)
        now = build_trace.totals()
        if now is not built:
            tracing.between(
                "train.build", start_ns, time.time_ns(), label=label,
                **now.since(built),
            )
        return out

    locked.lower = fn.lower
    locked.__wrapped__ = fn
    return locked


@jax.jit
def _init_metric_totals(metrics):
    """Eval accumulator seed, f32 (bf16 scalars would saturate — spacing
    2 past 256 — over long eval runs)."""
    return {key: value.astype(jnp.float32) for key, value in metrics.items()}


@jax.jit
def _accumulate_metric_totals(totals, metrics):
    return {
        key: totals[key] + metrics[key].astype(jnp.float32)
        for key in metrics
    }


# The eval accumulation runs on mesh-resident arrays — a multi-device
# program like the steps themselves, so it takes the same enqueue lock.
_init_metric_totals = _serialize_dispatch(_init_metric_totals)
_accumulate_metric_totals = _serialize_dispatch(_accumulate_metric_totals)


def _is_batch_carrying_metric(path) -> bool:
    """True when any key along the metric's tree path declares a
    batch-carrying value via BATCH_CARRYING_METRIC_PREFIXES."""
    for entry in path:
        key = getattr(entry, "key", None)
        if isinstance(key, str) and key.startswith(
            BATCH_CARRYING_METRIC_PREFIXES
        ):
            return True
    return False


def print_specification(model: AbstractT2RModel) -> None:
    """Startup spec dump (reference train_eval.py:72-93)."""
    for mode in (MODE_TRAIN, MODE_EVAL):
        print(f"*** Specifications for mode={mode} ***")
        for name, spec_fn in (
            ("features", model.get_feature_specification),
            ("labels", model.get_label_specification),
        ):
            for key, spec in spec_fn(mode).items():
                print(f"  {name}/{key}: {spec}")


def provide_input_generator_with_model_information(
    input_generator, model: AbstractT2RModel, mode: str
):
    """Binds the model's (preprocessor's) in-specs onto the generator
    (reference :96-127)."""
    input_generator.set_specification_from_model(model, mode)
    return input_generator


def maybe_wrap_for_tpu(model: AbstractT2RModel) -> AbstractT2RModel:
    if model.is_device_tpu and not isinstance(model, TPUT2RModelWrapper):
        return TPUT2RModelWrapper(model)
    return model


def _batch_labels(batch):
    """The batch's labels subtree, or None for label-less (self-supervised)
    models whose generators emit no 'labels' keys — grasp2vec's empty
    label spec is the in-repo case; preprocessors and model fns already
    accept labels=None."""
    try:
        return batch["labels"]
    except KeyError:
        return None


def _validate_model_matches_plan(model, plan) -> None:
    """A plan can PLACE layouts but cannot retrofit model structure: a
    sequence- or pipeline-parallel plan requires the model BUILT with the
    matching mesh / pipeline stages (plan.model_kwargs()). Without this
    check a mismatch trains silently replicated — the regime degrades to
    'replicated', whose layout audit is green, so nothing else would
    catch it."""
    candidates = [model, getattr(model, "_model", None)]
    candidates = [m for m in candidates if m is not None]
    if plan.pipe > 1:
        stages = next(
            (
                getattr(m, "_pipeline_stages")
                for m in candidates
                if hasattr(m, "_pipeline_stages")
            ),
            None,
        )
        if stages != plan.pipe:
            raise ValueError(
                f"plan {plan.name!r} runs {plan.pipe} pipeline stages but "
                f"the model was built with pipeline_stages={stages}; "
                "construct the model with plan.model_kwargs() (and the "
                "plan's mesh)"
            )
    if plan.sequence > 1:
        model_mesh = next(
            (
                getattr(m, "_mesh")
                for m in candidates
                if getattr(m, "_mesh", None) is not None
            ),
            None,
        )
        seq = (
            dict(model_mesh.shape).get(mesh_lib.SEQUENCE_AXIS, 1)
            if model_mesh is not None
            else None
        )
        if seq != plan.sequence:
            raise ValueError(
                f"plan {plan.name!r} shards the sequence {plan.sequence}-"
                f"way but the model's mesh carries sequence axis {seq}; "
                "construct the model with the plan's mesh "
                "(plan.build_mesh()) so attention actually runs "
                "sequence-parallel"
            )


# -- the measured plan-search probe (planner.measured_rerank's tier 2) --------

#: Monotonic count of train-step compiles paid by measure_plan_candidate.
#: The planner's zero-compile warm-cache contract is audited against this
#: counter (planner.last_search()['probe_compiles'], bench.py plan).
_PLAN_PROBE_COMPILES = 0


def plan_probe_compile_count() -> int:
    return _PLAN_PROBE_COMPILES


def _executable_memory(executable):
    """compiled.memory_analysis() -> (total per-device bytes, fields).

    The TRUE HBM accounting the analytic estimate is audited against.
    Backends without the analysis (CPU builds, older runtimes) return
    (None, None) — the caller records the analytic estimate unaudited
    rather than failing the probe."""
    try:
        analysis = executable.memory_analysis()
    except Exception as err:  # noqa: BLE001 - backend-optional surface
        return None, {"unavailable": f"{type(err).__name__}: {err}"}
    if analysis is None:
        return None, {"unavailable": "memory_analysis() returned None"}
    fields = {}
    for key in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        value = getattr(analysis, key, None)
        if isinstance(value, (int, float)):
            fields[key] = int(value)
    total = (
        fields.get("argument_size_in_bytes", 0)
        + fields.get("output_size_in_bytes", 0)
        + fields.get("temp_size_in_bytes", 0)
        - fields.get("alias_size_in_bytes", 0)
    )
    return (total if total > 0 else None), (fields or None)


def measure_plan_candidate(
    model,
    plan: planner_lib.ShardingPlan,
    example_batch,
    *,
    steps: int = 3,
    warmup: int = 1,
) -> Dict[str, Any]:
    """Compile-and-measure probe for ONE shortlisted plan: builds the
    plan's mesh and CompiledModel (donated state — the real train-step
    economics), compiles the train step with the persistent compile
    cache bypassed, reads compiled.memory_analysis(), and times `steps`
    real steps after `warmup` (median). Returns a record for the ranked
    table; a plan the model cannot run (pipe/sequence mismatch) or a
    probe failure comes back as {'skipped': reason} — the search skips
    it loudly, it never kills the run."""
    global _PLAN_PROBE_COMPILES
    record: Dict[str, Any] = {"name": plan.name}
    try:
        _validate_model_matches_plan(model, plan)
    except ValueError as err:
        record["skipped"] = str(err)
        return record
    # A cache HIT hands back an executable with near-zero compile time,
    # which poisons both the timing and the compile counter the search
    # ranks and audits with.
    with compile_cache_bypass():
        try:
            mesh = plan.build_mesh()
            compiled = CompiledModel(
                model, mesh=mesh, donate_state=True, plan=plan
            )
            state = compiled.init_state(jax.random.PRNGKey(0), example_batch)
            rng = jax.random.PRNGKey(1)
            start = time.perf_counter()
            executable = compiled.train_step.lower(
                state, example_batch, rng
            ).compile()
            _PLAN_PROBE_COMPILES += 1
            record["compile_ms"] = (time.perf_counter() - start) * 1e3
        except Exception as err:  # noqa: BLE001 - recorded, search goes on
            record["skipped"] = f"{type(err).__name__}: {err}"
            return record
        memory_total, memory_fields = _executable_memory(executable)
        record["memory_per_device_bytes"] = memory_total
        record["memory_analysis"] = memory_fields
        times_ms: List[float] = []
        try:
            for i in range(warmup + max(steps, 1)):
                start = time.perf_counter()
                state, _ = executable(state, example_batch, rng)
                jax.block_until_ready(state)
                if i >= warmup:
                    times_ms.append((time.perf_counter() - start) * 1e3)
        except Exception as err:  # noqa: BLE001 - recorded, search goes on
            record["skipped"] = f"{type(err).__name__}: {err}"
            return record
    times_ms.sort()
    record["step_time_ms"] = times_ms[len(times_ms) // 2]
    record["steps_timed"] = len(times_ms)
    return record


class CompiledModel:
    """The model's hooks compiled into mesh-placed pure step functions."""

    def __init__(
        self,
        model: AbstractT2RModel,
        mesh=None,
        donate_state: bool = True,
        param_min_shard_size: int = mesh_lib.MIN_WEIGHT_SIZE,
        remat: bool = False,
        grad_accum_steps: int = 1,
        shard_weight_update: bool = False,
        collective_quant: Optional[str] = None,
        collective_block: Optional[int] = None,
        weight_update_axes: Optional[Sequence[str]] = None,
        plan: Optional[planner_lib.ShardingPlan] = None,
    ):
        """Args beyond the model/mesh:

        remat: rematerialize the forward pass under autodiff
          (jax.checkpoint) — activations are recomputed in the backward
          instead of stored, trading ~1/3 more FLOPs for O(depth) less
          HBM; the standard lever when a big batch or long episode
          doesn't fit.
        shard_weight_update: in pure data parallelism, shard optimizer
          moments and the EMA mirror over the data axis (cross-replica
          weight-update sharding, arXiv:2004.13336 / ZeRO-2) — params
          stay replicated for compute while optimizer-state memory drops
          by the data-axis size; GSPMD rewrites the gradient all-reduce
          into reduce-scatter + sharded update + all-gather. Ignored when
          the fsdp/model axes already shard parameters.
        grad_accum_steps: K>1 splits each batch into K microbatches,
          accumulates gradients over them in a lax.scan, and applies ONE
          optimizer update of their mean — the effective batch stays the
          same while peak activation memory drops by ~K. Caveat: batch
          norm computes statistics per MICRObatch (the standard
          grad-accumulation behavior), so BN models are not bit-identical
          to the unaccumulated step.
        collective_quant / collective_block: wire format for the ZeRO-2
          gradient collectives (parallel/collectives.py). None reads the
          central T2R_COLLECTIVE_QUANT / T2R_COLLECTIVE_BLOCK flags;
          'none' (the default) keeps today's GSPMD-inserted psum
          byte-for-byte. 'fp16'/'int8'/'fp8_e4m3'/'fp8_e5m2' switch the
          shard_weight_update regime to an EXPLICIT shard_map step:
          blockwise-quantized reduce-scatter of gradients + all-gather
          of updates with per-block scales, and an error-feedback
          residual carried in the train state (re-injected next step,
          so the compression bias cancels and convergence is
          preserved). The fp8 formats move the same 1 byte/element as
          int8 but round RELATIVE per value (e4m3 ~2^-4, e5m2 ~2^-3)
          instead of absolute per block. Only engages in
          the pure data-parallel ZeRO-2 regime (shard_weight_update on,
          data axis > 1, all other axes 1) — ignored elsewhere, so the
          env flag can stay set fleet-wide. In this regime optimizer
          state and the EMA mirror live on the flat block-padded
          parameter vector (per-shard elementwise optimizer update —
          Adam & friends; tree-structure-aware transforms like
          global-norm clipping see one shard and are unsupported), and
          per-replica batch-norm statistics average across the data
          axis (the local-BN caveat, same family as grad-accum's
          per-microbatch stats).
        weight_update_axes: replica axes the ZeRO-2 weight update shards
          across (mesh.weight_update_sharding's generalization). None =
          ("data",), byte-for-byte today's layout; a composed 3D plan
          passes every axis the params are replicated over, e.g.
          ("data", "sequence").
        plan: a planner_lib.ShardingPlan as the single source of
          sharding truth. The plan is AUTHORITATIVE for the mesh (when
          `mesh` is None), shard_weight_update, weight_update_axes,
          collective_quant/block (pinned — the env flags are not
          consulted), and param_min_shard_size; after init_state places
          the TrainState, the layout is audited leaf-for-leaf against
          the plan's predictions and a mismatch raises. None (the
          default, and the T2R_PLAN=off path) keeps the explicit kwargs
          exactly as before.
        """
        build_trace.install()
        self.model = model
        self.plan = plan
        if plan is not None:
            if mesh is None:
                mesh = plan.build_mesh()
            elif not plan.matches_mesh(mesh):
                raise ValueError(
                    f"mesh axes {dict(mesh.shape)} disagree with plan "
                    f"{plan.name!r} axes {plan.axes_dict()}"
                )
            _validate_model_matches_plan(model, plan)
            shard_weight_update = plan.shard_weight_update
            weight_update_axes = plan.weight_update_axes
            collective_quant = plan.collective_quant
            collective_block = plan.collective_block
            param_min_shard_size = plan.param_min_shard_size
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh()
        self.preprocessor = model.preprocessor
        self.optimizer = model.create_optimizer()
        self._donate = donate_state
        self._param_min_shard_size = param_min_shard_size
        self._shard_weight_update = shard_weight_update
        self._weight_update_axes = tuple(
            weight_update_axes
            if weight_update_axes is not None
            else (mesh_lib.DATA_AXIS,)
        )
        if grad_accum_steps < 1:
            raise ValueError(f"grad_accum_steps must be >= 1, got {grad_accum_steps}")

        # Quantized gradient collectives (parallel/collectives.py): only
        # the pure data-parallel ZeRO-2 regime has the reduce-scatter /
        # all-gather pair to compress; everywhere else the flag is inert
        # so it can stay exported fleet-wide.
        quant_name = (
            collective_quant
            if collective_quant is not None
            else flags.get_enum("T2R_COLLECTIVE_QUANT")
        )
        quant_block = (
            collective_block
            if collective_block is not None
            else flags.get_int("T2R_COLLECTIVE_BLOCK")
        )
        pure_data_parallel = all(
            self.mesh.shape[axis] == 1
            for axis in (
                mesh_lib.FSDP_AXIS,
                mesh_lib.MODEL_AXIS,
                mesh_lib.SEQUENCE_AXIS,
                mesh_lib.PIPE_AXIS,
                mesh_lib.EXPERT_AXIS,
            )
        )
        self._quant_collective = None
        if (
            quant_name != "none"
            and shard_weight_update
            and pure_data_parallel
            and self.mesh.shape[mesh_lib.DATA_AXIS] > 1
        ):
            self._quant_collective = collectives.get_collective(
                quant_name, quant_block
            )
        # Set by init_state in the quantized-collective regime.
        self._flat_layout = None
        self._flat_unravel = None
        self._quant_state_specs = None

        # The layout plan this trainer ACTUALLY runs: the explicit plan,
        # or an ad-hoc one distilled from the resolved kwargs. Either
        # way, init_state's placement rules come from here — the planner
        # is the single source of sharding truth; the hand-wired kwargs
        # are just one way of naming a plan.
        mesh_axes = dict(self.mesh.shape)
        self._layout = planner_lib.ShardingPlan(
            name=plan.name if plan is not None else "adhoc",
            data=mesh_axes.get(mesh_lib.DATA_AXIS, 1),
            fsdp=mesh_axes.get(mesh_lib.FSDP_AXIS, 1),
            model=mesh_axes.get(mesh_lib.MODEL_AXIS, 1),
            sequence=mesh_axes.get(mesh_lib.SEQUENCE_AXIS, 1),
            pipe=mesh_axes.get(mesh_lib.PIPE_AXIS, 1),
            expert=mesh_axes.get(mesh_lib.EXPERT_AXIS, 1),
            shard_weight_update=self._shard_weight_update,
            weight_update_axes=self._weight_update_axes,
            collective_quant=(
                self._quant_collective.name
                if self._quant_collective is not None
                else "none"
            ),
            collective_block=(
                self._quant_collective.block
                if self._quant_collective is not None
                else quant_block
            ),
            param_min_shard_size=self._param_min_shard_size,
        )

        def forward_loss(params, variables, features, labels, rng_net):
            variables = dict(variables)
            variables["params"] = params
            f, l, outputs, mutable = model.packed_inference(
                variables, features, MODE_TRAIN, labels=labels, rng=rng_net
            )
            loss, train_metrics = model.model_train_fn(
                f, l, outputs, MODE_TRAIN
            )
            return loss, (train_metrics, mutable)

        if remat:
            # Differentiating through the checkpointed forward recomputes
            # activations in the backward pass instead of storing them.
            forward_loss = jax.checkpoint(
                forward_loss, static_argnums=(), policy=None
            )

        def compute_grads(state, features, labels, rng_net):
            """(loss, metrics, mutable, grads) for one (micro)batch."""
            (loss, (train_metrics, mutable)), grads = jax.value_and_grad(
                forward_loss, has_aux=True
            )(state.params, state.variables, features, labels, rng_net)
            return loss, train_metrics, mutable, grads

        def _microbatch(tree, index):
            """Slice microbatch `index` out of every batch-carrying leaf.

            Mirrors shard_batch's tolerance: leaves whose leading dim
            divides K split; 0-d and unit-leading leaves replicate into
            every microbatch; a >1 leading dim that does not divide is a
            real batch that cannot split — raise.
            """

            def take(leaf):
                shape = getattr(leaf, "shape", ())
                if len(shape) == 0 or shape[0] == 1:
                    return leaf
                if shape[0] % grad_accum_steps != 0:
                    raise ValueError(
                        f"Leaf batch {shape[0]} not divisible by "
                        f"grad_accum_steps={grad_accum_steps}"
                    )
                size = shape[0] // grad_accum_steps
                return jax.lax.dynamic_slice_in_dim(
                    leaf, index * size, size, axis=0
                )

            return jax.tree_util.tree_map(take, tree)

        def accumulated_grads(state, features, labels, rng_net):
            """Grads averaged over K microbatches via lax.scan — one
            microbatch's activations alive at a time, ONE traced copy of
            the model (the accumulator is seeded with zeros shaped via
            eval_shape; microbatches are dynamic slices of the full
            batch, so the forward/backward graph exists only in the scan
            body). Metrics come back stacked per microbatch and are
            recombined by KEY afterwards (see combine_metric /
            BATCH_CARRYING_METRIC_PREFIXES).
            """
            if grad_accum_steps == 1:
                return compute_grads(state, features, labels, rng_net)

            def grads_at(index):
                return compute_grads(
                    state,
                    _microbatch(features, index),
                    _microbatch(labels, index),
                    # Independent stochasticity (dropout masks) per
                    # microbatch, as one large-batch draw would have.
                    jax.random.fold_in(rng_net, index),
                )

            shapes = jax.eval_shape(grads_at, jnp.int32(0))
            zeros = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                (shapes[0], shapes[2], shapes[3]),
            )

            def body(carry, index):
                loss, metrics, mutable, grads = grads_at(index)
                acc_loss, _, acc_grads = carry
                new_carry = (
                    acc_loss + loss / grad_accum_steps,
                    mutable,  # last microbatch's batch-norm stats win
                    jax.tree_util.tree_map(
                        lambda a, g: a + g / grad_accum_steps,
                        acc_grads,
                        grads,
                    ),
                )
                return new_carry, metrics

            (loss, mutable, grads), stacked_metrics = jax.lax.scan(
                body, zeros, jnp.arange(grad_accum_steps)
            )

            def combine_metric(path, stacked):
                # Per-metric stacked leaves are [K, ...]. Batch-carrying
                # metrics are identified by KEY, not shape (a fixed-size
                # vector metric could coincide with B/K): keys under the
                # `golden/` (add_golden_tensor) or `per_example/` prefix
                # concatenate back to the full batch; everything else is
                # reduced over the K axis shape-preserving — floats
                # average (mean of per-microbatch means == full-batch
                # mean), integer counts sum. Contract documented on
                # AbstractT2RModel.model_train_fn.
                if _is_batch_carrying_metric(path) and stacked.ndim >= 2:
                    return stacked.reshape((-1,) + stacked.shape[2:])
                if jnp.issubdtype(stacked.dtype, jnp.floating):
                    return jnp.mean(stacked, axis=0)
                return jnp.sum(stacked, axis=0)

            train_metrics = jax.tree_util.tree_map_with_path(
                combine_metric, stacked_metrics
            )
            return loss, train_metrics, mutable, grads

        def train_step(state: TrainState, batch, rng):
            step_rng = jax.random.fold_in(rng, state.step)
            rng_pre, rng_net = jax.random.split(step_rng)
            features, labels = self.preprocessor.preprocess(
                batch["features"], _batch_labels(batch),
                mode=MODE_TRAIN, rng=rng_pre,
            )
            loss, train_metrics, mutable, grads = accumulated_grads(
                state, features, labels, rng_net
            )
            updates, opt_state = self.optimizer.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
            variables = dict(state.variables)
            variables.update(mutable)
            variables["params"] = params
            ema = state.ema_params
            if ema is not None:
                ema = update_ema(ema, params, model.avg_model_params_decay)
            metrics = {"loss": loss}
            metrics.update(train_metrics)
            new_state = state.replace(
                step=state.step + 1,
                variables=variables,
                opt_state=opt_state,
                ema_params=ema,
            )
            return new_state, metrics

        def eval_step(state: TrainState, batch, use_ema: bool):
            features, labels = self.preprocessor.preprocess(
                batch["features"], _batch_labels(batch),
                mode=MODE_EVAL, rng=None,
            )
            variables = state.export_variables(use_ema=use_ema)
            f, l, outputs, _ = model.packed_inference(
                variables, features, MODE_EVAL, labels=labels
            )
            return model.model_eval_fn(f, l, outputs)

        def predict_step(variables, features):
            f, _, outputs, _ = model.packed_inference(
                variables, features, MODE_PREDICT
            )
            return model.create_export_outputs_fn(f, outputs)

        def train_scan(state: TrainState, stacked_batch, rng):
            """K train steps under one dispatch: lax.scan over the leading
            [K, B, ...] axis (the iterations_per_loop equivalent — reference
            models/abstract_model.py:76-77 TPUConfig.iterations_per_loop)."""
            return jax.lax.scan(
                lambda s, b: train_step(s, b, rng), state, stacked_batch
            )

        def quant_train_step(state: TrainState, batch, rng):
            """ZeRO-2 step with EXPLICIT quantized collectives.

            The GSPMD regime lets sharded autodiff insert the gradient
            reduce-scatter and the update all-gather; to compress those
            wires the step goes manual instead: shard_map over the data
            axis, each replica computing grads on its local batch shard,
            then (1) error-feedback residual added to the raveled
            gradient, (2) blockwise-quantized reduce-scatter — each
            replica encodes one chunk per peer, all_to_all, receivers
            decode and sum exactly in fp32, (3) per-shard elementwise
            optimizer update on this replica's contiguous slice of the
            flat parameter vector (the ZeRO-2 sharded update), (4)
            blockwise-quantized all-gather of the UPDATE (not the params:
            every replica applies the same dequantized update, so params
            never drift apart), (5) both quantization errors carried to
            the next step in state.collective_residual. The payloads in
            (2)/(4) are the gradient exchange — the traffic that scales
            with parameter count and what wire_summary counts; metric
            pmeans and batch-carrying metric gathers ride alongside
            uncompressed and uncounted.
            """
            coll = self._quant_collective
            layout = self._flat_layout
            axis = mesh_lib.DATA_AXIS
            num_shards = self.mesh.shape[axis]

            def batch_spec(leaf):
                # Mirrors shard_batch's tolerance (planner-owned spec).
                return mesh_lib.batch_partition_spec(
                    self.mesh, getattr(leaf, "shape", ())
                )

            def local_step(state, batch, rng):
                device = collectives.axis_index(axis)
                step_rng = jax.random.fold_in(rng, state.step)
                rng_pre, rng_net = jax.random.split(step_rng)
                # Independent stochasticity per replica, as one global
                # large-batch draw would have (the microbatch fold_in
                # precedent in accumulated_grads).
                rng_pre = jax.random.fold_in(rng_pre, device)
                rng_net = jax.random.fold_in(rng_net, device)
                features, labels = self.preprocessor.preprocess(
                    batch["features"], _batch_labels(batch),
                    mode=MODE_TRAIN, rng=rng_pre,
                )
                loss, train_metrics, mutable, grads = accumulated_grads(
                    state, features, labels, rng_net
                )
                residual = state.collective_residual
                flat_grads = jax.flatten_util.ravel_pytree(grads)[0]
                grads_fb = layout.pad(flat_grads) + residual["grad"][0]
                rows = layout.rows(grads_fb)
                reduced, sent = coll.reduce_scatter(rows, axis)
                grad_residual = (rows - sent).reshape(1, layout.padded)
                # Local losses are means over the LOCAL shard; the global
                # mean gradient is the cross-replica sum / N.
                grad_shard = reduced / num_shards
                flat_params = layout.pad(
                    jax.flatten_util.ravel_pytree(state.params)[0]
                )
                param_shard = layout.rows(flat_params)[device]
                updates, opt_state = self.optimizer.update(
                    grad_shard, state.opt_state, param_shard
                )
                update_fb = updates + residual["update"]
                full_update, sent_update = coll.all_gather_shard(
                    update_fb, axis
                )
                update_residual = update_fb - sent_update
                params = self._flat_unravel(
                    layout.unpad(flat_params + full_update)
                )
                # Per-replica batch-norm statistics average across the
                # data axis — exact for the means; the variance-of-means
                # term is the standard local-BN caveat (same family as
                # grad-accum's per-microbatch statistics).
                mutable = collectives.pmean(mutable, axis)
                variables = dict(state.variables)
                variables.update(mutable)
                variables["params"] = params
                ema = state.ema_params
                if ema is not None:
                    # The EMA mirror follows the flat sharded layout: each
                    # replica advances its own shard with the update it
                    # just applied (dequantized, so the mirror tracks the
                    # params every replica actually holds).
                    decay = model.avg_model_params_decay
                    new_param_shard = param_shard + sent_update
                    ema = ema * decay + new_param_shard * (1.0 - decay)
                metrics = {"loss": loss}
                metrics.update(train_metrics)

                def combine(path, value):
                    # Same key-driven contract as the grad-accum
                    # recombination: batch-carrying metrics concatenate
                    # back to the global batch, floats average, integer
                    # counts sum.
                    if (
                        _is_batch_carrying_metric(path)
                        and getattr(value, "ndim", 0) >= 1
                    ):
                        return collectives.all_gather(
                            value, axis, tiled=True
                        )
                    if jnp.issubdtype(
                        jnp.result_type(value), jnp.floating
                    ):
                        return collectives.pmean(value, axis)
                    return collectives.psum(value, axis)

                metrics = jax.tree_util.tree_map_with_path(
                    combine, metrics
                )
                new_state = state.replace(
                    step=state.step + 1,
                    variables=variables,
                    opt_state=opt_state,
                    ema_params=ema,
                    collective_residual={
                        "grad": grad_residual,
                        "update": update_residual,
                    },
                )
                return new_state, metrics

            in_specs = (
                self._quant_state_specs,
                jax.tree_util.tree_map(batch_spec, batch),
                mesh_lib.REPLICATED_SPEC,
            )
            out_specs = (self._quant_state_specs, mesh_lib.REPLICATED_SPEC)
            return collectives.smap(
                local_step, self.mesh, in_specs, out_specs
            )(state, batch, rng)

        def quant_train_scan(state: TrainState, stacked_batch, rng):
            return jax.lax.scan(
                lambda s, b: quant_train_step(s, b, rng),
                state,
                stacked_batch,
            )

        if self._quant_collective is not None:
            step_fn, scan_fn = quant_train_step, quant_train_scan
        else:
            step_fn, scan_fn = train_step, train_scan
        self.train_step = _serialize_dispatch(jax.jit(
            step_fn, donate_argnums=(0,) if donate_state else ()
        ))
        self.train_scan = _serialize_dispatch(jax.jit(
            scan_fn, donate_argnums=(0,) if donate_state else ()
        ))
        self.eval_step = _serialize_dispatch(
            jax.jit(eval_step, static_argnums=(2,))
        )
        self.predict_step = _serialize_dispatch(jax.jit(predict_step))
        # The un-jitted forward, for callers that must control tracing
        # themselves: a serving fn that rewrites the forward at trace
        # time (serve_quant.native_lowering's flax interception) cannot
        # go through the jitted version — an eager call with avals the
        # jit cache has already seen would silently execute the OLD
        # program, interception skipped.
        self.predict_step_fn = predict_step

    def init_state(self, rng: jax.Array, example_batch) -> TrainState:
        # Host time of the call (hundreds of small eager programs traced,
        # compiled or loaded, and enqueued), not the device's.
        # Each span also counts the programs the thread built inside it.
        with build_trace.span("train.init_state"):
            return self._init_state(rng, example_batch)

    def _init_state(self, rng: jax.Array, example_batch) -> TrainState:
        # The model initializes at its own (post-preprocess) contract: run the
        # preprocessor on the example batch outside jit once, in TRAIN mode so
        # init shapes match exactly what train_step will feed the network.
        with build_trace.span("train.init_state.preprocess"):
            features, _ = self.preprocessor.preprocess(
                example_batch["features"],
                _batch_labels(example_batch),
                mode=MODE_TRAIN,
                rng=jax.random.PRNGKey(0),
            )
        with build_trace.span("train.init_state.model_init"):
            state = create_train_state(
                self.model, rng, features, self.optimizer,
            )

        def place(tree, base_rule):
            # Pipeline-stage placement layers over every regime: leaves
            # under the pipe_stages key shard dim 0 over `pipe` (a
            # passthrough to base_rule when the pipe axis is 1).
            rule = mesh_lib.pipe_stage_param_rule(self.mesh, base_rule)
            return jax.tree_util.tree_map_with_path(
                lambda path, x: jax.device_put(x, rule(path, x)), tree
            )

        # Placement rules come from the layout plan — the regime branch
        # below mirrors ShardingPlan.regime() exactly, so a plan-driven
        # trainer and a kwargs-driven one place identically (the preset
        # byte-equality contract; audited below when a plan is set).
        regime = self._layout.regime()
        if regime == "quant_zero2":
            return self._audited(self._init_quant_state(state, place))

        if regime == "sharded_params":
            # Sharded-parameter regimes: fsdp shards large leaves (and the
            # mirrored optimizer/EMA copies) ZeRO-style; the model axis
            # column-splits kernels for tensor parallelism. GSPMD
            # propagates these shardings through the optimizer update, so
            # params stay sharded across steps.
            return self._audited(
                place(state, self._layout.base_param_rule(self.mesh))
            )
        # Replicate onto the mesh so jitted steps see mesh-placed inputs.
        replicate_rule = self._layout.base_param_rule(self.mesh)
        if regime == "zero2":
            # Cross-replica weight-update sharding (ZeRO-2): only the
            # optimizer-side mirrors shard; params/variables stay
            # replicated for the forward/backward. The mirrors go straight
            # to their sharded layout — materializing them replicated
            # first would need the very memory this mode exists to avoid.
            opt_state, ema_params = place(
                (state.opt_state, state.ema_params),
                self._layout.weight_update_rule(self.mesh),
            )
            state = state.replace(opt_state=(), ema_params=None)
            state = place(state, replicate_rule)
            return self._audited(
                state.replace(opt_state=opt_state, ema_params=ema_params)
            )
        return self._audited(place(state, replicate_rule))

    def _audited(self, state: TrainState) -> TrainState:
        """Leaf-for-leaf layout audit against the plan's predictions —
        only when an EXPLICIT plan drives this trainer (the hand-wired
        path stays exactly as cheap as before)."""
        if self.plan is None:
            return state
        audit = planner_lib.audit_state_layout(self._layout, self.mesh, state)
        if audit["mismatches"]:
            raise RuntimeError(
                f"plan {self.plan.name!r} layout audit failed on "
                f"{len(audit['mismatches'])} of {audit['leaves']} leaves: "
                f"{audit['mismatches'][:5]}"
            )
        return state

    def _init_quant_state(self, state: TrainState, place) -> TrainState:
        """Quantized-collective (ZeRO-2) state layout.

        Params/variables stay replicated for the forward/backward exactly
        as in the GSPMD regime; optimizer state and the EMA mirror move to
        the FLAT block-padded parameter vector, sharded over the data axis
        (each replica owns the slice its shard_map step updates), and the
        error-feedback residual joins the state as zeros. This changes
        the opt-state and EMA checkpoint layout — checkpoints are not
        interchangeable with the tree-layout regimes.
        """
        mesh = self.mesh
        num_shards = mesh.shape[mesh_lib.DATA_AXIS]
        flat, unravel = jax.flatten_util.ravel_pytree(state.params)
        self._flat_unravel = unravel
        layout = collectives.FlatShardLayout(
            flat.size, num_shards, self._quant_collective.block
        )
        self._flat_layout = layout
        replicated = mesh_lib.replicated(mesh)
        sharded = mesh_lib.flat_shard_sharding(mesh)

        def mirror_sharding(leaf):
            if getattr(leaf, "ndim", 0) == 0:
                return replicated
            return sharded

        ema = state.ema_params
        state = state.replace(opt_state=(), ema_params=None)
        state = place(state, lambda leaf: replicated)
        # The flat mirrors are born on their sharded layout: computing
        # them through jit with sharded out_shardings lets SPMD emit each
        # device's slice directly, so no device ever holds a full-size
        # padded Adam mu/nu (or the [N, padded] residual — N x params!)
        # the way materialize-then-device_put would transiently require.
        # That transient is exactly what ZeRO-2 sharding exists to avoid.
        opt_shardings = jax.tree_util.tree_map(
            mirror_sharding,
            jax.eval_shape(lambda f: self.optimizer.init(layout.pad(f)), flat),
        )
        opt_state = jax.jit(
            lambda f: self.optimizer.init(layout.pad(f)),
            out_shardings=opt_shardings,
        )(flat)
        if ema is not None:
            flat_ema = jax.flatten_util.ravel_pytree(ema)[0]
            ema = jax.jit(layout.pad, out_shardings=sharded)(flat_ema)
        residual = jax.jit(
            lambda: {
                # Per-replica untransmitted gradient remainder; dim 0 is
                # the data axis, so each replica sees its own [1, padded]
                # slice.
                "grad": jnp.zeros(
                    (num_shards, layout.padded), jnp.float32
                ),
                # Per-owner untransmitted update remainder on the flat
                # layout.
                "update": jnp.zeros((layout.padded,), jnp.float32),
            },
            out_shardings={"grad": sharded, "update": sharded},
        )()
        spec = mesh_lib.FLAT_SHARD_SPEC
        self._quant_state_specs = TrainState(
            step=mesh_lib.REPLICATED_SPEC,
            variables=jax.tree_util.tree_map(
                lambda _: mesh_lib.REPLICATED_SPEC, state.variables
            ),
            opt_state=jax.tree_util.tree_map(
                lambda leaf: (
                    mesh_lib.REPLICATED_SPEC
                    if getattr(leaf, "ndim", 0) == 0
                    else spec
                ),
                opt_state,
            ),
            ema_params=None if ema is None else spec,
            collective_residual={"grad": spec, "update": spec},
        )
        return state.replace(
            opt_state=opt_state,
            ema_params=ema,
            collective_residual=residual,
        )

    def collective_log_record(self, measure: bool = True) -> Dict[str, float]:
        """The gradient-collective observability channel: pre/post
        compression bytes of the GRADIENT EXCHANGE per device-step
        (analytic — the reduce-scatter/all-gather payloads; metric
        pmeans/gathers ride alongside uncounted) and, when `measure`, the
        measured wall-time of one exchange. {} outside the quantized
        regime. Key names are shared with `bench.py comms` via
        metrics.collective_record."""
        if self._quant_collective is None or self._flat_layout is None:
            return {}
        pre, post = collectives.wire_summary(
            self._quant_collective, self._flat_layout.padded
        )
        wall_ms = self.measure_collective_ms() if measure else None
        return collective_record(pre, post, wall_ms)

    def measure_collective_ms(self, repeats: int = 5) -> float:
        """Median wall-time of one gradient exchange (quantized
        reduce-scatter + update all-gather) in isolation, on a zeros
        payload of the real layout — compile excluded, timed per call."""
        coll, layout = self._quant_collective, self._flat_layout
        axis = mesh_lib.DATA_AXIS

        def local(flat):
            reduced, _ = coll.reduce_scatter(layout.rows(flat), axis)
            full, _ = coll.all_gather_shard(
                reduced / layout.num_shards, axis
            )
            return full

        fn = _serialize_dispatch(jax.jit(
            collectives.smap(
                local,
                self.mesh,
                (mesh_lib.REPLICATED_SPEC,),
                mesh_lib.REPLICATED_SPEC,
            )
        ))
        payload = jnp.zeros((layout.padded,), jnp.float32)
        jax.block_until_ready(fn(payload))
        times = []
        for _ in range(repeats):
            start = time.perf_counter()
            jax.block_until_ready(fn(payload))
            times.append((time.perf_counter() - start) * 1000.0)
        times.sort()
        return times[len(times) // 2]

    def shard_batch(self, batch):
        return mesh_lib.shard_batch(batch, self.mesh)


# -- checkpointing ------------------------------------------------------------


def create_checkpoint_manager(
    model_dir: str,
    save_interval_steps: int,
    keep_checkpoint_max: int = 5,
) -> ocp.CheckpointManager:
    return ocp.CheckpointManager(
        os.path.abspath(os.path.join(model_dir, "checkpoints")),
        options=ocp.CheckpointManagerOptions(
            max_to_keep=keep_checkpoint_max,
            save_interval_steps=save_interval_steps,
            create=True,
            enable_async_checkpointing=True,
        ),
    )


# Re-exported from durability (its importable, orbax-free home) for the
# trainer-side callers below and existing importers.
latest_durable_step_in = durability.latest_durable_step_in


def restore_or_init_state(
    manager: ocp.CheckpointManager, compiled: CompiledModel, rng, example_batch
) -> TrainState:
    state = compiled.init_state(rng, example_batch)
    latest = latest_durable_step_in(manager)
    if latest is not None:
        # Chaos site: `restore` (slow-restore delay / exception injection).
        chaos.maybe_fire("restore")
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            state,
        )
        state = manager.restore(latest, args=ocp.args.StandardRestore(abstract))
    return state


# -- evaluation ---------------------------------------------------------------


def normalize_eval_generators(input_generator_eval) -> Dict[str, Any]:
    """Normalizes the eval-generator argument to a {name: generator} map.

    None -> {}; a bare generator -> {"": generator}; a mapping passes
    through (multi-eval: one named dataset per entry, reference
    utils/train_eval.py:541-566).
    """
    if input_generator_eval is None:
        return {}
    if isinstance(input_generator_eval, dict):
        if "" in input_generator_eval and len(input_generator_eval) > 1:
            raise ValueError(
                "Multi-eval maps require every eval to be named (got an "
                "empty-string name alongside others)."
            )
        return dict(input_generator_eval)
    return {"": input_generator_eval}


def eval_dir_name(name: str) -> str:
    """'eval' for the unnamed eval, 'eval_<name>' per named dataset (the
    reference's per-eval-name output dirs)."""
    return "eval" if not name else f"eval_{name}"


def run_named_evals(
    compiled: "CompiledModel",
    state: "TrainState",
    eval_generators: Dict[str, Any],
    eval_steps: Optional[int],
    use_ema: bool,
    step: Optional[int] = None,
    writers: Optional[Dict[str, MetricsWriter]] = None,
) -> Dict[str, float]:
    """Evaluates every named dataset; returns merged metrics.

    The FIRST entry is the primary eval: its metrics keep unprefixed keys —
    that is what exporter compare_fns gate on. The primary never silently
    changes: if it returns no results this round, no unprefixed metrics are
    emitted (a Best gate must not compare across datasets). Every named
    eval's metrics are also recorded under '<name>/<key>'.
    """
    merged: Dict[str, float] = {}
    for i, (name, generator) in enumerate(eval_generators.items()):
        metrics = evaluate(
            compiled,
            state,
            iter(generator.create_dataset(MODE_EVAL)),
            eval_steps=eval_steps,
            use_ema=use_ema,
        )
        if not metrics:
            continue
        if writers is not None and step is not None and name in writers:
            writers[name].write(step, metrics)
        if i == 0:
            merged.update(metrics)
        if name:
            merged.update({f"{name}/{k}": v for k, v in metrics.items()})
    return merged


def evaluate(
    compiled: CompiledModel,
    state: TrainState,
    eval_batches: Iterator,
    eval_steps: Optional[int] = None,
    use_ema: bool = False,
) -> Dict[str, float]:
    """Averages model_eval_fn metrics over up to eval_steps batches.

    Accumulates on-device: steps dispatch back-to-back (transfers
    double-buffered) and the host reads the totals once at the end, rather
    than a blocking device_get per batch.
    """
    if eval_steps is not None:
        eval_batches = itertools.islice(eval_batches, eval_steps)
    totals: Optional[Dict[str, jax.Array]] = None
    count = 0
    deferred = DeferredFetch()
    for batch in infeed.device_prefetch(
        eval_batches, compiled.shard_batch, depth=infeed.resolve_depth(),
        name="eval_infeed",
    ):
        metrics = compiled.eval_step(state, batch, use_ema)
        # On-device f32 accumulation through the locked jitted helpers:
        # these adds are mesh-spanning programs like the steps, so they
        # must enqueue under the same dispatch lock (see _DISPATCH_LOCK).
        if totals is None:
            totals = _init_metric_totals(metrics)
        else:
            totals = _accumulate_metric_totals(totals, metrics)
        count += 1
        if count % 32 == 0:
            # Periodic sync: without it nothing bounds the dispatch queue
            # and long evals pile batches up on the device. Deferred by
            # one window: enqueue this window's accumulator handle and
            # drain the PREVIOUS one (finished ~32 steps ago, so the
            # readback returns immediately instead of serializing
            # dispatch behind the newest computation).
            deferred.push(next(iter(totals.values())))
    if not count or totals is None:
        return {}
    host_totals = jax.device_get(totals)
    return {key: float(value) / count for key, value in host_totals.items()}


# -- the entry point ----------------------------------------------------------


def _host_path_record(before, after, steps: int) -> Dict[str, float]:
    """Where the host's time went over one log interval, from two reads of
    the recorder's cumulative counters (utils/tracing.py): milliseconds a
    step the train thread waited for a batch, placed it and dispatched;
    milliseconds of parse workers' time one batch took (the sum over its
    slices where it was parsed in slices); the share of batches
    for which the dataset's prefetch queue was empty when asked; the
    milliseconds the loop stood in `checkpoint_and_eval`; and the programs
    the process built and the seconds that took (`jit.*` spans of
    utils/build_trace.py, every thread): after the first interval, which
    holds the step's own build, anything but 0 is a recompile in the loop."""

    def delta(key):
        return after.get(key, 0) - before.get(key, 0)

    steps = max(steps, 1)
    return {
        "infeed/wait_ms_per_step": delta("infeed.wait.ns") / 1e6 / steps,
        "infeed/h2d_ms_per_step": delta("infeed.h2d.ns") / 1e6 / steps,
        "dispatch_ms_per_step": delta("train.dispatch.ns") / 1e6 / steps,
        "input/parse_ms_per_batch": (
            delta("data.parse_chunk.ns") / 1e6
            / max(delta("data.parse_batches"), 1)
        ),
        "input/prefetch_empty_share": (
            delta("data.prefetch_empty") / max(delta("data.prefetch_gets"), 1)
        ),
        "checkpoint/stall_ms": delta("train.checkpoint.ns") / 1e6,
        "compile/programs_built": float(delta("jit.compile.n")),
        "compile/seconds": (
            delta("jit.trace.ns") + delta("jit.lower.ns")
            + delta("jit.compile.ns")
        ) / 1e9,
    }


_TOKEN_KEYS = ("tokens", "pad_tokens")
#: Counts a model with routed experts adds to them (layers/moe.RoutedExperts).
_MOE_KEYS = (
    "moe_routed_rows", "moe_max_expert_rows", "moe_peak_rows", "moe_positions"
)


def add_token_counts(sums, metrics):
    """Token-sequence models put `tokens` (positions with a loss) and
    `pad_tokens` into a step's metrics, and those with routed experts the
    layers' `_MOE_KEYS`. Adds one step's counts (or a scanned chunk's
    stacked ones) to the running sums on the device, which the next log
    reads back with the metrics it reads anyway. `sums` comes back as it was
    for any other model."""
    if any(key not in metrics for key in _TOKEN_KEYS):
        return sums
    counts = {
        key: jnp.round(jnp.sum(metrics[key])).astype(jnp.int32)
        for key in _TOKEN_KEYS + _MOE_KEYS if key in metrics
    }
    if sums is None:
        return counts
    return {key: sums[key] + counts[key] for key in counts}


def token_log_record(token_sums, seconds: float) -> Dict[str, float]:
    """From the counts summed over an interval's steps, already read back:
    the recorder's counters `train.tokens` and `train.pad_tokens` grow by
    them, and the log record gets `tokens_per_s` and `pad_share` (padding
    over the positions that are a token or padding). Where the steps counted
    routed experts, `moe.routed_rows` and `moe.max_expert_rows` grow too and
    the record gets `moe_rows_per_token` (pairs routed to held experts over
    positions routed, summed over layers) and `moe_imbalance` (the fullest
    held expert's rows over the mean held expert's). Empty where no step
    counted tokens."""
    if token_sums is None:
        return {}
    tokens, pad = (int(token_sums[key]) for key in _TOKEN_KEYS)
    tracing.count("train.tokens", tokens)
    tracing.count("train.pad_tokens", pad)
    record = {
        "tokens_per_s": tokens / max(seconds, 1e-9),
        "pad_share": pad / max(tokens + pad, 1),
    }
    if all(key in token_sums for key in _MOE_KEYS):
        routed, fullest, peak, positions = (
            int(token_sums[key]) for key in _MOE_KEYS
        )
        tracing.count("moe.routed_rows", routed)
        tracing.count("moe.max_expert_rows", fullest)
        record["moe_rows_per_token"] = routed / max(positions, 1)
        record["moe_imbalance"] = peak / max(routed, 1)
    return record


def train_eval_model(
    t2r_model: AbstractT2RModel,
    input_generator_train=None,
    input_generator_eval=None,
    model_dir: str = "/tmp/t2r_tpu_model",
    max_train_steps: int = 1000,
    eval_steps: Optional[int] = 100,
    save_checkpoints_steps: int = 500,
    keep_checkpoint_max: int = 5,
    log_every_steps: int = 100,
    create_exporters_fn: Optional[Callable] = None,
    hook_builders: Optional[List[HookBuilder]] = None,
    mesh=None,
    seed: int = 0,
    use_ema_for_eval: Optional[bool] = None,
    use_tensorboard: Optional[bool] = None,
    iterations_per_loop: int = 1,
    infeed_depth: Optional[int] = None,
    remat: bool = False,
    grad_accum_steps: int = 1,
    shard_weight_update: bool = False,
    plan: Optional[planner_lib.ShardingPlan] = None,
) -> Dict[str, float]:
    """Trains (and periodically evaluates/exports) the model.

    Returns the final eval metrics (empty dict when no eval generator).
    Resumes from the latest checkpoint in model_dir if present.

    iterations_per_loop > 1 runs K device steps per host dispatch via a
    jitted lax.scan (reference TPUConfig.iterations_per_loop); per-step
    hooks then observe loop granularity, exactly as reference SessionRunHooks
    did under TPUEstimator. infeed_depth batches are kept device-resident
    ahead of the consumer (None reads T2R_INFEED_DEPTH; default 2 =
    double-buffered host->device transfer).
    remat / grad_accum_steps / shard_weight_update are the memory levers
    (see CompiledModel): recompute activations in the backward, split
    each batch into K gradient-accumulation microbatches, and/or shard
    optimizer state across data-parallel replicas (ZeRO-2).
    plan: a planner_lib.ShardingPlan driving mesh + regime (see
    CompiledModel); None consults the T2R_PLAN flag ('off' = the
    hand-wired kwargs path, byte-for-byte; a preset name or 'auto'
    resolves a plan through parallel/planner.py).
    """
    build_trace.install()
    model = maybe_wrap_for_tpu(t2r_model)
    print_specification(model)
    os.makedirs(model_dir, exist_ok=True)
    _save_operative_config(model_dir)

    infeed_depth = infeed.resolve_depth(infeed_depth)
    if use_ema_for_eval is None:
        use_ema_for_eval = getattr(model, "use_avg_model_params", False)

    if input_generator_train is None:
        raise ValueError("train_eval_model requires input_generator_train.")
    provide_input_generator_with_model_information(
        input_generator_train, model, MODE_TRAIN
    )
    train_batches = iter(input_generator_train.create_dataset(MODE_TRAIN))
    # Multi-eval: a {name: generator} map evaluates every named dataset per
    # eval round (reference multi-eval-name -> EvalSpec override,
    # utils/train_eval.py:541-566). A bare generator is the single-eval case.
    eval_generators = normalize_eval_generators(input_generator_eval)
    for generator in eval_generators.values():
        provide_input_generator_with_model_information(
            generator, model, MODE_EVAL
        )

    # Writer-side durability sweep BEFORE the manager opens: torn step
    # dirs (a SIGKILL mid-save, a half-copied restore source) move to
    # checkpoints.quarantine/ so the resumed run re-saves the replayed
    # window without colliding with the wreckage, and latest_step can
    # never name them. The trainer owns this dir — readers only skip.
    for torn_name, torn_reason in durability.sweep_torn_checkpoints(model_dir):
        print(
            f"Quarantined torn checkpoint {torn_name!r}: {torn_reason}",
            flush=True,
        )
    manager = create_checkpoint_manager(
        model_dir, save_interval_steps=save_checkpoints_steps,
        keep_checkpoint_max=keep_checkpoint_max,
    )
    rng = jax.random.PRNGKey(seed)
    rng_init, rng_train = jax.random.split(rng)
    first_batch = next(train_batches)
    if plan is None:
        # The T2R_PLAN gate: 'off' (default) returns None and the kwargs
        # below drive the trainer exactly as before; a preset name or
        # 'auto' makes the planner the source of mesh + regime.
        plan = planner_lib.resolve_plan_from_flag(model, first_batch)
    compiled = CompiledModel(
        model, mesh=mesh, remat=remat, grad_accum_steps=grad_accum_steps,
        shard_weight_update=shard_weight_update,
        plan=plan,
    )
    # init_state runs the preprocessor and the model's init eagerly: over
    # the mesh like every later batch, not over the global batch on the
    # first device, which holds one device's share and no more. The
    # laid-over copy is let go with the call; `first_batch` goes on into
    # `host_batches` as the host arrays it is.
    state = restore_or_init_state(
        manager, compiled, rng_init, compiled.shard_batch(first_batch)
    )
    start_step = int(jax.device_get(state.step))

    writer = MetricsWriter(
        os.path.join(model_dir, "train"),
        use_tensorboard=(
            use_tensorboard
            if use_tensorboard is not None
            else model.use_summaries
        ),
    )
    eval_writers = {
        name: MetricsWriter(
            os.path.join(model_dir, eval_dir_name(name)),
            use_tensorboard=False,
        )
        for name in eval_generators
    }

    hooks: List[Hook] = []
    for builder in hook_builders or []:
        hooks.extend(builder.create_hooks(model, trainer=compiled))
    ctx = HookContext(model=model, model_dir=model_dir, step=start_step,
                      state=state)
    for hook in hooks:
        hook.on_train_begin(ctx)

    exporters = (
        create_exporters_fn(model) if create_exporters_fn is not None else []
    )

    def run_eval_and_export(state, step: int) -> Dict[str, float]:
        eval_metrics = run_named_evals(
            compiled,
            state,
            eval_generators,
            eval_steps=eval_steps,
            use_ema=use_ema_for_eval,
            step=step,
            writers=eval_writers,
        )
        for exporter in exporters:
            exporter.maybe_export(
                step=step,
                state=state,
                eval_metrics=eval_metrics,
                compiled=compiled,
                model_dir=model_dir,
            )
        ctx.step = step
        ctx.state = state
        ctx.eval_metrics = eval_metrics
        for hook in hooks:
            hook.after_eval(ctx)
        return eval_metrics

    final_eval: Dict[str, float] = {}
    step = start_step
    t_last = time.time()
    last_log_step = start_step
    last_saved_step = start_step
    host_batches = itertools.chain([first_batch], train_batches)
    if start_step > 0:
        # Crash-consistency contract: step k of a RESUMED run must see
        # the same batch step k of an uninterrupted run saw, or the
        # replayed trajectory diverges from the one the crash
        # interrupted. Deterministic generators restart their stream
        # from batch 0 each process, so skip the batches the restored
        # steps already consumed. (Linear in start_step — the price of
        # replay-exactness; shuffled real-data pipelines were never
        # bitwise-resumable and merely skip cheap host parses here.)
        host_batches = itertools.islice(host_batches, start_step, None)

    # Collective observability (quantized ZeRO-2 regime only): byte
    # counters plus a one-off wall-time probe, merged into every log
    # record so the metrics stream carries the comms cost alongside
    # steps_per_sec. Empty dict everywhere else.
    collective_info = compiled.collective_log_record()

    host_counters = tracing.counters()

    token_sums = None  # add_token_counts: every step since the last log

    def log_metrics(step: int, metrics) -> Dict[str, float]:
        nonlocal t_last, last_log_step, host_counters, token_sums
        with tracing.span("train.log", ordinal=step - 1):
            fetched, interval_tokens = jax.device_get((metrics, token_sums))
            token_sums = None
            host_metrics = {
                key: float(value)
                for key, value in fetched.items()
                if getattr(value, "ndim", 0) == 0
            }
            now = time.time()
            host_metrics["steps_per_sec"] = (
                (step - last_log_step) / max(now - t_last, 1e-9)
            )
            host_metrics.update(collective_info)
            host_metrics.update(token_log_record(interval_tokens, now - t_last))
            counters = tracing.counters()
            host_metrics.update(
                _host_path_record(host_counters, counters, step - last_log_step)
            )
            host_counters = counters
            t_last = now
            last_log_step = step
            writer.write(step, host_metrics)
            return host_metrics

    # after_checkpoint_saved's contract is a DURABLE on-disk checkpoint
    # (backup/eval hooks read ctx.checkpoint_path); only when such a hook
    # is actually installed does the loop pay a finalize barrier. Plain
    # runs let the async save overlap the next train window and finalize
    # at exit (the `finally` below) or at the next save (orbax serializes
    # saves internally).
    ckpt_hooks_present = any(
        type(hook).after_checkpoint_saved is not Hook.after_checkpoint_saved
        for hook in hooks
    )

    def checkpoint_and_eval(state, step: int) -> Dict[str, float]:
        nonlocal last_saved_step
        # The loop stands still for all of this: the snapshot of the state
        # to host memory, hooks, evals and exporters.
        with tracing.span("train.checkpoint", ordinal=step - 1):
            previous_saved = last_saved_step
            # Async save: orbax snapshots device arrays to host memory before
            # returning, then writes in the background — the next scan window
            # dispatches immediately instead of stalling on serialization.
            manager.save(step, args=ocp.args.StandardSave(state), force=True)
            # Issuing this save was the commit barrier for the PREVIOUS one
            # (orbax serializes saves): publish its durability manifest.
            # No-op when no prior save exists (previous_saved is start_step
            # on the first call; publish_durable ignores absent dirs).
            durability.publish_durable(model_dir, previous_saved)
            # Chaos site: the async write for `step` is now in flight — a
            # `kill` clause here is the SIGKILL-mid-orbax-save fault the
            # crash-consistency suite injects. (After the previous step's
            # blessing: a crash mid-save must not cost the durable past.)
            chaos.maybe_fire("save")
            last_saved_step = step
            ctx.checkpoint_path = str(
                os.path.join(model_dir, "checkpoints", str(step))
            )
            if ckpt_hooks_present:
                manager.wait_until_finished()
                durability.publish_durable(model_dir, step)
            for hook in hooks:
                hook.after_checkpoint_saved(ctx)
            return run_eval_and_export(state, step)

    try:
        if iterations_per_loop <= 1:
            # The global step is the ordinal the dataset gave the batch it
            # consumes (the resume contract above), so one batch's spans
            # share it from `data.read_chunk` to `train.dispatch`. The loop
            # body is spans end to end: a device idle gap always falls
            # under a named one.
            device_batches = infeed.device_prefetch(
                host_batches, compiled.shard_batch, depth=infeed_depth,
                ordinals=itertools.count(step),
            )
            for batch in device_batches:
                if step >= max_train_steps:
                    break
                ctx.step = step
                with tracing.span("train.hooks", ordinal=step):
                    for hook in hooks:
                        hook.before_step(ctx)
                with tracing.span("train.dispatch", ordinal=step) as dispatch:
                    dispatch.add(late=infeed.late_at_dispatch(batch))
                    state, metrics = compiled.train_step(state, batch, rng_train)
                    token_sums = add_token_counts(token_sums, metrics)
                    # The enqueued step holds the batch from here. The
                    # loop's reference goes now, so that freeing it falls
                    # under this span and not between two.
                    del batch
                step += 1
                ctx.step = step
                ctx.state = state
                # Full per-step metric tree as device arrays (hooks fetch
                # lazily; golden-value capture reads non-scalar entries).
                ctx.device_metrics = metrics
                if step % log_every_steps == 0 or step == max_train_steps:
                    ctx.metrics = log_metrics(step, metrics)
                else:
                    ctx.metrics = None
                with tracing.span("train.hooks", ordinal=step - 1):
                    for hook in hooks:
                        hook.after_step(ctx)
                if step % save_checkpoints_steps == 0 or step == max_train_steps:
                    final_eval = checkpoint_and_eval(state, step)
        else:
            # Multi-step regime: chunk sizes clamp at checkpoint boundaries
            # so every checkpoint still lands on its exact step.
            def chunk_sizes():
                s = step
                while s < max_train_steps:
                    boundary = min(
                        max_train_steps,
                        (s // save_checkpoints_steps + 1) * save_checkpoints_steps,
                    )
                    k = min(iterations_per_loop, boundary - s)
                    yield k
                    s += k

            def host_chunks():
                for k in chunk_sizes():
                    chunk = list(itertools.islice(host_batches, k))
                    if len(chunk) < k:
                        return  # host data exhausted
                    yield chunk

            # `infeed.wait` covers a chunk's k host batches, `infeed.h2d`
            # its stacking and placement; a chunk's ordinal is that of its
            # first batch.
            device_chunks = infeed.device_prefetch(
                host_chunks(),
                lambda chunk: infeed.shard_stacked_batch(
                    infeed.stack_batches(chunk), compiled.mesh
                ),
                depth=infeed_depth,
                ordinals=itertools.accumulate(
                    chunk_sizes(), initial=step
                ),
            )
            for device_chunk in device_chunks:
                k = int(jax.tree_util.tree_leaves(device_chunk)[0].shape[0])
                ctx.step = step
                with tracing.span("train.hooks", ordinal=step):
                    for hook in hooks:
                        hook.before_step(ctx)
                with tracing.span("train.dispatch", ordinal=step) as dispatch:
                    dispatch.add(late=infeed.late_at_dispatch(device_chunk))
                    state, stacked_metrics = compiled.train_scan(
                        state, device_chunk, rng_train
                    )
                    token_sums = add_token_counts(token_sums, stacked_metrics)
                    # Hooks observe loop granularity: the final step's
                    # metrics (one small eager program a leaf).
                    ctx.device_metrics = jax.tree_util.tree_map(
                        lambda leaf: leaf[-1], stacked_metrics
                    )
                    del device_chunk  # as in the single-step loop
                step += k
                ctx.step = step
                ctx.state = state
                if step % log_every_steps < k or step == max_train_steps:
                    ctx.metrics = log_metrics(step, ctx.device_metrics)
                else:
                    ctx.metrics = None
                with tracing.span("train.hooks", ordinal=step - 1):
                    for hook in hooks:
                        hook.after_step(ctx)
                if step % save_checkpoints_steps == 0 or step == max_train_steps:
                    final_eval = checkpoint_and_eval(state, step)
                if step >= max_train_steps:
                    break

        if step > last_saved_step:
            # Host data exhausted mid-interval: checkpoint the trained steps
            # instead of silently dropping them.
            final_eval = checkpoint_and_eval(state, step)

    finally:
        for hook in hooks:
            hook.on_train_end(ctx)
        writer.close()
        for eval_writer in eval_writers.values():
            eval_writer.close()
        manager.wait_until_finished()
        # Exit barrier: the final async save is committed — publish its
        # durability manifest so the next run restores from it without
        # falling back to the structural check (no-op when nothing saved).
        durability.publish_durable(model_dir, last_saved_step)
        manager.close()
        _save_operative_config(model_dir)
    return final_eval


def _save_operative_config(model_dir: str) -> None:
    """Persists the operative config artifact (gin parity: the reference's
    GinConfigSaverHook wrote the operative config on the chief,
    models/abstract_model.py:772-775)."""
    from tensor2robot_tpu import config as cfg_mod

    try:
        cfg_mod.save_operative_config(model_dir)
    except OSError as e:
        import logging

        logging.warning("Could not write operative config to %s: %s", model_dir, e)


def predict_from_model(
    t2r_model: AbstractT2RModel,
    input_generator,
    model_dir: str,
    mesh=None,
) -> Iterator[TensorSpecStruct]:
    """Restores the latest checkpoint and yields export outputs per batch
    (reference predict_from_model :389-419)."""
    model = maybe_wrap_for_tpu(t2r_model)
    compiled = CompiledModel(model, mesh=mesh, donate_state=False)
    provide_input_generator_with_model_information(
        input_generator, model, MODE_PREDICT
    )
    batches = iter(input_generator.create_dataset(MODE_PREDICT))
    first = next(batches)
    manager = create_checkpoint_manager(model_dir, save_interval_steps=1)
    if latest_durable_step_in(manager) is None:
        raise FileNotFoundError(
            f"No durable checkpoint found under {model_dir!r}; refusing to "
            "serve randomly-initialized (or torn) weights. Use init_randomly "
            "on a predictor if that is intended."
        )
    state = restore_or_init_state(
        manager, compiled, jax.random.PRNGKey(0), first
    )
    use_ema = getattr(model, "use_avg_model_params", False)
    variables = state.export_variables(use_ema=use_ema)

    def predict(batch):
        batch = compiled.shard_batch(batch)
        features, _ = compiled.preprocessor.preprocess(
            batch["features"],
            batch.get("labels"),
            mode=MODE_PREDICT,
            rng=None,
        )
        return jax.device_get(compiled.predict_step(variables, features))

    yield predict(first)
    for batch in batches:
        yield predict(batch)


# What importing the training stack (jax, flax, optax, orbax, this package)
# cost the process, from the package's first touch to here (`own_ns`: of
# it, this module's first line to its last; the rest is whatever the caller
# imported and did between the two); and when the process began, so that
# what ran before that touch is a difference.
tracing.since(
    "program.import", tensor2robot_tpu.IMPORT_START_NS,
    own_ns=time.time_ns() - _IMPORT_BEGAN_NS,
)
_PROCESS_START_NS = build_trace.process_start_ns()
if _PROCESS_START_NS is not None:
    tracing.count("process.start_ns", _PROCESS_START_NS)
