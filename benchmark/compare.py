"""The comparison that decides `correct` for a training cell.

The program's first three steps (driven through the window's own call and
feed during set-up, on the very object the window then drives) are read
as a handful of numbers; the plain reference follows the same three steps
from the same seeded weights on the same raw batches, once the window has
closed and the program's state is freed. Compared, each against a limit
of its own (`limits` in the cell's file, set from chip readings, PERF.md):

  loss1..3     |program - reference| / |reference| of each step's loss
  grad_norm    worst leaf of the first gradient as the optimizer got it:
               | ||g_program|| - ||g_reference|| | over the larger of the
               reference's norm of that leaf and of its median leaf
  update_norm  the same measure of the parameters' change over the three
               steps; leaves whose reference gradient is under a thousandth
               of the median leaf's are left out of it (they move by
               round-off alone under Adam)
  grad_norm_median, update_norm_median
               the median leaf's gap where the other two take the worst
               leaf's: steady from seed to seed where the worst leaf is an
               ill-conditioned sum that any rounding upsets (PERF.md)

The reference side is plain: value_and_grad of the reference's loss, a
hand-written momentum or Adam update, float32 at `highest` precision. On
a cell of several chips it follows the one global batch: the rows are laid
over the cell's devices, the weights and the optimizer's state on each, and
the same jitted step is partitioned by the compiler, so the statistics, the
loss and the gradient are those of all the rows (the critic's float32 step
takes 12.3 GB at 256 rows: a chip holds its own share of the rows and no
more). On one device nothing is placed.
`quant` lowers the precision (the control); `fault` plants one of the
faults a training cell can have into the reference put in the program's
place (tests and the control script read them).
"""

from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp
import numpy as np

STEPS = 3
SKIP_UPDATE_BELOW = 1e-3  # of the median leaf's reference gradient norm


# -- lower precisions for the control -----------------------------------------


def _round_trip(name):
    """x -> x rounded to `name` and back to float32; formats of small range
    are scaled by the tensor's own largest magnitude, as fp8 and int8
    recipes do."""
    if name == "bfloat16":
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    formats = {
        "float8_e4m3": (jnp.float8_e4m3fn, 448.0),
        "float8_e5m2": (jnp.float8_e5m2, 57344.0),
    }
    if name in formats:
        dtype, top = formats[name]

        def fp8(x):
            scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
            return (x / scale).astype(dtype).astype(jnp.float32) * scale

        return fp8
    if name == "int8":
        def int8(x):
            scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
            return jnp.clip(jnp.round(x / scale), -127, 127) * scale

        return int8
    raise ValueError(f"no quantizer {name!r}")


def quantizer(name):
    """What a later PR computing its matrix products in precision `name`
    would feed its matrix units: every operand of every convolution and
    dot rounded to `name` on the way forward, and the gradient that comes
    back for it rounded the same way (the backward products take it as an
    operand). None for float32."""
    if name in (None, "float32"):
        return None
    round_trip = _round_trip(name)

    @jax.custom_vjp
    def quant(x):
        return round_trip(x)

    quant.defvjp(lambda x: (round_trip(x), None), lambda _, g: (round_trip(g),))
    return quant


# -- the reference's three steps ----------------------------------------------


def _leaf_norms(tree):
    return {key: jnp.sqrt(jnp.sum(jnp.square(value))) for key, value in tree.items()}


def _optimizer_update(spec, params, grads, opt, count):
    """One plain update; returns (new params, new optimizer state)."""
    lr = spec["learning_rate"]
    if spec["kind"] == "momentum":
        trace = {k: grads[k] + spec["momentum"] * opt[k] for k in params}
        return {k: params[k] - lr * trace[k] for k in params}, trace
    if spec["kind"] == "adam":
        b1, b2, eps = spec["b1"], spec["b2"], spec["eps"]
        mu = {k: b1 * opt["mu"][k] + (1 - b1) * grads[k] for k in params}
        nu = {k: b2 * opt["nu"][k] + (1 - b2) * jnp.square(grads[k]) for k in params}
        t = count + 1
        new = {
            k: params[k] - lr * (mu[k] / (1 - b1 ** t))
            / (jnp.sqrt(nu[k] / (1 - b2 ** t)) + eps)
            for k in params
        }
        return new, {"mu": mu, "nu": nu}
    raise ValueError(f"no optimizer {spec['kind']!r}")


def _optimizer_init(spec, params):
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    if spec["kind"] == "adam":
        return {"mu": zeros, "nu": dict(zeros)}
    return zeros


_STEP_CACHE = {}


def _reference_step(ref, config, quant):
    """The jitted plain step, built once for a configuration and a
    precision: the key and the step number are arguments, so that a second
    seed finds the program compiled."""
    cache_key = (ref.__name__, repr(sorted(config["model"].items())), quant)
    if cache_key not in _STEP_CACHE:
        spec = ref.optimizer(config)
        quant_fn = quantizer(quant)

        def step(params, opt, batch, base_key, count):
            key = jax.random.fold_in(base_key, count)
            loss, grads = jax.value_and_grad(
                lambda p: ref.loss_fn(p, batch, key, config, quant_fn)
            )(params)
            new_params, new_opt = _optimizer_update(
                spec, params, grads, opt, count
            )
            return new_params, new_opt, loss, _leaf_norms(grads)

        _STEP_CACHE[cache_key] = jax.jit(step)
    return _STEP_CACHE[cache_key]


_delta_norms = jax.jit(
    lambda new, old: _leaf_norms({k: new[k] - old[k] for k in new})
)


def _placements(devices):
    """(rows, whole): a batch with its leading axis split over `devices`,
    and a tree held whole on each of them. The identity on one device."""
    if devices is None or len(devices) < 2:
        return (lambda tree: tree), (lambda tree: tree)
    mesh = jax.sharding.Mesh(np.asarray(devices), ("rows",))
    split = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("rows"))
    whole = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    return (
        lambda tree: jax.device_put(tree, split),
        lambda tree: jax.device_put(tree, whole),
    )


def reference_readings(ref, config, params0, batches, base_key, *,
                       quant=None, fault=None, devices=None):
    """Follows STEPS plain steps; returns {"loss": [..], "grad_norms": {..},
    "update_norms": {..}} as host floats.

    `batches`: the raw batch of each step (the same object STEPS times
    where the batch is resident). `base_key`: the key the program's step
    folds its step number into. `fault`: None, "state_unchanged" or
    "half_batch". `devices`: the cell's devices; with more than one the
    rows of each batch are split over them (the batch's rows divide by
    their number, as the program's do).
    """
    step = _reference_step(ref, config, quant)
    rows, whole = _placements(devices)
    params0 = whole(params0)
    base_key = whole(base_key)
    params = params0
    opt = whole(_optimizer_init(ref.optimizer(config), params0))
    losses, grad_norms = [], None
    for index in range(STEPS):
        batch = batches[index]
        if fault == "half_batch":
            batch = jax.tree_util.tree_map(lambda x: x[: len(x) // 2], batch)
        new_params, new_opt, loss, norms = step(
            params, opt, rows(batch), base_key, whole(jnp.asarray(index, jnp.int32))
        )
        if fault != "state_unchanged":
            params, opt = new_params, new_opt
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = {k: float(v) for k, v in norms.items()}
    update = _delta_norms(params, params0)
    return {
        "loss": losses,
        "grad_norms": grad_norms,
        "update_norms": {k: float(v) for k, v in update.items()},
    }


# -- the numbers compared -----------------------------------------------------


def _leaf_gaps(program, reference, keys):
    """(worst gap, its leaf, median gap) of the norms of `keys`: each gap is
    |program's norm - reference's norm| over the larger of the reference's
    norm of that leaf and of its median leaf."""
    median = statistics.median(reference[k] for k in reference)
    gaps = {}
    for key in keys:
        scale = max(reference[key], median, 1e-30)
        gap = abs(program[key] - reference[key]) / scale
        gaps[key] = gap if np.isfinite(gap) else float("inf")
    worst_key = max(gaps, key=gaps.get)
    return gaps[worst_key], worst_key, statistics.median(gaps.values())


def _mean_gap(program, reference, keys):
    """Mean over `keys` of |program's norm - reference's| / reference's."""
    return statistics.fmean(
        abs(program[k] - reference[k]) / max(reference[k], 1e-30) for k in keys
    )


def compared_numbers(program, reference):
    """{name: value} of the numbers held to a limit, and the worst leaves."""
    numbers = {}
    for index in range(STEPS):
        ref_loss = reference["loss"][index]
        gap = abs(program["loss"][index] - ref_loss) / max(abs(ref_loss), 1e-30)
        numbers[f"loss{index + 1}"] = gap if np.isfinite(gap) else float("inf")
    grads = reference["grad_norms"]
    missing = sorted(set(grads) ^ set(program["grad_norms"]))
    if missing:
        raise KeyError(f"program and reference name different leaves: {missing[:6]}")
    numbers["grad_norm"], grad_leaf, numbers["grad_norm_median"] = _leaf_gaps(
        program["grad_norms"], grads, sorted(grads)
    )
    median = statistics.median(grads.values())
    moved = [k for k in sorted(grads) if grads[k] >= SKIP_UPDATE_BELOW * median]
    numbers["update_norm"], update_leaf, numbers["update_norm_median"] = (
        _leaf_gaps(program["update_norms"], reference["update_norms"], moved)
    )
    return numbers, {"grad_norm": grad_leaf, "update_norm": update_leaf,
                     "leaves_skipped": len(grads) - len(moved)}


def judge(numbers, cell_limits):
    """(correct, {name: [value, limit]}): every limited number under its
    limit. A number the cell's limits do not name is shown with no limit
    and not judged (PERF.md says which and why)."""
    shown, correct = {}, True
    for name, value in numbers.items():
        limit = cell_limits.get(name)
        shown[name] = [value, limit]
        if limit is not None and not value <= limit:
            correct = False
    return correct, shown
