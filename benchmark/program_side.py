"""What the benchmark takes from the program: the system under test, built
from a configuration file, with the benchmark's seeded weights handed in
through the model's own warm-start hook, and a few readings of its state.

This is the only module that knows the program's constructors, its
variable tree and optax's state classes.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

import compare


def flatten(tree, prefix=""):
    """{'a/b/c': leaf} of a nested dict of arrays."""
    out = {}
    for key, value in tree.items():
        path = f"{prefix}{key}"
        if isinstance(value, dict):
            out.update(flatten(value, f"{path}/"))
        else:
            out[path] = value
    return out


def _unflatten_like(template, flat, prefix=""):
    out = {}
    for key, value in template.items():
        path = f"{prefix}{key}"
        if isinstance(value, dict):
            out[key] = _unflatten_like(value, flat, f"{path}/")
        else:
            if path not in flat:
                raise KeyError(f"the reference has no weight for {path!r}")
            if tuple(flat[path].shape) != tuple(value.shape):
                raise ValueError(
                    f"{path}: reference shape {tuple(flat[path].shape)} "
                    f"against the program's {tuple(value.shape)}"
                )
            out[key] = flat[path]
    return out


def build_model(config, weights, wrap=True):
    """The configuration's model, wrapped for the TPU as the trainer does
    (`wrap=False`: left for `train_eval_model` to wrap; a configuration
    with `"device_type": "cpu"`, as the tiny presets of the tests have, is
    never wrapped and runs in float32), warm-started from `weights`
    ({checkpoint path: array})."""
    from tensor2robot_tpu.train.train_eval import maybe_wrap_for_tpu

    module_name, class_name = config["constructor"].rsplit(".", 1)
    cls = getattr(importlib.import_module(module_name), class_name)
    unused = set(weights)

    def warm_start(variables):
        variables = dict(variables)
        # Copies: the trainer donates its state, and the comparison still
        # needs the seeded weights once the window has closed.
        flat = {key: jnp.copy(value) for key, value in weights.items()}
        variables["params"] = _unflatten_like(variables["params"], flat)
        unused.difference_update(flatten(variables["params"]))
        if unused:
            raise KeyError(
                f"reference weights the program has no leaf for: {sorted(unused)[:6]}"
            )
        return variables

    kwargs = dict(config.get("arguments", {}))
    for key, value in kwargs.items():
        if isinstance(value, list):
            kwargs[key] = tuple(value)
    model = cls(
        device_type=config.get("device_type", "tpu"),
        init_from_checkpoint_fn=warm_start, **kwargs,
    )
    return maybe_wrap_for_tpu(model) if wrap else model


def as_program_batch(raw):
    """{"features": {...}, "labels": {...}} of flat dicts as the structs the
    program's preprocessors read by attribute."""
    from tensor2robot_tpu.specs import TensorSpecStruct

    return {
        group: TensorSpecStruct(dict(values))
        for group, values in raw.items()
    }


def _first_gradient(opt_state, spec):
    """The first gradient as the optimizer got it, from its state after one
    step: momentum's trace is the gradient itself, Adam's first moment is
    (1 - b1) times it."""
    import optax

    for node in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda n: isinstance(
            n, (optax.TraceState, optax.ScaleByAdamState))
    ):
        if isinstance(node, optax.TraceState):
            return flatten(node.trace), 1.0
        if isinstance(node, optax.ScaleByAdamState):
            return flatten(node.mu), 1.0 / (1.0 - spec["b1"])
    raise TypeError("no momentum trace or Adam moment in the optimizer state")


class StepReadings:
    """Reads the program's first steps as the numbers `compare` wants.

    Every reading is enqueued on the device behind the step that made it
    and in front of the step that will take the donated state, and fetched
    only in `result()`: nothing here waits for the device in the loop.
    """

    def __init__(self, optimizer_spec):
        self._spec = optimizer_spec
        self._params0 = None
        self._losses = []
        self._grad_norms = None
        self._update_norms = None
        self._norms = jax.jit(
            lambda tree, scale: {
                k: scale * jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                for k, v in tree.items()
            }
        )
        self._delta_norms = jax.jit(
            lambda new, old: {
                k: jnp.sqrt(jnp.sum(jnp.square(new[k] - old[k]))) for k in new
            }
        )

    def begin(self, state):
        self._params0 = jax.tree_util.tree_map(jnp.copy, flatten(state.params))

    def after_step(self, steps_done, state, metrics):
        if steps_done > compare.STEPS:
            return
        self._losses.append(metrics["loss"])
        if steps_done == 1:
            grads, scale = _first_gradient(state.opt_state, self._spec)
            self._grad_norms = self._norms(grads, scale)
        if steps_done == compare.STEPS:
            self._update_norms = self._delta_norms(
                flatten(state.params), self._params0
            )
            self._params0 = None

    def result(self):
        return {
            "loss": [float(x) for x in self._losses],
            "grad_norms": {k: float(v) for k, v in self._grad_norms.items()},
            "update_norms": {k: float(v) for k, v in self._update_norms.items()},
        }
