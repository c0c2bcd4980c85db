"""Operations and bytes of a train step, counted from the plain reference.

`count(fn, params, *inputs)` walks `jax.make_jaxpr(fn)` at the cell's
shapes and finds every `conv_general_dilated` and `dot_general`. For each
it takes 2 x multiply-accumulates as the forward operations, and the
operands and the result at the configuration's compute dtype as the
forward bytes. The convention for a train step, written down here and
never changed:

  * an equation with an operand that depends on the parameters and one
    that carries activations costs three passes (forward, gradient of the
    input, gradient of the weight), each of the forward's operations and
    each reading two of {input, weight, result} and writing the third, so
    3 x the forward's operations and 3 x its bytes;
  * an equation whose activation operand does not depend on any parameter
    (the first layer: its input is the image) needs no input gradient and
    costs two passes;
  * elementwise work, reductions, pooling, the optimizer: not counted. They
    are bandwidth, not model FLOPs; MFU is convolution and matrix work over
    the chip's peak, and the roofline share is about those kernels alone.

The count comes from the reference's equations, so a PR that rewrites the
program's stem, pads a stage or fuses differently changes XLA's
`cost_analysis` and leaves this yardstick where it was.
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.extend.core import Literal


def conv_forward(lhs_shape, rhs_shape, out_shape, dimension_numbers,
                 feature_group_count=1, batch_group_count=1):
    """(multiply-accumulates, operand+result elements) of one convolution."""
    rhs_spec = dimension_numbers.rhs_spec  # (out feature, in feature, *spatial)
    in_features = rhs_shape[rhs_spec[1]]
    kernel_spatial = math.prod(rhs_shape[d] for d in rhs_spec[2:])
    macs = math.prod(out_shape) * in_features * kernel_spatial
    macs //= batch_group_count
    del feature_group_count  # rhs in-feature dim is already per group
    elements = math.prod(lhs_shape) + math.prod(rhs_shape) + math.prod(out_shape)
    return macs, elements


def dot_forward(lhs_shape, rhs_shape, out_shape, dimension_numbers):
    (lhs_contract, _), _ = dimension_numbers
    contract = math.prod(lhs_shape[d] for d in lhs_contract)
    macs = math.prod(out_shape) * contract
    elements = math.prod(lhs_shape) + math.prod(rhs_shape) + math.prod(out_shape)
    return macs, elements


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        values = value if isinstance(value, (tuple, list)) else (value,)
        for item in values:
            if hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr          # ClosedJaxpr
            elif hasattr(item, "eqns"):
                yield item                # Jaxpr


def _walk(jaxpr, tainted_in, rows):
    """Appends (primitive, macs, elements, passes) rows; returns the taint
    of the outvars. `tainted_in`: which invars depend on the parameters."""
    tainted = {
        var for var, flag in zip(jaxpr.invars, tainted_in) if flag
    }

    def is_tainted(var):
        return not isinstance(var, Literal) and var in tainted

    for eqn in jaxpr.eqns:
        flags = [is_tainted(v) for v in eqn.invars]
        name = eqn.primitive.name
        if name in ("conv_general_dilated", "dot_general"):
            lhs, rhs = eqn.invars[0].aval.shape, eqn.invars[1].aval.shape
            out = eqn.outvars[0].aval.shape
            if name == "conv_general_dilated":
                macs, elements = conv_forward(
                    lhs, rhs, out, eqn.params["dimension_numbers"],
                    eqn.params.get("feature_group_count", 1),
                    eqn.params.get("batch_group_count", 1),
                )
            else:
                macs, elements = dot_forward(
                    lhs, rhs, out, eqn.params["dimension_numbers"]
                )
            # One pass forward, one more for each operand whose gradient
            # the step needs: an operand that depends on the parameters.
            passes = 1 + sum(flags[:2])
            rows.append((name, macs, elements, passes, tuple(out)))
        subs = list(_sub_jaxprs(eqn))
        out_flag = any(flags)
        if subs:
            for sub in subs:
                if len(sub.invars) == len(eqn.invars):
                    sub_flags = flags
                else:  # consts or carried values in front: be conservative
                    sub_flags = [out_flag] * len(sub.invars)
                _walk(sub, sub_flags, rows)
        if out_flag:
            tainted.update(eqn.outvars)
    return [is_tainted(v) for v in jaxpr.outvars]


def count(fn, params, *inputs, bytes_per_element=2):
    """Model FLOPs and kernel bytes of one train step of `fn(params, *inputs)`.

    Arguments may be arrays or `jax.ShapeDtypeStruct`s: nothing runs.
    Returns {"forward_flops", "step_flops", "step_bytes", "equations"}.
    """
    closed = jax.make_jaxpr(fn)(params, *inputs)
    n_param_leaves = len(jax.tree_util.tree_leaves(params))
    tainted_in = [i < n_param_leaves for i in range(len(closed.jaxpr.invars))]
    rows = []
    _walk(closed.jaxpr, tainted_in, rows)
    forward = sum(2 * macs for _, macs, _, _, _ in rows)
    step = sum(2 * macs * passes for _, macs, _, passes, _ in rows)
    step_bytes = sum(
        elements * bytes_per_element * passes
        for _, _, elements, passes, _ in rows
    )
    return {
        "forward_flops": int(forward),
        "step_flops": int(step),
        "step_bytes": int(step_bytes),
        "equations": len(rows),
    }


def abstract(tree):
    """Shapes of a tree of arrays, so that counting holds no data."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), tree
    )
