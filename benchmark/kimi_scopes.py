"""The `jax.named_scope`s of the Kimi-Linear program (layers/kda.py,
layers/transformer.LatentAttention, ops/moe.routed_experts, SwiGLU, the
head), the groups the cell's readers sum, and the one reduction of the
trace that all of them read (`scope_sums.py` with this program's lists)."""

import numpy as np

import scope_sums

KDA = ("kda/qkv_proj", "kda/conv", "kda/gates", "kda/delta_rule",
       "kda/gate_norm", "kda/out_proj")
MLA = ("mla/q_proj", "mla/kv_down", "mla/kv_up", "attention", "attention_proj")
MOE = ("moe/router", "moe/dispatch", "moe/experts", "moe/combine", "moe/shared")
ALL = KDA + MLA + MOE + ("mlp", "lm_head")
MECHANISMS = KDA + MLA + MOE
#: The scopes that are one plain projection each (or a SwiGLU's three): what
#: the reference's `projection_costs` counts.
PROJECTIONS = ("kda/qkv_proj", "kda/out_proj", "mla/q_proj", "mla/kv_down",
               "mla/kv_up", "attention_proj", "mlp", "moe/shared", "lm_head")
#: The chip's compiler emits `lax.ragged_dot` as a custom call with no name
#: stack, labelled `ragged-dot-<n>`: the routed experts' grouped products.
UNLABELLED = (("ragged-dot", "moe/experts"),)


def per_step(run, scopes):
    """Seconds a traced step under `scopes`, or None."""
    return scope_sums.per_step(run, scopes, ALL, UNLABELLED)


def roofline(run, name, cost, scopes):
    """100 x least time of `cost` over the device time under `scopes`."""
    measured = per_step(run, scopes)
    if not measured or cost is None:
        return None
    return scope_sums.roofline(run, name, cost, measured, scopes)


def kernel_costs(run):
    """The reference's `kernel_costs` at the cell's shapes, or {}."""
    if not hasattr(run.reference, "kernel_costs"):
        return {}
    return run.reference.kernel_costs(
        run.config, run.cell["batch"] * len(run.devices),
        run.config["arguments"]["sequence_length"],
        np.dtype(run.config["compute_dtype"]).itemsize,
    )
