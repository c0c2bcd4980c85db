"""Roofline share of the plain projections: least time for one step of
every product that is a weight matrix times the positions (the mixers' q,
k, v, latent and output projections, the dense SwiGLU, the shared experts,
the head: the reference's `projection_costs`, three passes, FLOPs or the
matrices and their operands and results at the compute precision once a
pass) over the device time under their scopes. The scopes hold the
elementwise work fused to the products and what the backward recomputes of
them: the share reads low, never high."""

import numpy as np

import kimi_scopes


def read(run):
    if not hasattr(run.reference, "projection_costs"):
        return None
    cost = run.reference.projection_costs(
        run.config, run.cell["batch"] * len(run.devices),
        run.config["arguments"]["sequence_length"],
        np.dtype(run.config["compute_dtype"]).itemsize,
    )
    return kimi_scopes.roofline(run, "projection", cost, kimi_scopes.PROJECTIONS)
