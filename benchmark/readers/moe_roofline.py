"""Roofline share of the routed experts' grouped products: least time for
the rows the program counted as routed to the experts it holds in the
traced steps (the driver's `moe.routed_rows.traced` over
`moe.traced_steps`; the reference's `moe_costs`: FLOPs of three matrices a
row in three passes, or the held matrices once a pass and the rows'
operands and results) over the device time under scope `moe/experts` a
traced step: rows and time of the same steps."""

import numpy as np

import kimi_scopes


def read(run):
    rows = run.counters.get("moe.routed_rows.traced")
    steps = run.counters.get("moe.traced_steps")
    if not rows or not steps or not hasattr(run.reference, "moe_costs"):
        return None
    cost = run.reference.moe_costs(
        run.config, rows / steps, np.dtype(run.config["compute_dtype"]).itemsize,
    )
    return kimi_scopes.roofline(run, "moe", cost, ("moe/experts",))
