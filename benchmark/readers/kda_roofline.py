"""Roofline share of the chunked delta rule: the least time the chip could
take for one step's delta rules, forward and backward (the larger of their
FLOPs over peak FLOP/s and the bytes they cannot avoid moving over peak HBM
bytes/s: the reference's `kernel_costs["kda"]`), over the device time
under scope `kda/delta_rule` a step. The solve and the elementwise decay
work are in the time and not in the count: the share reads low, never
high."""

import kimi_scopes


def read(run):
    return kimi_scopes.roofline(
        run, "kda", kimi_scopes.kernel_costs(run).get("kda"), ("kda/delta_rule",)
    )
