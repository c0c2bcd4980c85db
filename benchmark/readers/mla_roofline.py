"""Roofline share of latent attention's score and value products (not the
projections): least time from the reference's `kernel_costs["mla"]` (the
causal blocks' FLOPs; bytes of q, k, v, o and the log-sum-exp once a pass,
not the logits) over the device time under scope `attention` a step."""

import kimi_scopes


def read(run):
    return kimi_scopes.roofline(
        run, "mla", kimi_scopes.kernel_costs(run).get("mla"), ("attention",)
    )
