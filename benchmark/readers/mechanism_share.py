"""Share of the step's device-busy time under `kda/*`, `mla/*`, `attention`,
`attention_proj` and `moe/*`: how much of the step the delta rule, latent
attention and the routed experts are, beside the dense layer's MLP, the
head and the optimizer."""

import kimi_scopes


def read(run):
    value = kimi_scopes.per_step(run, kimi_scopes.MECHANISMS)
    if value is None or not run.trace_summary["busy_s"]:
        return None
    return 100.0 * value * run.trace_summary["steps"] / run.trace_summary["busy_s"]
