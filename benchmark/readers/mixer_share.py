"""Share of the step's device-busy time under the mixers' scopes
(`mamba2/*`, `attention`, `attention_proj`): how much of the step the
state-space and attention mechanisms are, beside the MLPs, the head and
the optimizer."""

import scope_time


def read(run):
    value = scope_time.per_step(run, scope_time.MIXER_SCOPES)
    if value is None or not run.trace_summary["busy_s"]:
        return None
    return 100.0 * value * run.trace_summary["steps"] / run.trace_summary["busy_s"]
