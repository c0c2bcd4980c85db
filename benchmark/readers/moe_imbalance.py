"""The fullest held expert's rows over the mean held expert's, summed over
the routed layers and the window's steps: counter `moe.max_expert_rows`
over `moe.routed_rows` / experts held. 1 under even routing. In a resident
cell it restates the seed's routing; a change of the router or of the
traffic moves it."""


def read(run):
    fullest = run.counters.get("moe.max_expert_rows")
    rows = run.counters.get("moe.routed_rows")
    if fullest is None or not rows:
        return None
    return fullest / (rows / run.config["model"]["num_experts"])
