"""Pairs routed to the experts held over the positions routed: counter
`moe.routed_rows` over the window's steps x positions a step x routed
layers. Experts per token x held / scored under even routing (0.25 for 8
of 256 at 8 a token)."""


def read(run):
    rows = run.counters.get("moe.routed_rows")
    if rows is None:
        return None
    model = run.config["model"]
    layers = model["num_hidden_layers"] - model["first_k_dense_replace"]
    positions = (
        run.cell["batch"] * len(run.devices)
        * run.config["arguments"]["sequence_length"]
    )
    return rows / (run.window.results()["steps"] * positions * layers)
