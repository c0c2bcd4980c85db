"""Seconds of set-up inside `CompiledModel.init_state` (the program's
`train.init_state` span: the eager preprocessor pass and the model's init,
host time of the call); the earlier line splits it by child span."""

import program_spans


def read(run):
    snap = program_spans.recorded(run)
    spans = snap["spans"] if snap else ()
    inits = [s for s in spans if s["name"] == "train.init_state"]
    if not inits:
        return None
    first = inits[0]
    run.reporter.say("init_state: " + ", ".join(
        f"{s['name']} {(s['end_ns'] - s['start_ns']) / 1e9:.3f} s"
        for s in spans if s is first or s["parent"] == first["id"]
    ))
    return (first["end_ns"] - first["start_ns"]) / 1e9
