"""90th percentile of the time between one step's completion and the next
over the run's window: where the steps come in bursts (the fed cell) it
swings by a fifth from run to run, so it stands among the per-layer metrics
and not under a bound. In a traced run the window holds the profiler's own
start and stop."""


def read(run):
    return run.window.results()["metrics"].get("train.step_ms.p90")
