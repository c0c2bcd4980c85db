"""Milliseconds a step spends inside the host iterator's `next()`: the
driver's wrapper times every call (it runs inline in the train loop's
thread, so this is the time a step waits for data) over the window."""


def read(run):
    calls = run.counters.get("host_input.next_calls")
    if not calls:
        return None
    return 1e3 * run.counters["host_input.next_seconds"] / calls
