"""Milliseconds a step of collective operations on the device's synchronous
line: the ops whose HLO category is an all-reduce, all-gather,
reduce-scatter, collective-permute or all-to-all (the `-start` / `-done`
halves of their asynchronous forms among them), mean over the device
planes. That line is serial, so what stands on it is exposed: no compute
runs on that chip meanwhile. A chip that waits in an all-reduce for a
slower one is billed here too. Says on an earlier line each category's
milliseconds and each collective op's, with how often a step runs it: the
gradient's all-reduce is one large op, the BatchNorm statistics' are many
small ones. Nothing to read where the trace holds no collective (one chip)."""

import xplane


def read(run):
    summary = run.trace_summary
    if not summary or not summary["steps"]:
        return None
    steps = summary["steps"]
    categories = {
        name: seconds for name, seconds in summary["category_s"].items()
        if xplane.is_collective(name)
    }
    if not categories:
        return None
    ops = summary.get("collectives", {})
    run.reporter.say(
        f"collectives on the synchronous line, ms a step over "
        f"{summary['devices']} device planes: " + ", ".join(
            f"{name} {1e3 * seconds / steps:.3f}"
            for name, seconds in categories.items()
        ) + f"; {len(ops)} ops, the longest: " + ", ".join(
            f"{name} ({category}) {1e3 * seconds / steps:.3f} ms x "
            f"{count / steps:.2f} a step"
            for name, (category, seconds, count) in list(ops.items())[:12]
        )
    )
    return 1e3 * sum(categories.values()) / steps
