"""Serving engine: mean share of the batch's slots bound to a request per
step of the measured window (from the engine's slot allocator)."""


def read(run):
    steps = run.window_steps
    if not steps:
        return None
    return 100.0 * sum(s["bound"] for s in steps) / \
        (len(steps) * run.serve["batch"])
