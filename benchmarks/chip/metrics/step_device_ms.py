"""Model step: device busy time per decode step, from the profiler trace
(union of the device's operation intervals over the traced steps)."""


def read(run):
    t = run.trace
    if t is None or not t["steps"]:
        return None
    return 1e3 * t["busy_s"] / t["steps"]
