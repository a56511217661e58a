"""Serving engine: seconds the engine build spent planning its weights
into digit planes (the ``serve.plan_params`` region of
``repro_region_seconds`` in the program's metrics registry, counted
whether or not tracing is on), a part of ``setup_s``.

The registry covers the one engine the run builds, whose steps include
the warm-up (about 5 of about 665).  A program without the
``serve.step`` region reads nothing."""
from repro.obs import metrics


def read(run):
    values = metrics.snapshot().get("repro_region_seconds", {}) \
        .get("values", {})
    steps = values.get("region=serve.step", {}).get("count", 0)
    plan = values.get("region=serve.plan_params", {})
    if not steps or not plan.get("count", 0):
        return None
    return plan["sum"]
