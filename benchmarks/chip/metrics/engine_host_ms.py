"""Serving engine: host milliseconds a step spends where the device has no
step to run, from the engine's own regions (``repro_region_seconds`` in
the program's metrics registry, counted whether or not tracing is on):
the seconds of ``serve.admit``, ``serve.upload``, ``serve.dispatch`` and
``serve.advance`` over the count of ``serve.step``.  Read-back, the wait
for the sampled tokens, is left out: the device runs the step then.  So
is the first step's ``serve.compile``, where JAX traces and compiles.

The registry covers every step the run's engine served: the warm-up
steps (about 5 of about 665) as well as the window's.  A program without
the regions reads nothing."""
from repro.obs import metrics

PARTS = ("serve.admit", "serve.upload", "serve.dispatch", "serve.advance")


def read(run):
    fam = metrics.snapshot().get("repro_region_seconds", {})
    regions = {k[len("region="):]: v
               for k, v in fam.get("values", {}).items()}
    steps = regions.get("serve.step", {}).get("count", 0)
    if not steps:
        return None
    return 1e3 * sum(regions[r]["sum"] for r in PARTS
                     if r in regions) / steps
