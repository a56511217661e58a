"""KV cache: share of the cache positions the decode step reads that hold
a live token, from the engine's counters in the program's metrics
registry (counted whether or not tracing is on): 100 x
``repro_serve_kv_positions_live_total`` (pos + 1 of each bound slot,
summed over steps) / ``repro_serve_kv_positions_read_total`` (the
positions each step reads, summed over steps).

The registry covers every step the run's engine served: the warm-up
steps (about 5 of about 665) as well as the window's.  A program without
the ``serve.step`` region or the counters reads nothing."""
from repro.obs import metrics


def read(run):
    snap = metrics.snapshot()
    steps = snap.get("repro_region_seconds", {}).get("values", {}) \
        .get("region=serve.step", {}).get("count", 0)
    live, read_ = (snap.get(f"repro_serve_kv_positions_{k}_total", {})
                   .get("values", {}).get("", 0) for k in ("live", "read"))
    if not steps or not read_:
        return None
    return 100.0 * live / read_
