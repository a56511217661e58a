"""Kernels: the grouped expert GEMM kernel's share of its roofline.

For the traced steps, the least time of the routed expert GEMMs (the
configuration's work module, ``run.work.expert_gemm_least_time``: per
layer and matrix, the larger of 2 M K N over the int8 peak and the bytes
of the active experts' weights and the routed rows over the HBM
bandwidth), from the routes and active experts a step, divided by the
device time of the ``bw_gemm_experts`` kernel.  Routes and active experts
come from the engine's counters in the program's metrics registry
(``repro_moe_routes_held_total``, ``repro_moe_experts_active_total``)
over its ``serve.step`` count, warm-up steps included.  A program without
the counters or the kernel, or a work module without expert counts,
reads nothing."""
import profile_trace

from repro.obs import metrics

KERNEL_PATTERNS = ("bw_gemm_experts*",)


def read(run):
    t = run.trace
    least_time = getattr(run.work, "expert_gemm_least_time", None)
    if t is None or not t["steps"] or least_time is None:
        return None
    snap = metrics.snapshot()
    steps = snap.get("repro_region_seconds", {}).get("values", {}) \
        .get("region=serve.step", {}).get("count", 0)
    routes, active = (snap.get(f"repro_moe_{k}_total", {}).get("values", {})
                      .get("", 0) for k in ("routes_held", "experts_active"))
    kernel_s = profile_trace.matching_time(t, KERNEL_PATTERNS)
    if not steps or not routes or kernel_s <= 0.0:
        return None
    least = t["steps"] * least_time(run.model, routes / steps,
                                    active / steps, run.peaks, run.bits)
    return 100.0 * least / kernel_s
