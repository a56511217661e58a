"""First-class quantized-GEMM configuration + pluggable engine registry.

``QuantSpec`` is the single configuration object for quantized GEMM
(planes, encoding, bits, impl, block overrides, activation-quant policy);
``GemmEngine`` strategies registered here execute it.  Specs are passed
explicitly down the call chain — there is no process-global impl switch —
so engines with different specs coexist in one process (the seam for
per-request impls, autotuning, and multi-backend serving).

    from repro.engine import QuantSpec, get_engine
    spec = QuantSpec.parse("planes=3,encoding=ent,impl=pallas_fused")
    y = get_engine(spec.impl).apply(w, x, spec)
"""
from .spec import (QuantSpec, IMPLS, ACT_QUANT_POLICIES,  # noqa: F401
                   normalize_impl, spec_from_flags)
from .registry import (GemmEngine, register, get_engine,  # noqa: F401
                       engine_names, engine_for_route, active_planes)

__all__ = ["QuantSpec", "IMPLS", "ACT_QUANT_POLICIES", "normalize_impl",
           "spec_from_flags",
           "GemmEngine", "register", "get_engine", "engine_names",
           "engine_for_route", "active_planes"]
