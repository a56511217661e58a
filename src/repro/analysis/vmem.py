"""VMEM budget pass for the bw_gemm kernel launch configurations.

Pallas TPU kernels fail (or silently spill) when the blocks + scratch a
grid step keeps resident exceed the core's VMEM (~16 MiB).  The dense and
v2 sparse kernels are naturally bounded — their footprint is a handful of
``block_*``-sized tiles — but the v3 pipelined kernels hold an
``(M_pad, block_n)`` int32 accumulator *panel* covering every output row,
which grows with the problem's M: at grok-scale (``d_ff = 32768``) the
panel alone is 16.8 MB even at ``block_n = 128``, over budget before a
single double buffer is counted.  ROADMAP names this the VMEM budget
guard: compute the footprint *statically*, reject configs that cannot
fit, and suggest the clamp (smaller blocks) or the fallback route (the
v2 kernels, whose accumulator lives in the out BlockSpec) that does.

``vmem_footprint`` itemizes the resident bytes per route, mirroring the
kernels' BlockSpecs and ``scratch_shapes`` in ``kernels/bw_gemm.py``;
``check_vmem`` turns an over-budget footprint into a ``VMEM_OVER_BUDGET``
diagnostic carrying a machine-actionable ``suggestion`` dict;
``filter_vmem_configs`` is the autotuner's hard candidate filter
(over-budget candidates are never measured).  The budget defaults to
16 MiB and can be overridden with ``REPRO_VMEM_BUDGET`` (bytes).
"""
from __future__ import annotations

import os
from typing import List, Optional, Tuple

from .diagnostics import Report, INFO

__all__ = ["DEFAULT_VMEM_BUDGET", "ENV_BUDGET", "vmem_budget",
           "vmem_footprint", "check_vmem", "clamp_suggestion",
           "filter_vmem_configs"]

DEFAULT_VMEM_BUDGET = 16 * 2 ** 20        # bytes per TPU core, ~v4/v5e
ENV_BUDGET = "REPRO_VMEM_BUDGET"

# Block dims the clamp search walks, largest first (MXU floor is 128).
_CLAMP_STEPS = (512, 384, 256, 128)


def vmem_budget(budget: Optional[int] = None) -> int:
    """The VMEM byte budget: explicit arg > $REPRO_VMEM_BUDGET > 16 MiB."""
    if budget is not None:
        return int(budget)
    env = os.environ.get(ENV_BUDGET)
    return int(env) if env else DEFAULT_VMEM_BUDGET


def _pad_up(dim: int, block: int) -> int:
    return -(-dim // block) * block


def vmem_footprint(route: str, m: int, k: int, n: int, *, block_m: int,
                   block_k: int, block_n: int, n_planes: int,
                   fused: bool = True, out_bytes: int = 4) -> dict:
    """Resident VMEM bytes of one grid step of ``route``'s kernel.

    route: 'dense' | 'sparse' | 'pipelined' (the planned_dense_apply
    dispatch routes).  m/k/n: the logical GEMM dims (m = kernel rows =
    planned output channels; the pipelined panel spans m padded to
    block_m).  n_planes: BW digit planes resident per dense-grid step.
    Itemized dict; 'total' is the comparison key.
    """
    if route not in ("dense", "sparse", "pipelined"):
        raise ValueError(f"route must be dense|sparse|pipelined, "
                         f"got {route!r}")
    m_pad = _pad_up(m, block_m)
    parts = {}
    if route in ("dense", "sparse"):
        # BlockSpec tiles, each held twice by the Pallas pipeline (the
        # next step's fetch lands while this step reads): all BW planes of
        # the A block (dense) or ONE plane (the v2 compacted schedule),
        # the B block and the out block; the fused kernels' int32
        # accumulator is a scratch held once, the unfused ones accumulate
        # in the out block itself
        planes = n_planes if route == "dense" else 1
        parts["digit_blocks"] = 2 * planes * block_m * block_k
        parts["b_block"] = 2 * block_k * block_n
        if fused:
            parts["acc_block"] = block_m * block_n * 4
            parts["out_block"] = 2 * block_m * block_n * out_bytes
        else:
            parts["acc_block"] = 2 * block_m * block_n * 4
    else:                                  # pipelined (v3)
        # scratch_shapes of bw_gemm_sparse[_fused]_pipelined
        parts["acc_panel"] = m_pad * block_n * 4
        parts["digit_dbl_buf"] = 2 * block_m * block_k
        parts["b_dbl_buf"] = 2 * block_k * block_n
        parts["stage_block"] = block_m * block_n * \
            (out_bytes if fused else 4)
    if fused:
        # epilogue vectors: per-row scale + bias ([M_pad, 1] f32 — whole
        # in VMEM for the pipelined kernels, one block otherwise) and the
        # per-column scale ([1, block_n])
        rows = m_pad if route == "pipelined" else block_m
        parts["epilogue_vecs"] = (2 * rows + block_n) * 4
    parts["schedule"] = 0 if route == "dense" else 9 * 4  # per-step row
    parts["total"] = sum(parts.values())
    return parts


def clamp_suggestion(route: str, m: int, k: int, n: int, *, block_m: int,
                     block_k: int, block_n: int, n_planes: int,
                     fused: bool = True, out_bytes: int = 4,
                     budget: Optional[int] = None) -> Optional[dict]:
    """Smallest-change config that fits ``budget``, or a route fallback.

    Returns a suggestion dict ``{"block_m":…, "block_k":…, "block_n":…}``
    (clamped dims only differ from the input), or ``{"route": …}`` when
    no block shrink can fit — the pipelined acc panel scales with M, so
    grok-sized rows must fall back to a v2 route — or None when the
    input already fits.
    """
    budget = vmem_budget(budget)

    def total(bm, bk, bn):
        return vmem_footprint(route, m, k, n, block_m=bm, block_k=bk,
                              block_n=bn, n_planes=n_planes, fused=fused,
                              out_bytes=out_bytes)["total"]

    if total(block_m, block_k, block_n) <= budget:
        return None
    # shrink the least-harmful dims first: block_n (throughput scales out
    # over the j grid anyway), then block_k, then block_m
    options = [bn for bn in _CLAMP_STEPS if bn <= block_n] or [128]
    for bn in sorted(set(options)):
        for bk in sorted({bk for bk in _CLAMP_STEPS if bk <= block_k}
                         | {128}):
            for bm in sorted({bm for bm in _CLAMP_STEPS if bm <= block_m}
                             | {128}):
                if total(bm, bk, bn) <= budget:
                    return {"block_m": bm, "block_k": bk, "block_n": bn}
    if route == "pipelined":
        # the panel alone blows the budget at any block shape: fall back
        # to the v2 routes, whose accumulator lives per-block
        return {"route": "sparse", "order": "m_major"}
    return {"route": "dense"}


def check_vmem(route: str, m: int, k: int, n: int, *, block_m: int,
               block_k: int, block_n: int, n_planes: int,
               fused: bool = True, out_bytes: int = 4,
               budget: Optional[int] = None,
               severity: str = "error", where: Optional[str] = None,
               report: Optional[Report] = None) -> Report:
    """Add a ``VMEM_OVER_BUDGET`` diagnostic when the footprint exceeds
    the budget, carrying the clamp/fallback suggestion."""
    report = report if report is not None else Report("vmem")
    budget = vmem_budget(budget)
    parts = vmem_footprint(route, m, k, n, block_m=block_m, block_k=block_k,
                           block_n=block_n, n_planes=n_planes, fused=fused,
                           out_bytes=out_bytes)
    if parts["total"] <= budget:
        return report
    top = max((v, name) for name, v in parts.items() if name != "total")
    suggestion = clamp_suggestion(
        route, m, k, n, block_m=block_m, block_k=block_k, block_n=block_n,
        n_planes=n_planes, fused=fused, out_bytes=out_bytes, budget=budget)
    report.add(
        "VMEM_OVER_BUDGET",
        f"route {route!r} at blocks (m={block_m}, k={block_k}, "
        f"n={block_n}) for a {m}x{k}x{n} GEMM keeps "
        f"{parts['total']:,} bytes resident "
        f"(budget {budget:,}; dominant term {top[1]}={top[0]:,})",
        severity=severity,
        where=where or f"{m}x{k}x{n}/{route}", suggestion=suggestion)
    return report


def filter_vmem_configs(m: int, k: int, n: int, configs: List[dict], *,
                        n_planes: int = 4, budget: Optional[int] = None) \
        -> Tuple[List[dict], Report]:
    """The autotuner's hard candidate filter.

    Splits candidate configs (dicts with block_m/block_k/block_n and a
    ``dispatch`` route) into the in-budget list and a Report holding one
    INFO diagnostic per rejected candidate (info: rejection is the guard
    *working*, not a defect in the checked-in state).  Never empties the
    pool: if every candidate is over budget the smallest-footprint one is
    kept so the sweep still returns a winner (with its diagnostic left at
    error severity in that case).
    """
    report = Report(f"vmem-filter {m}x{k}x{n}")
    kept, rejected = [], []
    for cfg in configs:
        route = cfg.get("dispatch", "dense")
        parts = vmem_footprint(route, m, k, n, block_m=cfg["block_m"],
                               block_k=cfg["block_k"], block_n=cfg["block_n"],
                               n_planes=n_planes)
        if parts["total"] <= vmem_budget(budget):
            kept.append(cfg)
        else:
            rejected.append((parts["total"], cfg))
            check_vmem(route, m, k, n, block_m=cfg["block_m"],
                       block_k=cfg["block_k"], block_n=cfg["block_n"],
                       n_planes=n_planes, budget=budget, severity=INFO,
                       report=report)
    if not kept and rejected:
        rejected.sort(key=lambda t: t[0])
        fallback = rejected[0][1]
        check_vmem(fallback.get("dispatch", "dense"), m, k, n,
                   block_m=fallback["block_m"], block_k=fallback["block_k"],
                   block_n=fallback["block_n"], n_planes=n_planes,
                   budget=budget, report=report)
        kept = [fallback]
    return kept, report
