"""Cross-check of ``GemmEngine.cost()`` counters against a symbolic walk.

The engine cost models (``repro.engine.registry``) are the autotuner's and
the serving tier router's view of kernel reality: ``grid_steps``,
``dma_bytes`` and ``b_dma_elided`` claim to describe what the kernels
actually execute.  Nothing previously *held* them to that claim — a model
edit (or a schedule-shape change) could silently drift the counters and
re-rank every routing decision.  This pass re-derives the three counters
by walking the plan's schedule step by step with the kernels' fetch rules
(dense grid: every BW plane of every block each step; v2 sparse: one
digit block + one B block per scheduled step, sentinels included —
BlockSpec gathers don't care about the weight; v3 pipelined: digit copies
only on real steps, B copies only where B_FETCH=1, flushes at LAST
steps) and reports any divergence as ``COST_MODEL_DRIFT``.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from .diagnostics import Report

__all__ = ["symbolic_counters", "crosscheck_cost"]

_WEIGHT, _LAST, _BFETCH = 3, 5, 8


def symbolic_counters(route: str, n: int, *, block_m: int, block_k: int,
                      block_n: int, mb: int, kb: int, n_planes: int,
                      schedule=None, acc_hbm_bytes: int = 0) -> dict:
    """Walk one launch of ``route``'s kernel and count what it executes.

    Returns {'grid_steps', 'dma_bytes', 'b_dma_elided'} — the counters
    the engine cost models must reproduce.  ``schedule`` is required for
    the sparse routes (the walk IS the schedule); ``mb``/``kb`` are the
    padded block-grid dims (from the plan's mask), ``acc_hbm_bytes`` the
    engine's epilogue-placement HBM term (0 for the fused engines).
    """
    nb = -(-n // block_n)
    if route == "dense":
        # full predicated grid: every step fetches all BW planes of the A
        # block and the B block; one out block per (m, n) tile
        grid = mb * nb * kb
        dma = grid * (n_planes * block_m * block_k + block_k * block_n) \
            + mb * nb * block_m * block_n * 4 + acc_hbm_bytes
        return {"grid_steps": grid, "dma_bytes": int(dma),
                "b_dma_elided": 0}
    if schedule is None:
        raise ValueError(f"route {route!r} needs the plan's schedule")
    sched = np.asarray(schedule)
    steps = sched.shape[0]
    dma = elided = flushes = 0
    if route == "sparse":
        # v2 scalar-prefetch kernels: the BlockSpec gathers one digit
        # plane block and one B block EVERY step — sentinels and padding
        # included (index maps don't read the weight); the out block is
        # written once per row (its LAST step)
        for s in range(steps):
            dma += block_m * block_k + block_k * block_n
            if sched[s, _LAST] == 1:
                flushes += 1
    elif route == "pipelined":
        # v3 manual-DMA kernels: digit copies only on real steps, B
        # copies only where B_FETCH=1 (the reuse walk elides the rest),
        # staged flush at each LAST step
        for s in range(steps):
            if sched[s, _WEIGHT] != 0:
                dma += block_m * block_k
                if sched[s, _BFETCH] == 1:
                    dma += block_k * block_n
                else:
                    elided += 1
        for s in range(steps):
            if sched[s, _LAST] == 1:
                flushes += 1
    else:
        raise ValueError(f"unknown route {route!r}")
    return {
        "grid_steps": steps * nb,
        "dma_bytes": int(dma * nb + flushes * nb * block_m * block_n * 4
                         + acc_hbm_bytes),
        "b_dma_elided": elided * nb,
    }


def crosscheck_cost(impl: str, m: int, k: int, n: int, spec, plan, *,
                    report: Optional[Report] = None) -> Report:
    """Compare ``get_engine(impl).cost(..., plan=plan)`` to the walk.

    plan: a plan record (``ops.plan_dense_weight``) or PlannedOperand for
    the [M, K] operand.  Any diverging counter is a ``COST_MODEL_DRIFT``
    error naming both values — the cost model may not disagree with the
    schedule it claims to price.
    """
    from repro.engine.registry import engine_names, get_engine

    report = report if report is not None else Report(f"cost {impl}")
    engine = get_engine(impl)
    route = engine.route
    if route is None:
        kernel_engines = [name for name in engine_names()
                          if get_engine(name).route is not None]
        report.add("COST_MODEL_DRIFT",
                   f"impl {impl!r} has no schedule-backed cost model to "
                   f"cross-check (kernel engines: {kernel_engines})")
        return report
    got = engine.cost(m, k, n, spec, plan=plan)
    bm, bk, bn, mb, kb, _nb = engine._geometry(m, k, n, spec, plan)
    sched = plan["schedule"] if isinstance(plan, dict) \
        else getattr(plan, "schedule", None)
    if sched is not None:
        sched = np.asarray(sched)
        if sched.ndim != 2:
            sched = None                  # stacked plans: nothing to walk
    if route != "dense" and sched is None:
        report.add("COST_MODEL_DRIFT",
                   f"impl {impl!r} prices the {route!r} route but the plan "
                   f"carries no walkable schedule", where=f"{m}x{k}x{n}")
        return report
    want = symbolic_counters(
        route, n, block_m=bm, block_k=bk, block_n=bn, mb=mb, kb=kb,
        n_planes=spec.num_digits, schedule=sched,
        acc_hbm_bytes=engine._acc_hbm_bytes(m, n))
    for key, expected in want.items():
        if int(got.get(key, -1)) != int(expected):
            report.add(
                "COST_MODEL_DRIFT",
                f"{impl}.cost() reports {key}={got.get(key)} but the "
                f"symbolic walk of the plan's schedule counts {expected}",
                where=f"{m}x{k}x{n}/{route}")
    return report
