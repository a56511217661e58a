"""Jitted public wrappers around the Pallas kernels: padding, plane
encoding, occupancy masks, weight planning (magnitude-ordered row
permutation) and the quantised-linear entry point used by the models.

Every spec-level entry point (``plan_for`` / ``plan_dense_weight`` /
``planned_dense_apply`` / ``quantized_dense`` / ``plan_params`` /
``select_block_sizes``) is configured by a single
:class:`repro.engine.QuantSpec` — planes, encoding, bits, and block-size
overrides all travel inside the spec, so callers with different specs
(e.g. two ServeEngines, or an autotuner sweeping block shapes) coexist in
one process; a bare int plane budget is accepted as legacy sugar for a
default-grid spec.  The per-parameter plan cache keys on (weight,
spec.plan_key()), so the same weight planned under two specs holds two
independent entries.

On non-TPU backends the wrappers run the kernels in interpret mode (the
kernel body executes in Python on CPU) so every code path is testable here;
on TPU the same calls compile to MXU programs.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import chaos as _chaos
from repro.core import encodings as enc
from repro.core import quant as quantlib
from repro.engine.spec import QuantSpec
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from . import bw_gemm as _bw
from . import quant_gemm as _qg
from . import ref as kref

# pre-bound metric families (import-time lookup keeps the per-call cost
# to one method call; the per-dispatch counter is additionally gated on
# obs_trace.enabled() so the hot path is a no-op branch when obs is off)
_M_PLAN_HITS = obs_metrics.get_registry().counter(
    "repro_plan_cache_hits_total")
_M_PLAN_MISSES = obs_metrics.get_registry().counter(
    "repro_plan_cache_misses_total")
_M_SCHED_DENSITY = obs_metrics.get_registry().histogram(
    "repro_schedule_density", obs_metrics.GLOSSARY[
        "repro_schedule_density"]["edges"])
_M_B_ELIDED = obs_metrics.get_registry().counter(
    "repro_schedule_b_dma_elided_total")
_M_DISPATCH = obs_metrics.get_registry().counter(
    "repro_gemm_dispatch_total")

__all__ = ["PlannedOperand", "encode_planes", "plane_block_mask",
           "plan_operand", "bw_gemm", "quant_gemm", "plane_density",
           "select_block_sizes", "bw_gemm_fused", "quant_gemm_fused",
           "plan_for", "plan_cache_stats", "plan_cache_clear",
           "quantized_dense", "plan_dense_weight", "planned_dense_apply",
           "plan_params", "build_schedule", "pad_schedule",
           "schedule_stats", "bw_gemm_sparse", "bw_gemm_sparse_fused",
           "bw_gemm_sparse_pipelined", "bw_gemm_sparse_fused_pipelined",
           "SPARSE_DENSITY_THRESHOLD", "SCHEDULE_ORDERS", "DISPATCHES",
           "verification_enabled", "ENV_VERIFY", "count_dispatch"]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def count_dispatch(route: str) -> None:
    """Count one quantized-GEMM dispatch under ``route`` (callers gate
    this on obs being enabled)."""
    _M_DISPATCH.labels(route=route).inc()


# ---------------------------------------------------------------------------
# Static verification (repro.analysis) at the planning/apply seams
# ---------------------------------------------------------------------------
# REPRO_VERIFY=1 turns the schedule verifier + DMA-hazard walk on by
# default at every plan build and (pre-kernel) at planned_dense_apply; the
# test suite enables it globally in tests/conftest.py.  Verified schedules
# are memoized by identity (weakref-evicted) so eager serving loops pay
# the pure-python walk once per plan, not once per matmul.

ENV_VERIFY = "REPRO_VERIFY"

_VERIFIED_SCHEDULES: dict = {}


def _verify_enabled(verify: Optional[bool]) -> bool:
    if verify is not None:
        return bool(verify)
    return os.environ.get(ENV_VERIFY, "0").lower() not in (
        "", "0", "false", "off", "no")


def verification_enabled() -> bool:
    """True when plan verification is on by default ($REPRO_VERIFY)."""
    return _verify_enabled(None)


def _schedule_verified(sched) -> bool:
    ref = _VERIFIED_SCHEDULES.get(id(sched))
    return ref is not None and ref() is sched


def _mark_schedule_verified(sched) -> None:
    try:
        _VERIFIED_SCHEDULES[id(sched)] = weakref.ref(
            sched, lambda _r, key=id(sched):
            _VERIFIED_SCHEDULES.pop(key, None))
    except TypeError:
        pass                  # not weakref-able: skip the memo, stay correct


def _verify_planned(planned: "PlannedOperand") -> None:
    """Run the static analyzers over a freshly built plan (plan_for &co)."""
    from repro import analysis
    analysis.verify_plan(planned, enc.radix(planned.encoding),
                         planned.order).raise_if_errors()
    _mark_schedule_verified(planned.schedule)


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def encode_planes(a, encoding: str = "ent", bits: int = 8):
    """int8 A [M, K] -> digit planes int8 [BW, M, K]."""
    return kref.encode_planes_ref(a, encoding, bits)


# planning encodes through one compiled computation: run op by op, the
# encoder's int32 temporaries take several times the planes' bytes (a
# 98304 x 2304 LM head ran out of a v5e's memory that way)
_encode_planes_jit = jax.jit(kref.encode_planes_ref,
                             static_argnames=("encoding", "bits"))


def _check_operand_k(k: int, planned_k: int) -> None:
    """Real validation (asserts vanish under ``python -O``)."""
    if k != planned_k:
        raise ValueError(
            f"b has K={k} rows but the planned operand was built with "
            f"K={planned_k}; re-plan the weight or fix the activation "
            f"reshape")


def _check_gemm_k(k: int, k2: int) -> None:
    if k != k2:
        raise ValueError(
            f"inner-dim mismatch: a has K={k} columns but b has K={k2} "
            f"rows")


def _check_has_schedule(planned: "PlannedOperand") -> None:
    if planned.schedule is None:
        raise ValueError(
            "plan has no schedule; build it with plan_operand / "
            "build_schedule before calling a sparse kernel")


# ---------------------------------------------------------------------------
# Per-shape block-size selection
# ---------------------------------------------------------------------------
# Static fallback table for the kernel execution path: first row whose
# minimum (M, K, N) thresholds are all met wins.  Bigger blocks amortise
# grid overhead and raise MXU occupancy on large GEMMs; 128 is the
# MXU-aligned floor.  Since the measured autotuner landed, this table is
# only the *fallback*: select_block_sizes consults the autotune cache
# (repro.kernels.autotune, REPRO_AUTOTUNE_CACHE) first.
_BLOCK_TABLE = (
    # (min_m, min_k, min_n)  ->  (block_m, block_k, block_n)
    ((512, 2048, 512), (256, 512, 256)),
    ((256, 1024, 256), (256, 512, 128)),
    ((128, 512, 128), (128, 256, 128)),
    ((0, 0, 0), (128, 128, 128)),
)


def select_block_sizes(m: int, k: int, n: int,
                       spec: Optional[QuantSpec] = None):
    """(block_m, block_k, block_n) for a logical [M, K] x [K, N] GEMM.

    Resolution order: (1) a measured winner from the autotune cache for
    this (shape, spec-plan) key, (2) the static dispatch table — with an
    AutotuneCacheMissWarning when an explicitly configured cache lacks the
    shape.  A spec's explicit block_m/block_k/block_n overrides win
    component-wise over both.
    """
    from . import autotune
    hit = autotune.get_cache().lookup(m, k, n, spec)
    if hit is not None:
        sel = (hit["block_m"], hit["block_k"], hit["block_n"])
    else:
        sel = _BLOCK_TABLE[-1][1]
        for (mn_m, mn_k, mn_n), blocks in _BLOCK_TABLE:
            if m >= mn_m and k >= mn_k and n >= mn_n:
                sel = blocks
                break
    if spec is not None:
        sel = (spec.block_m or sel[0], spec.block_k or sel[1],
               spec.block_n or sel[2])
    return sel


def plane_block_mask(digits, block_m: int, block_k: int):
    """bool [BW, M/bm, K/bk]: True where a plane block has any non-zero digit."""
    bw, m, k = digits.shape
    d = digits.reshape(bw, m // block_m, block_m, k // block_k, block_k)
    return (d != 0).any(axis=(2, 4))


def plane_density(digits, block_m: int, block_k: int) -> dict:
    """Fraction of non-skippable blocks per plane (perf introspection)."""
    mask = np.asarray(plane_block_mask(digits, block_m, block_k))
    return {f"plane{i}": float(mask[i].mean()) for i in range(mask.shape[0])}


# ---------------------------------------------------------------------------
# Compacted sparse block schedules (CSR-of-blocks over the occupancy mask)
# ---------------------------------------------------------------------------
# Above this plane-block density the sparse kernels fall back to the dense
# ones: at high density the compacted schedule runs *more* grid steps than
# the dense grid (which retires all BW planes of a block in one step), so
# the DMA savings no longer pay for the extra iterations.  The measured
# autotuner can override the dispatch per (shape, density-bucket).
SPARSE_DENSITY_THRESHOLD = 0.5

# Schedule visit orders (build_schedule order=):
#   m_major -- by m-block row, within a row by (k-block, plane): each output
#              block is visited in consecutive steps, as the v2 sparse
#              kernels' out-BlockSpec accumulation requires.
#   k_major -- by k-block globally, within a k-block by (row, plane):
#              consecutive steps across *different* output rows share a B
#              block so the pipelined kernels elide its DMA entirely;
#              output blocks are revisited non-consecutively, which only
#              the pipelined kernels' VMEM accumulator panel supports.
SCHEDULE_ORDERS = ("m_major", "k_major")

# planned_dense_apply dispatch values ('auto' resolves to one of the rest)
DISPATCHES = ("dense", "sparse", "pipelined", "auto")


def _annotate_schedule(entries) -> np.ndarray:
    """(plane, row, kblk, weight) tuples -> int32 [L, 9] SCHED_COLS rows.

    Derives the flags the kernels consume from the visit sequence alone:
    FIRST/LAST mark each output row's overall first/last step (accumulator
    init / flush boundaries — correct in any visit order because the
    pipelined kernels keep every row's accumulator VMEM-resident for the
    whole walk); D_SLOT/B_SLOT alternate per *fetch* so an in-flight copy
    can never target the buffer the current step is reading; B_FETCH is 0
    whenever the step's k-block is already resident (consecutive same-k
    steps — zero-weight steps fetch nothing and leave residency alone).
    """
    first_step, last_step = {}, {}
    for i, (_p, row, _kk, _w) in enumerate(entries):
        first_step.setdefault(row, i)
        last_step[row] = i
    sched = np.zeros((len(entries), 9), dtype=np.int32)
    resident_k = None
    n_dfetch = n_bfetch = 0
    for i, (p, row, kk, w) in enumerate(entries):
        d_slot = b_slot = b_fetch = 0
        if w != 0:
            d_slot = n_dfetch % 2
            n_dfetch += 1
            if kk != resident_k:
                b_fetch = 1
                b_slot = n_bfetch % 2
                n_bfetch += 1
                resident_k = kk
            else:
                b_slot = (n_bfetch - 1) % 2
        sched[i] = (p, row, kk, w, int(first_step[row] == i),
                    int(last_step[row] == i), d_slot, b_slot, b_fetch)
    return sched


def build_schedule(mask, radix: int, order: str = "m_major") -> np.ndarray:
    """Compact a plane-block occupancy mask into an int32 [L, 9] schedule.

    mask: bool [BW, Mb, Kb].  One schedule entry per True cell, in the
    requested visit ``order`` (see SCHEDULE_ORDERS); every empty row gets
    one zero-weight sentinel entry so its output block is still visited,
    zeroed and written.  Columns are bw_gemm.SCHED_COLS: (plane, row,
    kblk, weight=radix**plane, first, last, d_slot, b_slot, b_fetch); the
    first six drive the v2 kernels, the last three bake the pipelined
    kernels' double-buffer rotation and B-reuse elision in (see
    _annotate_schedule).
    """
    if order not in SCHEDULE_ORDERS:
        raise ValueError(f"order must be one of {SCHEDULE_ORDERS}, "
                         f"got {order!r}")
    mask = np.asarray(mask)
    with obs_trace.span("plan.build_schedule", order=order,
                        blocks=int(mask.size)):
        return _build_schedule(mask, radix, order)


def _build_schedule(mask, radix: int, order: str) -> np.ndarray:
    bw_n, mb, kb = mask.shape
    entries = []
    if order == "m_major":
        for row in range(mb):
            cells = np.argwhere(mask[:, row, :])      # (plane, kblk) pairs
            if cells.size == 0:
                # sentinel: visit the output block once with weight 0 so
                # the row is written as exact zeros
                entries.append((0, row, 0, 0))
                continue
            o = np.lexsort((cells[:, 0], cells[:, 1]))  # by (kblk, plane)
            entries.extend((int(p), row, int(kk), radix ** int(p))
                           for p, kk in cells[o])
    else:                                # k_major: global B-block reuse
        for row in range(mb):
            if not mask[:, row, :].any():
                entries.append((0, row, 0, 0))        # sentinels up front
        for kk in range(kb):
            cells = np.argwhere(mask[:, :, kk])       # (plane, row) pairs
            o = np.lexsort((cells[:, 0], cells[:, 1]))  # by (row, plane)
            entries.extend((int(p), int(row), kk, radix ** int(p))
                           for p, row in cells[o])
    sched = _annotate_schedule(entries)
    if mask.size:                                  # metrics: built plans
        real = int((sched[:, 3] != 0).sum())
        _M_SCHED_DENSITY.observe(real / mask.size)
        if sched.shape[1] >= 9:
            _M_B_ELIDED.inc(real - int(sched[:, 8].sum()))
    return sched


def pad_schedule(schedule: np.ndarray, length: int) -> np.ndarray:
    """Pad a schedule to ``length`` steps with exact no-op entries.

    Padding replicates the final entry with weight 0 and cleared
    first/last flags, *appended after* it: the output block index stays on
    the last row, so the padded steps neither re-zero the accumulator nor
    re-run the epilogue, and the block is flushed once with its correct
    content.  The pipelined-kernel columns are cleared too (B_FETCH 0, no
    slot rotation), so padding steps issue no DMA and wait on no
    semaphore.  Needed when per-layer schedules of different lengths are
    stacked for jax.lax.scan.
    """
    sched = np.asarray(schedule)
    if sched.shape[0] > length:
        raise ValueError(f"cannot pad a {sched.shape[0]}-step schedule "
                         f"down to {length}")
    if sched.shape[0] == length:
        return sched
    pad = np.repeat(sched[-1:], length - sched.shape[0], axis=0)
    pad[:, 3:] = 0          # weight/first/last + slot/fetch cols cleared
    return np.concatenate([sched, pad], axis=0)


def schedule_stats(schedule, mask) -> dict:
    """Real (non-sentinel, non-padding) entry count and block density."""
    sched = np.asarray(schedule)
    mask = np.asarray(mask)
    real = int((sched[:, 3] != 0).sum())          # weight 0 = no-op entry
    total = int(mask.size)
    out = {"steps": int(sched.shape[0]), "nnz_blocks": real,
           "total_blocks": total,
           "density": real / total if total else 0.0}
    if sched.shape[1] >= 9:              # annotated: B-reuse accounting
        fetches = int(sched[:, 8].sum())
        out["b_fetches"] = fetches
        out["b_dma_elided"] = real - fetches
    return out


@dataclasses.dataclass
class PlannedOperand:
    """A pre-encoded multiplicand ready for bw_gemm.

    row_perm sorts rows by high-plane occupancy so that non-zero high-weight
    digits cluster into few row blocks (turning the paper's element-level PP
    sparsity into MXU-block sparsity).  inv_perm restores output order.
    """
    digits: jax.Array           # int8 [BW, M_pad, K_pad]
    mask: jax.Array             # bool [BW, M_pad/bm, K_pad/bk]
    row_perm: np.ndarray        # [M_pad]
    inv_perm: np.ndarray        # [M_pad]
    m: int                      # original M
    k: int
    block_m: int
    block_k: int
    encoding: str
    schedule: Optional[np.ndarray] = None   # int32 [L, 9], build_schedule
    order: str = "m_major"                  # the schedule's visit order
    sharded: Optional[object] = None        # parallel.plan.ShardedPlan

    def density(self) -> float:
        """Fraction of non-zero plane blocks (the sparse-dispatch signal)."""
        return float(np.asarray(self.mask).mean())


def plan_operand(a_int8, encoding: str = "ent", block_m: int = 128,
                 block_k: int = 256, reorder_rows: bool = True,
                 encode_impl: str = "ref", bits: int = 8,
                 order: str = "m_major") -> PlannedOperand:
    """Encode + (optionally) magnitude-order the multiplicand rows.

    a_int8: int8 [M, K] (e.g. a transposed weight matrix).
    encode_impl: 'ref' (jnp oracle) or 'kernel' (the fused Pallas EN-T
    encoder, repro.kernels.encode — interpret mode off-TPU).
    order: schedule visit order (SCHEDULE_ORDERS); 'k_major' schedules
    require the pipelined kernels.
    """
    a = jnp.asarray(a_int8, jnp.int8)
    m, k = a.shape
    a = _pad_to(_pad_to(a, block_m, 0), block_k, 1)
    if reorder_rows:
        # rows with any |value| >= 43 need plane 3 (EN-T: 2*(1+4+16)=42 is the
        # largest 3-plane-representable magnitude); sort rows by their
        # high-plane digit count so those rows pack into few blocks.  Score
        # over the top min(2, BW) planes: narrow encodings (e.g. 2-bit
        # operands have a single radix-4 plane) must not index past plane 0.
        d0 = _encode_planes_jit(a, encoding=encoding, bits=bits)
        hi = np.zeros(a.shape[0], dtype=np.int64)
        for p in range(min(2, d0.shape[0])):
            hi = hi * 1000 + np.asarray((d0[-(p + 1)] != 0).sum(axis=1))
        del d0
        row_perm = np.argsort(-hi, kind="stable").astype(np.int32)
    else:
        row_perm = np.arange(a.shape[0], dtype=np.int32)
    inv_perm = np.argsort(row_perm).astype(np.int32)
    a_sorted = a[row_perm]
    if encode_impl == "kernel" and encoding == "ent" and bits == 8:
        from . import encode as _enc_kernel
        digits, mask = _enc_kernel.ent_encode(
            a_sorted, block_m=block_m, block_k=block_k,
            interpret=_interpret())
    else:
        digits = _encode_planes_jit(a_sorted, encoding=encoding, bits=bits)
        mask = plane_block_mask(digits, block_m, block_k)
    schedule = build_schedule(np.asarray(mask), enc.radix(encoding), order)
    return PlannedOperand(digits, mask, row_perm, inv_perm, m, k,
                          block_m, block_k, encoding, schedule, order)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret",
                                             "block_m", "block_k", "radix"))
def _bw_gemm_padded(planned_digits, mask, b, inv_perm, *, block_n,
                    interpret, block_m, block_k, radix):
    out = _bw.bw_gemm(planned_digits, b, mask, block_m=block_m,
                      block_n=block_n, block_k=block_k, radix=radix,
                      interpret=interpret)
    return out[inv_perm]


def bw_gemm(planned: PlannedOperand, b, *, block_n: int = 128,
            interpret: Optional[bool] = None):
    """C = A @ B with A pre-planned.  b: int8 [K, N] -> int32 [M, N]."""
    if interpret is None:
        interpret = _interpret()
    k, n = b.shape
    _check_operand_k(k, planned.k)
    b = _pad_to(_pad_to(jnp.asarray(b, jnp.int8), planned.block_k, 0),
                block_n, 1)
    out = _bw_gemm_padded(
        planned.digits, planned.mask, b, jnp.asarray(planned.inv_perm),
        block_n=block_n, interpret=bool(interpret),
        block_m=planned.block_m, block_k=planned.block_k,
        radix=enc.radix(planned.encoding))
    return out[:planned.m, :n]


def bw_gemm_sparse(planned: PlannedOperand, b, *, block_n: int = 128,
                   interpret: Optional[bool] = None):
    """C = A @ B through the compacted-schedule kernel (scalar prefetch).

    Bit-identical to bw_gemm on the same plan; an all-zero plane-block
    costs neither a DMA nor a grid step.  b: int8 [K, N] -> int32 [M, N].
    """
    if interpret is None:
        interpret = _interpret()
    k, n = b.shape
    _check_operand_k(k, planned.k)
    _check_has_schedule(planned)
    # the v2 out-BlockSpec accumulates only across *consecutive* revisits;
    # a k_major plan would silently clobber partial sums on real TPUs
    # (interpret mode hides it), so refuse it here, not just in dispatch
    if planned.order != "m_major":
        raise ValueError(
            f"bw_gemm_sparse requires an m_major plan, got "
            f"{planned.order!r} (use bw_gemm_sparse_pipelined)")
    b = _pad_to(_pad_to(jnp.asarray(b, jnp.int8), planned.block_k, 0),
                block_n, 1)
    out = _bw.bw_gemm_sparse(
        planned.digits, b, jnp.asarray(planned.schedule),
        block_m=planned.block_m, block_n=block_n, block_k=planned.block_k,
        interpret=bool(interpret))
    return out[jnp.asarray(planned.inv_perm)][:planned.m, :n]


def bw_gemm_sparse_fused(planned: PlannedOperand, b, scale, bias=None, *,
                         activation=None, block_n: int = 128,
                         out_dtype=jnp.float32,
                         interpret: Optional[bool] = None):
    """bw_gemm_fused through the compacted-schedule kernel.

    Same contract as bw_gemm_fused: scale/bias are per-row vectors of
    length M in the operand's original row order.
    """
    if interpret is None:
        interpret = _interpret()
    k, n = b.shape
    _check_operand_k(k, planned.k)
    _check_has_schedule(planned)
    # see bw_gemm_sparse: v2 accumulation is only legal on m_major plans
    if planned.order != "m_major":
        raise ValueError(
            f"bw_gemm_sparse_fused requires an m_major plan, got "
            f"{planned.order!r} (use bw_gemm_sparse_fused_pipelined)")
    m_pad = planned.digits.shape[1]
    row_perm = jnp.asarray(planned.row_perm)
    scale_rows = _channel_rows(scale, planned.m, m_pad, row_perm)
    bias_rows = None
    if bias is not None:
        bias_rows = _channel_rows(bias, planned.m, m_pad, row_perm)
    b = _pad_to(_pad_to(jnp.asarray(b, jnp.int8), planned.block_k, 0),
                block_n, 1)
    out = _bw.bw_gemm_sparse_fused(
        planned.digits, b, jnp.asarray(planned.schedule), scale_rows,
        bias_rows, block_m=planned.block_m, block_n=block_n,
        block_k=planned.block_k, interpret=bool(interpret),
        activation=activation, out_dtype=out_dtype)
    return out[jnp.asarray(planned.inv_perm)][:planned.m, :n]


def bw_gemm_sparse_pipelined(planned: PlannedOperand, b, *,
                             block_n: int = 128,
                             interpret: Optional[bool] = None):
    """C = A @ B through the double-buffered pipelined kernel.

    Bit-identical to bw_gemm_sparse on the same plan in either schedule
    order; step s+1's plane gather overlaps step s's MXU pass and
    consecutive same-k steps reuse the resident B block without a DMA.
    """
    if interpret is None:
        interpret = _interpret()
    k, n = b.shape
    _check_operand_k(k, planned.k)
    _check_has_schedule(planned)
    b = _pad_to(_pad_to(jnp.asarray(b, jnp.int8), planned.block_k, 0),
                block_n, 1)
    out = _bw.bw_gemm_sparse_pipelined(
        planned.digits, b, jnp.asarray(planned.schedule),
        block_m=planned.block_m, block_n=block_n, block_k=planned.block_k,
        interpret=bool(interpret))
    return out[jnp.asarray(planned.inv_perm)][:planned.m, :n]


def bw_gemm_sparse_fused_pipelined(planned: PlannedOperand, b, scale,
                                   bias=None, *, activation=None,
                                   block_n: int = 128,
                                   out_dtype=jnp.float32,
                                   interpret: Optional[bool] = None):
    """bw_gemm_sparse_fused through the double-buffered pipelined kernel.

    Same contract as bw_gemm_fused: scale/bias are per-row vectors of
    length M in the operand's original row order.
    """
    if interpret is None:
        interpret = _interpret()
    k, n = b.shape
    _check_operand_k(k, planned.k)
    _check_has_schedule(planned)
    m_pad = planned.digits.shape[1]
    row_perm = jnp.asarray(planned.row_perm)
    scale_rows = _channel_rows(scale, planned.m, m_pad, row_perm)
    bias_rows = None
    if bias is not None:
        bias_rows = _channel_rows(bias, planned.m, m_pad, row_perm)
    b = _pad_to(_pad_to(jnp.asarray(b, jnp.int8), planned.block_k, 0),
                block_n, 1)
    out = _bw.bw_gemm_sparse_fused_pipelined(
        planned.digits, b, jnp.asarray(planned.schedule), scale_rows,
        bias_rows, block_m=planned.block_m, block_n=block_n,
        block_k=planned.block_k, interpret=bool(interpret),
        activation=activation, out_dtype=out_dtype)
    return out[jnp.asarray(planned.inv_perm)][:planned.m, :n]


def quant_gemm(a, b, *, block_m: int = 128, block_n: int = 128,
               block_k: int = 256, interpret: Optional[bool] = None):
    """Baseline int8 GEMM (pads to block multiples, slices back)."""
    if interpret is None:
        interpret = _interpret()
    m, k = a.shape
    k2, n = b.shape
    _check_gemm_k(k, k2)
    a = _pad_to(_pad_to(jnp.asarray(a, jnp.int8), block_m, 0), block_k, 1)
    b = _pad_to(_pad_to(jnp.asarray(b, jnp.int8), block_k, 0), block_n, 1)
    out = _qg.quant_gemm(a, b, block_m=block_m, block_n=block_n,
                         block_k=block_k, interpret=bool(interpret))
    return out[:m, :n]


def bw_gemm_fused(planned: PlannedOperand, b, scale, bias=None, *,
                  activation=None, block_n: int = 128,
                  out_dtype=jnp.float32, interpret: Optional[bool] = None):
    """C = act((A @ B)_int * scale + bias) with A pre-planned.

    b: int8 [K, N].  scale/bias: per-row vectors of length M (the planned
    operand's original row order -- permutation into planned order and the
    padding are handled here).  Returns float [M, N].
    """
    if interpret is None:
        interpret = _interpret()
    k, n = b.shape
    _check_operand_k(k, planned.k)
    m_pad = planned.digits.shape[1]
    row_perm = jnp.asarray(planned.row_perm)
    scale_rows = _channel_rows(scale, planned.m, m_pad, row_perm)
    bias_rows = None
    if bias is not None:
        bias_rows = _channel_rows(bias, planned.m, m_pad, row_perm)
    b = _pad_to(_pad_to(jnp.asarray(b, jnp.int8), planned.block_k, 0),
                block_n, 1)
    out = _bw.bw_gemm_fused(
        planned.digits, b, planned.mask, scale_rows, bias_rows,
        block_m=planned.block_m, block_n=block_n, block_k=planned.block_k,
        radix=enc.radix(planned.encoding), interpret=bool(interpret),
        activation=activation, epilogue_axis="m", out_dtype=out_dtype)
    return out[jnp.asarray(planned.inv_perm)][:planned.m, :n]


def quant_gemm_fused(a, b, scale, bias=None, *, activation=None,
                     block_m: int = 128, block_n: int = 128,
                     block_k: int = 256, out_dtype=jnp.float32,
                     interpret: Optional[bool] = None):
    """Baseline int8 GEMM + fused dequant epilogue (pads, slices back).

    scale/bias: per-output-channel vectors of length N (epilogue axis 'n').
    """
    if interpret is None:
        interpret = _interpret()
    m, k = a.shape
    k2, n = b.shape
    _check_gemm_k(k, k2)
    a = _pad_to(_pad_to(jnp.asarray(a, jnp.int8), block_m, 0), block_k, 1)
    b = _pad_to(_pad_to(jnp.asarray(b, jnp.int8), block_k, 0), block_n, 1)
    scale = _pad_to(jnp.asarray(scale, jnp.float32).reshape(1, n), block_n, 1)
    if bias is not None:
        bias = _pad_to(jnp.asarray(bias, jnp.float32).reshape(1, n),
                       block_n, 1)
    out = _qg.quant_gemm_fused(
        a, b, scale, bias, block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=bool(interpret), activation=activation, epilogue_axis="n",
        out_dtype=out_dtype)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# Weight-planning cache: plan once per parameter, reuse every call
# ---------------------------------------------------------------------------
# jax.Arrays are immutable, so identity is a sound cache key while the array
# is alive; a weakref finalizer evicts the entry when the buffer dies so a
# recycled id() can never alias a stale plan.  Mutable numpy inputs fall back
# to a content fingerprint.  This is the EN-T move of pushing encoding out of
# the inner loop: serving pays the encode + permutation + occupancy-mask cost
# once per weight, not once per matmul.

class _PlanCache:
    MAX_ENTRIES = 256     # FIFO cap: content-keyed (numpy) entries have no
                          # weakref eviction and would otherwise grow forever

    def __init__(self):
        self._entries = {}
        self.hits = 0
        self.misses = 0

    def _key(self, w, params):
        if isinstance(w, np.ndarray):
            digest = hashlib.blake2b(np.ascontiguousarray(w).tobytes(),
                                     digest_size=16).hexdigest()
            return ("hash", w.shape, str(w.dtype), digest) + params, None
        return ("id", id(w)) + params, w

    def lookup(self, w, params, build):
        key, anchor = self._key(w, params)
        hit = self._entries.get(key)
        if hit is not None:
            self.hits += 1
            _M_PLAN_HITS.inc()
            return hit[0]
        self.misses += 1
        _M_PLAN_MISSES.inc()
        value = build()
        finalizer = None
        if anchor is not None:
            try:
                finalizer = weakref.ref(
                    anchor, lambda _ref, k=key: self._entries.pop(k, None))
            except TypeError:
                # id-keyed but not weakref-able: caching would risk a
                # recycled id() aliasing a stale plan -- don't cache
                return value
        while len(self._entries) >= self.MAX_ENTRIES:
            self._entries.pop(next(iter(self._entries)))
        self._entries[key] = (value, finalizer)
        return value

    def clear(self):
        self._entries.clear()
        self.hits = self.misses = 0

    def stats(self):
        return {"entries": len(self._entries), "hits": self.hits,
                "misses": self.misses}


_PLAN_CACHE = _PlanCache()


def plan_cache_stats() -> dict:
    return _PLAN_CACHE.stats()


def plan_cache_clear() -> None:
    _PLAN_CACHE.clear()


def plan_for(w, spec, order: str = "m_major",
             verify: Optional[bool] = None, shards=None):
    """Quantize + plan a dense weight for the kernel path, with caching.

    w: float [K, N] (d_in, d_out).  spec: QuantSpec (or legacy int plane
    budget).  order: schedule visit order (SCHEDULE_ORDERS).  Returns
    (PlannedOperand of W^T with [N, K] layout -- output channels as
    kernel rows -- and the per-channel weight scale sw of shape [1, N]).
    Cache entries key on (weight, spec.plan_key(), order, shards): the
    same weight planned under two specs, two schedule orders or two mesh
    shard grids coexists as independent entries.

    shards: optional ``(s_data, s_model)`` mesh shard grid — the
    returned PlannedOperand additionally carries a
    ``repro.parallel.plan.ShardedPlan`` (per-shard schedules + padded
    record) in its ``sharded`` field for ``sharded_planned_apply``.

    verify: run the repro.analysis schedule verifier + DMA-hazard walk on
    the freshly built plan (per shard too, when sharded) and raise
    ``AnalysisError`` on any violation (None: the ``REPRO_VERIFY`` env
    toggle; cached plans were verified at build time and are not
    re-checked).
    """
    if isinstance(w, jax.core.Tracer):
        raise TypeError(
            "plan_for needs concrete weights (planning is a one-time eager "
            "step); under tracing use the jnp oracle path instead")
    spec = QuantSpec.coerce(spec)
    k, n = w.shape
    block_m, block_k, _ = select_block_sizes(n, k, 128, spec)
    if shards is not None:
        from repro.parallel.collectives import normalize_shards
        shards = normalize_shards(shards)
        if shards == (1, 1):
            shards = None
    params = spec.plan_key() + (int(block_m), int(block_k), k, n, order,
                                shards)

    def build():
        with obs_trace.span("plan.plan_for", k=k, n=n, order=order,
                            planes=spec.planes,
                            shards=str(shards) if shards else "1x1"):
            qw, sw = quantlib.quantize_for_spec(
                jnp.asarray(w).astype(jnp.float32), spec, axis=0)
            planned = plan_operand(qw.T, encoding=spec.encoding,
                                   block_m=block_m, block_k=block_k,
                                   bits=spec.bits, order=order)
            if _verify_enabled(verify):
                _verify_planned(planned)
            sw = jnp.asarray(sw, jnp.float32)
            if shards is not None:
                from repro.parallel.plan import shard_plan
                planned.sharded = shard_plan(planned, shards, sw=sw,
                                             verify=verify)
            return planned, sw

    return _PLAN_CACHE.lookup(w, params, build)


def _channel_rows(vec, n: int, m_pad: int, row_perm) -> jax.Array:
    """[N] per-channel vector -> [M_pad, 1] rows in planned (permuted) order."""
    full = jnp.zeros((m_pad,), jnp.float32).at[:n].set(
        jnp.asarray(vec, jnp.float32).reshape(-1))
    return full[row_perm].reshape(-1, 1)


def plan_dense_weight(w, spec, use_cache: bool = True,
                      order: str = "m_major",
                      verify: Optional[bool] = None) -> dict:
    """Quantize + plan a dense weight into a pure-array plan record.

    The record is a pytree of arrays only (digit planes, occupancy mask,
    channel permutations, permuted weight scales), so it can be attached to
    a model's param tree, sliced by jax.lax.scan over stacked layers, and
    fed to the fused kernel *under tracing* -- the planning itself happens
    here, eagerly, once per weight.

    The record does not carry the encoding name or the schedule order:
    planned_dense_apply takes the same QuantSpec (reconstructing the radix
    from it, and checking the plane count against the record's shapes, so
    an ent plan applied under a bit-serial spec fails loudly instead of
    decoding silently wrong) and the same ``order`` (which only gates the
    sparse-vs-pipelined dispatch — the pipelined kernels themselves run
    any annotated schedule correctly).
    """
    spec = QuantSpec.coerce(spec)
    if use_cache:
        planned, sw = plan_for(w, spec, order=order, verify=verify)
    else:
        k, n = w.shape
        block_m, block_k, _ = select_block_sizes(n, k, 128, spec)
        qw, sw = quantlib.quantize_for_spec(
            jnp.asarray(w).astype(jnp.float32), spec, axis=0)
        planned = plan_operand(qw.T, encoding=spec.encoding, block_m=block_m,
                               block_k=block_k, bits=spec.bits, order=order)
        if _verify_enabled(verify):
            _verify_planned(planned)
        sw = jnp.asarray(sw, jnp.float32)
    n = w.shape[1]
    m_pad = planned.digits.shape[1]
    row_perm = jnp.asarray(planned.row_perm)
    return {
        "digits": planned.digits,                     # int8 [BW, M_pad, K_pad]
        "mask": planned.mask,                         # bool [BW, M/bm, K/bk]
        "schedule": jnp.asarray(planned.schedule),    # int32 [L, 6]
        "row_perm": row_perm,                         # int32 [M_pad]
        "inv_perm": jnp.asarray(planned.inv_perm),    # int32 [M_pad]
        "sw_rows": _channel_rows(sw.reshape(-1), n, m_pad, row_perm),
    }


def _resolve_dispatch(dispatch: str, plan: dict, spec, n_out: int, k: int,
                      batch: int, order: str) -> str:
    """Resolve to a concrete kernel route: 'dense'|'sparse'|'pipelined'.

    The decision is *static* (shape-derived, jit/scan-safe): the schedule
    length L counts nnz blocks + per-empty-row sentinels (+ stack padding),
    so L / mask.size is a sound density proxy.  'auto' consults the
    measured autotune cache for a per-(shape, density-bucket) winner and
    falls back to the SPARSE_DENSITY_THRESHOLD heuristic on a miss —
    sparse routes become 'sparse' (the v2 scalar-prefetch kernels) for
    m_major schedules and 'pipelined' for k_major ones, whose
    non-consecutive output revisits only the pipelined kernels support.
    """
    if order not in SCHEDULE_ORDERS:
        raise ValueError(f"order must be one of {SCHEDULE_ORDERS}, "
                         f"got {order!r}")
    if dispatch == "dense" or plan.get("schedule") is None:
        return "dense"
    if dispatch == "sparse":
        if order == "k_major":
            raise ValueError(
                "dispatch='sparse' (the v2 kernels) requires an m_major "
                "schedule: k_major revisits output blocks non-consecutively"
                " — use dispatch='pipelined' (or 'auto')")
        return "sparse"
    if dispatch == "pipelined":
        return "pipelined"
    if dispatch != "auto":
        raise ValueError(f"dispatch must be one of {DISPATCHES}, "
                         f"got {dispatch!r}")
    sparse_route = "pipelined" if order == "k_major" else "sparse"
    density = plan["schedule"].shape[0] / max(plan["mask"].size, 1)
    from . import autotune
    hit = autotune.get_cache().lookup(n_out, k, batch, spec, density=density)
    if hit is not None and hit.get("dispatch") in ("sparse", "dense",
                                                   "pipelined"):
        won = hit["dispatch"]
        if won == "dense":
            return "dense"
        # a measured sparse-route winner only transfers when it was
        # measured under *this plan's* schedule order (a k_major-measured
        # pipelined win says nothing about an m_major schedule's walk);
        # pre-tag entries (order absent) are trusted as order-agnostic
        if hit.get("order") in (None, order):
            if won == "pipelined":
                return "pipelined"
            if order == "m_major":                    # won == "sparse"
                return "sparse"
        elif won in ("sparse", "pipelined") and order == "k_major":
            # a sparse-route win that cannot run v2 on this plan: the
            # nearest legal sparse route is still measured-informed
            return "pipelined"
        # otherwise the ranking does not transfer: fall through
    return sparse_route if density <= SPARSE_DENSITY_THRESHOLD else "dense"


def _maybe_verify_plan(plan: dict, spec, order: str,
                       verify: Optional[bool]) -> None:
    """planned_dense_apply's pre-kernel verification seam.

    Skipped under tracing (schedule/mask are tracers inside scan over
    stacked plans — the eager plan build already verified them), for
    stacked [layers, L, 9] schedules, and for schedules this process has
    already verified (identity memo)."""
    if not _verify_enabled(verify):
        return
    sched, mask = plan.get("schedule"), plan.get("mask")
    if sched is None or isinstance(sched, jax.core.Tracer) or \
            isinstance(mask, jax.core.Tracer):
        return
    if getattr(sched, "ndim", 0) != 2 or _schedule_verified(sched):
        return
    from repro import analysis
    analysis.verify_plan(
        {"schedule": sched, "mask": mask}, spec.radix,
        order).raise_if_errors()
    _mark_schedule_verified(sched)


def planned_dense_apply(plan: dict, x, spec, n_out: int, *, bias=None,
                        activation=None, out_dtype=jnp.float32,
                        block_n: Optional[int] = None,
                        interpret: Optional[bool] = None,
                        fused: bool = True, dispatch: str = "dense",
                        order: str = "m_major",
                        verify: Optional[bool] = None):
    """y = act((x @ w)_int * s_x * s_w + bias) through the bw_gemm kernel.

    plan: record from plan_dense_weight (possibly a scan-sliced layer of a
    stacked plan), built under the *same* spec.  Activations are quantized
    at call time per the spec's act_quant policy: ``per_tensor`` folds the
    single activation scale into the per-channel weight scale; ``per_token``
    keeps one scale per activation row and (fused=True) feeds it to the
    kernel epilogue as a per-column vector -- tokens sit on the kernel N
    axis in the planned-weight layout -- so continuous-batching decode
    outputs do not depend on what else is packed in the batch.  With
    fused=True the dequant, bias add and activation run in the kernel
    epilogue on the VMEM-resident accumulator; with fused=False the kernel
    returns the int32 accumulator and the epilogue runs in jnp.  Traceable
    end to end: safe inside jit / scan (block sizes come from static array
    shapes, radix from the static spec).

    dispatch: 'dense' (the predicated full-grid kernels), 'sparse' (the
    v2 compacted-schedule scalar-prefetch kernels), 'pipelined' (the
    double-buffered manual-DMA kernels), or 'auto' (density-based: a
    sparse route when the schedule-length density proxy is at most
    SPARSE_DENSITY_THRESHOLD, with autotune-cache overrides).  order
    names the plan's schedule visit order: 'k_major' plans (built for
    B-block reuse) can only take the dense or pipelined routes.  The
    decision is shape-derived, so it stays static under jit/scan.

    verify: run the static schedule verifier + DMA-hazard walk before
    dispatching the kernel (None: the ``REPRO_VERIFY`` env toggle); a
    corrupt schedule raises ``repro.analysis.AnalysisError`` instead of
    silently miscomputing.  Skipped under tracing, where the schedule is
    a tracer (the eager plan build already verified it).
    """
    spec = QuantSpec.coerce(spec)
    if interpret is None:
        interpret = _interpret()
    digits, mask = plan["digits"], plan["mask"]
    bw_n, m_pad, k_pad = digits.shape
    if bw_n != spec.num_digits:
        raise ValueError(
            f"plan record has {bw_n} digit planes but spec "
            f"{spec.encoding!r}/{spec.bits}b implies {spec.num_digits}; "
            f"was the plan built under a different spec?")
    # verify only after the spec/plan compatibility check: a plan applied
    # under a foreign spec should fail with the specific message above,
    # not with the verifier's radix-mismatch diagnostics
    _maybe_verify_plan(plan, spec, order, verify)
    block_m = m_pad // mask.shape[1]
    block_k = k_pad // mask.shape[2]
    k = x.shape[-1]
    lead = x.shape[:-1]
    per_token = spec.act_quant == "per_token"
    qx, sx = quantlib.quantize_for_spec(
        jnp.asarray(x).astype(jnp.float32), spec,
        axis=-1 if per_token else None)
    x2 = qx.reshape(-1, k)
    batch = x2.shape[0]
    if block_n is None:
        block_n = select_block_sizes(n_out, k, batch, spec)[2]
    bt = _pad_to(_pad_to(x2.T, block_k, 0), block_n, 1)
    sx_cols = None
    if per_token:                        # one scale per activation row ->
        sx_cols = _pad_to(sx.reshape(1, -1), block_n, 1)  # kernel N axis
    route = _resolve_dispatch(dispatch, plan, spec, n_out, k, batch, order)
    # chaos seam: one branch when no plan is armed; fires only on eager
    # (or trace-time) calls — a jit'd serve step never re-enters here
    if _chaos.enabled():
        _chaos.maybe_raise("kernel.dispatch", target=route)
    # hot path: the span + dispatch counter take one no-op branch when
    # obs is disabled (pinned by the obs.overhead bench lane)
    if obs_trace.enabled():
        count_dispatch(route)
        sp = obs_trace.span("ops.planned_dense_apply", cat="kernel",
                            route=route, fused=bool(fused), order=order,
                            m=int(n_out), k=int(k), n=int(batch))
    else:
        sp = obs_trace.NULL_SPAN
    with sp:
        if fused:
            scale_rows = plan["sw_rows"] if per_token \
                else plan["sw_rows"] * sx
            bias_rows = None
            if bias is not None:
                bias_rows = _channel_rows(bias, n_out, m_pad,
                                          plan["row_perm"])
            if route == "pipelined":
                out = _bw.bw_gemm_sparse_fused_pipelined(
                    digits, bt, plan["schedule"], scale_rows, bias_rows,
                    sx_cols, block_m=block_m, block_n=block_n,
                    block_k=block_k, interpret=bool(interpret),
                    activation=activation, out_dtype=jnp.float32)
            elif route == "sparse":
                out = _bw.bw_gemm_sparse_fused(
                    digits, bt, plan["schedule"], scale_rows, bias_rows,
                    sx_cols, block_m=block_m, block_n=block_n,
                    block_k=block_k, interpret=bool(interpret),
                    activation=activation, out_dtype=jnp.float32)
            else:
                out = _bw.bw_gemm_fused(
                    digits, bt, mask, scale_rows, bias_rows, sx_cols,
                    block_m=block_m, block_n=block_n, block_k=block_k,
                    radix=spec.radix, interpret=bool(interpret),
                    activation=activation, epilogue_axis="m",
                    out_dtype=jnp.float32)
            y = out[plan["inv_perm"]][:n_out, :batch].T
        else:
            if route == "pipelined":
                acc = _bw.bw_gemm_sparse_pipelined(
                    digits, bt, plan["schedule"], block_m=block_m,
                    block_n=block_n, block_k=block_k,
                    interpret=bool(interpret))
            elif route == "sparse":
                acc = _bw.bw_gemm_sparse(
                    digits, bt, plan["schedule"], block_m=block_m,
                    block_n=block_n, block_k=block_k,
                    interpret=bool(interpret))
            else:
                acc = _bw.bw_gemm(
                    digits, bt, mask, block_m=block_m, block_n=block_n,
                    block_k=block_k, radix=spec.radix,
                    interpret=bool(interpret))
            acc = acc[plan["inv_perm"]][:n_out, :batch]
            sw = plan["sw_rows"][plan["inv_perm"]][:n_out]  # orig order
            s = sw * (sx.reshape(1, -1) if per_token else sx)
            y = (acc.astype(jnp.float32) * s).T
            if bias is not None:
                y = y + jnp.asarray(bias, jnp.float32)
            if activation is not None:
                y = _bw.EPILOGUE_ACTIVATIONS[activation](y)
    return y.reshape(*lead, n_out).astype(out_dtype)


def planned_experts_apply(plan: dict, x, spec, n_out: int, counts=None, *,
                          activation=None, out_dtype=jnp.float32,
                          block_n: Optional[int] = None,
                          interpret: Optional[bool] = None):
    """y[h] = act((x[h] @ w[h])_int * s_x * s_w) for each held expert h,
    in one grouped ``bw_gemm_experts`` call.

    plan: a layer's stacked expert plan, leaves [H, ...] (plan_params on
    a [.., H, K, N] expert weight).  x: [H, T, K] each expert's gathered
    tokens, its ``counts[h]`` routed ones first (None: all T).  Activations
    are quantized as in planned_dense_apply.  Returns [H, T, n_out]."""
    spec = QuantSpec.coerce(spec)
    if interpret is None:
        interpret = _interpret()
    digits, mask = plan["digits"], plan["mask"]
    n_exp, bw_n, m_pad, k_pad = digits.shape
    if bw_n != spec.num_digits:
        raise ValueError(
            f"expert plan has {bw_n} digit planes but spec "
            f"{spec.encoding!r}/{spec.bits}b implies {spec.num_digits}")
    block_m, block_k = m_pad // mask.shape[2], k_pad // mask.shape[3]
    _, t, k = x.shape
    per_token = spec.act_quant == "per_token"
    qx, sx = quantlib.quantize_for_spec(
        jnp.asarray(x).astype(jnp.float32), spec,
        axis=-1 if per_token else None)
    if block_n is None:
        block_n = select_block_sizes(n_out, k, t, spec)[2]
    bt = _pad_to(_pad_to(jnp.swapaxes(qx, 1, 2), block_k, 1), block_n, 2)
    sx_cols = None
    scale_rows = plan["sw_rows"]
    if per_token:                        # one scale per token: kernel N
        sx_cols = _pad_to(jnp.swapaxes(sx, 1, 2), block_n, 2)
    else:
        scale_rows = scale_rows * sx
    if counts is None:
        counts = jnp.full((n_exp,), t, jnp.int32)
    out = _bw.bw_gemm_experts(
        digits, bt, mask, scale_rows, counts, sx_cols, block_m=block_m,
        block_n=block_n, block_k=block_k, radix=spec.radix,
        interpret=bool(interpret), activation=activation,
        out_dtype=jnp.float32)
    y = jnp.take_along_axis(out, plan["inv_perm"][..., None], axis=1)
    return jnp.swapaxes(y[:, :n_out, :t], 1, 2).astype(out_dtype)


def quantized_dense(x, w, spec, *, bias=None, activation=None,
                    out_dtype=jnp.float32,
                    block_n: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    fused: bool = True, dispatch: str = "dense",
                    order: str = "m_major"):
    """Eager kernel-path dense: plan (cached per parameter) + bw_gemm.

    x: [..., K] float.  w: [K, N] float (concrete).  bias: optional [N].
    spec: QuantSpec (or legacy int plane budget).  order: schedule visit
    order the weight is planned with (SCHEDULE_ORDERS).  Under tracing
    use plan_params + planned_dense_apply instead (the model layer routes
    this automatically).
    """
    spec = QuantSpec.coerce(spec)
    plan = plan_dense_weight(w, spec, order=order)
    return planned_dense_apply(plan, x, spec, w.shape[1], bias=bias,
                               activation=activation, out_dtype=out_dtype,
                               block_n=block_n, interpret=interpret,
                               fused=fused, dispatch=dispatch, order=order)


# Param-dict names whose "w" never flows through the quantized dense path
# (raw matmuls / unquantized projections) -- planning them would carry dead
# digit-plane arrays (~4x the weight bytes) through the serve step.
_NO_PLAN_KEYS = frozenset({
    "router", "frontend_proj",                      # raw matmul / unquantized
    "mix_w1", "mix_w2", "w_lora1", "w_lora2",       # rwkv6 mixing loras
    "dt_proj", "x_to_dt", "x_to_bc",                # ssm fp32 projections
})


def _plan_stack(w, spec, order: str) -> dict:
    """Plans of every [K, N] matrix of a stacked weight ``w`` [..., K, N],
    stacked with the same leading axes (so jax.lax.scan slices them
    alongside the weights).  Each plan moves to host memory as it is
    built and the stack is assembled there: stacking on the device would
    hold every plan's digit planes twice at the peak."""
    lead = w.shape[:-2]
    plans = [jax.tree.map(np.asarray, plan_dense_weight(
        w[idx], spec, use_cache=False, order=order))
        for idx in np.ndindex(*lead)]
    # schedules have data-dependent lengths: pad to the longest with exact
    # no-op entries so the stack scans cleanly
    max_steps = max(p["schedule"].shape[0] for p in plans)
    for p in plans:
        p["schedule"] = pad_schedule(p["schedule"], max_steps)
    return jax.tree.map(
        lambda *xs: jnp.asarray(np.stack(xs).reshape(lead + xs[0].shape)),
        *plans)


def plan_params(params, spec, should_plan=None, order: Optional[str] = None):
    """Attach a 'w_plan' record next to every dense weight in a param tree.

    2-D weights get a single plan; 3-D weights (layer-stacked for scan) get
    per-layer plans stacked on axis 0 so jax.lax.scan slices them alongside
    the weights.  The expert weights of an MoE layer (``w_up``,
    ``w_gate``, ``w_down`` beside its ``router``: [held, K, N], or
    [layers, held, K, N] stacked) get a plan per (layer, expert) as
    ``<name>_plan``, stacked the same way, for ``planned_experts_apply``.
    spec: QuantSpec (or legacy int plane budget).  Returns
    (new_params, planned_count).  The original tree is not mutated;
    non-dict leaves and non-dense weights pass through.

    should_plan: optional (path_tuple, w) -> bool to narrow which weights
    get plans.  The default plans every dense "w" except dicts named in
    _NO_PLAN_KEYS (known raw-matmul consumers like the MoE router).

    order: schedule visit order; None derives it from the spec's engine
    (the pallas_pipelined engine plans k_major schedules for B-block
    reuse, everything else m_major) so the plans match the order the
    engine's apply() will dispatch under.
    """
    spec = QuantSpec.coerce(spec)
    if order is None:
        order = "k_major" if spec is not None and \
            spec.impl == "pallas_pipelined" else "m_major"
    count = 0
    if should_plan is None:
        def should_plan(path, _w):
            return not (path and path[-1] in _NO_PLAN_KEYS)

    def walk(node, path):
        nonlocal count
        if not isinstance(node, dict):
            return node
        out = {k: walk(v, path + (k,)) for k, v in node.items()}
        if "router" in node:             # an MoE layer: plan its experts
            for name in _EXPERT_KEYS:
                w = node.get(name)
                if getattr(w, "ndim", 0) >= 3 and \
                        should_plan(path + (name,), w):
                    out[name + "_plan"] = _plan_stack(w, spec, order)
                    count += int(np.prod(w.shape[:-2]))
        w = node.get("w")
        ndim = getattr(w, "ndim", 0)
        if ndim not in (2, 3) or not should_plan(path, w):
            return out
        if ndim == 2:
            out["w_plan"] = plan_dense_weight(w, spec, order=order)
            count += 1
        else:                  # [L, K, N] stacked for the layer scan
            out["w_plan"] = _plan_stack(w, spec, order)
            count += w.shape[0]
        return out

    return walk(params, ()), count


# the expert weights of an MoE layer (models.moe.EXPERT_WEIGHTS)
_EXPERT_KEYS = ("w_up", "w_gate", "w_down")


def plan_tree_density(params) -> Optional[float]:
    """Aggregate plane-block density over every plan record ('w_plan',
    and the experts' '<name>_plan') in a planned param tree
    (plane-block-count weighted); None when the tree holds no plans.
    This is the measured-density input to the schedule-aware
    GemmEngine.cost / serving tier estimates."""
    nnz = total = 0

    def walk(node):
        nonlocal nnz, total
        if not isinstance(node, dict):
            return
        for key, v in node.items():
            if key.endswith("_plan") and isinstance(v, dict) and "mask" in v:
                mask = np.asarray(v["mask"])
                nnz += int(mask.sum())
                total += int(mask.size)
            else:
                walk(v)

    walk(params)
    return (nnz / total) if total else None
