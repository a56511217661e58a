"""Pluggable GemmEngine registry: one strategy object per quantized-matmul
implementation, selected *per call* by ``QuantSpec.impl`` — never by
process-global state.

Each engine exposes:

    plan(w, spec)                -> optional pre-planned weight record
    apply(plan_or_w, x, spec)    -> act((x @ w)_int * scales + bias)
    cost(m, k, n, spec)          -> coarse static cost model (dict)

Registered engines:

    ref          -- single int32 dot on the spec's quantization grid; the
                    most direct jnp reference (quantized_matmul_ref
                    semantics on a plane-bounded grid), STE-trainable.
    planes       -- bit-exact digit-plane decomposed GEMM (one int dot per
                    BW plane of spec.encoding); the kernel's jnp oracle,
                    STE-trainable.  Historical default.
    int8         -- one int8 dot_general on the same grid: the cost the
                    fused TPU kernel pays *before* plane skipping,
                    STE-trainable.
    pallas       -- the Pallas bw_gemm kernel with digit-plane block
                    skipping; dequant/bias/activation epilogue in jnp.
    pallas_fused -- bw_gemm with the epilogue fused in-kernel on the
                    VMEM-resident int32 accumulator (the serving path).
    pallas_sparse-- compacted sparse block schedules through scalar
                    prefetch (bw_gemm_sparse_fused): skipped plane-blocks
                    cost zero DMA and zero grid steps; falls back to the
                    dense fused kernel for high-density plans.
    pallas_pipelined -- the v3 double-buffered kernels on k_major
                    schedules (bw_gemm_sparse_fused_pipelined): step s+1's
                    plane gather overlaps step s's MXU pass through manual
                    DMA + semaphores, and the global k-block visit order
                    lets consecutive steps reuse the resident B block
                    without a DMA (cost reports the savings as
                    ``b_dma_elided``); falls back to the dense fused
                    kernel for high-density plans.

The kernel engines have three tiers (mirroring the old implicit routing):
a pre-planned array record (traceable under jit/scan), eager concrete
operands (plan-on-first-use, cached per parameter), and a traced-no-plan
fallback that lowers to the int8 engine — bit-identical in the integer
accumulator, so compiled-cost numbers reflect the kernelized technique.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import bw_ref
from repro.core import quant as quantlib
from .spec import IMPLS, QuantSpec

__all__ = ["GemmEngine", "register", "get_engine", "engine_names",
           "engine_for_route", "active_planes", "TRACED_INT8_ROUTE"]

# repro_gemm_dispatch_total route label of a kernel engine called under
# tracing without a plan record (lowered to one int8 dot, no kernel)
TRACED_INT8_ROUTE = "traced_int8"

_REGISTRY: Dict[str, "GemmEngine"] = {}


def register(engine: "GemmEngine") -> "GemmEngine":
    """Register a GemmEngine strategy instance under ``engine.name``."""
    if not engine.name:
        raise ValueError("engine needs a non-empty name")
    if engine.name in _REGISTRY:
        raise ValueError(f"engine {engine.name!r} already registered")
    _REGISTRY[engine.name] = engine
    return engine


def get_engine(name: str) -> "GemmEngine":
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown quant impl {name!r}; "
                         f"one of {engine_names()}") from None


def engine_names() -> tuple:
    return tuple(_REGISTRY)


def engine_for_route(route: str) -> "GemmEngine":
    """The fused kernel engine whose cost model prices the GEMM ``route``
    ('dense' | 'sparse' | 'pipelined'): the engine a measurement of that
    route calibrates."""
    for engine in _REGISTRY.values():
        if engine.uses_plans and engine.fused and engine.route == route:
            return engine
    raise ValueError(f"no fused kernel engine prices route {route!r}")


def active_planes(spec: QuantSpec) -> int:
    """MXU passes a digit-plane engine cannot structurally skip.

    Sign-magnitude encodings (ent / mbe / bitserial_sm) leave planes above
    the quantization bound all-zero, so only ``spec.planes`` passes can
    carry work.  Two's-complement bit-serial sign-extends negatives into
    the high planes, so every plane stays live.
    """
    if spec.encoding == "bitserial":
        return spec.num_digits
    return min(spec.planes, spec.num_digits)


def _is_traced(*arrays) -> bool:
    return any(isinstance(a, jax.core.Tracer) for a in arrays)


def _epilogue(y, bias, activation, out_dtype):
    if bias is not None:
        y = y + bias.astype(y.dtype)
    if activation is not None:
        from repro.kernels.bw_gemm import EPILOGUE_ACTIVATIONS
        y = EPILOGUE_ACTIVATIONS[activation](y)
    return y.astype(out_dtype)


# ---------------------------------------------------------------------------
# STE-trainable jnp matmul cores, specialized per (engine, spec, out dtype).
# custom_vjp forward = exact int GEMM on the spec grid; backward =
# straight-through float gradient.  The lru_cache keys on the frozen spec,
# so two engines with different specs coexist without interference.
# ---------------------------------------------------------------------------

def _quantize_operands(x, w, spec: QuantSpec):
    act_axis = -1 if spec.act_quant == "per_token" else None
    qx, sx = quantlib.quantize_for_spec(x.astype(jnp.float32), spec,
                                        axis=act_axis)
    qw, sw = quantlib.quantize_for_spec(w.astype(jnp.float32), spec, axis=0)
    return qx, sx, qw, sw


@functools.lru_cache(maxsize=None)
def _ste_matmul(kind: str, spec: QuantSpec, dtype_name: str):
    """custom_vjp quantized matmul specialized on (engine kind, spec)."""
    out_dtype = jnp.dtype(dtype_name)

    def impl(x, w):
        qx, sx, qw, sw = _quantize_operands(x, w, spec)
        x2 = qx.reshape(-1, qx.shape[-1])
        if kind == "int8":
            acc = jax.lax.dot_general(
                x2.astype(jnp.int8), qw, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
        elif kind == "ref":
            acc = jax.lax.dot_general(
                x2.astype(jnp.int32), qw.astype(jnp.int32),
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
        else:                            # "planes": exact digit-plane GEMM
            acc = bw_ref.bw_matmul_jnp(x2, qw, spec.encoding, spec.bits)
        acc = acc.reshape(*qx.shape[:-1], qw.shape[-1])
        return (acc.astype(jnp.float32) * (sx * sw)).astype(out_dtype)

    @jax.custom_vjp
    def f(x, w):
        return impl(x, w)

    def fwd(x, w):
        return impl(x, w), (x, w)

    def bwd(res, g):
        x, w = res
        gf = g.astype(jnp.float32)
        xf = x.astype(jnp.float32).reshape(-1, x.shape[-1])
        dx = (gf.reshape(-1, gf.shape[-1]) @ w.astype(jnp.float32).T
              ).reshape(x.shape).astype(x.dtype)
        dw = (xf.T @ gf.reshape(-1, gf.shape[-1])).astype(w.dtype)
        return dx, dw

    f.defvjp(fwd, bwd)
    return f


# ---------------------------------------------------------------------------
# Engine strategies
# ---------------------------------------------------------------------------

# Nominal pricing bandwidths for predict_seconds (bytes/s).  Only the
# *relative* cost across engines matters for routing; the absolute scale
# is what obs.calibrate.CostCalibrator measures drift against.  ICI
# matches launch.roofline.ICI_BW so the cost seams price a sharded
# reduce identically; serving.tiers aliases both.
NOMINAL_HBM_BPS = 300e9
NOMINAL_ICI_BPS = 50e9


class GemmEngine:
    """Strategy interface for one quantized-GEMM implementation."""

    name: str = ""
    uses_plans: bool = False      # consumes pre-planned weight records
    route: Optional[str] = None   # the kernel route its cost model prices

    def plan(self, w, spec: QuantSpec):
        """Pre-plan a dense weight [K, N] for repeated application.

        Returns an engine-specific plan record, or None when the engine
        has no planning step (jnp engines re-quantize per call).
        """
        return None

    def apply(self, plan_or_w, x, spec: QuantSpec, *, n_out: int = None,
              bias=None, activation: Optional[str] = None,
              out_dtype=jnp.float32, interpret: Optional[bool] = None):
        """y = act((x @ w)_int * scales + bias), cast to out_dtype.

        plan_or_w: the raw float weight [K, N], or a record from plan()
        (kernel engines only; then n_out — the original N — is required,
        because the record carries only padded shapes).
        """
        raise NotImplementedError

    def cost(self, m: int, k: int, n: int, spec: QuantSpec, *,
             density: Optional[float] = None, plan=None,
             shards=None) -> dict:
        """Schedule-aware cost model of one [M,K]x[K,N] call (the
        autotuning / tier-routing seam).

        density: fraction of non-zero plane blocks over *all* digit
        planes (``PlannedOperand.density()``); ``plan`` (a plan record or
        PlannedOperand) supplies the measured value directly.  When
        neither is given, the estimate assumes the spec's active planes
        are fully dense — the pre-sparsity upper bound.

        shards: optional ``(s_data, s_model)`` mesh shard grid.  The
        counters then describe one device's shard — the K axis divided
        ``s_data`` ways, the N axis (kernel rows) ``s_model`` ways, M
        (tokens) replicated — and ``collective_bytes`` prices the
        cross-shard ``psum`` of the partial int32 accumulator
        (per-device ring traffic; 0 when unsharded or K is unsplit).
        Serving orientation throughout: tokens on M, output features on
        N, matching ``serving.tiers.step_cost``.

        Keys: ``mxu_passes`` (structural per-element pass multiplier),
        ``int_macs`` (integer MACs actually executed — density-scaled on
        the kernel engines), ``acc_hbm_bytes`` (epilogue-placement HBM
        round-trip), ``grid_steps`` (Pallas grid iterations; 0 for the
        jnp engines), ``dma_bytes`` (HBM block traffic the BlockSpecs /
        manual copies imply), ``b_dma_elided`` (B-block copies the
        k_major pipelined schedule order skips by operand reuse — already
        subtracted from ``dma_bytes``; 0 everywhere else) and
        ``collective_bytes`` (see above).
        """
        from repro.parallel.collectives import (gemm_collective_bytes,
                                                normalize_shards)
        s_data, s_model = normalize_shards(shards)
        if (s_data, s_model) == (1, 1):
            out = self._cost1(m, k, n, spec, density=density, plan=plan)
            out["collective_bytes"] = 0
            return out
        if density is None:
            density = self._plan_density(plan)
        # per-shard counters: the plan record describes the *global*
        # schedule, so only its measured density transfers to a shard
        out = self._cost1(m, -(-k // s_data), -(-n // s_model), spec,
                          density=density, plan=None)
        out["collective_bytes"] = gemm_collective_bytes(m, n, s_data,
                                                        s_model)
        return out

    def predict_seconds(self, m: int, k: int, n: int, spec: QuantSpec, *,
                        density: Optional[float] = None, plan=None,
                        shards=None, design: str = "tpu") -> float:
        """cost() priced into seconds on a ``core.hwmodel`` design.

        The single pricing seam shared by ``serving.tiers
        .estimate_step_time`` and ``obs.calibrate`` — compute at the
        design's peak integer throughput, the epilogue accumulator
        round-trip at ``NOMINAL_HBM_BPS``, cross-shard collectives at
        ``NOMINAL_ICI_BPS``.  Absolute seconds are nominal; the
        ``CostCalibrator`` tracks per-impl drift vs measured timings.
        """
        from repro.core import hwmodel as hw
        c = self.cost(m, k, n, spec, density=density, plan=plan,
                      shards=shards)
        ops_per_s = hw.peak_tops(hw.TABLE7[design]) * 1e12
        return (2.0 * c["int_macs"] / ops_per_s
                + c["acc_hbm_bytes"] / NOMINAL_HBM_BPS
                + c["collective_bytes"] / NOMINAL_ICI_BPS)

    def _cost1(self, m: int, k: int, n: int, spec: QuantSpec, *,
               density: Optional[float] = None, plan=None) -> dict:
        """Single-device counters (engines override this, not cost())."""
        passes = self._passes(spec)
        acc = self._acc_hbm_bytes(m, n)
        return {
            "mxu_passes": passes,
            "int_macs": passes * m * k * n,
            "acc_hbm_bytes": acc,
            "grid_steps": 0,     # jnp engines: one fused XLA dot, no grid
            "dma_bytes": m * k + k * n + 4 * m * n + acc,
            "b_dma_elided": 0,
        }

    @staticmethod
    def _plan_density(plan) -> Optional[float]:
        if plan is None:
            return None
        mask = plan["mask"] if isinstance(plan, dict) else plan.mask
        import numpy as np
        return float(np.asarray(mask).mean())

    def _passes(self, spec: QuantSpec) -> int:
        return 1

    def _acc_hbm_bytes(self, m: int, n: int) -> int:
        return 0                 # jnp engines: XLA fuses the epilogue


class _JnpEngine(GemmEngine):
    """Shared driver for the STE-trainable pure-jnp engines."""

    kind: str = ""

    def apply(self, plan_or_w, x, spec, *, n_out=None, bias=None,
              activation=None, out_dtype=jnp.float32, interpret=None):
        if isinstance(plan_or_w, dict):
            raise TypeError(f"engine {self.name!r} takes raw weights, not "
                            f"plan records")
        y = _ste_matmul(self.kind, spec, jnp.dtype(out_dtype).name)(
            x, plan_or_w)
        return _epilogue(y, bias, activation, out_dtype)


class RefEngine(_JnpEngine):
    name = "ref"
    kind = "ref"


class PlanesEngine(_JnpEngine):
    name = "planes"
    kind = "planes"

    def _passes(self, spec):
        return active_planes(spec)


class Int8Engine(_JnpEngine):
    name = "int8"
    kind = "int8"


class PallasEngine(GemmEngine):
    """bw_gemm kernel path, dequant/bias/activation epilogue in jnp."""

    name = "pallas"
    uses_plans = True
    fused = False
    route = "dense"
    dispatch = "dense"           # sparse-schedule routing (pallas_sparse)
    order = "m_major"            # schedule visit order the plans carry

    def plan(self, w, spec):
        from repro.kernels import ops
        return ops.plan_dense_weight(w, spec, order=self.order)

    def apply(self, plan_or_w, x, spec, *, n_out=None, bias=None,
              activation=None, out_dtype=jnp.float32, interpret=None):
        from repro.kernels import ops
        if isinstance(plan_or_w, dict):       # pre-planned: jit/scan-safe
            if n_out is None:
                raise ValueError("n_out is required with a plan record "
                                 "(the record only carries padded shapes)")
            return ops.planned_dense_apply(
                plan_or_w, x, spec, n_out, bias=bias, activation=activation,
                out_dtype=out_dtype, interpret=interpret, fused=self.fused,
                dispatch=self.dispatch, order=self.order)
        w = plan_or_w
        if _is_traced(x, w):
            # traced without a plan (dry-run cost analysis, jit'd train
            # steps): lower to the int8 engine -- one int8 dot is the
            # kernel's cost-representative, bit-exact lowering.  Counted
            # under its own route, so a serving path that should run the
            # kernels can prove it never took this lowering.
            from repro.obs import trace as obs_trace
            if obs_trace.enabled():
                ops.count_dispatch(TRACED_INT8_ROUTE)
            return get_engine("int8").apply(
                w, x, spec, bias=bias, activation=activation,
                out_dtype=out_dtype)
        return ops.quantized_dense(
            x, w, spec, bias=bias, activation=activation,
            out_dtype=out_dtype, interpret=interpret, fused=self.fused,
            dispatch=self.dispatch, order=self.order)

    def _passes(self, spec):
        return active_planes(spec)

    def _acc_hbm_bytes(self, m, n):
        # unfused: int32 accumulator is written to HBM, then re-read (and
        # the float result written) by the jnp epilogue
        return 3 * 4 * m * n

    # -- schedule-aware cost -------------------------------------------------

    def _geometry(self, m, k, n, spec, plan=None):
        """(bm, bk, bn, mb, kb, nb) for the cost model.

        With a plan record / PlannedOperand in hand the block grid is
        read off its arrays (the plan may have been built under different
        block sizes than select_block_sizes would pick today — e.g. an
        autotune-cache update between planning and costing), so the
        counters describe the schedule that will actually run."""
        from repro.kernels import ops
        bm, bk, bn = ops.select_block_sizes(m, k, n, spec)
        mb, kb = -(-m // bm), -(-k // bk)
        if plan is not None:
            mask = plan.get("mask") if isinstance(plan, dict) \
                else getattr(plan, "mask", None)
            digits = plan.get("digits") if isinstance(plan, dict) \
                else getattr(plan, "digits", None)
            if getattr(mask, "ndim", 0) == 3 and \
                    getattr(digits, "ndim", 0) == 3:
                _, mb, kb = mask.shape
                bm = digits.shape[1] // mb
                bk = digits.shape[2] // kb
        return (bm, bk, bn, mb, kb, -(-n // bn))

    def _cost1(self, m, k, n, spec, *, density=None, plan=None):
        """Dense predicated kernel: the full (M/bm, N/bn, K/bk) grid is
        walked and every digit plane of every block is DMA'd; only the
        *MXU passes* of empty plane-blocks are skipped (pl.when)."""
        if density is None:
            density = self._plan_density(plan)
        bm, bk, bn, mb, kb, nb = self._geometry(m, k, n, spec, plan)
        bwn = spec.num_digits
        if density is None:
            density = active_planes(spec) / bwn
        acc = self._acc_hbm_bytes(m, n)
        return {
            "mxu_passes": self._passes(spec),
            # logical MACs actually executed: density * all-planes work.
            # (Kept un-padded so jnp- and kernel-engine estimates stay
            # comparable for tier routing; the block-quantized reality
            # lives in grid_steps / dma_bytes.)
            "int_macs": int(density * bwn * m * k * n),
            "acc_hbm_bytes": acc,
            "grid_steps": mb * nb * kb,
            # per grid step: all BW digit planes of the A block + the B
            # block (int8); plus one float out block per (m, n) tile
            "dma_bytes": int(mb * nb * kb * (bwn * bm * bk + bk * bn)
                             + mb * nb * bm * bn * 4 + acc),
            "b_dma_elided": 0,
        }


class PallasFusedEngine(PallasEngine):
    """bw_gemm with the epilogue fused onto the VMEM-resident accumulator."""

    name = "pallas_fused"
    fused = True

    def _acc_hbm_bytes(self, m, n):
        return 0                 # only the final float block leaves VMEM


class PallasSparseEngine(PallasFusedEngine):
    """Compacted-schedule sparse dispatch (scalar prefetch): skipped
    plane-blocks cost zero DMA and zero grid steps.

    ``apply`` routes through ``planned_dense_apply(dispatch='auto')``: the
    sparse kernels when the plan's density proxy clears
    ``ops.SPARSE_DENSITY_THRESHOLD`` (or the autotune cache says so), the
    dense fused kernel otherwise — high-density plans would *pay* for
    compaction, since the dense grid retires all BW planes of a block in
    one step."""

    name = "pallas_sparse"
    route = "sparse"
    dispatch = "auto"

    @staticmethod
    def _plan_schedule(plan, min_cols: int = 6):
        """The plan's concrete [L, >=min_cols] schedule, or None (no plan,
        stacked per-layer plans, or a schedule missing the columns the
        caller's counters need)."""
        if plan is None:
            return None
        sched = plan.get("schedule") if isinstance(plan, dict) \
            else getattr(plan, "schedule", None)
        if sched is None:
            return None
        import numpy as np
        sched = np.asarray(sched)
        # stacked per-layer plans ([layers, L, 9]) fall back to the
        # density estimate: per-layer counters would need per-layer shapes
        if sched.ndim != 2 or sched.shape[1] < min_cols:
            return None
        return sched

    def _cost1(self, m, k, n, spec, *, density=None, plan=None):
        if density is None:
            density = self._plan_density(plan)
        bm, bk, bn, mb, kb, nb = self._geometry(m, k, n, spec, plan)
        bwn = spec.num_digits
        if density is None:
            density = active_planes(spec) / bwn
        sched = self._plan_schedule(plan)
        if sched is not None:
            # measured: the schedule length (nnz + sentinels + padding) IS
            # the walk — the estimate below would under-count whenever
            # sentinel/padding steps outnumber the rounding slack
            steps = sched.shape[0]
        else:
            nnz = density * bwn * mb * kb
            # every m-block row is visited at least once (zero-weight
            # sentinels keep empty output rows written)
            steps = max(int(round(nnz)), mb)
        return {
            "mxu_passes": self._passes(spec),
            "int_macs": int(density * bwn * m * k * n),
            "acc_hbm_bytes": 0,
            "grid_steps": steps * nb,
            # per scheduled step: ONE digit plane block + the B block;
            # plus one float out block per (m, n) tile
            "dma_bytes": int(steps * nb * (bm * bk + bk * bn)
                             + mb * nb * bm * bn * 4),
            "b_dma_elided": 0,
        }


class PallasPipelinedEngine(PallasSparseEngine):
    """v3 double-buffered schedule pipelining on k_major schedules.

    ``plan`` builds schedules in k_major order (global k-block walk:
    consecutive steps share a B block across output rows, so the kernel
    reuses the resident VMEM buffer instead of re-DMAing it) and ``apply``
    routes through ``planned_dense_apply(dispatch='auto',
    order='k_major')`` — the pipelined kernels when the density proxy (or
    a measured autotune winner) says sparse pays, the dense fused kernel
    otherwise.

    The cost model is *overlap-aware*: the double buffering issues step
    s+1's gather under step s's MXU pass, so ``dma_bytes`` counts only
    the copies actually issued — real scheduled plane-blocks (sentinels
    and padding issue nothing) plus one B fetch per k-block *run* rather
    than per step; the B copies saved by the reuse are reported as
    ``b_dma_elided``.  With a plan record in hand both counters are exact
    (read off the schedule's B_FETCH column); without one they are
    estimated from the density.
    """

    name = "pallas_pipelined"
    route = "pipelined"
    order = "k_major"

    def _cost1(self, m, k, n, spec, *, density=None, plan=None):
        if density is None:
            density = self._plan_density(plan)
        bm, bk, bn, mb, kb, nb = self._geometry(m, k, n, spec, plan)
        bwn = spec.num_digits
        if density is None:
            density = active_planes(spec) / bwn
        sched = self._plan_schedule(plan, 9)   # B_FETCH column required
        if sched is not None:             # measured: exact schedule counts
            steps = sched.shape[0]
            real = int((sched[:, 3] != 0).sum())      # weight column
            b_fetches = int(sched[:, 8].sum())        # B_FETCH column
        else:                             # estimated from density
            real = max(int(round(density * bwn * mb * kb)), 0)
            steps = max(real, mb)         # sentinels keep empty rows alive
            # one B fetch per k-block visited (the k_major walk touches
            # each k-block in one contiguous run per j iteration)
            b_fetches = min(kb, real)
        return {
            "mxu_passes": self._passes(spec),
            "int_macs": int(density * bwn * m * k * n),
            "acc_hbm_bytes": 0,
            "grid_steps": steps * nb,
            # per real step: ONE digit plane block; B blocks only on the
            # k-block boundaries the schedule did not elide; one float out
            # block per (m, n) tile (sentinel rows included — their zeros
            # are still flushed)
            "dma_bytes": int(real * nb * bm * bk + b_fetches * nb * bk * bn
                             + mb * nb * bm * bn * 4),
            "b_dma_elided": max(real - b_fetches, 0) * nb,
        }


for _engine in (RefEngine(), PlanesEngine(), Int8Engine(), PallasEngine(),
                PallasFusedEngine(), PallasSparseEngine(),
                PallasPipelinedEngine()):
    register(_engine)

assert engine_names() == IMPLS, (engine_names(), IMPLS)
