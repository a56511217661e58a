"""Bit-weight decomposed INT8 GEMM Pallas kernel with digit-plane block
skipping -- the TPU-native adaptation of the paper's sparse-encoded TPE.

The multiplicand A is pre-encoded (EN-T / MBE, repro.core.encodings) into BW
radix-4 digit planes, digits in {-2..2}:

    C = sum_bw  (digits[bw] @ B) * 4**bw          (paper Eq. (4)/(5))

The hardware insight "skip zero encoded partial products" has no per-element
analogue on the MXU (a systolic matmul retires a full tile per pass), so it
is adapted to *block granularity*: a per-(plane, m-block, k-block) occupancy
mask is computed when the operand is encoded, and the kernel predicates the
whole MXU pass of a block with ``pl.when`` -- an all-zero digit-plane block
costs neither the dot product nor the accumulate.  For LLM weight
distributions the high-weight planes (4^2, 4^3) are sparse exactly as the
paper's Table III predicts (avg 2.2/4 non-zero digits), and ops.py's
magnitude-ordered row permutation concentrates the non-zero high-plane
digits into few row blocks, turning element sparsity into block sparsity.

The deferred shift of OPT2 maps naturally: the per-plane scale 4**bw is
applied once per block *after* the MXU pass (on the int32 accumulator), not
per partial product.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs import metrics as obs_metrics

__all__ = ["bw_gemm", "bw_gemm_fused", "bw_gemm_experts", "bw_gemm_sparse",
           "bw_gemm_sparse_fused", "bw_gemm_sparse_pipelined",
           "bw_gemm_sparse_fused_pipelined", "EPILOGUE_ACTIVATIONS",
           "SCHED_COLS"]

# Column layout of the compacted sparse block schedule (int32 [L, 9]): one
# row per non-zero (plane, m-block, k-block) of the occupancy mask, plus one
# zero-weight sentinel per empty m-block row so every output block is
# visited and written.  WEIGHT is the deferred-shift plane scale
# radix**plane (0 for sentinels/padding); FIRST / LAST flag each output
# row's overall first/last scheduled step, driving accumulator init and the
# (fused) epilogue.  The last three columns exist for the *pipelined*
# kernels and are baked in by ops.build_schedule's annotation pass:
# D_SLOT / B_SLOT name which of the two double-buffered VMEM scratch slots
# a step's digit plane / B block live in (alternating per fetch), and
# B_FETCH is 1 only when the step's k-block differs from the currently
# resident one — consecutive same-k steps reuse the resident B buffer and
# skip the DMA entirely (the "k_major" schedule order maximises those
# runs).  The v2 kernels (bw_gemm_sparse[_fused]) read only the first six
# columns.
SCHED_COLS = {"plane": 0, "row": 1, "kblk": 2, "weight": 3,
              "first": 4, "last": 5, "d_slot": 6, "b_slot": 7, "b_fetch": 8}
(_PLANE, _ROW, _KBLK, _WEIGHT, _FIRST, _LAST,
 _DSLOT, _BSLOT, _BFETCH) = range(9)
# The kernels read the schedule row-major from a flat int32 [L * _NCOLS]
# SMEM array.  A 2-D [L, 9] array is padded to 128 words a row there, so
# the v5e's 1 MiB of SMEM would refuse any schedule past about 2k steps.
_NCOLS = len(SCHED_COLS)

# Activations the fused epilogue can apply on the dequantised accumulator.
# Single source of truth: repro.models.layers.activation resolves names
# from this mapping too.
EPILOGUE_ACTIVATIONS = {
    None: lambda x: x,
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


# set when a dense or expert kernel is traced: which tile each weight took
_M_TILE_BYTES = obs_metrics.get_registry().gauge("repro_bw_gemm_tile_bytes")
_M_GRID_STEPS = obs_metrics.get_registry().gauge("repro_bw_gemm_grid_steps")


def _record_tile(kernel: str, grid, *, n_planes: int, m: int, k: int,
                 block_m: int, block_k: int) -> None:
    """Record the tile a dense-grid kernel streams: digit bytes a grid
    step and steps a call, labelled by the weight's kernel M and K."""
    labels = dict(kernel=kernel, m=m, k=k)
    _M_TILE_BYTES.labels(**labels).set(n_planes * block_m * block_k)
    _M_GRID_STEPS.labels(**labels).set(math.prod(grid))


def _check_dims(fn: str, m: int, k: int, k2: int, n: int, block_m: int,
                block_n: int, block_k: int) -> None:
    """Real validation, not ``assert`` (which vanishes under ``python -O``
    and reports nothing useful)."""
    if k != k2:
        raise ValueError(
            f"{fn}: digits have inner dim K={k} but b has K={k2} rows")
    for dim, name, blk, bname in ((m, "M", block_m, "block_m"),
                                  (n, "N", block_n, "block_n"),
                                  (k, "K", block_k, "block_k")):
        if dim % blk:
            raise ValueError(
                f"{fn}: {name}={dim} is not a multiple of {bname}={blk}; "
                f"pad the operands first (ops.planned_dense_apply does "
                f"this)")


def _check_mask(fn: str, mask, bw_n: int, mb: int, kb: int) -> None:
    if mask.shape != (bw_n, mb, kb):
        raise ValueError(
            f"{fn}: mask shape {tuple(mask.shape)} != expected "
            f"({bw_n}, {mb}, {kb}) = [BW, M/block_m, K/block_k]")


def _check_schedule(fn: str, schedule, *, annotated: bool = False) -> None:
    want = len(SCHED_COLS) if annotated else 6
    ok = (schedule.ndim == 2
          and (schedule.shape[1] == want if annotated
               else schedule.shape[1] >= want))
    if not ok:
        rel = "exactly" if annotated else "at least"
        raise ValueError(
            f"{fn}: schedule must be a 2-D int array with {rel} {want} "
            f"columns (SCHED_COLS), got shape {tuple(schedule.shape)}")


def _check_epilogue(fn: str, activation, scale, scale_shape, scale_n,
                    n: int) -> None:
    if activation not in EPILOGUE_ACTIVATIONS:
        raise ValueError(
            f"{fn}: unknown activation {activation!r}; expected one of "
            f"{sorted(a for a in EPILOGUE_ACTIVATIONS if a)} or None")
    if scale.shape != scale_shape:
        raise ValueError(
            f"{fn}: scale shape {tuple(scale.shape)} != expected "
            f"{scale_shape}")
    if scale_n is not None and scale_n.shape != (1, n):
        raise ValueError(
            f"{fn}: scale_n shape {tuple(scale_n.shape)} != expected "
            f"(1, {n})")


def _int_dot(d, b):
    """int8 x int8 -> int32 on the MXU (exact: no int32 operand widening,
    which the TPU's matmul unit does not take)."""
    return jax.lax.dot_general(d, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _flat_mask(mask):
    """bool [BW, Mb, Kb] -> int32 [BW*Mb*Kb] for scalar prefetch (SMEM
    holds 32-bit scalars, and a 1-D array is not tile-padded there)."""
    return jnp.asarray(mask).astype(jnp.int32).reshape(-1)


def _flat_schedule(schedule):
    """int [L, >=6] schedule -> int32 [L * _NCOLS] (missing pipelined
    columns zero-filled; the v2 kernels never read them)."""
    sched = jnp.asarray(schedule, jnp.int32)
    sched = jnp.pad(sched, ((0, 0), (0, _NCOLS - sched.shape[1])))
    return sched.reshape(-1)


def _cell(sched, step, col):
    """Schedule entry (step, col) of a flat row-major schedule ref."""
    return sched[step * _NCOLS + col]


def _kernel(mask_ref, d_ref, b_ref, o_ref, *, n_planes: int, radix: int,
            mb: int, kb: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
    b = b_ref[...]
    i, kk = pl.program_id(0), pl.program_id(2)
    for bw in range(n_planes):          # unrolled: BW is small and static
        weight = radix ** bw

        @pl.when(mask_ref[(bw * mb + i) * kb + kk] != 0)
        def _plane(bw=bw, weight=weight):
            pp = _int_dot(d_ref[bw], b)
            # deferred shift (OPT2): one scale per plane-block, post-MXU
            o_ref[...] += pp * weight


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "radix", "interpret"))
def bw_gemm(digits, b, mask, *, block_m: int = 128, block_n: int = 128,
            block_k: int = 256, radix: int = 4, interpret: bool = False):
    """C[M,N] = sum_bw (digits[bw] @ B) * radix**bw with block skipping.

    digits: int8 [BW, M, K] encoded planes of the multiplicand.
    b:      int8 [K, N].
    mask:   bool [BW, M//block_m, K//block_k] plane-block occupancy.
    """
    bw_n, m, k = digits.shape
    k2, n = b.shape
    _check_dims("bw_gemm", m, k, k2, n, block_m, block_n, block_k)
    _check_mask("bw_gemm", mask, bw_n, m // block_m, k // block_k)
    grid = (m // block_m, n // block_n, k // block_k)
    kernel = functools.partial(_kernel, n_planes=bw_n, radix=radix,
                               mb=grid[0], kb=grid[2])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # plane-block mask: scalar-prefetched into SMEM, read by pl.when
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            # all BW planes of the (i, kk) block of A
            pl.BlockSpec((bw_n, block_m, block_k),
                         lambda i, j, kk, msk: (0, i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk, msk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, kk, msk: (i, j)),
    )
    _record_tile("bw_gemm", grid, n_planes=bw_n, m=m, k=k,
                 block_m=block_m, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(_flat_mask(mask), digits, b)


def _fused_kernel(mask_ref, d_ref, b_ref, scale_ref, scale_n_ref, bias_ref,
                  o_ref, acc_ref, *, n_planes: int, radix: int, mb: int,
                  kb: int, activation, has_bias: bool, has_scale_n: bool):
    """bw_gemm with the dequant epilogue folded in.

    The int32 accumulator lives in a VMEM scratch block revisited across the
    K grid; only the final float result is written to the output in HBM, so
    the accumulator never round-trips through HBM.  On the last K step the
    epilogue applies scale (act scale x per-channel weight scale; with a
    second per-column vector when the act scale is per-token), optional
    bias, and optional activation -- all on the register/VMEM-resident block.
    """
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
    b = b_ref[...]
    i, kk = pl.program_id(0), pl.program_id(2)
    for bw in range(n_planes):          # unrolled: BW is small and static
        weight = radix ** bw

        @pl.when(mask_ref[(bw * mb + i) * kb + kk] != 0)
        def _plane(bw=bw, weight=weight):
            acc_ref[...] += _int_dot(d_ref[bw], b) * weight

    @pl.when(kk == kb - 1)
    def _epilogue():
        s = scale_ref[...]
        if has_scale_n:
            # combine the two scale vectors first so the accumulator is
            # multiplied by one float, bit-matching the jnp oracle's
            # `acc * (sx * sw)` ordering
            s = s * scale_n_ref[...]
        y = acc_ref[...].astype(jnp.float32) * s
        if has_bias:
            y = y + bias_ref[...]
        y = EPILOGUE_ACTIVATIONS[activation](y)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "block_k", "radix", "interpret", "activation",
    "out_dtype"))
def bw_gemm_fused(digits, b, mask, scale, bias=None, scale_n=None, *,
                  block_m: int = 128, block_n: int = 128, block_k: int = 256,
                  radix: int = 4, interpret: bool = False, activation=None,
                  out_dtype=jnp.float32):
    """C = act((sum_bw (digits[bw] @ B) * radix**bw) * scales + bias).

    digits: int8 [BW, M, K] encoded planes of the multiplicand.
    b:      int8 [K, N].
    mask:   bool [BW, M//block_m, K//block_k] plane-block occupancy.
    scale:  f32 [M, 1] per-row scale: weight channels on M, as in the
            planned-weight layout.
    bias:   optional f32 [M, 1].
    scale_n: optional f32 [1, N] per-column scale.  This is how per-token
            activation scales reach the fused epilogue: the planned-weight
            layout puts tokens on the kernel N axis, so a per-token act
            scale is a per-column vector multiplied into the per-channel
            row scale in-kernel.
    """
    bw_n, m, k = digits.shape
    k2, n = b.shape
    _check_dims("bw_gemm_fused", m, k, k2, n, block_m, block_n, block_k)
    _check_mask("bw_gemm_fused", mask, bw_n, m // block_m, k // block_k)
    _check_epilogue("bw_gemm_fused", activation, scale, (m, 1), scale_n, n)
    vec_spec = pl.BlockSpec((block_m, 1), lambda i, j, kk, msk: (i, 0))
    col_spec = pl.BlockSpec((1, block_n), lambda i, j, kk, msk: (0, j))
    has_scale_n = scale_n is not None
    if not has_scale_n:                 # placeholder so arity is static
        scale_n = jnp.ones((1, n), jnp.float32)
    has_bias = bias is not None
    if not has_bias:                    # placeholder so arity is static
        bias = jnp.zeros_like(scale)
    grid = (m // block_m, n // block_n, k // block_k)
    kernel = functools.partial(_fused_kernel, n_planes=bw_n, radix=radix,
                               mb=grid[0], kb=grid[2], activation=activation,
                               has_bias=has_bias, has_scale_n=has_scale_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bw_n, block_m, block_k),
                         lambda i, j, kk, msk: (0, i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk, msk: (kk, j)),
            vec_spec,
            col_spec,
            vec_spec,
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, kk, msk: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
    )
    _record_tile("bw_gemm_fused", grid, n_planes=bw_n, m=m, k=k,
                 block_m=block_m, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.dtype(out_dtype)),
        interpret=interpret,
    )(_flat_mask(mask), digits, b, scale.astype(jnp.float32),
      scale_n.astype(jnp.float32), bias.astype(jnp.float32))


def _experts_kernel(mask_ref, count_ref, d_ref, b_ref, scale_ref,
                    scale_n_ref, o_ref, acc_ref, *, n_planes: int, radix: int,
                    mb: int, kb: int, block_n: int, activation,
                    has_scale_n: bool):
    """_fused_kernel with the expert as the leading grid axis.  Token
    blocks at or past the expert's routed count skip their MXU passes
    (their accumulator stays zero, and the caller never reads them)."""
    h, i, j, kk = (pl.program_id(a) for a in range(4))

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
    b = b_ref[...]
    live = j * block_n < count_ref[h]
    for bw in range(n_planes):          # unrolled: BW is small and static
        weight = radix ** bw

        @pl.when(jnp.logical_and(
            live, mask_ref[((h * n_planes + bw) * mb + i) * kb + kk] != 0))
        def _plane(bw=bw, weight=weight):
            acc_ref[...] += _int_dot(d_ref[bw], b) * weight

    @pl.when(kk == kb - 1)
    def _epilogue():
        s = scale_ref[...]
        if has_scale_n:
            s = s * scale_n_ref[...]
        y = EPILOGUE_ACTIVATIONS[activation](acc_ref[...].astype(jnp.float32)
                                             * s)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "block_k", "radix", "interpret", "activation",
    "out_dtype"))
def bw_gemm_experts(digits, b, mask, scale, counts, scale_n=None, *,
                    block_m: int = 128, block_n: int = 128,
                    block_k: int = 256, radix: int = 4,
                    interpret: bool = False, activation=None,
                    out_dtype=jnp.float32):
    """C[h] = act((sum_bw (digits[h, bw] @ B[h]) * radix**bw) * scales),
    one grouped call over a layer's held experts: the expert is the
    leading grid axis, and each grid step's ``index_map`` picks that
    expert's planes and token columns.

    digits:  int8 [H, BW, M, K] planes of each expert's weight (kernel
             rows = output channels).
    b:       int8 [H, K, N]: each expert's gathered tokens on N, its
             routed ones first.
    mask:    bool [H, BW, M//block_m, K//block_k] plane-block occupancy.
    scale:   f32 [H, M, 1] per-channel scales (act scale folded in when
             it is per tensor).
    counts:  int32 [H]: routed tokens of each expert.  Token blocks past
             the count skip their MXU passes, and their B block is not
             fetched again; their output is zero.
    scale_n: optional f32 [H, 1, N] per-token act scales.
    """
    n_exp, bw_n, m, k = digits.shape
    n_exp2, k2, n = b.shape
    _check_dims("bw_gemm_experts", m, k, k2, n, block_m, block_n, block_k)
    if n_exp2 != n_exp or mask.shape != (n_exp, bw_n, m // block_m,
                                         k // block_k):
        raise ValueError(
            f"bw_gemm_experts: {n_exp} experts of digits, {n_exp2} of b, "
            f"mask {tuple(mask.shape)}; expected "
            f"({n_exp}, {bw_n}, {m // block_m}, {k // block_k})")
    for name, arr, want in (("scale", scale, (n_exp, m, 1)),
                            ("counts", counts, (n_exp,))):
        if arr.shape != want:
            raise ValueError(f"bw_gemm_experts: {name} shape "
                             f"{tuple(arr.shape)} != {want}")
    if activation not in EPILOGUE_ACTIVATIONS:
        raise ValueError(f"bw_gemm_experts: unknown activation "
                         f"{activation!r}")
    has_scale_n = scale_n is not None
    if not has_scale_n:                 # placeholder so arity is static
        scale_n = jnp.ones((n_exp, 1, n), jnp.float32)
    elif scale_n.shape != (n_exp, 1, n):
        raise ValueError(f"bw_gemm_experts: scale_n shape "
                         f"{tuple(scale_n.shape)} != {(n_exp, 1, n)}")
    grid = (n_exp, m // block_m, n // block_n, k // block_k)
    kernel = functools.partial(
        _experts_kernel, n_planes=bw_n, radix=radix, mb=grid[1], kb=grid[3],
        block_n=block_n, activation=activation, has_scale_n=has_scale_n)

    def token_block(h, j, cnt):
        # past the routed tokens, stay on the last block that holds one
        return jnp.minimum(j, jnp.maximum(cnt[h] - 1, 0) // block_n)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # plane-block mask and routed counts: scalar-prefetched into SMEM
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bw_n, block_m, block_k),
                         lambda h, i, j, kk, msk, cnt: (h, 0, i, kk)),
            pl.BlockSpec((None, block_k, block_n),
                         lambda h, i, j, kk, msk, cnt:
                         (h, kk, token_block(h, j, cnt))),
            pl.BlockSpec((None, block_m, 1),
                         lambda h, i, j, kk, msk, cnt: (h, i, 0)),
            pl.BlockSpec((None, 1, block_n),
                         lambda h, i, j, kk, msk, cnt: (h, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, block_m, block_n),
                               lambda h, i, j, kk, msk, cnt: (h, i, j)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
    )
    _record_tile("bw_gemm_experts", grid, n_planes=bw_n, m=m, k=k,
                 block_m=block_m, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_exp, m, n), jnp.dtype(out_dtype)),
        interpret=interpret,
        name="bw_gemm_experts",
    )(_flat_mask(mask), counts.astype(jnp.int32), digits, b,
      scale.astype(jnp.float32), scale_n.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Sparse dispatch: compacted block schedules via scalar prefetch
# ---------------------------------------------------------------------------
# The dense kernels above *predicate* an empty plane-block (pl.when skips the
# MXU pass) but still DMA every BW plane of every block and still walk the
# full (M/bm, N/bn, K/bk) grid.  The kernels below consume a compacted
# schedule (SCHED_COLS) through pltpu.PrefetchScalarGridSpec instead: the
# grid is (N/bn, L) with L = nnz blocks (+ one sentinel per empty row), the
# digits BlockSpec index_map gathers only the single plane a step actually
# needs, and the deferred-shift weight is looked up from the schedule -- an
# all-zero plane-block costs neither bandwidth nor a grid iteration.  The
# schedule is ordered by m-block row, so each output block is visited in
# consecutive steps (TPU-legal accumulation: the block stays VMEM-resident
# between FIRST and LAST and is flushed exactly once).


def _sparse_kernel(sched_ref, d_ref, b_ref, o_ref):
    s = pl.program_id(1)

    @pl.when(_cell(sched_ref, s, _FIRST) == 1)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    pp = _int_dot(d_ref[0], b_ref[...])
    # deferred shift (OPT2): the plane scale comes from the schedule, so
    # sentinel/padding steps (weight 0) contribute exact zeros
    o_ref[...] += pp * _cell(sched_ref, s, _WEIGHT)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def bw_gemm_sparse(digits, b, schedule, *, block_m: int = 128,
                   block_n: int = 128, block_k: int = 256,
                   interpret: bool = False):
    """C[M,N] = sum over schedule entries of (digits[plane] @ B) * weight.

    digits:   int8 [BW, M, K] encoded planes of the multiplicand.
    b:        int8 [K, N].
    schedule: int32 [L, >=6] compacted block schedule in "m_major" order
              (see SCHED_COLS); the radix is baked into the WEIGHT column
              at build time.  Only the first six columns are read.
    """
    bw_n, m, k = digits.shape
    k2, n = b.shape
    _check_dims("bw_gemm_sparse", m, k, k2, n, block_m, block_n, block_k)
    _check_schedule("bw_gemm_sparse", schedule)
    steps = schedule.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // block_n, steps),
        in_specs=[
            # gather exactly the one digit plane this step needs
            pl.BlockSpec((1, block_m, block_k),
                         lambda j, s, sched: (_cell(sched, s, _PLANE),
                                              _cell(sched, s, _ROW),
                                              _cell(sched, s, _KBLK))),
            pl.BlockSpec((block_k, block_n),
                         lambda j, s, sched: (_cell(sched, s, _KBLK), j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda j, s, sched: (_cell(sched, s, _ROW), j)),
    )
    return pl.pallas_call(
        _sparse_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(_flat_schedule(schedule), digits, b)


def _sparse_fused_kernel(sched_ref, d_ref, b_ref, scale_ref, scale_n_ref,
                         bias_ref, o_ref, acc_ref, *, activation,
                         has_bias: bool, has_scale_n: bool):
    """bw_gemm_sparse with the dequant epilogue folded in.

    The int32 accumulator lives in a VMEM scratch block; FIRST zeroes it,
    LAST runs the epilogue and writes the only HBM output of the row.
    Padding steps (weight 0, FIRST=LAST=0) are exact no-ops.
    """
    s = pl.program_id(1)

    @pl.when(_cell(sched_ref, s, _FIRST) == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pp = _int_dot(d_ref[0], b_ref[...])
    acc_ref[...] += pp * _cell(sched_ref, s, _WEIGHT)

    @pl.when(_cell(sched_ref, s, _LAST) == 1)
    def _epilogue():
        sc = scale_ref[...]
        if has_scale_n:
            # combine the scale vectors first so the accumulator is
            # multiplied by one float (bit-matches the dense fused kernel
            # and the jnp oracle's `acc * (sx * sw)` ordering)
            sc = sc * scale_n_ref[...]
        y = acc_ref[...].astype(jnp.float32) * sc
        if has_bias:
            y = y + bias_ref[...]
        y = EPILOGUE_ACTIVATIONS[activation](y)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "block_k", "interpret", "activation", "out_dtype"))
def bw_gemm_sparse_fused(digits, b, schedule, scale, bias=None, scale_n=None,
                         *, block_m: int = 128, block_n: int = 128,
                         block_k: int = 256, interpret: bool = False,
                         activation=None, out_dtype=jnp.float32):
    """Sparse-schedule bw_gemm with the fused dequant epilogue.

    Arguments mirror bw_gemm_fused (the planned-weight layout: weight
    channels on the kernel M axis, tokens on N), but
    the occupancy mask is replaced by the compacted schedule and the plane
    loop by one scheduled (plane, m-block, k-block) step per grid
    iteration.

    scale:   f32 [M, 1] per-row (per-output-channel) scale.
    bias:    optional f32 [M, 1].
    scale_n: optional f32 [1, N] per-column vector (per-token act scales).
    """
    bw_n, m, k = digits.shape
    k2, n = b.shape
    _check_dims("bw_gemm_sparse_fused", m, k, k2, n, block_m, block_n,
                block_k)
    _check_schedule("bw_gemm_sparse_fused", schedule)
    _check_epilogue("bw_gemm_sparse_fused", activation, scale, (m, 1),
                    scale_n, n)
    has_scale_n = scale_n is not None
    if not has_scale_n:                 # placeholder so arity is static
        scale_n = jnp.ones((1, n), jnp.float32)
    has_bias = bias is not None
    if not has_bias:                    # placeholder so arity is static
        bias = jnp.zeros_like(scale)
    steps = schedule.shape[0]
    vec_spec = pl.BlockSpec((block_m, 1),
                            lambda j, s, sched: (_cell(sched, s, _ROW), 0))
    col_spec = pl.BlockSpec((1, block_n), lambda j, s, sched: (0, j))
    kernel = functools.partial(_sparse_fused_kernel, activation=activation,
                               has_bias=has_bias, has_scale_n=has_scale_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // block_n, steps),
        in_specs=[
            pl.BlockSpec((1, block_m, block_k),
                         lambda j, s, sched: (_cell(sched, s, _PLANE),
                                              _cell(sched, s, _ROW),
                                              _cell(sched, s, _KBLK))),
            pl.BlockSpec((block_k, block_n),
                         lambda j, s, sched: (_cell(sched, s, _KBLK), j)),
            vec_spec,
            col_spec,
            vec_spec,
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda j, s, sched: (_cell(sched, s, _ROW), j)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.dtype(out_dtype)),
        interpret=interpret,
    )(_flat_schedule(schedule), digits, b,
      scale.astype(jnp.float32), scale_n.astype(jnp.float32),
      bias.astype(jnp.float32))


# ---------------------------------------------------------------------------
# v3: double-buffered schedule pipelining (manual DMA + semaphores)
# ---------------------------------------------------------------------------
# The v2 kernels above compact the schedule, but the walk is still serial:
# each grid step's single-plane BlockSpec gather must land before the MXU
# pass can start, so the sparsity win is bounded by DMA *latency* rather
# than bandwidth.  The pipelined kernels keep PrefetchScalarGridSpec for
# the schedule but take digits / B / out in ANY (HBM) memory space and
# stage blocks through double-buffered VMEM scratch themselves: while step
# s runs on the MXU out of slot p, step s+1's gather is already in flight
# into slot 1-p (pltpu.make_async_copy + per-slot DMA semaphores; the
# schedule's D_SLOT/B_SLOT/B_FETCH columns bake the slot rotation and the
# B-reuse elision in, so the kernel body is pure pl.when plumbing).
#
# Accumulation moves from the out BlockSpec to a VMEM-resident panel of
# ALL m-block accumulators ([M_pad, block_n] int32 scratch).  That lifts
# the v2 kernels' TPU-legality constraint that an output block may only be
# revisited in *consecutive* grid steps — which is exactly what the
# "k_major" schedule order violates (it walks k-blocks globally so
# consecutive steps share a B block across different output rows).  FIRST
# zeroes a row's panel slice at its overall first scheduled step, LAST
# flushes it (running the fused epilogue first) through a staging buffer
# to HBM — the FIRST/LAST protocol survives the software-pipeline skew
# because the flags travel in the same prefetched schedule the DMA
# issue/wait predicates read.  Sentinel and padding steps (weight 0,
# B_FETCH 0) issue no DMA and wait on nothing: a skipped plane-block costs
# zero bandwidth, zero semaphore traffic and zero MXU work.


def _pipelined_dma_plumbing(sched_ref, d_hbm, b_hbm, d_buf, b_buf, d_sem,
                            b_sem, *, block_m, block_n, block_k, steps):
    """Shared prologue: warm-up + next-step prefetch, current-step waits.

    Returns (d, b) int32 VMEM tiles for the current step (garbage on
    weight-0 steps — callers must predicate the MXU pass)."""
    j = pl.program_id(0)
    s = pl.program_id(1)

    def d_copy(step):
        slot = _cell(sched_ref, step, _DSLOT)
        return pltpu.make_async_copy(
            d_hbm.at[_cell(sched_ref, step, _PLANE),
                     pl.ds(_cell(sched_ref, step, _ROW) * block_m, block_m),
                     pl.ds(_cell(sched_ref, step, _KBLK) * block_k, block_k)],
            d_buf.at[slot], d_sem.at[slot])

    def b_copy(step):
        slot = _cell(sched_ref, step, _BSLOT)
        return pltpu.make_async_copy(
            b_hbm.at[pl.ds(_cell(sched_ref, step, _KBLK) * block_k, block_k),
                     pl.ds(j * block_n, block_n)],
            b_buf.at[slot], b_sem.at[slot])

    @pl.when(s == 0)
    def _warmup():                       # step 0 has no predecessor
        @pl.when(_cell(sched_ref, 0, _WEIGHT) != 0)
        def _():
            d_copy(0).start()

        @pl.when(_cell(sched_ref, 0, _BFETCH) == 1)
        def _():
            b_copy(0).start()

    @pl.when(s + 1 < steps)
    def _prefetch():                     # issue s+1's gather under s's MXU
        @pl.when(_cell(sched_ref, s + 1, _WEIGHT) != 0)
        def _():
            d_copy(s + 1).start()

        @pl.when(_cell(sched_ref, s + 1, _BFETCH) == 1)
        def _():
            b_copy(s + 1).start()

    # wait only for what was started: the issue predicates at step s-1 (or
    # the warm-up) read the same schedule cells, so starts and waits pair
    # exactly once per slot
    @pl.when(_cell(sched_ref, s, _WEIGHT) != 0)
    def _wait_d():
        d_copy(s).wait()

    @pl.when(_cell(sched_ref, s, _BFETCH) == 1)
    def _wait_b():
        b_copy(s).wait()

    d = d_buf[_cell(sched_ref, s, _DSLOT)]
    b = b_buf[_cell(sched_ref, s, _BSLOT)]
    return d, b


def _sparse_pipelined_kernel(sched_ref, d_hbm, b_hbm, o_hbm, acc_ref, d_buf,
                             b_buf, stage_ref, d_sem, b_sem, o_sem, *,
                             block_m: int, block_n: int, block_k: int,
                             steps: int):
    j = pl.program_id(0)
    s = pl.program_id(1)
    d, b = _pipelined_dma_plumbing(
        sched_ref, d_hbm, b_hbm, d_buf, b_buf, d_sem, b_sem,
        block_m=block_m, block_n=block_n, block_k=block_k, steps=steps)
    row = _cell(sched_ref, s, _ROW)

    @pl.when(_cell(sched_ref, s, _FIRST) == 1)
    def _init():
        acc_ref[pl.ds(row * block_m, block_m), :] = jnp.zeros(
            (block_m, block_n), jnp.int32)

    @pl.when(_cell(sched_ref, s, _WEIGHT) != 0)
    def _compute():
        pp = _int_dot(d, b)
        # deferred shift (OPT2): plane scale from the schedule
        acc_ref[pl.ds(row * block_m, block_m), :] += \
            pp * _cell(sched_ref, s, _WEIGHT)

    @pl.when(_cell(sched_ref, s, _LAST) == 1)
    def _flush():                        # row complete: write its only HBM
        stage_ref[...] = acc_ref[pl.ds(row * block_m, block_m), :]
        cp = pltpu.make_async_copy(
            stage_ref,
            o_hbm.at[pl.ds(row * block_m, block_m),
                     pl.ds(j * block_n, block_n)],
            o_sem)
        cp.start()
        cp.wait()


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def bw_gemm_sparse_pipelined(digits, b, schedule, *, block_m: int = 128,
                             block_n: int = 128, block_k: int = 256,
                             interpret: bool = False):
    """bw_gemm_sparse with double-buffered manual DMA pipelining.

    Bit-identical to ``bw_gemm_sparse`` on the same plan (int32
    accumulation is order-independent), but accepts schedules in *either*
    order — ``m_major`` like v2, or ``k_major`` whose global k-block walk
    revisits output blocks non-consecutively (legal here because the
    accumulators live in a VMEM panel, not the out BlockSpec).

    schedule: int32 [L, 9] annotated schedule (all SCHED_COLS columns).
    """
    bw_n, m, k = digits.shape
    k2, n = b.shape
    _check_dims("bw_gemm_sparse_pipelined", m, k, k2, n, block_m, block_n,
                block_k)
    _check_schedule("bw_gemm_sparse_pipelined", schedule, annotated=True)
    steps = schedule.shape[0]
    kernel = functools.partial(_sparse_pipelined_kernel, block_m=block_m,
                               block_n=block_n, block_k=block_k, steps=steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // block_n, steps),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),    # digits (HBM)
                  pl.BlockSpec(memory_space=pl.ANY)],   # B (HBM)
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((m, block_n), jnp.int32),           # acc panel
            pltpu.VMEM((2, block_m, block_k), jnp.int8),   # digit dbl-buf
            pltpu.VMEM((2, block_k, block_n), jnp.int8),   # B dbl-buf
            pltpu.VMEM((block_m, block_n), jnp.int32),     # flush staging
            pltpu.SemaphoreType.DMA((2,)),                 # digit sems
            pltpu.SemaphoreType.DMA((2,)),                 # B sems
            pltpu.SemaphoreType.DMA(()),                   # flush sem
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(_flat_schedule(schedule), digits, b)


def _sparse_fused_pipelined_kernel(sched_ref, d_hbm, b_hbm, scale_ref,
                                   scale_n_ref, bias_ref, o_hbm, acc_ref,
                                   d_buf, b_buf, stage_ref, d_sem, b_sem,
                                   o_sem, *, block_m: int, block_n: int,
                                   block_k: int, steps: int, activation,
                                   has_bias: bool, has_scale_n: bool):
    j = pl.program_id(0)
    s = pl.program_id(1)
    d, b = _pipelined_dma_plumbing(
        sched_ref, d_hbm, b_hbm, d_buf, b_buf, d_sem, b_sem,
        block_m=block_m, block_n=block_n, block_k=block_k, steps=steps)
    row = _cell(sched_ref, s, _ROW)

    @pl.when(_cell(sched_ref, s, _FIRST) == 1)
    def _init():
        acc_ref[pl.ds(row * block_m, block_m), :] = jnp.zeros(
            (block_m, block_n), jnp.int32)

    @pl.when(_cell(sched_ref, s, _WEIGHT) != 0)
    def _compute():
        pp = _int_dot(d, b)
        acc_ref[pl.ds(row * block_m, block_m), :] += \
            pp * _cell(sched_ref, s, _WEIGHT)

    @pl.when(_cell(sched_ref, s, _LAST) == 1)
    def _epilogue():
        sc = scale_ref[pl.ds(row * block_m, block_m), :]
        if has_scale_n:
            # combine the scale vectors first so the accumulator is
            # multiplied by one float (bit-matches the dense fused kernel
            # and the jnp oracle's `acc * (sx * sw)` ordering)
            sc = sc * scale_n_ref[...]
        y = acc_ref[pl.ds(row * block_m, block_m), :].astype(jnp.float32) \
            * sc
        if has_bias:
            y = y + bias_ref[pl.ds(row * block_m, block_m), :]
        y = EPILOGUE_ACTIVATIONS[activation](y)
        stage_ref[...] = y.astype(stage_ref.dtype)
        cp = pltpu.make_async_copy(
            stage_ref,
            o_hbm.at[pl.ds(row * block_m, block_m),
                     pl.ds(j * block_n, block_n)],
            o_sem)
        cp.start()
        cp.wait()


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "block_k", "interpret", "activation", "out_dtype"))
def bw_gemm_sparse_fused_pipelined(digits, b, schedule, scale, bias=None,
                                   scale_n=None, *, block_m: int = 128,
                                   block_n: int = 128, block_k: int = 256,
                                   interpret: bool = False, activation=None,
                                   out_dtype=jnp.float32):
    """bw_gemm_sparse_fused with double-buffered manual DMA pipelining.

    Same contract as bw_gemm_sparse_fused (scale [M, 1], optional bias
    [M, 1], optional per-column scale_n [1, N]); accepts either schedule
    order.  The epilogue runs once per output row at its LAST scheduled
    step, on the VMEM-resident accumulator panel slice, and the float
    result is staged and DMA'd straight to HBM — bit-identical to the v2
    fused kernel on the same plan.
    """
    bw_n, m, k = digits.shape
    k2, n = b.shape
    _check_dims("bw_gemm_sparse_fused_pipelined", m, k, k2, n, block_m,
                block_n, block_k)
    _check_schedule("bw_gemm_sparse_fused_pipelined", schedule,
                    annotated=True)
    _check_epilogue("bw_gemm_sparse_fused_pipelined", activation, scale,
                    (m, 1), scale_n, n)
    has_scale_n = scale_n is not None
    if not has_scale_n:                 # placeholder so arity is static
        scale_n = jnp.ones((1, n), jnp.float32)
    has_bias = bias is not None
    if not has_bias:                    # placeholder so arity is static
        bias = jnp.zeros_like(scale)
    steps = schedule.shape[0]
    kernel = functools.partial(
        _sparse_fused_pipelined_kernel, block_m=block_m, block_n=block_n,
        block_k=block_k, steps=steps, activation=activation,
        has_bias=has_bias, has_scale_n=has_scale_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // block_n, steps),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),          # digits (HBM)
            pl.BlockSpec(memory_space=pl.ANY),          # B (HBM)
            # the per-row vectors are tiny: keep them whole in VMEM and
            # slice the LAST row's span in the epilogue
            pl.BlockSpec((m, 1), lambda j, s, sched: (0, 0)),
            pl.BlockSpec((1, block_n), lambda j, s, sched: (0, j)),
            pl.BlockSpec((m, 1), lambda j, s, sched: (0, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((m, block_n), jnp.int32),           # acc panel
            pltpu.VMEM((2, block_m, block_k), jnp.int8),   # digit dbl-buf
            pltpu.VMEM((2, block_k, block_n), jnp.int8),   # B dbl-buf
            pltpu.VMEM((block_m, block_n), jnp.dtype(out_dtype)),
            pltpu.SemaphoreType.DMA((2,)),                 # digit sems
            pltpu.SemaphoreType.DMA((2,)),                 # B sems
            pltpu.SemaphoreType.DMA(()),                   # flush sem
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.dtype(out_dtype)),
        interpret=interpret,
    )(_flat_schedule(schedule), digits, b,
      scale.astype(jnp.float32), scale_n.astype(jnp.float32),
      bias.astype(jnp.float32))
