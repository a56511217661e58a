"""Baseline tiled INT8 GEMM Pallas kernel (the "parallel MAC" reference).

C[M, N] = A[M, K] @ B[K, N] with int32 accumulation, MXU-aligned tiles held
in VMEM.  Grid is (M/bm, N/bn, K/bk) with the K loop innermost so the output
block is revisited and accumulated in place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bw_gemm import EPILOGUE_ACTIVATIONS

__all__ = ["quant_gemm", "quant_gemm_fused"]


def _kernel(a_ref, b_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
    # int8 operands straight into the MXU, int32 accumulation
    o_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def quant_gemm(a, b, *, block_m: int = 128, block_n: int = 128,
               block_k: int = 256, interpret: bool = False):
    """int8 x int8 -> int32 tiled matmul.  Shapes must divide the blocks
    (repro.kernels.ops pads otherwise)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, n, k), (block_m, block_n, block_k))
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(a, b)


def _fused_kernel(a_ref, b_ref, scale_ref, bias_ref, o_ref, acc_ref, *,
                  k_steps: int, activation, has_bias: bool):
    """Baseline int8 GEMM with the dequant epilogue folded in (the int32
    accumulator stays in VMEM scratch; only the float result hits HBM)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        y = acc_ref[...].astype(jnp.float32) * scale_ref[...]
        if has_bias:
            y = y + bias_ref[...]
        y = EPILOGUE_ACTIVATIONS[activation](y)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "block_k", "interpret", "activation",
    "epilogue_axis", "out_dtype"))
def quant_gemm_fused(a, b, scale, bias=None, *, block_m: int = 128,
                     block_n: int = 128, block_k: int = 256,
                     interpret: bool = False, activation=None,
                     epilogue_axis: str = "n", out_dtype=jnp.float32):
    """C = act((A @ B) * scale + bias) with int32 accumulation in VMEM.

    scale/bias: f32 [1, N] (epilogue_axis='n') or [M, 1] (epilogue_axis='m').
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, n, k), (block_m, block_n, block_k))
    assert epilogue_axis in ("m", "n")
    assert activation in EPILOGUE_ACTIVATIONS, activation
    if epilogue_axis == "m":
        assert scale.shape == (m, 1), scale.shape
        vec_spec = pl.BlockSpec((block_m, 1), lambda i, j, kk: (i, 0))
    else:
        assert scale.shape == (1, n), scale.shape
        vec_spec = pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j))
    has_bias = bias is not None
    if not has_bias:
        bias = jnp.zeros_like(scale)
    grid = (m // block_m, n // block_n, k // block_k)
    kernel = functools.partial(_fused_kernel, k_steps=grid[2],
                               activation=activation, has_bias=has_bias)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            vec_spec,
            vec_spec,
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.dtype(out_dtype)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        interpret=interpret,
    )(a, b, scale.astype(jnp.float32), bias.astype(jnp.float32))
