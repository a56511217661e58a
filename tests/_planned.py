"""Run a raw digit-plane kernel on a ``plan_operand`` result.

The kernels of ``repro.kernels.bw_gemm`` take padded operands in the
plan's permuted row order; the served path reaches them only through
``ops.planned_dense_apply``.  Kernel-level tests call them through
``planned_kernel``, which does the padding and the un-permutation.
"""
import jax.numpy as jnp
import numpy as np

from repro.core import encodings as enc
from repro.kernels import bw_gemm as bwk

# the dense kernels read the occupancy mask (and the radix); the sparse
# and pipelined ones the schedule
_MASKED = (bwk.bw_gemm, bwk.bw_gemm_fused)


def planned_kernel(kernel, planned, b, *rows, block_n: int = 128, **kw):
    """``kernel`` on ``planned`` x int8 B [K, N], as [M, N] in the
    operand's own row order.  rows: per-row vectors of length M (the
    fused kernels' scale, then bias; None for no bias), in that order
    too.  kw: the kernel's epilogue options (activation, out_dtype)."""
    k, n = b.shape
    assert k == planned.k, (k, planned.k)
    m_pad, k_pad = planned.digits.shape[1:]
    n_pad = -(-n // block_n) * block_n
    bt = jnp.pad(jnp.asarray(b, jnp.int8), ((0, k_pad - k), (0, n_pad - n)))
    perm = np.asarray(planned.row_perm)
    rows = [None if v is None else jnp.asarray(
        np.pad(np.asarray(v, np.float32).reshape(-1),
               (0, m_pad - planned.m))[perm].reshape(-1, 1)) for v in rows]
    if kernel in _MASKED:
        table = planned.mask
        kw["radix"] = enc.radix(planned.encoding)
    else:
        table = jnp.asarray(planned.schedule)
    out = kernel(planned.digits, bt, table, *rows, block_m=planned.block_m,
                 block_n=block_n, block_k=planned.block_k, interpret=True,
                 **kw)
    return np.asarray(out)[np.asarray(planned.inv_perm)][:planned.m, :n]
