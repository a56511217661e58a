"""Pallas TPU kernels for the paper's compute hot spot (quantised GEMM).

  quant_gemm      -- baseline tiled INT8 GEMM (the parallel-MAC reference)
  bw_gemm         -- bit-weight decomposed GEMM with digit-plane block
                     skipping: bw_gemm (int32 accumulator) and
                     bw_gemm_fused (in-kernel dequant/bias/activation
                     epilogue); bw_gemm_experts (a layer's held experts in
                     one grouped call); bw_gemm_sparse[_fused] (the same
                     contracts through a compacted sparse block schedule,
                     scalar prefetch: skipped plane-blocks cost zero DMA
                     and zero grid steps); bw_gemm_sparse[_fused]_pipelined
                     (v3 double-buffering: manual async copies + DMA
                     semaphores overlap step s+1's gather with step s's
                     MXU pass, and the k_major schedule order reuses
                     resident B blocks across output rows)
  ops             -- plane encoding, masks, schedules + visit orders,
                     weight planning and its cache, per-shape block
                     selection, and the one apply path from a plan record
                     to a kernel (planned_dense_apply /
                     planned_experts_apply, routed by resolve_route);
                     spec-level entry points take a repro.engine.QuantSpec
  autotune        -- measured block-size / dispatch / (order, pipelined)
                     autotuner + backend-tagged JSON cache
  ref             -- pure-jnp oracles

NOTE on names: ``repro.kernels.bw_gemm`` and ``repro.kernels.quant_gemm``
are the *submodules* — ``import repro.kernels.bw_gemm as mod`` yields the
module, and the kernel entry-point functions live on it (``mod.bw_gemm``);
``ops.quant_gemm`` is the padded baseline wrapper.  The kernels take
padded operands; ``ops.planned_dense_apply`` pads, routes and un-permutes
for a plan record.
"""
from . import ops, ref  # noqa: F401
from .ops import (plan_operand, encode_planes,  # noqa: F401
                  quant_gemm_fused, quantized_dense, build_schedule,
                  plan_params, planned_dense_apply, select_block_sizes)
# the submodules win the package-attribute names (see NOTE above);
# importing them last makes that explicit and un-shadows them
from . import bw_gemm, quant_gemm  # noqa: F401
