"""`shard_map`-wrapped execution of sharded planned GEMMs.

``sharded_planned_apply`` runs the existing v2/v3 sparse/pipelined Pallas
kernels *per shard* on a ('data', 'model') mesh: each device holds one
(M-slice, K-slice) tile of the digit planes plus that tile's own
compacted [L, 9] schedule (shard-local block coordinates, re-derived
FIRST/LAST — see ``plan.shard_plan``), computes its partial int32
accumulator, and the partials are summed over the 'data' (K) axis with
``psum`` — or ``psum_scatter`` when the token axis divides, which stops
after the reduce-scatter half and leaves each data-shard holding its
token slice.  The collective is issued *inside* the shard_map body right
after the kernel, so XLA's latency-hiding scheduler (see
``collectives.enable_async_collectives``) can start it under the tail of
the grid; the integer accumulation itself is order-exact, so sharded
outputs match the single-device kernels bit-for-bit up to the epilogue's
float rounding.

Activation quantization and the dequant/bias/activation epilogue run
*outside* the shard_map at global shape: per-token activation scales
must span the full K axis (a per-shard max would change the integer
grid), and the epilogue's inverse row permutation is global.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import chaos as _chaos
from repro.core import quant as quantlib
from repro.engine.spec import QuantSpec
from repro.kernels.bw_gemm import (EPILOGUE_ACTIVATIONS, bw_gemm,
                                   bw_gemm_sparse,
                                   bw_gemm_sparse_pipelined)
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

from .collectives import gemm_collective_bytes
from .plan import ShardedPlan

__all__ = ["AXIS_DATA", "AXIS_MODEL", "make_gemm_mesh",
           "sharded_planned_apply"]

AXIS_DATA = "data"        # K shards; partial accumulators reduce over it
AXIS_MODEL = "model"      # M shards (output channels); no collective

REDUCES = ("auto", "psum", "psum_scatter")

_M_COLLECTIVE_BYTES = obs_metrics.get_registry().counter(
    "repro_collective_bytes_total")


def make_gemm_mesh(shards):
    """The (s_data, s_model) -> ('data', 'model') mesh for a ShardedPlan."""
    from repro.launch import mesh as meshlib
    s_data, s_model = (shards.shards if isinstance(shards, ShardedPlan)
                       else shards)
    return meshlib.make_mesh((s_data, s_model), (AXIS_DATA, AXIS_MODEL))


def _resolve_route(splan: ShardedPlan, dispatch: str) -> str:
    """Static shard-kernel routing, mirroring ops._resolve_dispatch rules.

    One route for every shard (shard_map bodies must agree across
    devices), picked from the *mean* shard density; the v2 sparse
    kernels stay m_major-only, k_major plans take the pipelined kernels.
    """
    sparse_route = "pipelined" if splan.order == "k_major" else "sparse"
    if dispatch == "dense":
        return "dense"
    if dispatch == "sparse":
        if splan.order == "k_major":
            raise ValueError(
                "dispatch='sparse' (the v2 kernels) requires m_major "
                "shard schedules — use dispatch='pipelined' (or 'auto')")
        return "sparse"
    if dispatch == "pipelined":
        return "pipelined"
    if dispatch != "auto":
        raise ValueError(f"dispatch must be one of {ops.DISPATCHES}, "
                         f"got {dispatch!r}")
    density = float(splan.densities.mean())
    return (sparse_route if density <= ops.SPARSE_DENSITY_THRESHOLD
            else "dense")


def sharded_planned_apply(splan: ShardedPlan, x, spec, n_out: int, *,
                          bias=None, activation: Optional[str] = None,
                          out_dtype=jnp.float32,
                          block_n: Optional[int] = None,
                          interpret: Optional[bool] = None,
                          dispatch: str = "auto", mesh=None,
                          reduce: str = "auto"):
    """y = act((x @ w)_int * s_x * s_w + bias), sharded over a mesh.

    Parity contract: matches single-device
    ``planned_dense_apply(fused=False)`` on the same weight/spec to
    cross-context tolerance (the integer partials are exact; only the
    jit boundary's float LSB differs).

    splan: from ``plan.shard_plan`` / ``plan.plan_sharded_weight``.
    mesh: a ('data', 'model') Mesh matching ``splan.shards`` (built via
    ``make_gemm_mesh`` when None — requires the devices to exist).
    reduce: 'psum' (all-reduce over 'data'; output replicated on the
    data axis), 'psum_scatter' (reduce-scatter; each data shard keeps
    its token slice — needs the padded token axis to divide), or 'auto'
    (scatter when it divides, else psum).
    """
    spec = QuantSpec.coerce(spec)
    if _chaos.enabled():     # one branch when no fault plan is armed
        _chaos.maybe_raise("parallel.shard")
    if interpret is None:
        interpret = ops._interpret()
    plan = splan.plan
    digits, mask = plan["digits"], plan["mask"]
    bw_n, m_pad, k_pad = digits.shape
    if bw_n != spec.num_digits:
        raise ValueError(
            f"sharded plan has {bw_n} digit planes but spec "
            f"{spec.encoding!r}/{spec.bits}b implies {spec.num_digits}; "
            f"was the plan built under a different spec?")
    if spec.radix != splan.radix:
        raise ValueError(f"sharded plan was built with radix "
                         f"{splan.radix} but the spec implies "
                         f"{spec.radix}")
    k = x.shape[-1]
    if k != splan.k:
        raise ValueError(
            f"x has K={k} features but the sharded plan was built with "
            f"K={splan.k}; re-plan the weight or fix the reshape")
    s_data, s_model = splan.shards
    lead = x.shape[:-1]
    per_token = spec.act_quant == "per_token"
    with obs_trace.span("parallel.quantize", cat="parallel",
                        k=int(k), per_token=per_token):
        qx, sx = quantlib.quantize_for_spec(
            jnp.asarray(x).astype(jnp.float32), spec,
            axis=-1 if per_token else None)
    x2 = qx.reshape(-1, k)
    batch = x2.shape[0]
    if block_n is None:
        block_n = ops.select_block_sizes(n_out, k, batch, spec)[2]
    bt = ops._pad_to(jnp.pad(x2.T, ((0, k_pad - k), (0, 0))), block_n, 1)
    n_cols = bt.shape[1]
    if reduce not in REDUCES:
        raise ValueError(f"reduce must be one of {REDUCES}, got {reduce!r}")
    scatter = s_data > 1 and n_cols % s_data == 0 \
        if reduce == "auto" else reduce == "psum_scatter"
    if scatter and n_cols % s_data:
        raise ValueError(
            f"psum_scatter needs the padded token axis ({n_cols}) to "
            f"divide by s_data={s_data}; use reduce='psum'")
    route = _resolve_route(splan, dispatch)
    if mesh is None:
        mesh = make_gemm_mesh(splan)
    if (mesh.shape.get(AXIS_DATA), mesh.shape.get(AXIS_MODEL)) != \
            (s_data, s_model):
        raise ValueError(
            f"mesh {dict(mesh.shape)} does not match the plan's shard "
            f"grid (data={s_data}, model={s_model})")
    block_m, block_k = splan.block_m, splan.block_k
    radix, interpret = splan.radix, bool(interpret)
    scheds = jnp.asarray(splan.schedules)

    def shard_body(d_l, m_l, s_l, b_l):
        sched = s_l.reshape(s_l.shape[-2], s_l.shape[-1])
        if route == "pipelined":
            acc = bw_gemm_sparse_pipelined(
                d_l, b_l, sched, block_m=block_m, block_n=block_n,
                block_k=block_k, interpret=interpret)
        elif route == "sparse":
            acc = bw_gemm_sparse(
                d_l, b_l, sched, block_m=block_m, block_n=block_n,
                block_k=block_k, interpret=interpret)
        else:
            acc = bw_gemm(
                d_l, b_l, m_l, block_m=block_m, block_n=block_n,
                block_k=block_k, radix=radix, interpret=interpret)
        if scatter:
            return jax.lax.psum_scatter(acc, AXIS_DATA,
                                        scatter_dimension=1, tiled=True)
        return jax.lax.psum(acc, AXIS_DATA)

    out_spec = P(AXIS_MODEL, AXIS_DATA) if scatter else P(AXIS_MODEL, None)
    if obs_trace.enabled():
        _M_COLLECTIVE_BYTES.inc(gemm_collective_bytes(
            m_pad, n_cols, s_data, s_model,
            reduce="psum_scatter" if scatter else "psum"))
        sp = obs_trace.span(
            "parallel.shard_map", cat="parallel", route=route,
            shards=f"{s_data}x{s_model}",
            reduce="psum_scatter" if scatter else "psum",
            m=int(m_pad), k=int(k_pad), n=int(n_cols))
    else:
        sp = obs_trace.NULL_SPAN
    with sp:
        acc = jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(P(None, AXIS_MODEL, AXIS_DATA),    # digit planes
                      P(None, AXIS_MODEL, AXIS_DATA),    # occupancy mask
                      P(AXIS_MODEL, AXIS_DATA, None, None),  # schedules
                      P(AXIS_DATA, None)),               # B (k-sliced)
            out_specs=out_spec, check_vma=False,
        )(digits, mask, scheds, bt)
    with obs_trace.span("parallel.epilogue", cat="parallel",
                        n_out=int(n_out), batch=int(batch)):
        acc = acc[plan["inv_perm"]][:n_out, :batch]
        sw = plan["sw_rows"][plan["inv_perm"]][:n_out]
        s = sw * (sx.reshape(1, -1) if per_token else sx)
        y = (acc.astype(jnp.float32) * s).T
        if bias is not None:
            y = y + jnp.asarray(bias, jnp.float32)
        if activation is not None:
            y = EPILOGUE_ACTIVATIONS[activation](y)
        return y.reshape(*lead, n_out).astype(out_dtype)
