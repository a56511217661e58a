"""Shard `PlannedOperand` weights *and their compacted schedules* over a
device mesh.

The plan records the single-device stack builds (`repro.kernels.ops`) are
block-structured end to end: digit planes are padded to (block_m,
block_k) tiles, the occupancy mask lives on the block grid, and the
compacted [L, 9] schedules are CSR-of-*blocks*.  That makes mesh
partitioning exact rather than approximate: slicing the block grid
``s_model`` ways along M (tensor-parallel output channels) and
``s_data`` ways along K (FSDP-style contraction split) slices the mask
into shard-local slabs, and re-running ``build_schedule`` on each slab
yields per-shard [L_s, 9] tables with correctly re-derived FIRST/LAST
flags, double-buffer slots and B-fetch elision — every global plane-block
lands in exactly one shard's schedule (the property the
``repro.analysis.verify_sharded_plan`` partition check pins).

Layout convention (matches `launch/mesh.py` axis names):

    axis 'model' (size s_model)  -> kernel rows   = output channels (M)
    axis 'data'  (size s_data)   -> contraction k-blocks (K); partial
                                    int32 accumulators are psum'd over it

`ShardedPlan.plan` is a full single-host plan record (same keys as
``plan_dense_weight``, block grid padded so both axes divide evenly);
`shard_map` slices it per device, so nothing here materializes per-shard
weight copies.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import encodings as enc
from repro.kernels import ops

from .collectives import normalize_shards

__all__ = ["ShardedPlan", "shard_plan", "plan_sharded_weight"]


@dataclasses.dataclass
class ShardedPlan:
    """A plan record partitioned over a (s_data, s_model) shard grid.

    plan: padded plan record (``plan_dense_weight`` keys); its block grid
    divides evenly by the shard grid, so ``shard_map`` slices it exactly.
    schedules: int32 [s_model, s_data, L_max, 9] per-shard compacted
    schedules in *shard-local* block coordinates, each padded to the
    longest shard's length with exact no-op entries.
    """
    plan: dict
    s_data: int
    s_model: int
    block_m: int
    block_k: int
    order: str
    radix: int
    m: int                      # original kernel rows (layer output dim)
    k: int                      # original contraction dim
    schedules: np.ndarray       # int32 [s_model, s_data, L_max, 9]
    sched_lens: np.ndarray      # int32 [s_model, s_data] pre-pad lengths
    densities: np.ndarray       # float [s_model, s_data] shard densities

    @property
    def shards(self) -> Tuple[int, int]:
        return (self.s_data, self.s_model)

    def density(self) -> float:
        """Global plane-block density (the sparse-dispatch signal)."""
        return float(np.asarray(self.plan["mask"]).mean())

    def shard_mask(self, i: int, j: int) -> np.ndarray:
        """Shard (model=i, data=j)'s slab of the global occupancy mask."""
        mask = np.asarray(self.plan["mask"])
        mb_s = mask.shape[1] // self.s_model
        kb_s = mask.shape[2] // self.s_data
        return mask[:, i * mb_s:(i + 1) * mb_s, j * kb_s:(j + 1) * kb_s]


def _pad_block_grid(digits, mask, row_perm, inv_perm, sw_rows,
                    s_data: int, s_model: int,
                    block_m: int, block_k: int):
    """Pad the block grid so both axes divide by the shard grid.

    Appended blocks are all-zero (mask False), so they add sentinel-only
    schedule entries and exact-zero output rows that the epilogue's
    ``[:n_out]`` slice drops — parity-neutral by construction.
    """
    bw_n, mb, kb = mask.shape
    mb2 = -(-mb // s_model) * s_model
    kb2 = -(-kb // s_data) * s_data
    m_pad, m_pad2 = mb * block_m, mb2 * block_m
    k_pad2 = kb2 * block_k
    if (mb2, kb2) != (mb, kb):
        digits = jnp.pad(digits, ((0, 0), (0, m_pad2 - m_pad),
                                  (0, k_pad2 - digits.shape[2])))
        mask = np.pad(mask, ((0, 0), (0, mb2 - mb), (0, kb2 - kb)))
        tail = np.arange(m_pad, m_pad2, dtype=np.int32)
        row_perm = np.concatenate([np.asarray(row_perm, np.int32), tail])
        inv_perm = np.concatenate([np.asarray(inv_perm, np.int32), tail])
        sw_rows = jnp.pad(jnp.asarray(sw_rows),
                          ((0, m_pad2 - m_pad), (0, 0)))
    return digits, mask, row_perm, inv_perm, sw_rows


def shard_plan(plan, shards, *, radix: Optional[int] = None,
               order: Optional[str] = None, sw=None,
               n_out: Optional[int] = None,
               verify: Optional[bool] = None) -> ShardedPlan:
    """Partition a plan along (K -> 'data', M -> 'model') shard axes.

    plan: a ``PlannedOperand`` (radix/order read off it) or a
    ``plan_dense_weight`` record dict (then ``radix``/``order`` are
    required — records do not carry them, same contract as
    ``planned_dense_apply``).  sw: per-channel weight scale [N] / [1, N]
    (PlannedOperand input only; records already carry ``sw_rows``).

    verify: run ``repro.analysis.verify_sharded_plan`` — each shard's
    schedule against its shard-local mask plus the global partition
    check — raising on any violation (None: the ``REPRO_VERIFY`` env
    toggle, always-on in tests).
    """
    s_data, s_model = normalize_shards(shards)
    if isinstance(plan, ops.PlannedOperand):
        radix = enc.radix(plan.encoding) if radix is None else radix
        order = plan.order if order is None else order
        n_out = plan.m if n_out is None else n_out
        digits, mask = plan.digits, np.asarray(plan.mask)
        row_perm, inv_perm = plan.row_perm, plan.inv_perm
        block_m, block_k, k = plan.block_m, plan.block_k, plan.k
        m_pad = digits.shape[1]
        if sw is None:
            sw_rows = jnp.ones((m_pad, 1), jnp.float32)
        else:
            sw_rows = ops._channel_rows(jnp.asarray(sw).reshape(-1),
                                        int(np.asarray(sw).size), m_pad,
                                        np.asarray(row_perm))
    else:
        if radix is None or order is None:
            raise ValueError("shard_plan needs radix= and order= with a "
                             "plan record (records do not carry them)")
        digits, mask = plan["digits"], np.asarray(plan["mask"])
        row_perm = np.asarray(plan["row_perm"])
        inv_perm = np.asarray(plan["inv_perm"])
        sw_rows = plan["sw_rows"]
        block_m = digits.shape[1] // mask.shape[1]
        block_k = digits.shape[2] // mask.shape[2]
        k = int(digits.shape[2])
        n_out = int(digits.shape[1]) if n_out is None else n_out
    if order not in ops.SCHEDULE_ORDERS:
        raise ValueError(f"order must be one of {ops.SCHEDULE_ORDERS}, "
                         f"got {order!r}")

    digits, mask, row_perm, inv_perm, sw_rows = _pad_block_grid(
        digits, mask, row_perm, inv_perm, sw_rows,
        s_data, s_model, block_m, block_k)
    bw_n, mb2, kb2 = mask.shape
    mb_s, kb_s = mb2 // s_model, kb2 // s_data

    per_shard = []
    lens = np.zeros((s_model, s_data), dtype=np.int32)
    dens = np.zeros((s_model, s_data), dtype=np.float64)
    for i in range(s_model):
        row = []
        for j in range(s_data):
            local = mask[:, i * mb_s:(i + 1) * mb_s,
                         j * kb_s:(j + 1) * kb_s]
            sched = ops.build_schedule(local, radix, order)
            lens[i, j] = sched.shape[0]
            dens[i, j] = float(local.mean())
            row.append(sched)
        per_shard.append(row)
    l_max = int(lens.max())
    schedules = np.stack(
        [np.stack([ops.pad_schedule(s, l_max) for s in row])
         for row in per_shard]).astype(np.int32)

    record = {
        "digits": digits,
        "mask": jnp.asarray(mask),
        "schedule": jnp.asarray(ops.build_schedule(mask, radix, order)),
        "row_perm": jnp.asarray(row_perm),
        "inv_perm": jnp.asarray(inv_perm),
        "sw_rows": jnp.asarray(sw_rows),
    }
    splan = ShardedPlan(plan=record, s_data=s_data, s_model=s_model,
                        block_m=block_m, block_k=block_k, order=order,
                        radix=radix, m=int(n_out), k=int(k),
                        schedules=schedules, sched_lens=lens,
                        densities=dens)
    if ops._verify_enabled(verify):
        from repro import analysis
        analysis.verify_sharded_plan(splan).raise_if_errors()
    return splan


def plan_sharded_weight(w, spec, shards, order: str = "m_major",
                        verify: Optional[bool] = None) -> ShardedPlan:
    """Quantize + plan + shard a dense float weight [K, N].

    Routes through ``ops.plan_for`` so sharded plans share the per-weight
    plan cache (keyed with the shard grid — the same weight planned for
    two meshes holds two entries) and the always-on verification seam.
    """
    planned, _sw = ops.plan_for(w, spec, order=order, verify=verify,
                                shards=normalize_shards(shards))
    return planned.sharded
