"""Mixture-of-Experts FFN: a dropless layer over the experts a chip holds,
and the training path's capacity-bounded scatter dispatch.

``moe_held_apply`` serves decode and prefill.  It is told which experts
it holds (``ModelConfig.experts_first`` / ``experts_held``), routes every
token over all ``n_experts`` (float32 softmax, top-k, renormalised over
the k), keeps the routes that land on its own experts, and gathers each
held expert's tokens into a buffer of capacity T: an expert takes at
most one route per token, so nothing is ever dropped.  The three expert
GEMMs run as one grouped call each over the held experts (the
digit-plane kernel ``bw_gemm_experts`` on planned weights), and the
gates are applied at the combine.  The result is the held experts' part
of the layer; the shares of a layer's experts over chips add up to the
whole layer.

``moe_apply`` is the training path (all experts held):

Top-k routing, Switch-style load-balancing auxiliary loss, and an
O(tokens * d) scatter/gather dispatch (no [tokens, E, C] one-hot einsum).
Experts are sharded over the 'model' mesh axis (expert parallelism) or,
for few-expert configs like Grok-1 (8e), over d_ff (tensor parallelism) --
cfg.moe_shard selects.

Distributed dispatch (cfg.moe_dispatch_groups, DESIGN.md §6 / EXPERIMENTS
§Perf): with the default single group, the dispatch scatter's indices are
global, so under pjit the partitioner must all-gather the token and
dispatch buffers across the data axis (~0.5 TB/layer moved for Grok-1).
Setting moe_dispatch_groups = DP-shard count splits tokens into
data-aligned groups, each with its own LOCAL capacity slots: scatter,
expert GEMMs, and combine all become shard-local (an all-to-all-free
2D (data x expert/mlp) MoE — the pure-GSPMD equivalent of the
DeepSpeed/MaxText local dispatch).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.sharding import box, constrain
from . import layers as L

__all__ = ["moe_init", "moe_apply", "moe_held_apply", "route",
           "EXPERT_WEIGHTS"]

# the expert weights of a layer, [held, K, N] each ("w_gate" when gated)
EXPERT_WEIGHTS = ("w_up", "w_gate", "w_down")


def moe_init(key, cfg, param_dtype=jnp.float32):
    """Router over all ``n_experts``, and the held experts' weights.

    Each expert draws from its own key (``split(key_w, n_experts)``), so
    a chip's share is the slice of the whole layer's weights that it
    holds, drawn without drawing the rest."""
    ks = jax.random.split(key, 4)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    first, held = cfg.experts_first, cfg.held_experts
    if first < 0 or first + held > e:
        raise ValueError(f"experts [{first}, {first + held}) are not among "
                         f"the layer's {e}")
    exp_axis = "expert" if cfg.moe_shard == "expert" else None
    mlp_axis = "mlp" if cfg.moe_shard == "mlp" else None
    # FSDP the d_model dim of expert weights in BOTH shard modes: for
    # moe_shard='mlp' this 2D-shards each expert (data x model) — without
    # it the per-layer fp32 dW all-reduce dominates the step (§Perf HC1).
    emb_axis = "embed"

    def w(k, shape, axes):
        keys = jax.random.split(k, e)[first:first + held]
        return box(jax.vmap(lambda ki: L.truncated_normal(
            ki, shape, 1.0, param_dtype))(keys), axes)

    p = {
        "router": {"w": box(L.truncated_normal(ks[0], (d, e), 1.0,
                                               param_dtype), ("embed_nofsdp",
                                                              None))},
        "w_up": w(ks[1], (d, f), (exp_axis, emb_axis, mlp_axis)),
        "w_down": w(ks[2], (f, d), (exp_axis, mlp_axis, emb_axis)),
    }
    if cfg.gated_mlp:
        p["w_gate"] = w(ks[3], (d, f), (exp_axis, emb_axis, mlp_axis))
    return p


def route(router_w, xf, k: int):
    """float32 router over all experts: softmax, top-k, renormalised over
    the k.  xf [T, d] -> (gate [T, k] float32, experts [T, k] int32)."""
    logits = jnp.dot(xf.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    gate, eidx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    return gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9), eidx


def _expert_gemm(p, name, x, count, cfg, dtype, activation=None):
    """x [H, T, K] through each held expert's ``name`` weight: one grouped
    digit-plane kernel call where the weights are planned, else each
    expert through ``dense_apply`` under the config's QuantSpec (bf16
    without one)."""
    plan = p.get(name + "_plan")
    spec = cfg.quant_spec()
    if plan is not None and spec is not None:
        from repro.kernels import ops
        return ops.planned_experts_apply(
            plan, x, spec, p[name].shape[-1], count, activation=activation,
            out_dtype=dtype)
    return jax.vmap(lambda w, xh: L.dense_apply(
        {"w": w}, xh, dtype, spec, activation=activation))(p[name], x)


def moe_held_apply(p, x, cfg, dtype=jnp.bfloat16):
    """x: [B, T, d] -> (y [B, T, d], counts int32 [2]): the held experts'
    part of the layer, dropless, and (token-routes that landed on held
    experts, held experts with at least one route)."""
    b, t, d = x.shape
    first, held = cfg.experts_first, cfg.held_experts
    xf = x.reshape(-1, d)
    gate, eidx = route(p["router"]["w"], xf, cfg.experts_per_token)
    # [T, k, H]: route r of token t lands on held expert h
    hit = (eidx - first)[..., None] == jnp.arange(held)
    weight = jnp.where(hit, gate[..., None], 0.0).sum(1)          # [T, H]
    routed = hit.any(1).T                                         # [H, T]
    count = routed.sum(1, dtype=jnp.int32)                        # [H]
    # each held expert's tokens, its routed ones first: capacity T
    order = jnp.argsort(~routed, axis=1, stable=True)             # [H, T]
    buf = jnp.take(xf, order, axis=0)                             # [H, T, d]
    if cfg.gated_mlp:
        h = L.activation(cfg.act)(
            _expert_gemm(p, "w_gate", buf, count, cfg, dtype)) * \
            _expert_gemm(p, "w_up", buf, count, cfg, dtype)
    else:
        h = _expert_gemm(p, "w_up", buf, count, cfg, dtype,
                         activation=cfg.act)
    out = _expert_gemm(p, "w_down", h, count, cfg, dtype)         # [H, T, d]
    # combine: each token's rows back from every held expert, by gate
    back = jnp.take_along_axis(out, jnp.argsort(order, axis=1)[..., None],
                               axis=1)
    y = jnp.einsum("htd,th->td", back.astype(jnp.float32), weight)
    counts = jnp.stack([count.sum(), (count > 0).sum(dtype=jnp.int32)])
    return y.reshape(b, t, d).astype(dtype), counts


def _dispatch(xf, eidx, gate, e: int, k: int, cap: int, dtype):
    """Tokens [T,d] + routing [T,k] -> (buf [e,cap,d], dest, keep, wgt)."""
    t, d = xf.shape
    flat_e = eidx.reshape(-1)                                 # [T*k]
    tk = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.cumsum(counts) - counts                      # exclusive
    ranks_sorted = jnp.arange(tk) - starts[flat_e[order]]
    ranks = jnp.zeros((tk,), jnp.int32).at[order].set(
        ranks_sorted.astype(jnp.int32))
    keep = ranks < cap                                        # dropped beyond C
    tok_idx = jnp.arange(tk) // k
    dest = jnp.where(keep, flat_e * cap + ranks, e * cap)     # dump slot
    buf = jnp.zeros((e * cap + 1, d), dtype)
    buf = buf.at[dest].set(xf[tok_idx].astype(dtype), mode="drop")
    wgt = jnp.where(keep, gate.reshape(-1), 0.0).astype(dtype)
    return buf[:e * cap].reshape(e, cap, d), dest, wgt


def _combine(out, dest, wgt, n_tok: int, k: int, dtype):
    """Expert outputs [e,cap,d] -> token outputs [T,d]."""
    e_cap = out.shape[0] * out.shape[1]
    out_flat = out.reshape(e_cap, -1)
    vals = jnp.take(out_flat, jnp.minimum(dest, e_cap - 1), axis=0)
    tok_idx = jnp.arange(dest.shape[0]) // k
    return jnp.zeros((n_tok, out.shape[-1]), dtype).at[tok_idx].add(
        vals * wgt[:, None])


def moe_apply(p, x, cfg, dtype=jnp.bfloat16):
    """x: [B, T, d] -> (y [B, T, d], aux_loss scalar)."""
    b, t, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    g = max(int(getattr(cfg, "moe_dispatch_groups", 1)), 1)
    xf = x.reshape(-1, d)
    n_tok = xf.shape[0]
    assert n_tok % g == 0, (n_tok, g)
    cap = int(np.ceil(n_tok / g * k / e * cfg.capacity_factor))

    logits = (xf.astype(jnp.float32)
              @ p["router"]["w"].astype(jnp.float32))        # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)                      # [T, k]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # ---- load-balance aux loss (Switch): E * sum_e f_e * P_e -------------
    me = probs.mean(axis=0)                                   # mean prob/expert
    one_hot_top1 = jax.nn.one_hot(eidx[:, 0], e, dtype=jnp.float32)
    ce = one_hot_top1.mean(axis=0)                            # dispatch frac
    aux = cfg.router_aux_coef * e * jnp.sum(me * ce)

    # with moe_shard='mlp' the expert dim stays replicated (d_ff is the
    # sharded axis); naming it 'expert' would double-map the mesh axis.
    exp_ax = "expert" if cfg.moe_shard == "expert" else None
    mlp_ax = "mlp" if cfg.moe_shard == "mlp" else None
    act = L.activation(cfg.act)

    if g == 1:
        buf, dest, wgt = _dispatch(xf, eidx, gate, e, k, cap, dtype)
        buf = constrain(buf, exp_ax, "capacity", None)
        up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(dtype))
        if cfg.gated_mlp:
            gt = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(dtype))
            h = act(gt) * up
        else:
            h = act(up)
        h = constrain(h, exp_ax, "capacity", mlp_ax)
        out = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dtype))
        out = constrain(out, exp_ax, "capacity", None)
        y = _combine(out, dest, wgt, n_tok, k, dtype)
        return y.reshape(b, t, d), aux

    # ---- local (per-DP-shard) dispatch: groups aligned with the data axis -
    tg = n_tok // g
    xg = xf.reshape(g, tg, d)
    eg = eidx.reshape(g, tg, k)
    gg = gate.reshape(g, tg, k)
    buf, dest, wgt = jax.vmap(
        lambda xi, ei, gi: _dispatch(xi, ei, gi, e, k, cap, dtype))(
        xg, eg, gg)                                           # [g,e,cap,d]
    buf = constrain(buf, "batch", exp_ax, "capacity", None)
    up = jnp.einsum("gecd,edf->gecf", buf, p["w_up"].astype(dtype))
    if cfg.gated_mlp:
        gt = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"].astype(dtype))
        h = act(gt) * up
    else:
        h = act(up)
    h = constrain(h, "batch", exp_ax, "capacity", mlp_ax)
    out = jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(dtype))
    out = constrain(out, "batch", exp_ax, "capacity", None)
    y = jax.vmap(lambda oi, di, wi: _combine(oi, di, wi, tg, k, dtype))(
        out, dest, wgt)                                       # [g,tg,d]
    return y.reshape(b, t, d), aux
