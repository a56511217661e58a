"""Grouped-query attention with RoPE, chunked (flash-style) causal
computation for long sequences, and a single-token decode path over a
preallocated KV cache.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.sharding import box, constrain
from . import layers as L

__all__ = ["attn_init", "attn_apply", "attn_decode", "init_kv_cache"]

NEG_INF = -1e30


def attn_init(key, cfg, param_dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    hd = cfg.head_dim
    p = {
        "wq": L.dense_init(ks[0], cfg.d_model, cfg.n_heads * hd,
                           ("embed", "heads"), bias=cfg.qkv_bias,
                           param_dtype=param_dtype),
        "wk": L.dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd,
                           ("embed", "kv_heads"), bias=cfg.qkv_bias,
                           param_dtype=param_dtype),
        "wv": L.dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd,
                           ("embed", "kv_heads"), bias=cfg.qkv_bias,
                           param_dtype=param_dtype),
        "wo": L.dense_init(ks[3], cfg.n_heads * hd, cfg.d_model,
                           ("heads", "embed"), param_dtype=param_dtype),
    }
    return p


def _project_qkv(p, x, cfg, positions, dtype):
    b, t, _ = x.shape
    hd = cfg.head_dim
    q = L.dense_apply(p["wq"], x, dtype, cfg.quant_spec())
    k = L.dense_apply(p["wk"], x, dtype, cfg.quant_spec())
    v = L.dense_apply(p["wv"], x, dtype, cfg.quant_spec())
    q = q.reshape(b, t, cfg.n_heads, hd)
    k = k.reshape(b, t, cfg.n_kv_heads, hd)
    v = v.reshape(b, t, cfg.n_kv_heads, hd)
    q, k = L.rope(q, k, positions, hd, cfg.rope_theta)
    q = constrain(q, "batch", "seq_inner", "heads", "head_dim")
    k = constrain(k, "batch", "seq_inner", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq_inner", "kv_heads", "head_dim")
    return q, k, v


def _repeat_kv(k, n_heads):
    """[B, S, n_kv, D] -> [B, S, n_heads, D] by group repetition."""
    n_kv = k.shape[2]
    if n_kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // n_kv, axis=2)


def _dense_causal(q, k, v, q_offset: int = 0):
    """Plain causal attention; q: [B,T,H,D], k/v already head-repeated."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / np.sqrt(d)
    qi = jnp.arange(tq)[:, None] + q_offset
    ki = jnp.arange(tk)[None, :]
    scores = jnp.where(ki <= qi, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _chunked_causal(q, k, v, chunk_q: int, chunk_kv: int):
    """Flash-style blockwise causal attention with online softmax.

    Memory is O(chunk_q * chunk_kv) per (batch, head) instead of O(T^2).
    Fully-masked kv blocks (kv_start > q_end) still occupy the scan but
    contribute nothing; see EXPERIMENTS.md SS Perf for the triangular-schedule
    iteration.
    """
    b, t, h, d = q.shape
    nq, nk = t // chunk_q, t // chunk_kv
    qb = q.reshape(b, nq, chunk_q, h, d)
    kb = k.reshape(b, nk, chunk_kv, h, d)
    vb = v.reshape(b, nk, chunk_kv, h, d)
    scale = 1.0 / np.sqrt(d)

    def q_block(qi, qblk):
        # online softmax over kv blocks
        def kv_step(carry, inputs):
            acc, m, l = carry
            ki_idx, kblk, vblk = inputs
            s = jnp.einsum("bqhd,bkhd->bhqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            qpos = qi * chunk_q + jnp.arange(chunk_q)[:, None]
            kpos = ki_idx * chunk_kv + jnp.arange(chunk_kv)[None, :]
            s = jnp.where(kpos <= qpos, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(qblk.dtype), vblk
            ).astype(jnp.float32)
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, h, chunk_q, d), jnp.float32)
        m0 = jnp.full((b, h, chunk_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, chunk_q), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (jnp.arange(nk), jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return jnp.moveaxis(out, 1, 2)          # [b, chunk_q, h, d]

    outs = jax.lax.map(lambda args: q_block(*args),
                       (jnp.arange(nq), jnp.moveaxis(qb, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, t, h, d)
    return out.astype(q.dtype)


def attn_apply(p, x, cfg, positions, dtype=jnp.bfloat16):
    """Full-sequence causal attention (train / prefill)."""
    b, t, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg, positions, dtype)
    k = _repeat_kv(k, cfg.n_heads)
    v = _repeat_kv(v, cfg.n_heads)
    if t > cfg.attn_chunk and t % cfg.attn_chunk == 0:
        out = _chunked_causal(q, k, v, min(cfg.attn_chunk, t), cfg.attn_chunk)
    else:
        out = _dense_causal(q, k, v)
    out = constrain(out, "batch", "seq_inner", "heads", "head_dim")
    out = out.reshape(b, t, cfg.n_heads * cfg.head_dim)
    return L.dense_apply(p["wo"], out, dtype, cfg.quant_spec()), (k, v)


def init_kv_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Per-layer KV cache [B, S, n_kv, D] (boxed logical axes for sharding)."""
    hd = cfg.head_dim
    shape = (batch, max_len, cfg.n_kv_heads, hd)
    ax = ("batch", "kv_seq", "kv_heads", "head_dim")
    return {
        "k": box(jnp.zeros(shape, dtype), ax),
        "v": box(jnp.zeros(shape, dtype), ax),
    }


def attn_decode(p, x, cfg, cache_k, cache_v, pos, dtype=jnp.bfloat16):
    """Single-token decode.  x: [B, 1, d]; pos: [B] current positions.

    GQA is computed with a grouped einsum instead of materializing
    `repeat_kv` over the cache: repeating a (possibly seq-sharded) cache
    n_heads/n_kv-fold forces an 8x resident blow-up and a reshard under
    GSPMD (observed: +200 GB collectives/step on qwen decode_32k).

    Returns (out [B,1,d], new_k, new_v) -- caller scatters into the cache.
    """
    b = x.shape[0]
    hd = cfg.head_dim
    n_kv = cfg.n_kv_heads
    g = cfg.n_heads // n_kv
    positions = pos[:, None]
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, dtype)
    # scatter the new token into the cache at `pos`
    with jax.named_scope("kv_write"):
        upd_idx = (jnp.arange(b), pos)
        cache_k = cache_k.at[upd_idx].set(k_new[:, 0])
        cache_v = cache_v.at[upd_idx].set(v_new[:, 0])
    with jax.named_scope("attention"):
        qg = q.reshape(b, 1, n_kv, g, hd)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, cache_k,
                            preferred_element_type=jnp.float32) / np.sqrt(hd)
        valid = jnp.arange(cache_k.shape[1])[None, None, None, None, :] <= \
            pos[:, None, None, None, None]
        scores = jnp.where(valid, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs, cache_v)
        out = out.reshape(b, 1, cfg.n_heads * hd)
    return (L.dense_apply(p["wo"], out, dtype, cfg.quant_spec()),
            cache_k, cache_v)
