"""Grouped-query attention with RoPE, chunked (flash-style) causal
computation for long sequences, and a single-token decode path over a
preallocated KV cache stored sequence-minor, [L, B, n_kv, D, S], and
updated in place one column per token.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.sharding import box, constrain
from . import layers as L

__all__ = ["attn_init", "attn_apply", "attn_decode", "attend_kv_cache",
           "init_kv_cache", "write_kv_column"]

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
    """Per-layer KV cache [B, n_kv, D, S] (boxed logical axes for sharding).

    The sequence axis is minor: the order decode attention reads the cache
    in, so the step reads it in place and writes one column per token."""
    hd = cfg.head_dim
    shape = (batch, cfg.n_kv_heads, hd, max_len)
    ax = ("batch", "kv_heads", "head_dim", "kv_seq")
    return {
        "k": box(jnp.zeros(shape, dtype), ax),
        "v": box(jnp.zeros(shape, dtype), ax),
    }


def write_kv_column(cache, col, layer, pos):
    """Write each slot's new K or V into the stacked cache.

    cache: [L, B, n_kv, D, S]; col: [B, n_kv, D]; layer: scalar int32;
    pos: [B].  Slot b's column lands at (layer, b, :, :, pos[b]), by one
    ``dynamic_update_slice`` per slot, which XLA does in place on a
    carried (donated) cache; a scatter would copy the stack.

    The layout XLA gives the carried cache follows the updates, so each
    update is shaped to leave it sequence-minor and unpadded: it goes
    through the [L, B, n_kv * D, S] view (a bitcast), and is built from a
    1-D row of the column, which has one layout.  Against a
    [1, 1, n_kv, D, 1] update, or one sliced out of a [B, 1, n_kv * D, 1]
    column that a kernel wrote batch-minor, the TPU compiler lays the
    stack out otherwise and copies it in and out every step."""
    n_layers, b, n_kv, hd, s = cache.shape
    flat = cache.reshape(n_layers, b, n_kv * hd, s)
    rows = col.reshape(b, n_kv * hd).astype(cache.dtype)
    zero = jnp.zeros((), jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    for i in range(b):
        flat = jax.lax.dynamic_update_slice(
            flat, rows[i].reshape(1, 1, n_kv * hd, 1),
            (layer, jnp.int32(i), zero, pos[i]))
    return flat.reshape(cache.shape)


def attend_kv_cache(q, cache_k, cache_v, pos, dtype=jnp.bfloat16):
    """One query token per slot against a layer's cache.

    q: [B, 1, H, D]; cache_k / cache_v: [B, n_kv, D, S]; pos: [B].
    Positions past ``pos[b]`` are masked.  GQA is a grouped einsum, not a
    ``repeat_kv`` of the cache: repeating a (possibly seq-sharded) cache
    n_heads/n_kv-fold forces an 8x resident blow-up and a reshard under
    GSPMD (observed: +200 GB collectives/step on qwen decode_32k).
    Returns [B, 1, H * D]."""
    b, _, h, hd = q.shape
    n_kv, s = cache_k.shape[1], cache_k.shape[3]
    qg = q.reshape(b, n_kv, h // n_kv, hd)
    scores = jnp.einsum("bkgd,bkds->bkgs", qg, cache_k,
                        preferred_element_type=jnp.float32) / np.sqrt(hd)
    valid = jnp.arange(s)[None, None, None, :] <= pos[:, None, None, None]
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    out = jnp.einsum("bkgs,bkds->bkgd", probs, cache_v)
    return out.reshape(b, 1, h * hd)


def attn_decode(p, x, cfg, cache_k, cache_v, layer, pos,
                dtype=jnp.bfloat16):
    """Single-token decode of one layer against the stacked cache.

    x: [B, 1, d]; cache_k / cache_v: [L, B, n_kv, D, S]; layer: this
    layer's index; pos: [B] current positions.  Writes the token's K/V
    column, then attends over the layer's slice of the updated stack.
    Returns (out [B, 1, d], cache_k, cache_v)."""
    q, k_new, v_new = _project_qkv(p, x, cfg, pos[:, None], dtype)
    with jax.named_scope("kv_write"):
        cache_k = write_kv_column(cache_k, k_new[:, 0], layer, pos)
        cache_v = write_kv_column(cache_v, v_new[:, 0], layer, pos)
    with jax.named_scope("attention"):
        out = attend_kv_cache(
            q, jax.lax.dynamic_index_in_dim(cache_k, layer, keepdims=False),
            jax.lax.dynamic_index_in_dim(cache_v, layer, keepdims=False),
            pos, dtype)
    return (L.dense_apply(p["wo"], out, dtype, cfg.quant_spec()),
            cache_k, cache_v)
