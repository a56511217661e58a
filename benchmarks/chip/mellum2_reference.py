"""Plain float32 reference of Mellum2-12B-A2.5B's block, for the check.

The dense reference (``reference.py``) does the seeding, the embedding,
the untied quantized LM head and the comparison; this module writes the
two things that differ, as its contract asks: the weights of one layer
and the jitted block.  It imports nothing of the program under test.

Weights are drawn from the seed by the model code's key tree and
initializer: a layer's key splits into attention and MoE keys; the MoE
key into the router and the ``w_up``, ``w_down``, ``w_gate`` keys, each
of which splits into one key per expert of the layer's 64, of which the
held ones are drawn.  What differs by layer, the window and the RoPE
tables, rides in the weights as arrays.

The block is pre-norm attention, then a pre-norm MoE layer, in float32
at the highest matmul precision, with every projection and expert matrix
on the configuration's integer grid (``qdense``):

- attention: grouped-query heads, RoPE from the layer's own inverse
  frequencies and cos/sin scale (the default RoPE on sliding layers,
  transformers' YaRN on full ones, transcribed here with numpy), a causal
  mask and, on sliding layers, the window: a query at p sees keys
  p - window < j <= p.  Computed one sequence at a time, so that eight
  2048-position sequences fit;
- MoE: a float32 router over all experts, softmax, top-k, renormalised
  over the k.  Every held expert runs over every token (gated SiLU), and
  its output counts by the token's gate for it, 0 where the token was not
  routed to it.  Routes to experts not held here add nothing, as on the
  chip that serves this share.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import reference as R

plane_qmax = R.plane_qmax
NO_WINDOW = 1 << 30
# YaRN's published betas and attention factor, Mellum2's as transformers'
BETA_FAST, BETA_SLOW = 32, 1


def inv_freq_table(model: dict, kind: str):
    """(inverse frequencies float32 [head_dim // 2], cos/sin scale) of a
    sliding ("S") or full ("F") layer."""
    dim, base = model["head_dim"], float(model["rope_theta"])
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    factor = float(model.get("yarn_factor", 0.0))
    if kind == "S" or factor <= 0:
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    orig = model["yarn_original_max_position"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / \
            (2 * math.log(base))
    low = max(math.floor(correction_dim(BETA_FAST)), 0)
    high = min(math.ceil(correction_dim(BETA_SLOW)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1 - ramp                      # the extrapolated share
    inv = (1.0 / (factor * pos_freqs)) * (1 - keep) + (1.0 / pos_freqs) * keep
    return inv.astype(np.float32), 0.1 * math.log(factor) + 1


def layer_kind(model: dict, layer: int) -> str:
    pattern = model.get("layer_pattern") or "F"
    return pattern[layer % len(pattern)]


def weights(seed: int, model: dict, layer: int):
    """Layer ``layer``'s weights and per-layer tables."""
    _, layer_keys, _ = R._keys(seed, model["n_layers"])
    attn_key, moe_key, _, _ = jax.random.split(layer_keys[layer], 4)
    d, hd, ff = model["d_model"], model["head_dim"], model["d_ff"]
    q, kv = model["n_heads"] * hd, model["n_kv_heads"] * hd
    e = model["n_experts"]
    first = model.get("experts_first", 0)
    held = model.get("experts_held") or e
    dt = model["param_dtype"]
    ka = jax.random.split(attn_key, 4)
    km = jax.random.split(moe_key, 4)

    def experts(key, shape):
        keys = jax.random.split(key, e)[first:first + held]
        return jnp.stack([R._draw(k, shape, 1.0, dt) for k in keys])
    kind = layer_kind(model, layer)
    inv, scale = inv_freq_table(model, kind)
    return {"wq": R._draw(ka[0], (d, q), 1.0, dt),
            "wk": R._draw(ka[1], (d, kv), 1.0, dt),
            "wv": R._draw(ka[2], (d, kv), 1.0, dt),
            "wo": R._draw(ka[3], (q, d), 1.0, dt),
            "router": R._draw(km[0], (d, e), 1.0, dt),
            "up": experts(km[1], (d, ff)),
            "down": experts(km[2], (ff, d)),
            "gate": experts(km[3], (d, ff)),
            "inv_freq": jnp.asarray(inv),
            "rope_scale": jnp.float32(scale),
            "window": jnp.int32(model["sliding_window"] if kind == "S"
                                else NO_WINDOW)}


def rope(x, inv_freq, scale):
    """x [T, H, D] rotated by position along T, cos and sin scaled."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(w, y, model: dict, qmax: int):
    """Windowed or full causal attention of the normalized ``y``
    [S, T, d], one sequence at a time, through the output projection."""
    s, t, _ = y.shape
    h, kvh, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    q = R.qdense(y, w["wq"], qmax).reshape(s, t, h, hd)
    k = R.qdense(y, w["wk"], qmax).reshape(s, t, kvh, hd)
    v = R.qdense(y, w["wv"], qmax).reshape(s, t, kvh, hd)
    i = jnp.arange(t)
    seen = (i[None, :] <= i[:, None]) & (i[None, :] > i[:, None] - w["window"])

    def one(args):
        qs, ks, vs = args
        qs = rope(qs, w["inv_freq"], w["rope_scale"])
        ks = jnp.repeat(rope(ks, w["inv_freq"], w["rope_scale"]), h // kvh, 1)
        vs = jnp.repeat(vs, h // kvh, 1)
        scores = jnp.einsum("qhd,khd->hqk", qs, ks) / np.sqrt(hd)
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), vs)
    o = jax.lax.map(one, (q, k, v))
    return R.qdense(o.reshape(s, t, h * hd), w["wo"], qmax)


def moe(w, y, model: dict, qmax: int):
    """The held experts' part of the MoE layer of the normalized ``y``."""
    k = model["experts_per_token"]
    first = model.get("experts_first", 0)
    held = w["up"].shape[0]
    probs = jax.nn.softmax(y @ w["router"].astype(jnp.float32), -1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / gate.sum(-1, keepdims=True)
    out = jnp.zeros_like(y)
    for j in range(held):
        g = jnp.where(idx == first + j, gate, 0.0).sum(-1)[..., None]
        m = R.act(model["act"], R.qdense(y, w["gate"][j], qmax)) * \
            R.qdense(y, w["up"][j], qmax)
        out = out + g * R.qdense(m, w["down"][j], qmax)
    return out


@functools.partial(jax.jit, static_argnames=("model_items", "qmax"))
def block(w, x, model_items, qmax):
    """Pre-norm attention, then the pre-norm held-expert layer."""
    model = dict(model_items)
    with jax.default_matmul_precision("highest"):
        x = x + attention(w, R.rmsnorm(x), model, qmax)
        return x + moe(w, R.rmsnorm(x), model, qmax)


def logit_gaps(seed, model, seqs, starts, length, qmax, control_qmax=None):
    return R.logit_gaps(seed, model, seqs, starts, length, qmax,
                        control_qmax, weights=weights, block=block)
