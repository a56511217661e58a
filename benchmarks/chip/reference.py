"""Plain float32 reference of the served decoder, for the correctness check.

Imports nothing of the program under test and takes nothing it made.  It
draws the weights itself from the seed, by the same key tree and the same
truncated-normal initializer as the model code (so both sides hold the
same bfloat16 weights), and runs the whole sequence at once: no kernels,
no cache, no batching of unrelated requests, float32 with the highest
matmul precision, layer by layer so that it fits after the program has
freed the chip.

The arithmetic is the quantized arithmetic the configuration states:
every linear layer rounds its input per token and its weight per output
channel to the symmetric grid of ``qmax`` (``plane_qmax``), multiplies the
integers exactly and rescales.  Everything else (embedding, RMSNorm,
RoPE, causal softmax attention, gated SiLU MLP, tied or quantized LM head)
is float32.

Departures from the published MiniCPM and Granite descriptions are the
model code's and are mirrored: no muP embedding/residual/logit scaling,
RMSNorm epsilon 1e-6 with unit scales, a vocabulary padded to a multiple
of 128 whose extra rows are initialized like the others.

This is the dense decoder's reference, and the default of every
configuration.  A configuration of another block names its own module of
this directory under ``"reference"``; ``run.check`` calls that module's
``plane_qmax(planes)`` and ``logit_gaps(seed, model, seqs, starts,
length, qmax, control_qmax)``.  Such a module imports this one and writes
only what differs: a per-layer weight drawer ``weights(seed, model,
layer)`` and a jitted per-layer block ``block(w, x, model_items, qmax)``,
handed to ``logit_gaps``.  The block gets the model's entries as the
hashable ``model_items`` (``_items``); what differs from layer to layer,
such as a window, rides in ``w`` as arrays.  The seeding (``_keys``,
``_draw``), ``qdense``, ``rmsnorm``, ``rope``, ``attention``, ``mlp``,
the embedding and head, and ``_gaps`` are its to import.
"""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6


def plane_qmax(planes: int, bits: int = 8) -> int:
    """Largest magnitude that ``planes`` radix-4 digit planes represent:
    2 (4^p - 1) / 3, clipped to the signed range of ``bits``."""
    return min(2 * (4 ** planes - 1) // 3, (1 << (bits - 1)) - 1)


def pad_vocab(v: int, multiple: int = 128) -> int:
    return -(-v // multiple) * multiple


# -- weights from the seed ----------------------------------------------------

def _normal(key, shape, scale: float, dtype):
    std = np.float32(scale / np.sqrt(max(shape[0], 1)))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def _keys(seed: int, n_layers: int):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return ks[0], jax.random.split(ks[1], n_layers), ks[2]


@functools.partial(jax.jit, static_argnames=("shape", "scale", "dtype"))
def _draw(key, shape, scale, dtype):
    return _normal(key, shape, scale, dtype)


def embedding(seed: int, model: dict) -> jax.Array:
    key, _, _ = _keys(seed, model["n_layers"])
    d = model["d_model"]
    return _draw(key, (pad_vocab(model["vocab_size"]), d),
                 float(np.sqrt(d)), model["param_dtype"])


def lm_head(seed: int, model: dict) -> jax.Array:
    _, _, key = _keys(seed, model["n_layers"])
    return _draw(key, (model["d_model"], pad_vocab(model["vocab_size"])),
                 1.0, model["param_dtype"])


def layer_weights(seed: int, model: dict, layer: int) -> Dict[str, jax.Array]:
    _, layer_keys, _ = _keys(seed, model["n_layers"])
    attn_key, mlp_key, _, _ = jax.random.split(layer_keys[layer], 4)
    d, hd, ff = model["d_model"], model["head_dim"], model["d_ff"]
    q, kv = model["n_heads"] * hd, model["n_kv_heads"] * hd
    dt = model["param_dtype"]
    ka = jax.random.split(attn_key, 4)
    km = jax.random.split(mlp_key, 3)
    w = {"wq": _draw(ka[0], (d, q), 1.0, dt),
         "wk": _draw(ka[1], (d, kv), 1.0, dt),
         "wv": _draw(ka[2], (d, kv), 1.0, dt),
         "wo": _draw(ka[3], (q, d), 1.0, dt),
         "up": _draw(km[0], (d, ff), 1.0, dt),
         "down": _draw(km[1], (ff, d), 1.0, dt)}
    if model.get("gated_mlp", True):
        w["gate"] = _draw(km[2], (d, ff), 1.0, dt)
    return w


# -- the forward pass ---------------------------------------------------------

def qdense(x, w, qmax: int):
    """x [..., K] @ w [K, N] on the symmetric integer grid of ``qmax``:
    per-row input scales, per-column weight scales, exact integer
    products, float32 rescale."""
    w = w.astype(jnp.float32)
    sx = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-8) / qmax
    sw = jnp.maximum(jnp.max(jnp.abs(w), 0, keepdims=True), 1e-8) / qmax
    qx = jnp.clip(jnp.round(x / sx), -qmax, qmax)
    qw = jnp.clip(jnp.round(w / sw), -qmax, qmax)
    return (qx @ qw) * sx * sw


def rmsnorm(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)


def rope(x, theta: float):
    """x [S, T, H, D] rotated by position along T."""
    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs     # [T, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def act(name: str, x):
    if name != "silu":
        raise ValueError(f"the reference knows SiLU only, not {name!r}")
    return jax.nn.silu(x)


def attention(w, y, model: dict, qmax: int):
    """Causal softmax attention of the normalized ``y`` [S, T, d] with
    RoPE on every position, through the output projection."""
    s, t, _ = y.shape
    h, kvh, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    q = qdense(y, w["wq"], qmax).reshape(s, t, h, hd)
    k = qdense(y, w["wk"], qmax).reshape(s, t, kvh, hd)
    v = qdense(y, w["wv"], qmax).reshape(s, t, kvh, hd)
    q, k = rope(q, model["rope_theta"]), rope(k, model["rope_theta"])
    k = jnp.repeat(k, h // kvh, axis=2)
    v = jnp.repeat(v, h // kvh, axis=2)
    scores = jnp.einsum("sqhd,skhd->shqk", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    o = jnp.einsum("shqk,skhd->sqhd", jax.nn.softmax(scores, -1), v)
    return qdense(o.reshape(s, t, h * hd), w["wo"], qmax)


def mlp(w, y, model: dict, qmax: int):
    """The (gated) MLP of the normalized ``y``."""
    if "gate" in w:
        m = act(model["act"], qdense(y, w["gate"], qmax)) * \
            qdense(y, w["up"], qmax)
    else:
        m = act(model["act"], qdense(y, w["up"], qmax))
    return qdense(m, w["down"], qmax)


@functools.partial(jax.jit, static_argnames=("model_items", "qmax"))
def _layer(w, x, model_items, qmax):
    """The dense decoder layer: pre-norm attention, then pre-norm MLP."""
    model = dict(model_items)
    with jax.default_matmul_precision("highest"):
        x = x + attention(w, rmsnorm(x), model, qmax)
        return x + mlp(w, rmsnorm(x), model, qmax)


def _logits(h, head, qmax: int, tied: bool):
    if tied:
        return h @ head.astype(jnp.float32).T
    return qdense(h, head, qmax)


@functools.partial(jax.jit, static_argnames=("qmax", "control_qmax", "tied"))
def _gaps(hid, hid_control, head, targets, qmax, control_qmax, tied):
    """Per position of each row: the reference's best logit less its
    logit of the target, and less its logit of the token that the
    control grid puts first (zeros without a control)."""
    def row(args):
        h, hc, target = args
        with jax.default_matmul_precision("highest"):
            ref = _logits(h, head, qmax, tied)
            best = ref.max(-1)
            served = best - jnp.take_along_axis(ref, target[:, None], -1)[:, 0]
            if not control_qmax:
                return served, jnp.zeros_like(served)
            pick = _logits(hc, head, control_qmax, tied).argmax(-1)
        return served, best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return jax.lax.map(row, (hid, hid_control, targets))


def _items(model):
    """The model's entries, hashable, as a block's static argument:
    a dict as its sorted pairs, a list as a tuple, at every depth."""
    if isinstance(model, dict):
        return tuple(sorted((k, _items(v)) for k, v in model.items()))
    if isinstance(model, list):
        return tuple(_items(v) for v in model)
    return model


def final_hidden(seed: int, model: dict, tokens: np.ndarray,
                 qmaxes: List[int], weights=layer_weights,
                 block=_layer) -> Dict[int, jax.Array]:
    """Normalized final hidden states [S, T, d] of ``tokens`` [S, T]
    under each grid in ``qmaxes``: ``block`` applied to each layer's
    ``weights(seed, model, layer)``, drawn once per layer."""
    table = embedding(seed, model)
    x0 = jnp.take(table, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    del table
    xs = {q: x0 for q in qmaxes}
    items = _items(model)
    for layer in range(model["n_layers"]):
        w = weights(seed, model, layer)
        xs = {q: block(w, x, items, q) for q, x in xs.items()}
    return {q: rmsnorm(x) for q, x in xs.items()}


def logit_gaps(seed: int, model: dict, seqs: List[List[int]],
               starts: List[int], length: int, qmax: int,
               control_qmax=None, weights=layer_weights,
               block=_layer) -> dict:
    """For each sequence (prompt + served tokens) and each served token
    (positions ``starts[i]`` onward), the gap by which the reference's
    logit of that token lies below the reference's best.  With
    ``control_qmax``, also the gap of the token that the reference at
    that coarser grid puts first.  Sequences are padded to ``length``
    positions, so every run of a cell compiles the same shapes.  The
    layers are ``weights`` and ``block`` (``final_hidden``).  Returns
    numpy arrays of the gaps of all served tokens."""
    tokens = np.zeros((len(seqs), length), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    grids = [qmax] + ([control_qmax] if control_qmax else [])
    hid = final_hidden(seed, model, tokens, grids, weights, block)
    tied = bool(model.get("tie_embeddings", False))
    head = embedding(seed, model) if tied else lm_head(seed, model)
    targets = np.zeros_like(tokens)
    targets[:, :-1] = tokens[:, 1:]
    served, control = _gaps(hid[qmax], hid[control_qmax or qmax], head,
                            jnp.asarray(targets), qmax, control_qmax, tied)
    served, control = np.asarray(served), np.asarray(control)
    rows = [(i, np.arange(starts[i] - 1, len(s) - 1))
            for i, s in enumerate(seqs)]
    out = {"served": np.concatenate([served[i, p] for i, p in rows])}
    if control_qmax:
        out["control"] = np.concatenate([control[i, p] for i, p in rows])
    return out
