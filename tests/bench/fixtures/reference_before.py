"""The dense reference's layer and driver as they stood before a
configuration could name its own reference: one jitted block with the
attention and MLP inline, static arguments of fixed keys, no drawer or
block arguments.  ``test_bench_modules`` holds ``reference.logit_gaps``
bit for bit to this.  The helpers it calls are unchanged and imported."""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from reference import (_gaps, act, embedding, layer_weights, lm_head, qdense,
                       rmsnorm, rope)


@functools.partial(jax.jit, static_argnames=("model_items", "qmax"))
def _layer(w, x, model_items, qmax):
    model = dict(model_items)
    s, t, _ = x.shape
    h, kvh, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    with jax.default_matmul_precision("highest"):
        y = rmsnorm(x)
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
        x = x + qdense(o.reshape(s, t, h * hd), w["wo"], qmax)
        y = rmsnorm(x)
        if "gate" in w:
            m = act(model["act"], qdense(y, w["gate"], qmax)) * \
                qdense(y, w["up"], qmax)
        else:
            m = act(model["act"], qdense(y, w["up"], qmax))
        return x + qdense(m, w["down"], qmax)


def _items(model: dict):
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "act", "rope_theta", "gated_mlp")
    return tuple((k, model[k]) for k in keys)


def final_hidden(seed: int, model: dict, tokens: np.ndarray,
                 qmaxes: List[int]) -> Dict[int, jax.Array]:
    table = embedding(seed, model)
    x0 = jnp.take(table, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    del table
    xs = {q: x0 for q in qmaxes}
    items = _items(model)
    for layer in range(model["n_layers"]):
        w = layer_weights(seed, model, layer)
        xs = {q: _layer(w, x, items, q) for q, x in xs.items()}
    return {q: rmsnorm(x) for q, x in xs.items()}


def logit_gaps(seed: int, model: dict, seqs: List[List[int]],
               starts: List[int], length: int, qmax: int,
               control_qmax=None) -> dict:
    tokens = np.zeros((len(seqs), length), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    grids = [qmax] + ([control_qmax] if control_qmax else [])
    hid = final_hidden(seed, model, tokens, grids)
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
