"""Decoder-only transformer LM (dense / MoE / VLM-stub) with scanned layers.

Covers olmoe, grok-1, phi-3-vision (backbone + patch-embedding stub),
minicpm, nemotron-4, qwen1.5, granite, mellum2.  Layers are stacked on a
leading 'layers' axis and applied with jax.lax.scan (+ optional remat) so
the HLO stays compact at 80+ layers.  Where layers differ in kind (window
and full attention, per-layer RoPE), ``attention.layer_kinds`` gives the
per-layer tables as arrays, which ride in the scans' inputs beside the
layer parameters; a config whose layers are alike scans its parameters
alone.

Decode and prefill of an MoE config run the dropless held-expert layer
(``moe.moe_held_apply``); the full forward (training) runs it too where
the config holds a share of the experts, else the capacity dispatch.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.parallel.sharding import Boxed, constrain
from . import layers as L
from . import attention as A
from . import moe as M

__all__ = ["lm_init", "lm_apply", "lm_prefill", "lm_decode_step",
           "stack_layer_params", "norm_init", "norm_apply", "mlp_init",
           "mlp_apply"]


def norm_init(cfg, param_dtype=jnp.float32):
    return (L.rmsnorm_init(cfg.d_model, param_dtype) if cfg.norm == "rms"
            else L.layernorm_init(cfg.d_model, param_dtype))


def norm_apply(cfg, p, x):
    return (L.rmsnorm_apply(p, x) if cfg.norm == "rms"
            else L.layernorm_apply(p, x))


def mlp_init(key, cfg, param_dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {"up": L.dense_init(ks[0], cfg.d_model, cfg.d_ff, ("embed", "mlp"),
                            param_dtype=param_dtype),
         "down": L.dense_init(ks[1], cfg.d_ff, cfg.d_model, ("mlp", "embed"),
                              param_dtype=param_dtype)}
    if cfg.gated_mlp:
        p["gate"] = L.dense_init(ks[2], cfg.d_model, cfg.d_ff,
                                 ("embed", "mlp"), param_dtype=param_dtype)
    return p


def mlp_apply(p, x, cfg, dtype=jnp.bfloat16):
    act = L.activation(cfg.act)
    if cfg.gated_mlp:
        up = L.dense_apply(p["up"], x, dtype, cfg.quant_spec())
        g = L.dense_apply(p["gate"], x, dtype, cfg.quant_spec())
        h = act(g) * up
    else:
        # activation folded into the dense epilogue (fused in-kernel on the
        # pallas quantized path; identical math on the other impls)
        h = L.dense_apply(p["up"], x, dtype, cfg.quant_spec(),
                          activation=cfg.act)
    h = constrain(h, "batch", "seq_inner", "mlp")
    return L.dense_apply(p["down"], h, dtype, cfg.quant_spec())


def block_init(key, cfg, param_dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    p = {"ln1": norm_init(cfg, param_dtype),
         "attn": A.attn_init(ks[0], cfg, param_dtype),
         "ln2": norm_init(cfg, param_dtype)}
    if cfg.n_experts:
        p["moe"] = M.moe_init(ks[1], cfg, param_dtype)
    else:
        p["mlp"] = mlp_init(ks[1], cfg, param_dtype)
    return p


def block_apply(p, x, cfg, positions, dtype=jnp.bfloat16, kind=None):
    h, _ = A.attn_apply(p["attn"], norm_apply(cfg, p["ln1"], x), cfg,
                        positions, dtype, kind)
    x = x + h
    if cfg.n_experts and cfg.experts_held:
        h, _ = M.moe_held_apply(p["moe"], norm_apply(cfg, p["ln2"], x), cfg,
                                dtype)
        aux = jnp.zeros((), jnp.float32)
    elif cfg.n_experts:
        h, aux = M.moe_apply(p["moe"], norm_apply(cfg, p["ln2"], x), cfg,
                             dtype)
    else:
        h = mlp_apply(p["mlp"], norm_apply(cfg, p["ln2"], x), cfg, dtype)
        aux = jnp.zeros((), jnp.float32)
    return x + h, aux


def block_decode(p, x, cfg, ck, cv, layer, pos, dtype=jnp.bfloat16,
                 kind=None):
    """One layer of a decode step; ck / cv are the stacked caches.
    Returns (x, ck, cv, counts): the held-expert layer's routes and active
    experts for an MoE config, else None."""
    h, ck, cv = A.attn_decode(p["attn"], norm_apply(cfg, p["ln1"], x), cfg,
                              ck, cv, layer, pos, dtype, kind)
    x = x + h
    counts = None
    if cfg.n_experts:
        with jax.named_scope("moe"):
            h, counts = M.moe_held_apply(
                p["moe"], norm_apply(cfg, p["ln2"], x), cfg, dtype)
    else:
        with jax.named_scope("mlp"):
            h = mlp_apply(p["mlp"], norm_apply(cfg, p["ln2"], x), cfg, dtype)
    return x + h, ck, cv, counts


def stack_layer_params(key, n_layers: int, init_fn):
    """vmap an init over layer keys; prepend 'layers' to every logical axes."""
    stacked = jax.vmap(init_fn)(jax.random.split(key, n_layers))
    return jax.tree.map(
        lambda b: Boxed(b.value, ("layers",) + tuple(b.axes)),
        stacked, is_leaf=lambda x: isinstance(x, Boxed))


def lm_init(key, cfg, param_dtype=None):
    param_dtype = param_dtype or jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    params = {
        "embed": L.embed_init(ks[0], cfg.padded_vocab, cfg.d_model,
                              param_dtype),
        "blocks": stack_layer_params(
            ks[1], cfg.n_layers, lambda k: block_init(k, cfg, param_dtype)),
        "final_norm": norm_init(cfg, param_dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(ks[2], cfg.d_model, cfg.padded_vocab,
                                         ("embed", "vocab"),
                                         param_dtype=param_dtype)
    if cfg.frontend:
        # modality stub: a learned projection applied to precomputed
        # patch/frame embeddings supplied by input_specs().
        params["frontend_proj"] = L.dense_init(
            ks[3], cfg.d_model, cfg.d_model, ("embed_nofsdp", None),
            param_dtype=param_dtype)
    return params


def _layer_inputs(params, cfg):
    """The layer scans' inputs: the stacked layer parameters, with the
    per-layer attention tables beside them where layers differ."""
    kinds = A.layer_kinds(cfg)
    return params["blocks"] if kinds is None else (params["blocks"], kinds)


def _split_layer(xs, cfg):
    """(layer parameters, kind) of one scan step's inputs."""
    return (xs, None) if not cfg.has_layer_kinds else xs


def _run_blocks(params, x, cfg, positions, dtype):
    def body(carry, xs):
        h, aux = carry
        layer_params, kind = _split_layer(xs, cfg)
        h2, a = block_apply(layer_params, h, cfg, positions, dtype, kind)
        return (h2, aux + a), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                               _layer_inputs(params, cfg),
                               unroll=cfg.scan_unroll)
    return x, aux


def _logits(params, x, cfg, dtype):
    x = norm_apply(cfg, params["final_norm"], x)
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            logits = L.embed_logits(params["embed"], x, dtype)
        else:
            logits = L.dense_apply(params["lm_head"], x, dtype,
                                   cfg.quant_spec())
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits.astype(jnp.float32) / c)
    logits = constrain(logits, "batch", "seq_inner", "vocab")
    return logits


def lm_apply(params, tokens, cfg, frontend_embeds=None):
    """tokens [B, T] -> (logits [B, T, V], aux).  If the config has a
    modality frontend, `frontend_embeds` [B, F, d] *overwrite* the first F
    positions (packed multimodal sequence: patches/frames + text fill the
    fixed window, so T stays chunk-divisible; loss masks the prefix)."""
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed_apply(params["embed"], tokens, dtype)
    if cfg.frontend:
        fe = L.dense_apply(params["frontend_proj"], frontend_embeds.astype(dtype),
                           dtype)
        x = jax.lax.dynamic_update_slice(x, fe.astype(x.dtype), (0, 0, 0))
    b, t, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    x = constrain(x, "batch", "seq", None)
    x, aux = _run_blocks(params, x, cfg, positions, dtype)
    return _logits(params, x, cfg, dtype), aux


def init_caches(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Stacked per-layer KV caches [L, B, n_kv, hd, S] (boxed)."""
    one = A.init_kv_cache(cfg, batch, max_len, dtype)
    return jax.tree.map(
        lambda b: Boxed(jnp.broadcast_to(b.value[None], (cfg.n_layers,)
                                         + b.value.shape).copy(),
                        ("layers",) + tuple(b.axes)),
        one, is_leaf=lambda x: isinstance(x, Boxed))


def lm_prefill(params, tokens, cfg, max_len: int, frontend_embeds=None):
    """Run the full prompt, return (last-position logits, filled caches).

    Prefill reuses the train-path attention and transposes its K/V into
    the cache order, [L, B, n_kv, hd, max_len] -- keeps one attention
    code path.  tokens: [B, T]; caches sized for max_len >= T.
    """
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed_apply(params["embed"], tokens, dtype)
    if cfg.frontend:
        fe = L.dense_apply(params["frontend_proj"],
                           frontend_embeds.astype(dtype), dtype)
        x = jax.lax.dynamic_update_slice(x, fe.astype(x.dtype), (0, 0, 0))
    b, t, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))

    def body(h, xs):
        layer_params, kind = _split_layer(xs, cfg)
        hn = norm_apply(cfg, layer_params["ln1"], h)
        attn_out, (k, v) = A.attn_apply(layer_params["attn"], hn, cfg,
                                        positions, dtype, kind)
        h = h + attn_out
        if cfg.n_experts:
            m, _ = M.moe_held_apply(layer_params["moe"],
                                    norm_apply(cfg, layer_params["ln2"], h),
                                    cfg, dtype)
        else:
            m = mlp_apply(layer_params["mlp"],
                          norm_apply(cfg, layer_params["ln2"], h), cfg, dtype)
        # store unrepeated KV (first n_kv of the repeated heads are a
        # superset copy; slice group leads)
        rep = cfg.n_heads // cfg.n_kv_heads
        kc = k[:, :, ::rep, :]
        vc = v[:, :, ::rep, :]
        pad = max_len - t
        kc = jnp.pad(kc, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(vc, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return h + m, {"k": jnp.moveaxis(kc, 1, 3),
                       "v": jnp.moveaxis(vc, 1, 3)}

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, caches = jax.lax.scan(body_fn, x, _layer_inputs(params, cfg),
                             unroll=cfg.scan_unroll)
    logits = _logits(params, x[:, -1:, :], cfg, dtype)
    return logits, caches


def lm_decode_step(params, tokens, pos, caches, cfg, moe_counts=False):
    """One decode step.  tokens [B, 1]; pos [B]; caches from init/prefill.

    The stacked caches ride in the layer scan's carry with the layer
    index, and each layer writes its K/V column into them, so a donated
    cache is updated in place.
    Returns (logits [B, 1, V], updated caches), and with ``moe_counts``
    (an MoE config) also int32 [L, 2]: per layer, the token-routes that
    landed on held experts and the held experts with a route.
    """
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed_apply(params["embed"], tokens, dtype)
    x = constrain(x, "batch", None, None)

    def body(carry, xs):
        h, layer, ck, cv = carry
        layer_params, kind = _split_layer(xs, cfg)
        h, ck, cv, counts = block_decode(layer_params, h, cfg, ck, cv, layer,
                                         pos, dtype, kind)
        return (h, layer + 1, ck, cv), counts

    (x, _, ck, cv), counts = jax.lax.scan(
        body, (x, jnp.int32(0), caches["k"], caches["v"]),
        _layer_inputs(params, cfg), unroll=cfg.scan_unroll)
    out = (_logits(params, x, cfg, dtype), {"k": ck, "v": cv})
    return out + (counts,) if moe_counts else out
