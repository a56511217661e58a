"""pjit step builders: train_step / serve_step for any registered arch.

The same builders serve three callers:
  * the real training loop (examples/, launch/train.py) on CPU smoke scale;
  * the multi-pod dry-run (launch/dryrun.py) which lowers + compiles the
    identical code against ShapeDtypeStructs on a 256/512-device mesh;
  * the benchmarks.

State layout (one pytree, checkpointable as-is):
    TrainState(params, opt: OptState, residual | None)

Sharding derivation: params are init'd as Boxed(value, logical_axes);
``state_shardings`` maps logical axes -> NamedShardings through the active
AxisRules.  Batch inputs use the 'batch' rule on dim 0.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.api import get_api, loss_fn
from repro.parallel import sharding as sh
from . import optimizer as opt
from . import compress as comp

__all__ = ["TrainState", "make_train_step", "make_serve_step",
           "routes_experts",
           "abstract_train_state", "train_state_shardings",
           "batch_specs", "batch_shardings", "init_train_state",
           "decode_state_shardings", "abstract_decode_state"]


class TrainState(NamedTuple):
    params: Any
    opt: opt.OptState
    residual: Any          # error-feedback residual tree, or () if unused


# ---------------------------------------------------------------------------
# state construction / abstraction
# ---------------------------------------------------------------------------

def _boxed_init(cfg):
    api = get_api(cfg)
    def f(key):
        return api.init(key, cfg)
    return f


def init_train_state(key, cfg, opt_cfg: opt.OptConfig,
                     grad_compress: bool = False) -> TrainState:
    boxed = _boxed_init(cfg)(key)
    params = sh.unbox(boxed)
    state = opt.init_opt_state(params, opt_cfg)
    residual = comp.init_residual(params) if grad_compress else ()
    return TrainState(params, state, residual)


def abstract_train_state(cfg, opt_cfg: opt.OptConfig,
                         grad_compress: bool = False):
    """(abstract TrainState, boxed-axes param tree) — no allocation."""
    boxed = jax.eval_shape(_boxed_init(cfg), jax.random.PRNGKey(0))
    axes = sh.boxed_axes(boxed)
    params = sh.unbox(boxed)
    mdt = jnp.dtype(opt_cfg.moment_dtype)
    moment = lambda p: jax.ShapeDtypeStruct(p.shape, mdt)
    state = opt.OptState(step=jax.ShapeDtypeStruct((), jnp.int32),
                         mu=jax.tree.map(moment, params),
                         nu=jax.tree.map(moment, params))
    residual = (jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params)
        if grad_compress else ())
    return TrainState(params, state, residual), axes


def train_state_shardings(axes_tree, mesh: Mesh, rules: sh.AxisRules,
                          grad_compress: bool = False) -> TrainState:
    pshard = sh.named_sharding_tree(axes_tree, mesh, rules)
    scalar = NamedSharding(mesh, P())
    state = opt.OptState(step=scalar,
                         mu=jax.tree.map(lambda s: s, pshard),
                         nu=jax.tree.map(lambda s: s, pshard))
    residual = jax.tree.map(lambda s: s, pshard) if grad_compress else ()
    return TrainState(pshard, state, residual)


# ---------------------------------------------------------------------------
# batch specs
# ---------------------------------------------------------------------------

def batch_specs(cfg, global_batch: int, seq_len: int) -> Dict[str, Any]:
    """ShapeDtypeStructs for one training batch."""
    specs = {
        "tokens": jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32),
        "labels": jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32),
    }
    if cfg.frontend:
        specs["frontend"] = jax.ShapeDtypeStruct(
            (global_batch, cfg.frontend_tokens, cfg.d_model), jnp.float32)
    return specs


def batch_shardings(cfg, mesh: Mesh, rules: sh.AxisRules,
                    global_batch: int) -> Dict[str, Any]:
    batch_axes = rules.resolve("batch")
    # a global batch smaller than the DP shard count cannot be sharded
    n_shards = 1
    if batch_axes:
        names = (batch_axes,) if isinstance(batch_axes, str) else batch_axes
        n_shards = int(np.prod([mesh.shape[a] for a in names]))
    ax = batch_axes if global_batch % max(n_shards, 1) == 0 and \
        global_batch >= n_shards else None
    tok = NamedSharding(mesh, P(ax, None))
    out = {"tokens": tok, "labels": tok}
    if cfg.frontend:
        out["frontend"] = NamedSharding(mesh, P(ax, None, None))
    return out


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def make_train_step(cfg, opt_cfg: opt.OptConfig, *,
                    grad_compress: bool = False,
                    microbatches: int = 1) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics)."""
    api = get_api(cfg)

    def grads_of(params, batch):
        return jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, api), has_aux=True)(params)

    def train_step(state: TrainState, batch):
        params = state.params
        if microbatches > 1:
            b = batch["tokens"].shape[0]
            assert b % microbatches == 0
            mb = {k: v.reshape(microbatches, b // microbatches, *v.shape[1:])
                  for k, v in batch.items()}

            def acc_fn(carry, micro):
                g_acc, l_acc = carry
                (l, _), g = grads_of(params, micro)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                return (g_acc, l_acc + l), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            # cost-variant compiles (cfg.scan_unroll > 1) unroll the
            # microbatch loop too, so cost_analysis counts every microbatch
            (grads, loss_sum), _ = jax.lax.scan(
                acc_fn, (g0, 0.0), mb,
                unroll=microbatches if cfg.scan_unroll > 1 else 1)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            metrics = {"loss": loss_sum / microbatches,
                       "aux_loss": jnp.zeros((), jnp.float32),
                       "tokens": jnp.asarray(
                           float(batch["tokens"].size), jnp.float32)}
        else:
            (total, metrics), grads = grads_of(params, batch)

        residual = state.residual
        if grad_compress:
            grads, residual = comp.ef_compress_update(grads, residual)
        new_params, new_opt, om = opt.adamw_update(params, grads,
                                                   state.opt, opt_cfg)
        metrics = dict(metrics, **om)
        return TrainState(new_params, new_opt, residual), metrics

    return train_step


# ---------------------------------------------------------------------------
# serve step
# ---------------------------------------------------------------------------

def abstract_decode_state(cfg, batch: int, max_len: int):
    """(abstract unboxed decode state, axes tree) — no allocation."""
    api = get_api(cfg)
    boxed = jax.eval_shape(lambda: api.init_decode(cfg, batch, max_len))
    return sh.unbox(boxed), sh.boxed_axes(boxed)


def decode_state_shardings(axes_tree, mesh: Mesh, rules: sh.AxisRules):
    return sh.named_sharding_tree(axes_tree, mesh, rules)


def routes_experts(cfg) -> bool:
    """Whether ``cfg``'s serve step also returns its expert counts."""
    from repro.models import transformer
    return bool(cfg.n_experts) and \
        get_api(cfg).decode_step is transformer.lm_decode_step


def make_serve_step(cfg) -> Callable:
    """serve_step(params, tokens, pos, state) -> (next_tokens, state).

    One decode step: embeds the new token, attends over the cache /
    recurrent state, greedily samples.  Lowered for decode_* cells.
    For a decoder with experts (``routes_experts``) the step returns
    (next_tokens, state, counts): counts int32 [L, 2] holds each layer's
    token-routes to held experts and its held experts with a route, so
    the caller reads them with the tokens.
    """
    api = get_api(cfg)
    routed = routes_experts(cfg)

    def serve_step(params, tokens, pos, state):
        if routed:
            logits, new_state, counts = api.decode_step(
                params, tokens, pos, state, cfg, moe_counts=True)
        else:
            logits, new_state = api.decode_step(params, tokens, pos, state,
                                                cfg)
        with jax.named_scope("sample"):
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        if routed:
            return nxt[:, None], new_state, counts
        return nxt[:, None], new_state

    return serve_step


def make_prefill_step(cfg) -> Callable:
    """prefill_step(params, batch) -> last-position logits.

    The prefill_32k cells lower the full-sequence forward (train-path
    attention, no optimizer) and return only the final-position logits.
    """
    api = get_api(cfg)

    def prefill_step(params, batch):
        logits, _ = api.forward(params, batch, cfg)
        return logits[:, -1, :]

    return prefill_step
