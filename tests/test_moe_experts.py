"""The dropless held-expert layer: routes over all experts, computes the
part its held experts give, drops nothing under skew, and runs its expert
GEMMs as one grouped digit-plane kernel call on per-(layer, expert)
plans; the serving engine counts its routes with the sampled tokens."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.engine import QuantSpec
from repro.kernels import ops
from repro.models import moe as M
from repro.parallel.sharding import unbox

KEY = jax.random.PRNGKey(0)
SPEC = QuantSpec.parse("planes=3,encoding=ent,impl=pallas_fused,"
                       "act_quant=per_token")


def _cfg(**kw):
    # 16 experts, top 4: eight shares of two experts each
    return get_config("mellum2-12b-a2.5b", smoke=True).replace(
        n_experts=16, experts_per_token=4, dtype="float32", **kw)


def _x(cfg, b=2, t=12, seed=2):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (b, t, cfg.d_model)).astype(np.float32))


def _plain_layer(p, x, cfg, first=0):
    """Token by token: softmax over all experts, top k renormalised, each
    routed held expert's gated SiLU MLP by its gate."""
    xf = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    router = np.asarray(p["router"]["w"], np.float64)
    out = np.zeros_like(xf)
    for t, row in enumerate(xf):
        logits = row @ router
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(-probs, kind="stable")[:cfg.experts_per_token]
        gates = probs[top] / probs[top].sum()
        for e, g in zip(top, gates):
            h = e - first
            if not 0 <= h < p["w_up"].shape[0]:
                continue
            gt = row @ np.asarray(p["w_gate"][h], np.float64)
            up = row @ np.asarray(p["w_up"][h], np.float64)
            m = gt / (1 + np.exp(-gt)) * up
            out[t] += g * (m @ np.asarray(p["w_down"][h], np.float64))
    return out.reshape(x.shape)


def test_held_layer_matches_a_plain_per_token_layer():
    cfg = _cfg(experts_held=4, experts_first=6)
    p = unbox(M.moe_init(KEY, cfg))
    x = _x(cfg)
    y, counts = M.moe_held_apply(p, x, cfg, jnp.float32)
    np.testing.assert_allclose(np.asarray(y), _plain_layer(p, x, cfg, 6),
                               rtol=1e-4, atol=1e-5)
    _, eidx = M.route(p["router"]["w"], x.reshape(-1, cfg.d_model),
                      cfg.experts_per_token)
    local = np.asarray(eidx) - 6
    held = (local >= 0) & (local < 4)
    assert int(counts[0]) == held.sum()
    assert int(counts[1]) == len(np.unique(local[held]))


def test_shares_add_up_to_the_uncut_layer():
    """Eight chips' shares (held = E/8, first = 0, E/8, ...) of one layer,
    each drawn by itself, add up to the layer with every expert held;
    each share's weights are the uncut layer's slice."""
    whole_cfg = _cfg()
    whole = unbox(M.moe_init(KEY, whole_cfg))
    x = _x(whole_cfg)
    want, whole_counts = M.moe_held_apply(whole, x, whole_cfg, jnp.float32)
    total = np.zeros(x.shape, np.float64)
    routes = 0
    for first in range(0, 16, 2):
        cfg = _cfg(experts_held=2, experts_first=first)
        share = unbox(M.moe_init(KEY, cfg))
        for name in M.EXPERT_WEIGHTS:
            np.testing.assert_array_equal(
                np.asarray(share[name]),
                np.asarray(whole[name][first:first + 2]))
        np.testing.assert_array_equal(np.asarray(share["router"]["w"]),
                                      np.asarray(whole["router"]["w"]))
        y, counts = M.moe_held_apply(share, x, cfg, jnp.float32)
        total += np.asarray(y, np.float64)
        routes += int(counts[0])
    np.testing.assert_allclose(total, np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    assert routes == int(whole_counts[0]) == \
        x.shape[0] * x.shape[1] * whole_cfg.experts_per_token


@pytest.mark.parametrize("planned", [False, True], ids=["bf16", "kernel"])
def test_skewed_routing_drops_nothing(planned):
    """Every token routed to the same top-k experts: each of them takes
    all T tokens (capacity T), so the layer equals the plain one."""
    cfg = _cfg(experts_held=8, experts_first=0)
    p = unbox(M.moe_init(KEY, cfg))
    k = cfg.experts_per_token
    # a router that ranks experts 1, 3, 5, 7 first for any input
    bias = np.zeros((cfg.d_model, cfg.n_experts), np.float32)
    bias[:, [1, 3, 5, 7]] = np.linspace(4.0, 1.0, k)[None, :] / \
        np.sqrt(cfg.d_model)
    p["router"]["w"] = jnp.asarray(bias)
    x = jnp.abs(_x(cfg, b=2, t=16)) + 1.0       # every logit ranks alike
    _, eidx = M.route(p["router"]["w"], x.reshape(-1, cfg.d_model), k)
    assert (np.sort(np.asarray(eidx), -1) == [1, 3, 5, 7]).all()
    if planned:
        cfg = cfg.replace(quant=SPEC, quant_planes=3)
        p = {**p, **{name + "_plan": ops.plan_params(
            {"router": p["router"], name: p[name]}, SPEC)[0][name + "_plan"]
            for name in M.EXPERT_WEIGHTS}}
    y, counts = M.moe_held_apply(p, x, cfg, jnp.float32)
    assert int(counts[0]) == 32 * k and int(counts[1]) == k
    want = _plain_layer(p, x, cfg)
    tol = 0.05 if planned else 1e-4         # 3-plane grid vs float
    np.testing.assert_allclose(np.asarray(y), want,
                               rtol=tol, atol=tol * np.abs(want).max())


def test_plan_params_plans_each_layer_and_expert():
    """Stacked expert weights [L, H, K, N] get one plan per (layer,
    expert), stacked the same way, and each applies exactly as that
    matrix planned on its own."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((2, 3, 256, 160)).astype(np.float32)
                    * 0.05)
    tree = {"moe": {"router": {"w": jnp.zeros((256, 8))}, "w_up": w}}
    out, count = ops.plan_params(tree, SPEC)
    assert count == 6
    assert "w_plan" not in out["moe"]["router"]
    plan = out["moe"]["w_up_plan"]
    assert plan["digits"].shape[:2] == (2, 3)
    x = jnp.asarray(rng.standard_normal((3, 10, 256)).astype(np.float32))
    counts = jnp.asarray([10, 3, 0], jnp.int32)
    for layer in range(2):
        one = jax.tree.map(lambda a: a[layer], plan)
        y = ops.planned_experts_apply(one, x, SPEC, 160, counts)
        for h in range(3):
            alone = ops.plan_dense_weight(w[layer, h], SPEC, use_cache=False)
            np.testing.assert_array_equal(
                np.asarray(one["digits"][h]), np.asarray(alone["digits"]))
            want = ops.planned_dense_apply(alone, x[h], SPEC, 160)
            c = int(counts[h])
            # routed rows exactly; a token block with no routed row is
            # skipped and reads zero
            np.testing.assert_array_equal(np.asarray(y[h, :c]),
                                          np.asarray(want[:c]))
        assert not np.asarray(y[2]).any()


def test_engine_counts_routes_with_the_tokens():
    """The kernel-path engine's step returns each layer's routes and
    active experts with the sampled tokens; the counters add them up.  A
    dense config's step returns nothing new."""
    from repro.obs import metrics as obs_metrics
    from repro.serving import Request, ServeEngine
    from repro.serving.scheduler import Scheduler
    from repro.train.steps import routes_experts
    reg = obs_metrics.get_registry()
    names = ("repro_moe_routes_held_total", "repro_moe_experts_active_total")
    cfg = get_config("mellum2-12b-a2.5b", smoke=True).replace(
        experts_held=4, experts_first=2)
    assert routes_experts(cfg)
    assert not routes_experts(get_config("minicpm-2b", smoke=True))
    eng = ServeEngine(cfg, 2, 12, quant=SPEC)
    moe = eng.params["blocks"]["moe"]
    assert {n + "_plan" for n in M.EXPERT_WEIGHTS} <= set(moe)
    assert moe["w_up_plan"]["digits"].shape[:2] == (cfg.n_layers, 4)
    sched = Scheduler("fcfs", max_len=12)
    sched.submit(Request(0, [1, 2, 3], 3))
    eng.admit_from(sched)
    before = [reg.counter(n).value for n in names]
    nxt, _, counts = eng.step_fn(eng.params, jnp.asarray(eng.slots.cur),
                                 jnp.asarray(eng.slots.pos), eng.state)
    counts = np.asarray(counts)
    assert counts.shape == (cfg.n_layers, 2)
    eng.step()
    after = [reg.counter(n).value for n in names]
    # both slots compute a token: 2 x k routes over all experts a layer
    assert 0 < after[0] - before[0] <= cfg.n_layers * 2 * \
        cfg.experts_per_token
    assert after[0] - before[0] == counts[:, 0].sum()
    assert after[1] - before[1] == counts[:, 1].sum()
    dense = ServeEngine(get_config("minicpm-2b", smoke=True), 2, 12)
    out = dense.step_fn(dense.params, jnp.asarray(dense.slots.cur),
                        jnp.asarray(dense.slots.pos), dense.state)
    assert len(out) == 2
    before = [reg.counter(n).value for n in names]
    dense.step()
    assert [reg.counter(n).value for n in names] == before
