"""The decoder-only KV cache: stored [L, B, n_kv, head_dim, max_len],
carried through the decode step's layer scan, written one column per slot
and token, and donated by the serving engine so it is updated in place."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import attention as A
from repro.models import layers as L
from repro.models import transformer as T
from repro.parallel.sharding import unbox

KEY = jax.random.PRNGKey(0)


def _f32(arch):
    return get_config(arch, smoke=True).replace(remat=False,
                                                dtype="float32")


def _ref_decode_step(params, tokens, pos, caches, cfg):
    """Plain per-layer decode over caches in the [B, S, n_kv, D] order:
    scatter the token's K/V at ``pos``, repeat the KV heads, and attend
    with a dense masked softmax."""
    dtype = jnp.dtype(cfg.dtype)
    b, hd = tokens.shape[0], cfg.head_dim
    x = L.embed_apply(params["embed"], tokens, dtype)
    ks, vs = [], []
    for layer in range(cfg.n_layers):
        p = jax.tree.map(lambda a: a[layer], params["blocks"])
        hn = T.norm_apply(cfg, p["ln1"], x)
        q, k, v = A._project_qkv(p["attn"], hn, cfg, pos[:, None], dtype)
        ck = caches["k"][layer].at[jnp.arange(b), pos].set(k[:, 0])
        cv = caches["v"][layer].at[jnp.arange(b), pos].set(v[:, 0])
        kk = A._repeat_kv(ck, cfg.n_heads)
        vv = A._repeat_kv(cv, cfg.n_heads)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(hd)
        valid = jnp.arange(ck.shape[1])[None, None, None, :] <= \
            pos[:, None, None, None]
        probs = jax.nn.softmax(jnp.where(valid, scores, A.NEG_INF), -1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
        x = x + L.dense_apply(p["attn"]["wo"],
                              out.reshape(b, 1, cfg.n_heads * hd), dtype)
        x = x + T.mlp_apply(p["mlp"], T.norm_apply(cfg, p["ln2"], x), cfg,
                            dtype)
        ks.append(ck)
        vs.append(cv)
    return T._logits(params, x, cfg, dtype), {"k": jnp.stack(ks),
                                              "v": jnp.stack(vs)}


def _to_stored(c):
    """[L, B, S, n_kv, D] -> the stored order [L, B, n_kv, D, S]."""
    return jnp.moveaxis(c, 2, 4)


@pytest.mark.parametrize("arch", ["granite-34b", "nemotron-4-15b",
                                  "minicpm-2b"])
def test_decode_matches_per_layer_reference(arch):
    """N decode steps of 3 slots at different positions, through the
    carried cache, against the plain per-layer reference; granite (1 KV
    head for 4) and nemotron (2 for 4) group queries, minicpm does not."""
    cfg = _f32(arch)
    assert cfg.n_layers == 2
    params = unbox(T.lm_init(KEY, cfg))
    b, s, steps = 3, 16, 4
    rng = np.random.default_rng(0)
    old = {n: jnp.asarray(rng.standard_normal(
        (cfg.n_layers, b, s, cfg.n_kv_heads, cfg.head_dim)), jnp.float32)
        for n in ("k", "v")}
    new = {n: _to_stored(c) for n, c in old.items()}
    assert new["k"].shape == unbox(T.init_caches(cfg, b, s))["k"].shape
    pos = jnp.asarray([0, 5, 11], jnp.int32)
    step = jax.jit(lambda p, t, q, c: T.lm_decode_step(p, t, q, c, cfg))
    for _ in range(steps):
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, 1)),
                           jnp.int32)
        want, old = _ref_decode_step(params, toks, pos, old, cfg)
        got, new = step(params, toks, pos, new)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        for n in ("k", "v"):
            np.testing.assert_allclose(np.asarray(new[n]),
                                       np.asarray(_to_stored(old[n])),
                                       rtol=1e-5, atol=1e-5)
        pos = pos + 1


def test_write_kv_column_lands_only_at_pos():
    rng = np.random.default_rng(1)
    cache = jnp.asarray(rng.standard_normal((2, 3, 2, 4, 8)), jnp.float32)
    col = jnp.asarray(rng.standard_normal((3, 2, 4)), jnp.float32)
    pos = jnp.asarray([0, 5, 7], jnp.int32)
    got = jax.jit(A.write_kv_column)(cache, col, jnp.int32(1), pos)
    want = np.array(cache)
    for b, p in enumerate([0, 5, 7]):
        want[1, b, :, :, p] = np.asarray(col[b])
    np.testing.assert_array_equal(np.asarray(got), want)


def test_decode_step_writes_one_column_per_layer_and_slot():
    cfg = _f32("nemotron-4-15b")
    params = unbox(T.lm_init(KEY, cfg))
    b, s = 3, 8
    rng = np.random.default_rng(2)
    caches = {n: jnp.asarray(rng.standard_normal(
        (cfg.n_layers, b, cfg.n_kv_heads, cfg.head_dim, s)), jnp.float32)
        for n in ("k", "v")}
    pos = [1, 4, 7]
    toks = jnp.asarray([[3], [5], [7]], jnp.int32)
    _, out = T.lm_decode_step(params, toks, jnp.asarray(pos, jnp.int32),
                              caches, cfg)
    written = np.zeros((cfg.n_layers, b, cfg.n_kv_heads, cfg.head_dim, s),
                       bool)
    for slot, p in enumerate(pos):
        written[:, slot, :, :, p] = True
    for n in ("k", "v"):
        changed = np.asarray(out[n]) != np.asarray(caches[n])
        assert changed.any(axis=(2, 3)).sum() == cfg.n_layers * b
        assert not (changed & ~written).any()


def test_prefill_caches_in_stored_order_continue_the_forward():
    """lm_prefill's caches, in the stored order, equal the ones decode
    builds token by token, and decoding on from them reproduces the full
    forward's logits."""
    cfg = _f32("granite-34b")
    params = unbox(T.lm_init(KEY, cfg))
    b, t, extra = 2, 5, 3
    max_len = t + extra + 2
    toks = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (b, t + extra)), jnp.int32)
    full, _ = T.lm_apply(params, toks, cfg)
    logits, caches = T.lm_prefill(params, toks[:, :t], cfg, max_len)
    assert caches["k"].shape == (cfg.n_layers, b, cfg.n_kv_heads,
                                 cfg.head_dim, max_len)
    built = unbox(T.init_caches(cfg, b, max_len, jnp.float32))
    for i in range(t):
        _, built = T.lm_decode_step(params, toks[:, i:i + 1],
                                    jnp.full((b,), i, jnp.int32), built, cfg)
    for n in ("k", "v"):
        np.testing.assert_allclose(np.asarray(caches[n]),
                                   np.asarray(built[n]), rtol=1e-4,
                                   atol=1e-4)
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full[:, t - 1]), rtol=1e-4,
                               atol=1e-4)
    for i in range(t, t + extra):
        li, caches = T.lm_decode_step(params, toks[:, i:i + 1],
                                      jnp.full((b,), i, jnp.int32), caches,
                                      cfg)
        np.testing.assert_allclose(np.asarray(li[:, 0]),
                                   np.asarray(full[:, i]), rtol=1e-4,
                                   atol=1e-4)


def test_engine_step_donates_the_state():
    """The engine's step updates the state in place: the previous state's
    arrays are deleted, and the compiled step aliases all of it."""
    from repro.obs import metrics as obs_metrics
    from repro.serving import Request, ServeEngine
    from repro.serving.scheduler import Scheduler
    cfg = get_config("minicpm-2b", smoke=True)
    eng = ServeEngine(cfg, 2, 12)
    sched = Scheduler("fcfs", max_len=12)
    sched.submit(Request(0, [1, 2, 3], 4))
    eng.admit_from(sched)
    reg = obs_metrics.get_registry()
    for _ in range(2):
        before = jax.tree.leaves(eng.state)
        eng.step()
        assert all(leaf.is_deleted() for leaf in before)
        state_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(eng.state))
        assert reg.gauge("repro_serve_step_alias_bytes").value >= \
            state_bytes
        assert reg.gauge("repro_serve_step_temp_bytes").value >= 0


def test_engine_does_not_donate_to_a_step_set_from_outside():
    """``step_fn`` keeps its input, and a step function set in its place
    is handed the state without donation: it may return or keep it."""
    from repro.serving import Request, ServeEngine
    from repro.serving.scheduler import Scheduler
    cfg = get_config("minicpm-2b", smoke=True)
    eng = ServeEngine(cfg, 2, 12)
    sched = Scheduler("fcfs", max_len=12)
    sched.submit(Request(0, [1, 2, 3], 4))
    eng.admit_from(sched)
    step, seen = eng.step_fn, []

    def keeps_state(params, tokens, pos, state):
        seen.append(state)
        nxt, _ = step(params, tokens, pos, state)
        return nxt, state
    eng.step_fn = keeps_state
    eng.step()
    eng.step()
    assert seen[0] is seen[1]
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(seen[0]))
