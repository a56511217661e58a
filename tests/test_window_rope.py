"""Layers of two kinds: sliding-window and full attention, each with its
own RoPE (YaRN on Mellum2's full layers), carried as per-layer arrays in
the layer scans; and a config whose layers are alike lowers as before."""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.engine import QuantSpec
from repro.models import attention as A
from repro.models import layers as L
from repro.models import transformer as T
from repro.parallel.sharding import unbox

KEY = jax.random.PRNGKey(0)


def _hf_yarn(dim, base, factor, orig, beta_fast=32, beta_slow=1,
             attention_factor=None):
    """transformers' ``_compute_yarn_parameters``, line by line in numpy
    (float32 where torch computes in float32)."""
    def get_mscale(scale, mscale=1):
        return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0
    if attention_factor is None:
        attention_factor = get_mscale(factor)

    def find_correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) / \
            (2 * math.log(base))
    low = math.floor(find_correction_dim(beta_fast))
    high = math.ceil(find_correction_dim(beta_slow))
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001
    linear = (np.arange(dim // 2, dtype=np.float32) - low) / (high - low)
    ramp = np.clip(linear, 0, 1)
    pos_freqs = base ** (np.arange(0, dim, 2).astype(np.float32) / dim)
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (factor * pos_freqs)
    extrapolation_factor = 1 - ramp
    inv = interpolation * (1 - extrapolation_factor) + \
        extrapolation * extrapolation_factor
    return inv.astype(np.float32), attention_factor


@pytest.mark.parametrize("arch, smoke", [("mellum2-12b-a2.5b", False),
                                         ("mellum2-12b-a2.5b", True)],
                         ids=["published", "smoke"])
def test_yarn_matches_the_transformers_formula(arch, smoke):
    cfg = get_config(arch, smoke=smoke)
    got, scale = L.yarn_inv_freq(cfg.head_dim, cfg.rope_theta,
                                 cfg.yarn_factor,
                                 cfg.yarn_original_max_position)
    want, want_scale = _hf_yarn(cfg.head_dim, cfg.rope_theta,
                                cfg.yarn_factor,
                                cfg.yarn_original_max_position)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)
    assert scale == pytest.approx(want_scale, rel=1e-12)
    default = L.rope_inv_freq(cfg.head_dim, cfg.rope_theta)
    # the ramp is non-trivial: some frequencies kept, some interpolated
    # by 1 / factor, some between
    ratio = got / default
    assert np.isclose(ratio[0], 1.0)
    assert np.isclose(ratio[-1], 1.0 / cfg.yarn_factor)
    between = (ratio < 1 - 1e-6) & (ratio > 1 / cfg.yarn_factor + 1e-6)
    assert between.any()


def test_published_yarn_parameters_are_the_defaults():
    """Mellum2 publishes beta_fast 32, beta_slow 1 and attention factor
    1.2772588722239782, which yarn_inv_freq takes by default; other
    values still reach the formula."""
    cfg = get_config("mellum2-12b-a2.5b")
    args = (cfg.head_dim, cfg.rope_theta, cfg.yarn_factor,
            cfg.yarn_original_max_position)
    inv, scale = L.yarn_inv_freq(*args)
    assert scale == 1.2772588722239782
    want, _ = _hf_yarn(*args, beta_fast=32, beta_slow=1)
    np.testing.assert_array_equal(inv, want)
    other, other_scale = L.yarn_inv_freq(*args, beta_fast=16, beta_slow=2,
                                         attention_factor=1.5)
    want, _ = _hf_yarn(*args, beta_fast=16, beta_slow=2)
    np.testing.assert_allclose(other, want, rtol=1e-6)
    assert other_scale == 1.5 and not np.allclose(other, inv)


def test_default_rope_is_unchanged():
    """rope() without tables is the old formula, bit for bit, and the
    default table with scale 1 gives the same."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 5, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 5, 2, 16)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, 100, (2, 5)), jnp.int32)
    freqs = 1.0 / (1e4 ** (np.arange(0, 8, dtype=np.float32) / 8))
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]

    def rot(x):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    got = L.rope(q, k, pos, 16, 1e4)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(rot(q)))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(rot(k)))
    tab = L.rope(q, k, pos, 16, inv_freq=L.rope_inv_freq(16, 1e4), scale=1.0)
    np.testing.assert_array_equal(np.asarray(tab[0]), np.asarray(got[0]))


def test_layer_kinds_follow_the_pattern():
    cfg = get_config("mellum2-12b-a2.5b").replace(n_layers=12)
    kinds = A.layer_kinds(cfg)
    assert [cfg.layer_kind(i) for i in range(12)] == list("SSSF" * 3)
    np.testing.assert_array_equal(
        kinds["window"], [1024, 1024, 1024, A.NO_WINDOW] * 3)
    np.testing.assert_allclose(kinds["rope_scale"],
                               [1.0, 1.0, 1.0, 1.2772588722239782] * 3)
    default = L.rope_inv_freq(128, 500000.0)
    yarn, _ = L.yarn_inv_freq(128, 500000.0, 16.0, 8192)
    for i in range(12):
        want = yarn if i % 4 == 3 else default
        np.testing.assert_array_equal(kinds["inv_freq"][i], want)
    assert A.layer_kinds(get_config("minicpm-2b")) is None
    with pytest.raises(ValueError, match="sliding_window"):
        A.layer_kinds(cfg.replace(sliding_window=0))


def _naive_attention(q, k, v, window):
    """q, k, v [B, T, H, D]: softmax attention with the causal mask and,
    where window is set, keys p - window < j <= p only."""
    t = q.shape[1]
    i = np.arange(t)
    seen = i[None, :] <= i[:, None]
    if window is not None:
        seen &= i[None, :] > i[:, None] - window
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = np.where(seen, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("window", [None, 1, 3, 7, 100])
def test_window_mask_in_dense_and_chunked_attention(window):
    rng = np.random.default_rng(1)
    q, k, v = (rng.standard_normal((2, 16, 2, 8)).astype(np.float32)
               for _ in range(3))
    want = _naive_attention(q, k, v, window)
    w = None if window is None else jnp.int32(window)
    dense = A._dense_causal(*map(jnp.asarray, (q, k, v)), window=w)
    chunked = A._chunked_causal(*map(jnp.asarray, (q, k, v)), 4, 4, w)
    np.testing.assert_allclose(np.asarray(dense), want, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(chunked), want, rtol=1e-5,
                               atol=1e-5)


def test_window_mask_in_decode_attention():
    """attend_kv_cache with a window: slot b at pos[b] sees cache columns
    pos[b] - window < j <= pos[b]."""
    rng = np.random.default_rng(2)
    b, h, kvh, hd, s, window = 3, 4, 2, 8, 12, 4
    q = rng.standard_normal((b, 1, h, hd)).astype(np.float32)
    ck = rng.standard_normal((b, kvh, hd, s)).astype(np.float32)
    cv = rng.standard_normal((b, kvh, hd, s)).astype(np.float32)
    pos = np.asarray([0, 5, 11], np.int32)
    got = A.attend_kv_cache(jnp.asarray(q), jnp.asarray(ck), jnp.asarray(cv),
                            jnp.asarray(pos), jnp.float32,
                            jnp.int32(window))
    for slot, p in enumerate(pos):
        j = np.arange(max(p - window + 1, 0), p + 1)
        kk = np.repeat(ck[slot][..., j], h // kvh, axis=0)   # [H, D, J]
        vv = np.repeat(cv[slot][..., j], h // kvh, axis=0)
        sc = np.einsum("hd,hdj->hj", q[slot, 0], kk) / np.sqrt(hd)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        want = np.einsum("hj,hdj->hd", pr, vv).reshape(-1)
        np.testing.assert_allclose(np.asarray(got[slot, 0]), want,
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("held", [0, 4], ids=["all_held", "share"])
def test_decode_through_the_cache_matches_the_full_forward(held):
    """Teacher-forced decode over the KV cache, past the window, against
    lm_apply's full forward and against lm_prefill, with window and full
    layers and YaRN (float32; ample capacity where all experts are held,
    so that the training path's dispatch drops nothing)."""
    cfg = get_config("mellum2-12b-a2.5b", smoke=True).replace(
        dtype="float32", experts_held=held, experts_first=2 if held else 0,
        capacity_factor=4.0)
    assert cfg.sliding_window == 6
    params = unbox(T.lm_init(KEY, cfg))
    b, t = 2, 16
    toks = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (b, t)), jnp.int32)
    full, _ = T.lm_apply(params, toks, cfg)
    caches = unbox(T.init_caches(cfg, b, t, jnp.float32))
    for i in range(t):
        li, caches = T.lm_decode_step(params, toks[:, i:i + 1],
                                      jnp.full((b,), i, jnp.int32), caches,
                                      cfg)
        np.testing.assert_allclose(np.asarray(li[:, 0]),
                                   np.asarray(full[:, i]), rtol=2e-4,
                                   atol=2e-4)
    last, pre = T.lm_prefill(params, toks, cfg, t)
    np.testing.assert_allclose(np.asarray(last[:, 0]),
                               np.asarray(full[:, -1]), rtol=2e-4, atol=2e-4)
    for n in ("k", "v"):
        np.testing.assert_allclose(np.asarray(pre[n]), np.asarray(caches[n]),
                                   rtol=2e-4, atol=2e-4)


def test_window_changes_the_result_past_it():
    """The window is not a no-op: without it (all layers full) the logits
    past the window differ, and before it they do not."""
    cfg = get_config("mellum2-12b-a2.5b", smoke=True).replace(
        dtype="float32", experts_held=8, yarn_factor=0.0)
    params = unbox(T.lm_init(KEY, cfg))
    toks = jnp.asarray(np.random.default_rng(4).integers(
        0, cfg.vocab_size, (1, 12)), jnp.int32)
    windowed, _ = T.lm_apply(params, toks, cfg)
    full, _ = T.lm_apply(params, toks, cfg.replace(layer_pattern="F"))
    w = cfg.sliding_window
    np.testing.assert_allclose(np.asarray(windowed[:, :w]),
                               np.asarray(full[:, :w]), rtol=1e-5,
                               atol=1e-5)
    assert not np.allclose(np.asarray(windowed[:, w:]),
                           np.asarray(full[:, w:]), rtol=1e-3, atol=1e-3)


# the decode step of minicpm-2b's smoke config (4 slots, 48 positions)
# lowers to this many StableHLO operations, bf16 and on the kernel path:
# the counts of the tree before layer kinds and held experts existed
DENSE_STEP_OPS = {"bf16": 561, "pallas_fused": 1398}
NEW_FIELDS_AT_DEFAULTS = dict(
    layer_pattern="", sliding_window=0, yarn_factor=0.0,
    yarn_original_max_position=0, experts_held=0, experts_first=0)


def _step_ops(cfg, quant):
    from repro.kernels import ops
    from repro.train.steps import make_serve_step
    cfg = cfg.replace(quant=quant, quant_planes=quant.planes if quant else 0)
    params = unbox(T.lm_init(KEY, cfg))
    if quant is not None:
        params, _ = ops.plan_params(params, quant)
    state = jax.eval_shape(lambda: unbox(T.init_caches(cfg, 4, 48)))
    text = jax.jit(make_serve_step(cfg), donate_argnums=(3,)).lower(
        params, jax.ShapeDtypeStruct((4, 1), jnp.int32),
        jax.ShapeDtypeStruct((4,), jnp.int32), state).as_text(
            debug_info=False)
    return len(re.findall(r"= (\"?[\w.]+)", text))


@pytest.mark.parametrize("impl", sorted(DENSE_STEP_OPS))
def test_dense_decode_step_lowers_as_before(impl):
    quant = None if impl == "bf16" else QuantSpec.parse(
        "planes=3,encoding=ent,impl=pallas_fused,act_quant=per_token")
    cfg = get_config("minicpm-2b", smoke=True)
    assert _step_ops(cfg, quant) == DENSE_STEP_OPS[impl]
    assert _step_ops(cfg.replace(**NEW_FIELDS_AT_DEFAULTS), quant) == \
        DENSE_STEP_OPS[impl]
