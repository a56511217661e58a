"""Attention numerics: chunked/flash path vs dense oracle, RoPE, windows."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as A
from repro.models import hymba as H
from repro.models import layers as L


def _qkv(rng, b, t, h, d):
    q = jnp.asarray(rng.standard_normal((b, t, h, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, t, h, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, t, h, d)).astype(np.float32))
    return q, k, v


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_chunked_causal_matches_dense(chunk, rng):
    b, t, h, d = 2, 32, 3, 8
    q, k, v = _qkv(rng, b, t, h, d)
    dense = A._dense_causal(q, k, v)
    chunked = A._chunked_causal(q, k, v, chunk, chunk)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_chunked_rectangular_blocks(rng):
    b, t, h, d = 1, 32, 2, 8
    q, k, v = _qkv(rng, b, t, h, d)
    dense = A._dense_causal(q, k, v)
    chunked = A._chunked_causal(q, k, v, 8, 16)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_windowed_chunked_matches_windowed_dense(rng):
    b, t, h, d, w = 1, 32, 2, 8, 8
    q, k, v = _qkv(rng, b, t, h, d)
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    dense = H._windowed(q, k, v, w, positions)
    chunked = H._windowed_chunked(q, k, v, w, chunk=4)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_rope_preserves_norm_and_relative_phase(rng):
    b, t, h, d = 1, 6, 2, 8
    q = jnp.asarray(rng.standard_normal((b, t, h, d)).astype(np.float32))
    k = q + 0.0
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    qr, kr = L.rope(q, k, positions, d)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(qr), axis=-1),
                               np.linalg.norm(np.asarray(q), axis=-1),
                               rtol=1e-5)
    # relative property: <rope(q,i), rope(k,j)> depends only on i - j
    def dot(i, j):
        return float(jnp.einsum("d,d->", qr[0, i, 0], kr[0, j, 0]))
    # shift both positions by the same offset via recomputation
    q2r, k2r = L.rope(q, k, positions + 3, d)
    def dot2(i, j):
        return float(jnp.einsum("d,d->", q2r[0, i, 0], k2r[0, j, 0]))
    assert abs(dot(4, 2) - dot2(4, 2)) < 1e-3


def test_decode_attends_only_to_valid_positions(rng):
    """Tokens beyond `pos` in the cache must not affect decode output."""
    from repro.configs.registry import get_config
    from repro.parallel.sharding import unbox
    cfg = get_config("nemotron-4-15b", smoke=True)
    p = unbox(A.attn_init(jax.random.PRNGKey(0), cfg))
    b, s = 1, 8
    # stacked cache [L, B, n_kv, D, S], one layer
    ck = jnp.zeros((1, b, cfg.n_kv_heads, cfg.head_dim, s), jnp.bfloat16)
    cv = jnp.zeros_like(ck)
    x = jnp.asarray(rng.standard_normal((b, 1, cfg.d_model))
                    .astype(np.float32))
    pos = jnp.asarray([2], jnp.int32)
    out1, _, _ = A.attn_decode(p, x, cfg, ck, cv, 0, pos)
    # poison future cache slots
    ck2 = ck.at[..., 5:].set(99.0)
    cv2 = cv.at[..., 5:].set(-99.0)
    out2, _, _ = A.attn_decode(p, x, cfg, ck2, cv2, 0, pos)
    np.testing.assert_allclose(np.asarray(out1, np.float32),
                               np.asarray(out2, np.float32), atol=1e-5)


def test_gqa_repeat_kv():
    k = jnp.arange(2 * 3 * 2 * 4).reshape(2, 3, 2, 4).astype(jnp.float32)
    r = A._repeat_kv(k, 6)
    assert r.shape == (2, 3, 6, 4)
    np.testing.assert_array_equal(np.asarray(r[:, :, 0]),
                                  np.asarray(r[:, :, 1]))
    np.testing.assert_array_equal(np.asarray(r[:, :, 3]),
                                  np.asarray(r[:, :, 4]))
