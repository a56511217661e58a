"""The kernel execution path: fused-epilogue kernels vs the jnp oracle,
weight-plan caching/invalidation, and the model-stack routing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bw_ref, quant as quantlib
from repro.engine import QuantSpec
from repro.kernels import ops
from repro.kernels.bw_gemm import EPILOGUE_ACTIVATIONS


def _oracle_dense(x, w, planes, bias=None, activation=None):
    """jnp oracle on the same quant grid: digit-plane int GEMM + epilogue."""
    qx, sx = quantlib.quantize_to_planes(jnp.asarray(x, jnp.float32), planes)
    qw, sw = quantlib.quantize_to_planes(jnp.asarray(w, jnp.float32), planes,
                                         axis=0)
    acc = bw_ref.bw_matmul_jnp(qx.reshape(-1, qx.shape[-1]), qw)
    y = acc.astype(jnp.float32).reshape(*qx.shape[:-1], qw.shape[-1]) \
        * (sx * sw)
    if bias is not None:
        y = y + jnp.asarray(bias, jnp.float32)
    return np.asarray(EPILOGUE_ACTIVATIONS[activation](y))


# ---------------------------------------------------------------------------
# kernel-vs-oracle parity of the fused path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("planes", [2, 3, 4])
def test_quantized_dense_matches_oracle_planes(planes, rng):
    x = rng.normal(0, 1, size=(6, 128)).astype(np.float32)
    w = rng.normal(0, 0.05, size=(128, 96)).astype(np.float32)
    got = np.asarray(ops.quantized_dense(jnp.asarray(x), jnp.asarray(w),
                                         planes, interpret=True))
    want = _oracle_dense(x, w, planes)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("activation", [None, "silu", "gelu", "relu2"])
def test_quantized_dense_fused_bias_activation(activation, rng):
    x = rng.normal(0, 1, size=(5, 64)).astype(np.float32)
    w = rng.normal(0, 0.05, size=(64, 48)).astype(np.float32)
    b = rng.normal(0, 0.2, size=(48,)).astype(np.float32)
    got = np.asarray(ops.quantized_dense(
        jnp.asarray(x), jnp.asarray(w), 3, bias=jnp.asarray(b),
        activation=activation, interpret=True))
    want = _oracle_dense(x, w, 3, bias=b, activation=activation)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("batch,k,n", [(1, 31, 7), (3, 200, 130),
                                       (2, 129, 257), (7, 96, 384)])
def test_quantized_dense_odd_shapes(batch, k, n, rng):
    """Non-block-multiple shapes must round-trip the padding/slicing."""
    x = rng.normal(0, 1, size=(batch, k)).astype(np.float32)
    w = rng.normal(0, 0.05, size=(k, n)).astype(np.float32)
    got = np.asarray(ops.quantized_dense(jnp.asarray(x), jnp.asarray(w), 4,
                                         interpret=True))
    want = _oracle_dense(x, w, 4)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_quantized_dense_leading_dims(rng):
    """[B, T, K] inputs reshape through the kernel and back."""
    x = rng.normal(0, 1, size=(2, 5, 64)).astype(np.float32)
    w = rng.normal(0, 0.05, size=(64, 32)).astype(np.float32)
    got = np.asarray(ops.quantized_dense(jnp.asarray(x), jnp.asarray(w), 3,
                                         interpret=True))
    assert got.shape == (2, 5, 32)
    want = _oracle_dense(x, w, 3).reshape(2, 5, 32)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_bw_gemm_fused_int_accumulator_exact(rng):
    """With scale 1 the fused kernel must equal the int oracle bit-exactly."""
    a = rng.integers(-128, 128, size=(128, 128)).astype(np.int8)
    b = rng.integers(-128, 128, size=(128, 64)).astype(np.int8)
    planned = ops.plan_operand(a, block_m=128, block_k=128)
    ones = np.ones((128,), np.float32)
    got = np.asarray(ops.bw_gemm_fused(planned, jnp.asarray(b),
                                       jnp.asarray(ones), interpret=True))
    want = (a.astype(np.int64) @ b.astype(np.int64)).astype(np.int32)
    np.testing.assert_array_equal(got.astype(np.int64), want)


def test_quant_gemm_fused_matches_epilogue(rng):
    a = rng.integers(-128, 128, size=(100, 200)).astype(np.int8)
    b = rng.integers(-128, 128, size=(200, 60)).astype(np.int8)
    scale = rng.random(60).astype(np.float32) * 0.01
    bias = rng.normal(0, 1, size=(60,)).astype(np.float32)
    got = np.asarray(ops.quant_gemm_fused(
        jnp.asarray(a), jnp.asarray(b), jnp.asarray(scale),
        jnp.asarray(bias), activation="silu", interpret=True))
    acc = (a.astype(np.int64) @ b.astype(np.int64)).astype(np.float32)
    want = np.asarray(jax.nn.silu(jnp.asarray(acc * scale + bias)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# plan cache behaviour
# ---------------------------------------------------------------------------

def test_plan_cache_hit_and_invalidation_jax(rng):
    ops.plan_cache_clear()
    w1 = jnp.asarray(rng.normal(0, 0.05, size=(64, 32)).astype(np.float32))
    p1a, _ = ops.plan_for(w1, 3)
    p1b, _ = ops.plan_for(w1, 3)
    assert p1a is p1b
    assert ops.plan_cache_stats()["hits"] == 1
    # a "changed weight" is a new (immutable) array: must re-plan
    w2 = w1 * 2.0
    p2, _ = ops.plan_for(w2, 3)
    assert p2 is not p1a
    assert ops.plan_cache_stats()["misses"] == 2
    # different plane budget on the same weight is a different plan
    p3, _ = ops.plan_for(w1, 2)
    assert p3 is not p1a
    ops.plan_cache_clear()


def test_plan_cache_entry_evicted_when_weight_dies(rng):
    ops.plan_cache_clear()
    w = jnp.asarray(rng.normal(0, 0.05, size=(64, 32)).astype(np.float32))
    ops.plan_for(w, 3)
    assert ops.plan_cache_stats()["entries"] == 1
    del w
    import gc
    gc.collect()
    assert ops.plan_cache_stats()["entries"] == 0
    ops.plan_cache_clear()


def test_plan_cache_numpy_content_invalidation(rng):
    ops.plan_cache_clear()
    w = rng.normal(0, 0.05, size=(64, 32)).astype(np.float32)
    ops.plan_for(w, 3)
    ops.plan_for(w, 3)
    assert ops.plan_cache_stats()["hits"] == 1
    w[0, 0] += 1.0           # in-place mutation must invalidate (content key)
    ops.plan_for(w, 3)
    assert ops.plan_cache_stats()["misses"] == 2
    ops.plan_cache_clear()


def test_quantized_dense_result_tracks_weight_change(rng):
    """End to end: a changed weight must change the output (no stale plan)."""
    x = jnp.asarray(rng.normal(0, 1, size=(2, 64)).astype(np.float32))
    w1 = jnp.asarray(rng.normal(0, 0.05, size=(64, 32)).astype(np.float32))
    y1 = np.asarray(ops.quantized_dense(x, w1, 3, interpret=True))
    w2 = w1 * 0.5
    y2 = np.asarray(ops.quantized_dense(x, w2, 3, interpret=True))
    np.testing.assert_allclose(y2, _oracle_dense(np.asarray(x),
                                                 np.asarray(w2), 3),
                               rtol=1e-5, atol=1e-5)
    assert not np.allclose(y1, y2)


# ---------------------------------------------------------------------------
# plan_operand regression: encodings with < 2 digit planes
# ---------------------------------------------------------------------------

def test_plan_operand_single_plane_regression(rng):
    """2-bit operands have a single radix-4 plane; the high-plane row scoring
    used to index d0[-2] and crash."""
    a = rng.integers(-2, 2, size=(16, 32)).astype(np.int8)
    planned = ops.plan_operand(a, bits=2, block_m=8, block_k=8)
    assert planned.digits.shape[0] == 1
    # the plan must still be exact
    b = rng.integers(-128, 128, size=(32, 8)).astype(np.int8)
    got = np.asarray(ops.bw_gemm(planned, jnp.asarray(b), block_n=128,
                                 interpret=True))
    want = (a.astype(np.int64) @ b.astype(np.int64)).astype(np.int32)
    np.testing.assert_array_equal(got, want)


def test_plan_operand_two_planes(rng):
    a = rng.integers(-8, 8, size=(16, 32)).astype(np.int8)
    planned = ops.plan_operand(a, bits=4, block_m=8, block_k=8)
    assert planned.digits.shape[0] == 2


# ---------------------------------------------------------------------------
# dispatch: block-size table + model-layer routing
# ---------------------------------------------------------------------------

def test_select_block_sizes_table():
    for m, k, n in [(1, 1, 1), (64, 64, 64), (4096, 8192, 4096)]:
        bm, bk, bn = ops.select_block_sizes(m, k, n)
        assert bm % 128 == 0 and bn % 128 == 0 and bk % 128 == 0
    assert ops.select_block_sizes(64, 64, 64) == (128, 128, 128)
    big = ops.select_block_sizes(4096, 8192, 4096)
    assert big >= (128, 128, 128) and big != (128, 128, 128)


def test_dense_apply_kernel_impl_matches_oracle(rng):
    from repro.models import layers as L
    x = jnp.asarray(rng.normal(0, 1, size=(3, 64)).astype(np.float32))
    p = {"w": jnp.asarray(rng.normal(0, 0.05, size=(64, 48))
                          .astype(np.float32)),
         "b": jnp.asarray(rng.normal(0, 0.1, size=(48,)).astype(np.float32))}
    want = np.asarray(L.dense_apply(p, x, jnp.float32, 3), np.float32)
    for impl in ("pallas", "pallas_fused"):
        got = np.asarray(L.dense_apply(
            p, x, jnp.float32, QuantSpec(planes=3, impl=impl)), np.float32)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_planned_dense_apply_inside_jit_matches_oracle(rng):
    """The attached-plan route must work under jit (the serve-step shape)."""
    from repro.models import layers as L
    spec = QuantSpec(planes=3, impl="pallas_fused")
    x = jnp.asarray(rng.normal(0, 1, size=(3, 64)).astype(np.float32))
    params = {"proj": {"w": jnp.asarray(
        rng.normal(0, 0.05, size=(64, 48)).astype(np.float32))}}
    want = np.asarray(L.dense_apply(params["proj"], x, jnp.float32, 3),
                      np.float32)
    planned_params, count = ops.plan_params(params, spec)
    assert count == 1 and "w_plan" in planned_params["proj"]

    @jax.jit
    def step(p, xx):
        return L.dense_apply(p["proj"], xx, jnp.float32, spec)

    got = np.asarray(step(planned_params, x), np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_plan_spec_mismatch_fails_loudly(rng):
    """The plan record cannot carry its encoding; applying it under a spec
    from a different radix family must be refused instead of decoding
    silently wrong."""
    x = jnp.asarray(rng.normal(0, 1, size=(2, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.05, size=(64, 32)).astype(np.float32))
    plan = ops.plan_dense_weight(w, QuantSpec(planes=3, encoding="ent"))
    with pytest.raises(ValueError, match="digit planes"):
        ops.planned_dense_apply(
            plan, x, QuantSpec(planes=3, encoding="bitserial"), 32,
            interpret=True)


def test_plan_params_skips_raw_matmul_weights(rng):
    """Weights consumed outside the quantized dense path (e.g. the MoE
    router) must not get dead plan arrays attached."""
    params = {
        "router": {"w": jnp.asarray(
            rng.normal(0, 0.05, size=(64, 8)).astype(np.float32))},
        "up": {"w": jnp.asarray(
            rng.normal(0, 0.05, size=(64, 32)).astype(np.float32))},
    }
    planned, count = ops.plan_params(params, 3)
    assert count == 1
    assert "w_plan" in planned["up"] and "w_plan" not in planned["router"]


def test_plan_params_stacked_layers(rng):
    """3-D (scan-stacked) weights get per-layer plans stacked on axis 0."""
    w = jnp.asarray(rng.normal(0, 0.05, size=(2, 64, 32)).astype(np.float32))
    planned, count = ops.plan_params({"up": {"w": w}}, 3)
    assert count == 2
    plan = planned["up"]["w_plan"]
    assert plan["digits"].shape[0] == 2            # leading layer axis
    # each slice equals an independently-built plan
    single = ops.plan_dense_weight(w[1], 3, use_cache=False)
    np.testing.assert_array_equal(np.asarray(plan["digits"][1]),
                                  np.asarray(single["digits"]))
    np.testing.assert_array_equal(np.asarray(plan["sw_rows"][1]),
                                  np.asarray(single["sw_rows"]))


def test_fallback_under_tracing_without_plan_is_bit_exact(rng):
    """A kernel impl with traced, unplanned weights must lower to the
    int8 dot -- bit-identical to the planes oracle after dequant."""
    from repro.models import layers as L
    x = jnp.asarray(rng.normal(0, 1, size=(3, 64)).astype(np.float32))
    p = {"w": jnp.asarray(rng.normal(0, 0.05, size=(64, 48))
                          .astype(np.float32))}

    @jax.jit
    def step(pp, xx):
        return L.dense_apply(pp, xx, jnp.float32, 3)

    want = np.asarray(step(p, x), np.float32)      # planes impl
    spec = QuantSpec(planes=3, impl="pallas_fused")
    got = np.asarray(jax.jit(
        lambda pp, xx: L.dense_apply(pp, xx, jnp.float32, spec))(p, x),
        np.float32)
    np.testing.assert_array_equal(got, want)


def test_traced_fallback_counts_its_own_dispatch_route(rng):
    """The traced-without-plan int8 lowering is counted under its own
    route label, apart from the kernel routes a planned weight takes."""
    from repro import obs
    from repro.engine.registry import TRACED_INT8_ROUTE
    from repro.models import layers as L
    from repro.obs import metrics as obs_metrics
    spec = QuantSpec(planes=3, impl="pallas_fused")
    x = jnp.asarray(rng.normal(0, 1, size=(3, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.05, size=(64, 48)).astype(np.float32))
    planned = {"w": w, "w_plan": ops.plan_dense_weight(w, spec)}
    family = obs_metrics.get_registry().counter("repro_gemm_dispatch_total")

    def count(route):
        return family.labels(route=route).value

    was = obs.enabled()
    obs.enable()
    try:
        before = count(TRACED_INT8_ROUTE), count("dense")
        jax.jit(lambda pp, xx: L.dense_apply(pp, xx, jnp.float32, spec))(
            {"w": w}, x)
        assert count(TRACED_INT8_ROUTE) == before[0] + 1
        jax.jit(lambda pp, xx: L.dense_apply(pp, xx, jnp.float32, spec))(
            planned, x)
        assert count(TRACED_INT8_ROUTE) == before[0] + 1
        assert count("dense") == before[1] + 1
    finally:
        if not was:
            obs.disable()
