"""repro.engine: QuantSpec semantics, the GemmEngine registry, encoding
threading through the kernel path, block-size selection, and spec-keyed
plan caching."""
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _planned import planned_kernel
from _propcheck import assert_cross_context_close
from repro.core import encodings as enc
from repro.core import quant as quantlib
from repro.engine import (ACT_QUANT_POLICIES, IMPLS, QuantSpec,
                          engine_names, get_engine, spec_from_flags)
from repro.kernels import bw_gemm as bwk, ops


# ---------------------------------------------------------------------------
# QuantSpec: construction, parsing, validation
# ---------------------------------------------------------------------------

def test_spec_defaults_and_str_roundtrip():
    s = QuantSpec(planes=3, impl="pallas_fused")
    assert s.radix == 4 and s.num_digits == 4 and s.enabled
    assert QuantSpec.parse(str(s)) == s


def test_spec_parse_fields_and_off():
    s = QuantSpec.parse("planes=4,encoding=mbe,impl=pallas,block_k=256")
    assert (s.planes, s.encoding, s.impl, s.block_k) == \
        (4, "mbe", "pallas", 256)
    assert QuantSpec.parse("off") is None and QuantSpec.parse("") is None
    # parse must NOT alias the first-class unfused kernel engine away
    assert QuantSpec.parse("impl=pallas").impl == "pallas"


def test_spec_parse_rejects_unknown_keys():
    with pytest.raises(ValueError, match="unknown QuantSpec field"):
        QuantSpec.parse("planez=4")
    with pytest.raises(ValueError, match="key=value"):
        QuantSpec.parse("planes")


@pytest.mark.parametrize("kw", [
    {"encoding": "nope"}, {"impl": "nope"}, {"act_quant": "nope"},
    {"bits": 1}, {"planes": -1}, {"planes": 5},          # ent has 4 digits
    {"block_m": 100}, {"block_n": -128},
])
def test_spec_validation(kw):
    with pytest.raises(ValueError):
        QuantSpec(**kw)


def test_spec_planes_bound_tracks_encoding():
    assert QuantSpec(planes=8, encoding="bitserial").num_digits == 8
    with pytest.raises(ValueError):
        QuantSpec(planes=8, encoding="ent")


def test_spec_coerce():
    assert QuantSpec.coerce(None) is None
    assert QuantSpec.coerce(0) is None
    s = QuantSpec.coerce(3)
    assert s.planes == 3 and s.impl == "planes"
    assert QuantSpec.coerce(3, impl="pallas").impl == "pallas_fused"  # legacy
    assert QuantSpec.coerce(s) is s
    assert QuantSpec.coerce(QuantSpec(planes=0)) is None
    with pytest.raises(TypeError):
        QuantSpec.coerce("planes=3")


def test_spec_from_flags():
    assert spec_from_flags() is None
    s = spec_from_flags(quant_planes=3, quant_impl="planes")
    assert (s.planes, s.impl) == (3, "planes")
    s = spec_from_flags("encoding=mbe,impl=pallas", quant_planes=2)
    assert (s.planes, s.encoding, s.impl) == (2, "mbe", "pallas")


def test_spec_from_flags_legacy_impl_flag_keeps_fused_meaning():
    """--quant-impl pallas predates the registry and selected the fused
    kernel path; the sugar flag must keep that meaning, while an impl=
    inside --quant-spec is taken literally (the unfused engine)."""
    assert spec_from_flags(quant_planes=3, quant_impl="pallas").impl == \
        "pallas_fused"
    assert spec_from_flags("impl=pallas", quant_planes=3).impl == "pallas"


def test_spec_is_hashable_cache_key():
    a = QuantSpec(planes=3)
    b = QuantSpec(planes=3)
    assert a == b and hash(a) == hash(b) and a.replace(planes=2) != a


# ---------------------------------------------------------------------------
# Registry: all five engines, shared parity vs quantized_matmul_ref
# ---------------------------------------------------------------------------

def test_registry_has_all_engines():
    assert engine_names() == IMPLS == \
        ("ref", "planes", "int8", "pallas", "pallas_fused",
         "pallas_sparse", "pallas_pipelined")
    with pytest.raises(ValueError, match="unknown quant impl"):
        get_engine("nope")


@pytest.mark.parametrize("impl", IMPLS)
def test_engine_parity_vs_quantized_matmul_ref(impl, rng):
    """planes=4 on the default grid == plain int8 symmetric quantization:
    every registered engine must reproduce quantized_matmul_ref."""
    x = jnp.asarray(rng.normal(0, 1, size=(5, 96)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.05, size=(96, 64)).astype(np.float32))
    want = np.asarray(quantlib.quantized_matmul_ref(x, w))
    spec = QuantSpec(planes=4, impl=impl)
    got = np.asarray(get_engine(impl).apply(w, x, spec,
                                            out_dtype=jnp.float32))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", IMPLS)
def test_engine_bias_activation_epilogue(impl, rng):
    x = jnp.asarray(rng.normal(0, 1, size=(4, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.05, size=(64, 48)).astype(np.float32))
    b = jnp.asarray(rng.normal(0, 0.1, size=(48,)).astype(np.float32))
    spec = QuantSpec(planes=4, impl=impl)
    lin = np.asarray(get_engine(impl).apply(w, x, spec,
                                            out_dtype=jnp.float32))
    got = np.asarray(get_engine(impl).apply(
        w, x, spec, bias=b, activation="silu", out_dtype=jnp.float32))
    want = np.asarray(jax.nn.silu(jnp.asarray(lin) + b))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_jnp_engines_are_ste_differentiable(rng):
    x = jnp.asarray(rng.normal(0, 1, size=(4, 32)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.05, size=(32, 16)).astype(np.float32))
    for impl in ("ref", "planes", "int8"):
        spec = QuantSpec(planes=3, impl=impl)

        def loss(ww):
            y = get_engine(impl).apply(ww, x, spec, out_dtype=jnp.float32)
            return jnp.sum(y * y)

        g = np.asarray(jax.grad(loss)(w))
        assert g.shape == w.shape and np.isfinite(g).all() and \
            np.abs(g).sum() > 0


def test_kernel_engines_per_token_act_quant(rng):
    """per-token act scales reach the fused kernel epilogue (as a
    per-column vector: tokens sit on the kernel N axis) and keep decode
    rows independent of their batch-mates."""
    x = jnp.asarray(rng.normal(0, 1, size=(4, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.05, size=(64, 32)).astype(np.float32))
    spec = QuantSpec(planes=3, impl="pallas_fused", act_quant="per_token")
    oracle = np.asarray(get_engine("planes").apply(
        w, x, spec.replace(impl="planes"), out_dtype=jnp.float32))
    for impl in ("pallas", "pallas_fused"):
        got = np.asarray(get_engine(impl).apply(
            w, x, spec.replace(impl=impl), interpret=True,
            out_dtype=jnp.float32))
        assert_cross_context_close(got, oracle)
    # batch-independence: scaling row 1 must not change row 0's output
    # bitwise (per-tensor couples rows through the shared max-abs scale)
    y = np.asarray(get_engine("pallas_fused").apply(
        w, x, spec, interpret=True, out_dtype=jnp.float32))
    y2 = np.asarray(get_engine("pallas_fused").apply(
        w, x.at[1].multiply(100.0), spec, interpret=True,
        out_dtype=jnp.float32))
    assert (y[0] == y2[0]).all()
    # the jnp engines agree on the finer act grid too (still close to fp)
    got = np.asarray(get_engine("ref").apply(
        w, x, spec.replace(impl="ref", planes=4), out_dtype=jnp.float32))
    want = np.asarray(x.astype(jnp.float32) @ w.astype(jnp.float32))
    np.testing.assert_allclose(got, want, rtol=0.1, atol=0.05)


def test_engine_cost_model_sanity():
    m, k, n = 256, 512, 256
    spec = QuantSpec(planes=3)
    c_planes = get_engine("planes").cost(m, k, n, spec)
    c_int8 = get_engine("int8").cost(m, k, n, spec)
    c_pallas = get_engine("pallas").cost(m, k, n, spec)
    c_fused = get_engine("pallas_fused").cost(m, k, n, spec)
    # digit-plane engines pay one MXU pass per live plane
    assert c_planes["mxu_passes"] == c_pallas["mxu_passes"] == 3
    assert c_int8["mxu_passes"] == 1
    assert c_planes["int_macs"] == 3 * m * k * n
    # fusing the epilogue removes the int32 accumulator HBM round-trip
    assert c_fused["acc_hbm_bytes"] == 0 < c_pallas["acc_hbm_bytes"]
    # two's-complement bit-serial cannot structurally skip high planes
    bs = QuantSpec(planes=4, encoding="bitserial")
    assert get_engine("planes").cost(m, k, n, bs)["mxu_passes"] == 8


# ---------------------------------------------------------------------------
# Encoding/bits threading: every encoding reaches the kernel path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("encoding", enc.ENCODINGS)
def test_bw_gemm_roundtrips_every_encoding_bit_exactly(encoding, rng):
    """plan_operand + bw_gemm must be exact for all four encodings,
    radix-2 included (the spec carries the radix)."""
    a = rng.integers(-128, 128, size=(64, 64)).astype(np.int8)
    b = rng.integers(-128, 128, size=(64, 32)).astype(np.int8)
    planned = ops.plan_operand(a, encoding=encoding, block_m=64,
                               block_k=64)
    got = planned_kernel(bwk.bw_gemm, planned, b)
    want = (a.astype(np.int64) @ b.astype(np.int64)).astype(np.int32)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("encoding", enc.ENCODINGS)
@pytest.mark.parametrize("impl", ["pallas", "pallas_fused"])
def test_quantized_dense_every_encoding_matches_ref(encoding, impl, rng):
    """An mbe / bitserial / bitserial_sm spec must reach plan_dense_weight
    and the bw_gemm kernels and agree with the ref engine on the same
    quantization grid."""
    planes = enc.num_digits(encoding, 8)        # full-precision budget
    spec = QuantSpec(planes=planes, encoding=encoding, impl=impl)
    x = jnp.asarray(rng.normal(0, 1, size=(3, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.05, size=(64, 32)).astype(np.float32))
    b = jnp.asarray(rng.normal(0, 0.1, size=(32,)).astype(np.float32))
    got = np.asarray(ops.quantized_dense(
        x, w, spec, bias=b, activation="silu", interpret=True,
        fused=(impl == "pallas_fused")))
    want = np.asarray(get_engine("ref").apply(
        w, x, spec.replace(impl="ref"), bias=b, activation="silu",
        out_dtype=jnp.float32))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("encoding,bits", [("ent", 4), ("mbe", 6),
                                           ("bitserial_sm", 4)])
def test_narrow_bits_thread_through_kernel_path(encoding, bits, rng):
    """bits != 8 must reach the encoder (digit-plane count follows bits)."""
    planes = enc.num_digits(encoding, bits)
    spec = QuantSpec(planes=planes, encoding=encoding, bits=bits,
                     impl="pallas_fused")
    x = jnp.asarray(rng.normal(0, 1, size=(2, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.05, size=(64, 32)).astype(np.float32))
    plan = ops.plan_dense_weight(w, spec, use_cache=False)
    assert plan["digits"].shape[0] == planes
    got = np.asarray(ops.planned_dense_apply(plan, x, spec, 32,
                                             interpret=True))
    want = np.asarray(get_engine("ref").apply(
        w, x, spec.replace(impl="ref"), out_dtype=jnp.float32))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# select_block_sizes: table boundaries + spec overrides
# ---------------------------------------------------------------------------

def test_select_block_sizes_table_boundaries():
    assert ops.select_block_sizes(512, 2048, 512) == (256, 512, 256)
    # one short of any threshold drops to the next row
    assert ops.select_block_sizes(511, 2048, 512) == (256, 512, 128)
    assert ops.select_block_sizes(256, 1024, 255) == (128, 256, 128)
    assert ops.select_block_sizes(128, 512, 128) == (128, 256, 128)
    assert ops.select_block_sizes(127, 512, 128) == (128, 128, 128)
    assert ops.select_block_sizes(0, 0, 0) == (128, 128, 128)


def test_select_block_sizes_spec_override_wins():
    spec = QuantSpec(planes=3, block_k=1024)
    assert ops.select_block_sizes(64, 64, 64, spec) == (128, 1024, 128)
    full = QuantSpec(planes=3, block_m=256, block_k=256, block_n=384)
    assert ops.select_block_sizes(4096, 8192, 4096, full) == (256, 256, 384)
    # no override: spec is transparent
    assert ops.select_block_sizes(64, 64, 64, QuantSpec(planes=3)) == \
        ops.select_block_sizes(64, 64, 64)


# ---------------------------------------------------------------------------
# Plan cache: spec keying + weakref eviction
# ---------------------------------------------------------------------------

def test_plan_cache_keys_on_spec(rng):
    ops.plan_cache_clear()
    w = jnp.asarray(rng.normal(0, 0.05, size=(64, 32)).astype(np.float32))
    s_ent = QuantSpec(planes=3, encoding="ent")
    s_mbe = QuantSpec(planes=3, encoding="mbe")
    p1, _ = ops.plan_for(w, s_ent)
    p2, _ = ops.plan_for(w, s_mbe)
    assert p1 is not p2
    assert ops.plan_cache_stats()["entries"] == 2
    # same spec again: cache hit; impl does not affect the plan key
    p3, _ = ops.plan_for(w, s_ent.replace(impl="pallas_fused"))
    assert p3 is p1 and ops.plan_cache_stats()["hits"] == 1
    ops.plan_cache_clear()


def test_plan_cache_spec_entries_evicted_together(rng):
    ops.plan_cache_clear()
    w = jnp.asarray(rng.normal(0, 0.05, size=(64, 32)).astype(np.float32))
    ops.plan_for(w, QuantSpec(planes=3))
    ops.plan_for(w, QuantSpec(planes=2))
    assert ops.plan_cache_stats()["entries"] == 2
    del w
    gc.collect()
    assert ops.plan_cache_stats()["entries"] == 0
    ops.plan_cache_clear()


# ---------------------------------------------------------------------------
# act_quant policies are a closed set shared with the docs
# ---------------------------------------------------------------------------

def test_act_quant_policy_names():
    assert ACT_QUANT_POLICIES == ("per_tensor", "per_token")
