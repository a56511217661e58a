"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU): shape/dtype
sweeps with exact integer equality."""
import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from _planned import planned_kernel
from repro.core import quant as quantlib
from repro.kernels import ops, ref
import repro.kernels.bw_gemm as bwk          # the kernel submodules (the
import repro.kernels.quant_gemm as qgk       # package no longer shadows them)


def test_submodules_not_shadowed():
    """Regression: `import repro.kernels.bw_gemm as mod` must yield the
    *module* — the package once re-exported same-named functions that
    shadowed the submodule attributes (CHANGES.md PR 7 gotcha)."""
    import types

    import repro.kernels as pkg
    for name, alias in (("bw_gemm", bwk), ("quant_gemm", qgk)):
        mod = importlib.import_module(f"repro.kernels.{name}")
        assert isinstance(alias, types.ModuleType)
        assert alias is mod
        assert getattr(pkg, name) is mod
        # the entry-point function still exists, on the module
        assert callable(getattr(mod, name))
    assert callable(ops.quant_gemm)      # and the padded int8 baseline


def _rand_int8(rng, shape):
    return rng.integers(-128, 128, size=shape).astype(np.int8)


SHAPES = [(128, 256, 128), (256, 256, 256), (128, 512, 384), (384, 256, 128)]


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_quant_gemm_matches_oracle(m, k, n, rng):
    a = jnp.asarray(_rand_int8(rng, (m, k)))
    b = jnp.asarray(_rand_int8(rng, (k, n)))
    out = qgk.quant_gemm(a, b, block_m=128, block_n=128, block_k=256 if
                         k % 256 == 0 else 128, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.quant_gemm_ref(a, b)))


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_bw_gemm_matches_oracle(m, k, n, rng):
    a = jnp.asarray(_rand_int8(rng, (m, k)))
    b = jnp.asarray(_rand_int8(rng, (k, n)))
    bk = 256 if k % 256 == 0 else 128
    digits = ref.encode_planes_ref(a)
    mask = ops.plane_block_mask(digits, 128, bk)
    out = bwk.bw_gemm(digits, b, mask, block_m=128, block_n=128, block_k=bk,
                      interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.bw_gemm_ref(digits, b)))
    # and the BW decomposition itself equals the plain int GEMM
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.quant_gemm_ref(a, b)))


def test_bw_gemm_block_skipping_is_exact(rng):
    """Zeroed plane blocks must be skipped without changing the result."""
    m, k, n = 256, 256, 128
    a = _rand_int8(rng, (m, k))
    a[:128] = np.clip(a[:128], -10, 10)      # low planes only in rows 0..127
    a = jnp.asarray(a)
    b = jnp.asarray(_rand_int8(rng, (k, n)))
    digits = ref.encode_planes_ref(a)
    mask = ops.plane_block_mask(digits, 128, 256)
    assert not bool(np.asarray(mask).all())   # something actually skippable
    out = bwk.bw_gemm(digits, b, mask, block_m=128, block_n=128,
                      block_k=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.quant_gemm_ref(a, b)))


def test_bw_gemm_masked_oracle_consistency(rng):
    m, k, n = 128, 256, 128
    a = jnp.asarray(_rand_int8(rng, (m, k)))
    b = jnp.asarray(_rand_int8(rng, (k, n)))
    digits = ref.encode_planes_ref(a)
    mask = ops.plane_block_mask(digits, 128, 256)
    full = ref.bw_gemm_ref(digits, b)
    masked = ref.bw_gemm_masked_ref(digits, b, mask, 128, 256)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(masked))


@pytest.mark.parametrize("m,k,n", [(100, 200, 60), (1, 256, 1), (37, 73, 5)])
def test_ops_wrappers_pad_arbitrary_shapes(m, k, n, rng):
    """ops.quant_gemm and a planned bw_gemm take non-multiple shapes
    (pad+slice)."""
    a = _rand_int8(rng, (m, k))
    b = _rand_int8(rng, (k, n))
    want = (a.astype(np.int64) @ b.astype(np.int64)).astype(np.int32)
    got_q = np.asarray(ops.quant_gemm(jnp.asarray(a), jnp.asarray(b),
                                      interpret=True))
    np.testing.assert_array_equal(got_q, want)
    planned = ops.plan_operand(a)
    got_b = planned_kernel(bwk.bw_gemm, planned, b)
    np.testing.assert_array_equal(got_b, want)


def test_plan_operand_row_reordering_exact(rng):
    """Magnitude-ordered row permutation must not change results."""
    m, k, n = 300, 256, 64
    a = (rng.normal(0, 20, size=(m, k))).astype(np.int64).clip(-128, 127) \
        .astype(np.int8)
    b = _rand_int8(rng, (k, n))
    for reorder in (False, True):
        planned = ops.plan_operand(a, reorder_rows=reorder)
        got = planned_kernel(bwk.bw_gemm, planned, b)
        want = (a.astype(np.int64) @ b.astype(np.int64)).astype(np.int32)
        np.testing.assert_array_equal(got, want)


def test_row_reordering_improves_block_sparsity(rng):
    """The planner's row sort should never *reduce* skippable high-plane
    blocks for a heavy-tailed weight matrix."""
    m, k = 512, 512
    a = (rng.standard_t(3, size=(m, k)) * 12).clip(-128, 127).astype(np.int8)
    dense = ops.plan_operand(a, reorder_rows=False)
    sorted_ = ops.plan_operand(a, reorder_rows=True)
    d0 = float(np.asarray(dense.mask).mean())
    d1 = float(np.asarray(sorted_.mask).mean())
    assert d1 <= d0 + 1e-9


def test_plane_bounded_quantization_structurally_skips(rng):
    """quantize_to_planes(p) must leave planes >= p all-zero => the kernel
    skips those MXU passes entirely."""
    x = rng.normal(0, 1, size=(256, 256)).astype(np.float32)
    for planes in (1, 2, 3):
        q, s = quantlib.quantize_to_planes(jnp.asarray(x), planes)
        digits = np.asarray(ref.encode_planes_ref(q))
        assert (digits[planes:] == 0).all(), planes
        assert quantlib.plane_qmax(planes) == [0, 2, 10, 42][planes]


@pytest.mark.parametrize("m,k,bm,bk", [(128, 128, 128, 128),
                                       (256, 384, 128, 128),
                                       (384, 256, 128, 256)])
def test_ent_encode_kernel_matches_oracle(m, k, bm, bk, rng):
    enc_k = importlib.import_module("repro.kernels.encode")
    x = jnp.asarray(_rand_int8(rng, (m, k)))
    digits, mask = enc_k.ent_encode(x, block_m=bm, block_k=bk,
                                    interpret=True)
    want_d = np.asarray(ref.encode_planes_ref(x))
    want_m = np.asarray(ops.plane_block_mask(jnp.asarray(want_d), bm, bk))
    np.testing.assert_array_equal(np.asarray(digits), want_d)
    np.testing.assert_array_equal(np.asarray(mask), want_m)


def test_ent_encode_exhaustive_values():
    """Every int8 value decodes back through the kernel's digit planes."""
    enc_k = importlib.import_module("repro.kernels.encode")
    x = np.tile(np.arange(-128, 128, dtype=np.int8), 64).reshape(128, 128)
    digits, _ = enc_k.ent_encode(jnp.asarray(x), interpret=True)
    w = np.asarray([1, 4, 16, 64], np.int64)
    back = (np.asarray(digits).astype(np.int64)
            * w[:, None, None]).sum(axis=0)
    np.testing.assert_array_equal(back, x.astype(np.int64))


def test_quantized_matmul_ref_error_bound(rng):
    x = rng.normal(0, 1, size=(64, 128)).astype(np.float32)
    w = rng.normal(0, 0.02, size=(128, 32)).astype(np.float32)
    got = np.asarray(quantlib.quantized_matmul_ref(jnp.asarray(x),
                                                   jnp.asarray(w)))
    want = x @ w
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel < 0.05
