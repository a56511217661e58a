"""Sparse plane-block dispatch: compacted schedules + scalar prefetch.

Everything runs offline in interpret mode (tier-1 lanes).  The contract
under test: `bw_gemm_sparse[_fused]` is *bit-identical* to the dense
predicated kernels on the same plan — including degenerate schedules
(all-zero operand -> sentinel-only schedule -> exact zeros), fully-dense
masks, adversarial sparse-high-plane inputs, and non-block-divisible
shapes through the padded path — while an all-zero plane-block costs
neither a DMA nor a grid step (schedule-length / cost-model checks).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _planned import planned_kernel
from _propcheck import assert_cross_context_close
from repro.core import encodings as enc
from repro.core import quant as quantlib
from repro.engine import QuantSpec, get_engine
from repro.kernels import bw_gemm as bwk, ops
from repro.kernels.bw_gemm import SCHED_COLS


def _llmish(rng, m, k, planes=3):
    """LLM-like int8 multiplicand, plane-bounded so high planes are sparse."""
    w = (rng.standard_t(4, size=(m, k)) * 0.02).astype(np.float32)
    qw, _ = quantlib.quantize_to_planes(jnp.asarray(w), planes=planes)
    return np.asarray(qw).astype(np.int8)


# ---------------------------------------------------------------------------
# Schedule construction invariants
# ---------------------------------------------------------------------------

def test_build_schedule_layout_and_flags(rng):
    a = _llmish(rng, 256, 256)
    planned = ops.plan_operand(a, block_m=128, block_k=128)
    sched = np.asarray(planned.schedule)
    mask = np.asarray(planned.mask)
    c = SCHED_COLS
    # one real entry per non-zero plane-block, plus sentinels for empty rows
    nnz = int(mask.sum())
    rows_present = {int(r) for r in sched[:, c["row"]]}
    assert rows_present == set(range(mask.shape[1]))    # every row visited
    assert int((sched[:, c["weight"]] != 0).sum()) == nnz
    # rows are contiguous and non-decreasing (CSR-of-blocks order)
    assert (np.diff(sched[:, c["row"]]) >= 0).all()
    # exactly one FIRST and one LAST per row, at the row span's ends
    for row in rows_present:
        span = sched[sched[:, c["row"]] == row]
        assert span[0, c["first"]] == 1 and span[-1, c["last"]] == 1
        assert span[:, c["first"]].sum() == 1 == span[:, c["last"]].sum()
    # weights are radix**plane for real entries
    real = sched[sched[:, c["weight"]] != 0]
    assert (real[:, c["weight"]] == 4 ** real[:, c["plane"]]).all()


def test_build_schedule_empty_rows_get_sentinels():
    mask = np.zeros((4, 3, 2), bool)
    mask[1, 0, 1] = True                 # only row 0 has work
    sched = ops.build_schedule(mask, radix=4)
    c = SCHED_COLS
    assert sched.shape == (3, len(SCHED_COLS))   # 1 real + 2 sentinels
    sentinels = sched[sched[:, c["weight"]] == 0]
    assert {int(r) for r in sentinels[:, c["row"]]} == {1, 2}
    assert (sentinels[:, c["first"]] == 1).all()
    assert (sentinels[:, c["last"]] == 1).all()


def test_pad_schedule_appends_noops(rng):
    a = _llmish(rng, 128, 256)
    planned = ops.plan_operand(a, block_m=128, block_k=128)
    sched = np.asarray(planned.schedule)
    padded = ops.pad_schedule(sched, sched.shape[0] + 5)
    assert padded.shape[0] == sched.shape[0] + 5
    np.testing.assert_array_equal(padded[:sched.shape[0]], sched)
    tail = padded[sched.shape[0]:]
    c = SCHED_COLS
    assert (tail[:, c["weight"]] == 0).all()
    assert (tail[:, c["first"]] == 0).all()
    assert (tail[:, c["last"]] == 0).all()
    assert (tail[:, c["row"]] == sched[-1, c["row"]]).all()
    with pytest.raises(ValueError, match="cannot pad"):
        ops.pad_schedule(sched, sched.shape[0] - 1)


# ---------------------------------------------------------------------------
# Kernel bit-parity vs the dense predicated kernels
# ---------------------------------------------------------------------------

def test_sparse_bit_matches_dense_random(rng):
    a = _llmish(rng, 256, 256)
    b = rng.integers(-128, 128, size=(256, 128)).astype(np.int8)
    planned = ops.plan_operand(a, block_m=128, block_k=128)
    dense = planned_kernel(bwk.bw_gemm, planned, b)
    sparse = planned_kernel(bwk.bw_gemm_sparse, planned, b)
    want = (a.astype(np.int64) @ b.astype(np.int64)).astype(np.int32)
    np.testing.assert_array_equal(sparse, dense)
    np.testing.assert_array_equal(sparse, want)


def test_sparse_fused_bit_matches_dense_fused(rng):
    a = _llmish(rng, 256, 256)
    b = rng.integers(-128, 128, size=(256, 128)).astype(np.int8)
    scale = rng.uniform(0.5, 2.0, size=(256,)).astype(np.float32)
    bias = rng.normal(0, 0.1, size=(256,)).astype(np.float32)
    planned = ops.plan_operand(a, block_m=128, block_k=128)
    for act in (None, "silu"):
        dense = planned_kernel(bwk.bw_gemm_fused, planned, b, scale, bias,
                               activation=act)
        sparse = planned_kernel(bwk.bw_gemm_sparse_fused, planned, b, scale,
                                bias, activation=act)
        np.testing.assert_array_equal(sparse, dense)


def test_sparse_adversarial_high_plane_only(rng):
    """Values +-64 = +-4^3 occupy *only* EN-T plane 3, and only one block
    corner: the schedule must gather exactly that plane-block."""
    a = np.zeros((256, 256), np.int8)
    a[:128, :128] = rng.choice(np.int8([64, -64]), size=(128, 128))
    b = rng.integers(-128, 128, size=(256, 128)).astype(np.int8)
    planned = ops.plan_operand(a, block_m=128, block_k=128)
    st = ops.schedule_stats(planned.schedule, planned.mask)
    assert st["nnz_blocks"] == 1, st
    got = planned_kernel(bwk.bw_gemm_sparse, planned, b)
    want = (a.astype(np.int64) @ b.astype(np.int64)).astype(np.int32)
    np.testing.assert_array_equal(got, want)


def test_sparse_all_zero_plane_returns_exact_zeros(rng):
    """Degenerate schedule: an all-zero operand plans to a sentinel-only
    (empty) schedule and the kernel still writes exact zeros everywhere."""
    a = np.zeros((256, 256), np.int8)
    b = rng.integers(-128, 128, size=(256, 128)).astype(np.int8)
    planned = ops.plan_operand(a, block_m=128, block_k=128)
    st = ops.schedule_stats(planned.schedule, planned.mask)
    assert st["nnz_blocks"] == 0 and st["density"] == 0.0
    assert st["steps"] == planned.mask.shape[1]          # one per row
    got = planned_kernel(bwk.bw_gemm_sparse, planned, b)
    assert got.shape == (256, 128) and (got == 0).all()
    fused = planned_kernel(bwk.bw_gemm_sparse_fused, planned, b,
                           np.ones(256, np.float32))
    assert (fused == 0).all()


def test_sparse_fully_dense_mask_bit_matches_dense(rng):
    """Fully-dense occupancy (every plane of every block non-zero): the
    compacted schedule degenerates to the full cross product and must
    still bit-match the dense kernel."""
    a = (rng.integers(-128, 127, size=(128, 128)) | 1).astype(np.int8)
    planned = ops.plan_operand(a, block_m=128, block_k=128)
    assert planned.density() == 1.0
    assert planned.schedule.shape[0] == planned.mask.size
    b = rng.integers(-128, 128, size=(128, 128)).astype(np.int8)
    dense = planned_kernel(bwk.bw_gemm, planned, b)
    sparse = planned_kernel(bwk.bw_gemm_sparse, planned, b)
    np.testing.assert_array_equal(sparse, dense)


@pytest.mark.parametrize("encoding", enc.ENCODINGS)
def test_sparse_roundtrips_every_encoding(encoding, rng):
    """The schedule bakes radix**plane into WEIGHT, so radix-2 encodings
    must be exact through the same kernel."""
    a = rng.integers(-128, 128, size=(64, 64)).astype(np.int8)
    b = rng.integers(-128, 128, size=(64, 32)).astype(np.int8)
    planned = ops.plan_operand(a, encoding=encoding, block_m=64, block_k=64)
    got = planned_kernel(bwk.bw_gemm_sparse, planned, b)
    want = (a.astype(np.int64) @ b.astype(np.int64)).astype(np.int32)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Dispatch through plans, jit/scan, and the pallas_sparse engine
# ---------------------------------------------------------------------------

def test_planned_dense_apply_dispatch_parity_padded_shapes(rng):
    """Non-block-divisible (5, 96) x (96, 64) through the padded path:
    sparse, dense and auto dispatch agree bitwise, per-tensor and
    per-token."""
    x = jnp.asarray(rng.normal(0, 1, size=(5, 96)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.05, size=(96, 64)).astype(np.float32))
    bias = jnp.asarray(rng.normal(0, 0.1, size=(64,)).astype(np.float32))
    for aq in ("per_tensor", "per_token"):
        spec = QuantSpec(planes=3, impl="pallas_sparse", act_quant=aq)
        plan = ops.plan_dense_weight(w, spec)
        outs = {d: np.asarray(ops.planned_dense_apply(
                    plan, x, spec, 64, bias=bias, activation="silu",
                    dispatch=d))
                for d in ("dense", "sparse", "auto")}
        np.testing.assert_array_equal(outs["sparse"], outs["dense"])
        np.testing.assert_array_equal(outs["auto"], outs["dense"])


def test_sparse_dispatch_inside_jit_and_scan(rng):
    """The dispatch decision is shape-derived, so plans flow through jit
    and lax.scan; per-layer schedules of different lengths are padded to
    stack."""
    x = jnp.asarray(rng.normal(0, 1, size=(4, 96)).astype(np.float32))
    w = rng.normal(0, 0.05, size=(96, 64)).astype(np.float32)
    spec = QuantSpec(planes=3, impl="pallas_sparse", act_quant="per_token")
    stacked = jnp.asarray(np.stack([w, np.zeros_like(w), w * 3]))
    params, count = ops.plan_params({"lyr": {"w": stacked}}, spec)
    assert count == 3
    wp = params["lyr"]["w_plan"]
    assert wp["schedule"].ndim == 3      # [layers, L, 6], equal L

    @jax.jit
    def run(wp):
        def body(carry, sl):
            return carry, ops.planned_dense_apply(sl, x, spec, 64,
                                                  dispatch="auto")
        return jax.lax.scan(body, 0.0, wp)[1]

    outs = np.asarray(run(wp))
    single = ops.plan_dense_weight(jnp.asarray(w), spec, use_cache=False)
    want0 = np.asarray(ops.planned_dense_apply(single, x, spec, 64,
                                               dispatch="dense"))
    # jit-compiled vs eager act-quantization can differ by 1 float LSB
    # (XLA fusion); same-context bit-parity is covered by the eager tests
    assert_cross_context_close(outs[0], want0)
    assert (outs[1] == 0).all()          # the all-zero layer


def test_pallas_sparse_engine_matches_planes_oracle(rng):
    x = jnp.asarray(rng.normal(0, 1, size=(4, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.05, size=(64, 48)).astype(np.float32))
    spec = QuantSpec(planes=3, impl="pallas_sparse")
    oracle = np.asarray(get_engine("planes").apply(
        w, x, spec.replace(impl="planes"), out_dtype=jnp.float32))
    got = np.asarray(get_engine("pallas_sparse").apply(
        w, x, spec, interpret=True, out_dtype=jnp.float32))
    assert_cross_context_close(got, oracle)


# ---------------------------------------------------------------------------
# Schedule-aware cost model
# ---------------------------------------------------------------------------

def test_cost_counters_scale_with_density():
    m, k, n = 512, 512, 256
    spec = QuantSpec(planes=4, impl="pallas_sparse")
    eng_s = get_engine("pallas_sparse")
    eng_d = get_engine("pallas_fused")
    costs = [eng_s.cost(m, k, n, spec, density=d)
             for d in (0.125, 0.25, 0.5, 1.0)]
    # grid steps and DMA bytes drop monotonically as density drops
    assert all(a["grid_steps"] <= b["grid_steps"]
               for a, b in zip(costs, costs[1:]))
    assert all(a["dma_bytes"] <= b["dma_bytes"]
               for a, b in zip(costs, costs[1:]))
    assert all(a["int_macs"] < b["int_macs"]
               for a, b in zip(costs, costs[1:]))
    # at low density the sparse dispatch moves far fewer bytes and runs
    # far fewer grid steps than the dense predicated kernel
    dense = eng_d.cost(m, k, n, spec, density=0.125)
    assert costs[0]["dma_bytes"] < dense["dma_bytes"]
    assert costs[0]["grid_steps"] < dense["grid_steps"]
    # dense kernel DMA does not depend on density (it always moves every
    # plane); its executed MACs do
    assert dense["dma_bytes"] == eng_d.cost(m, k, n, spec,
                                            density=1.0)["dma_bytes"]


def test_cost_accepts_measured_plan(rng):
    w = jnp.asarray(rng.normal(0, 0.02, size=(256, 192)).astype(np.float32))
    spec = QuantSpec(planes=3, impl="pallas_sparse")
    plan = ops.plan_dense_weight(w, spec)
    eng = get_engine("pallas_sparse")
    measured = eng.cost(192, 256, 128, spec, plan=plan)
    density = float(np.asarray(plan["mask"]).mean())
    assert measured == eng.cost(192, 256, 128, spec, density=density)


def test_estimate_step_time_prices_density():
    from repro.configs.registry import get_config
    from repro.serving import estimate_step_time
    cfg = get_config("minicpm-2b", smoke=True)
    spec = QuantSpec(planes=4, impl="pallas_sparse",
                     act_quant="per_token")
    sparse_est = estimate_step_time(cfg, 4, spec, density=0.25)
    dense_est = estimate_step_time(cfg, 4, spec)        # assumes dense
    assert sparse_est < dense_est


def test_quantized_gemm_roofline_prices_sparsity():
    from repro.launch.roofline import quantized_gemm_roofline
    spec = QuantSpec(planes=4, impl="pallas_sparse")
    eng = get_engine("pallas_sparse")
    lo = quantized_gemm_roofline(eng.cost(512, 512, 256, spec, density=0.25))
    hi = quantized_gemm_roofline(eng.cost(512, 512, 256, spec, density=1.0))
    assert lo["t_compute_s"] < hi["t_compute_s"]
    assert lo["t_memory_s"] < hi["t_memory_s"]
    assert set(lo) >= {"t_compute_s", "t_memory_s", "bottleneck",
                       "grid_steps", "dma_bytes", "int_macs"}


def test_serve_engine_exposes_plan_density():
    from repro.configs.registry import get_config
    from repro.serving import ServeEngine
    cfg = get_config("minicpm-2b", smoke=True)
    spec = QuantSpec(planes=3, impl="pallas_sparse", act_quant="per_token")
    eng = ServeEngine(cfg, 2, 16, quant=spec)
    assert eng.plan_density is not None and 0.0 < eng.plan_density <= 1.0
    assert eng.quant.plan_stats["plane_block_density"] == eng.plan_density


def test_serve_tokens_identical_through_sparse_engine(rng):
    """Served traffic through the pallas_sparse engine (pre-planned
    weights, scan-sliced padded schedules, jit'd step) decodes
    token-for-token what the jnp oracle engine decodes."""
    from repro.configs.registry import get_config
    from repro.serving import ServeEngine, ServeRequest
    cfg = get_config("minicpm-2b", smoke=True)
    prompts = [rng.integers(0, cfg.vocab_size, 6).tolist() for _ in range(3)]

    def serve(impl):
        reqs = [ServeRequest(i, list(p), 5) for i, p in enumerate(prompts)]
        eng = ServeEngine(cfg, 2, 16, quant=QuantSpec(
            planes=3, impl=impl, act_quant="per_token"))
        eng.run(reqs)
        return [r.out for r in reqs]

    assert serve("pallas_sparse") == serve("planes")
