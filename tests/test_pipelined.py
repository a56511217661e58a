"""v3 double-buffered schedule pipelining + k_major B-reuse ordering.

Everything runs offline in interpret mode (tier-1 lanes).  The contract
under test: `bw_gemm_sparse_pipelined[_fused]` is *bit-identical* to the
v2 sparse kernels (and the dense predicated kernels) on the same plan —
in both schedule orders, across random densities, degenerate all-empty
schedules and pad_schedule no-op padding — while the k_major order elides
B-block DMAs (B_FETCH column / cost-model `b_dma_elided`) that the
per-row m_major walk must re-issue.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as hst
except ImportError:                     # offline: deterministic fallback
    from _propcheck import given, settings, strategies as hst
from _planned import planned_kernel
from _propcheck import assert_cross_context_close

from repro.core import quant as quantlib
from repro.engine import QuantSpec, get_engine
from repro.kernels import autotune, bw_gemm as bwk, ops
SCHED_COLS = bwk.SCHED_COLS


def _llmish(rng, m, k, planes=3):
    w = (rng.standard_t(4, size=(m, k)) * 0.02).astype(np.float32)
    qw, _ = quantlib.quantize_to_planes(jnp.asarray(w), planes=planes)
    return np.asarray(qw).astype(np.int8)


def _random_digits(seed: int, density: float, bw=4, mb=2, kb=2, bm=128,
                   bk=128):
    """Random digit planes with ~``density`` of the plane-blocks non-zero."""
    r = np.random.default_rng(seed)
    digits = r.integers(-2, 3, size=(bw, mb * bm, kb * bk)).astype(np.int8)
    keep = r.random((bw, mb, kb)) < density
    for p in range(bw):
        for i in range(mb):
            for j in range(kb):
                if not keep[p, i, j]:
                    digits[p, i * bm:(i + 1) * bm, j * bk:(j + 1) * bk] = 0
    return digits


def _reference(digits, b):
    acc = np.zeros((digits.shape[1], b.shape[1]), np.int64)
    for p in range(digits.shape[0]):
        acc += (4 ** p) * (digits[p].astype(np.int64) @ b.astype(np.int64))
    return acc.astype(np.int32)


# ---------------------------------------------------------------------------
# Schedule annotation invariants (both orders)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("order", ops.SCHEDULE_ORDERS)
def test_annotated_schedule_invariants(order, rng):
    a = _llmish(rng, 256, 256)
    planned = ops.plan_operand(a, block_m=128, block_k=128, order=order)
    sched = np.asarray(planned.schedule)
    mask = np.asarray(planned.mask)
    c = SCHED_COLS
    assert sched.shape[1] == len(SCHED_COLS)
    # every row visited; exactly one FIRST and one LAST per row, FIRST at
    # its earliest step and LAST at its latest (any visit order)
    for row in range(mask.shape[1]):
        steps = np.flatnonzero(sched[:, c["row"]] == row)
        assert steps.size > 0
        firsts = sched[steps, c["first"]]
        lasts = sched[steps, c["last"]]
        assert firsts.sum() == 1 and lasts.sum() == 1
        assert firsts[0] == 1 and lasts[-1] == 1
    real = sched[:, c["weight"]] != 0
    assert int(real.sum()) == int(mask.sum())
    # digit slots alternate per real step: an in-flight prefetch can never
    # target the slot the current step reads
    d_slots = sched[real, c["d_slot"]]
    assert (d_slots == np.arange(d_slots.size) % 2).all()
    # B slots alternate per *fetch*, and a step with B_FETCH=0 reuses the
    # k-block (and slot) of the most recent fetch
    fetches = sched[real][sched[real, c["b_fetch"]] == 1]
    assert (fetches[:, c["b_slot"]] == np.arange(len(fetches)) % 2).all()
    resident_k = resident_slot = None
    for entry in sched[real]:
        if entry[c["b_fetch"]] == 1:
            resident_k, resident_slot = entry[c["kblk"]], entry[c["b_slot"]]
        else:
            assert entry[c["kblk"]] == resident_k
            assert entry[c["b_slot"]] == resident_slot
    # the first real step always fetches
    if real.any():
        assert sched[real][0, c["b_fetch"]] == 1


def test_k_major_elides_b_fetches(rng):
    """With multiple m-blocks per k-block the global k-major walk fetches
    each B block once where the m-major walk re-fetches it per row."""
    a = _llmish(rng, 256, 256)
    pm = ops.plan_operand(a, block_m=128, block_k=128, order="m_major")
    pk = ops.plan_operand(a, block_m=128, block_k=128, order="k_major")
    sm = ops.schedule_stats(pm.schedule, pm.mask)
    sk = ops.schedule_stats(pk.schedule, pk.mask)
    assert sm["nnz_blocks"] == sk["nnz_blocks"]
    assert sk["b_fetches"] <= sm["b_fetches"]
    kb = np.asarray(pk.mask).shape[2]
    assert sk["b_fetches"] <= kb                 # one fetch per k-block
    assert sk["b_dma_elided"] > 0


def test_build_schedule_rejects_unknown_order():
    with pytest.raises(ValueError, match="order must be one of"):
        ops.build_schedule(np.ones((1, 1, 1), bool), 4, order="diagonal")


# ---------------------------------------------------------------------------
# Kernel bit-parity (property-tested across random densities + both orders)
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(seed=hst.integers(min_value=0, max_value=2 ** 31 - 1),
       density=hst.floats(min_value=0.0, max_value=1.0))
def test_pipelined_bit_matches_sparse_any_density(seed, density):
    """Across random plane-block densities (including the all-empty-rows
    edge at density 0) both schedule orders are bit-identical to the v2
    sparse kernel and the int64 reference, and pad_schedule padding is an
    exact no-op for the pipelined kernels."""
    digits = _random_digits(seed, density)
    r = np.random.default_rng(seed + 1)
    b = r.integers(-128, 128, size=(256, 128)).astype(np.int8)
    mask = ops.plane_block_mask(jnp.asarray(digits), 128, 128)
    want = _reference(digits, b)
    sched_m = ops.build_schedule(np.asarray(mask), 4, order="m_major")
    v2 = np.asarray(bwk.bw_gemm_sparse(
        jnp.asarray(digits), jnp.asarray(b), jnp.asarray(sched_m),
        block_m=128, block_n=128, block_k=128, interpret=True))
    np.testing.assert_array_equal(v2, want)
    for order in ops.SCHEDULE_ORDERS:
        sched = ops.build_schedule(np.asarray(mask), 4, order=order)
        for padded in (sched, ops.pad_schedule(sched, sched.shape[0] + 7)):
            got = np.asarray(bwk.bw_gemm_sparse_pipelined(
                jnp.asarray(digits), jnp.asarray(b), jnp.asarray(padded),
                block_m=128, block_n=128, block_k=128, interpret=True))
            np.testing.assert_array_equal(got, v2)


@settings(max_examples=4, deadline=None)
@given(seed=hst.integers(min_value=0, max_value=2 ** 31 - 1),
       density=hst.floats(min_value=0.0, max_value=1.0))
def test_pad_schedule_noop_invariance_both_orders(seed, density):
    """pad_schedule is a pure no-op for schedule *semantics*: padded and
    unpadded schedules in either order produce bit-identical fused
    results (weight/flags/fetch columns are all cleared on padding)."""
    digits = _random_digits(seed, density)
    r = np.random.default_rng(seed + 2)
    b = r.integers(-128, 128, size=(256, 128)).astype(np.int8)
    scale = r.uniform(0.5, 2.0, size=(256, 1)).astype(np.float32)
    mask = ops.plane_block_mask(jnp.asarray(digits), 128, 128)
    outs = []
    for order in ops.SCHEDULE_ORDERS:
        sched = ops.build_schedule(np.asarray(mask), 4, order=order)
        padded = ops.pad_schedule(sched, sched.shape[0] + 5)
        tail = padded[sched.shape[0]:]
        assert (tail[:, 3:] == 0).all()          # weight+flags+slots+fetch
        for s in (sched, padded):
            outs.append(np.asarray(bwk.bw_gemm_sparse_fused_pipelined(
                jnp.asarray(digits), jnp.asarray(b), jnp.asarray(s),
                jnp.asarray(scale), block_m=128, block_n=128, block_k=128,
                activation="silu", interpret=True)))
    for other in outs[1:]:
        np.testing.assert_array_equal(outs[0], other)


def test_pipelined_all_zero_operand_writes_exact_zeros(rng):
    """Degenerate schedule: sentinel-only (all rows empty) still writes
    every output block as exact zeros in both orders."""
    digits = np.zeros((4, 256, 256), np.int8)
    b = rng.integers(-128, 128, size=(256, 128)).astype(np.int8)
    mask = ops.plane_block_mask(jnp.asarray(digits), 128, 128)
    for order in ops.SCHEDULE_ORDERS:
        sched = ops.build_schedule(np.asarray(mask), 4, order=order)
        assert (sched[:, 3] == 0).all()          # sentinels only
        got = np.asarray(bwk.bw_gemm_sparse_pipelined(
            jnp.asarray(digits), jnp.asarray(b), jnp.asarray(sched),
            block_m=128, block_n=128, block_k=128, interpret=True))
        assert got.shape == (256, 128) and (got == 0).all()


def test_pipelined_fused_bit_matches_v2_fused(rng):
    a = _llmish(rng, 256, 256)
    b = rng.integers(-128, 128, size=(256, 128)).astype(np.int8)
    scale = rng.uniform(0.5, 2.0, size=(256,)).astype(np.float32)
    bias = rng.normal(0, 0.1, size=(256,)).astype(np.float32)
    pm = ops.plan_operand(a, block_m=128, block_k=128, order="m_major")
    pk = ops.plan_operand(a, block_m=128, block_k=128, order="k_major")
    for act in (None, "silu"):
        v2 = planned_kernel(bwk.bw_gemm_sparse_fused, pm, b, scale, bias,
                            activation=act)
        for planned in (pm, pk):
            got = planned_kernel(bwk.bw_gemm_sparse_fused_pipelined, planned,
                                 b, scale, bias, activation=act)
            np.testing.assert_array_equal(got, v2)


# ---------------------------------------------------------------------------
# Dispatch resolution and the pallas_pipelined engine
# ---------------------------------------------------------------------------

def test_planned_dense_apply_pipelined_dispatch_parity(rng):
    """All routes (dense / sparse / pipelined / auto) on both orders agree
    bitwise through the padded non-divisible path."""
    x = jnp.asarray(rng.normal(0, 1, size=(5, 96)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.05, size=(96, 64)).astype(np.float32))
    bias = jnp.asarray(rng.normal(0, 0.1, size=(64,)).astype(np.float32))
    spec = QuantSpec(planes=3, impl="pallas_pipelined",
                     act_quant="per_token")
    want = None
    for order in ops.SCHEDULE_ORDERS:
        plan = ops.plan_dense_weight(w, spec, order=order)
        routes = ("dense", "pipelined", "auto") if order == "k_major" \
            else ("dense", "sparse", "pipelined", "auto")
        for d in routes:
            out = np.asarray(ops.planned_dense_apply(
                plan, x, spec, 64, bias=bias, activation="silu",
                dispatch=d, order=order))
            if want is None:
                want = out
            np.testing.assert_array_equal(out, want)


def test_sparse_dispatch_rejects_k_major_schedule(rng):
    """The v2 kernels require consecutive output revisits: forcing
    dispatch='sparse' on a k_major plan must fail loudly."""
    x = jnp.asarray(rng.normal(0, 1, size=(4, 96)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.05, size=(96, 64)).astype(np.float32))
    spec = QuantSpec(planes=3, impl="pallas_pipelined")
    plan = ops.plan_dense_weight(w, spec, order="k_major")
    with pytest.raises(ValueError, match="m_major"):
        ops.planned_dense_apply(plan, x, spec, 64, dispatch="sparse",
                                order="k_major")


def test_v2_eager_wrappers_reject_k_major_plans(rng):
    """Every way to the v2 kernels refuses a k_major plan, fused and
    unfused, single-device and sharded (the one routing rule): on a real
    TPU the v2 out-BlockSpec would silently clobber partial sums on
    non-consecutive revisits (interpret mode hides it)."""
    x = jnp.asarray(rng.normal(0, 1, size=(4, 96)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.05, size=(96, 64)).astype(np.float32))
    spec = QuantSpec(planes=3, impl="pallas_pipelined")
    plan = ops.plan_dense_weight(w, spec, order="k_major")
    for fused in (True, False):
        with pytest.raises(ValueError, match="m_major"):
            ops.planned_dense_apply(plan, x, spec, 64, fused=fused,
                                    dispatch="sparse", order="k_major")
    with pytest.raises(ValueError, match="m_major"):
        ops.resolve_route("sparse", "k_major", 0.0)


def test_auto_dispatch_ignores_nontransferable_winner(rng):
    """A winner measured under k_major must not steer an m_major plan's
    'auto' route: the ranking does not transfer, so the density heuristic
    decides (and the result stays bit-identical either way)."""
    x = jnp.asarray(rng.normal(0, 1, size=(4, 96)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.05, size=(96, 64)).astype(np.float32))
    spec = QuantSpec(planes=2, impl="pallas_sparse")
    plan = ops.plan_dense_weight(w, spec, order="m_major")
    density = plan["schedule"].shape[0] / plan["mask"].size
    cache = autotune.AutotuneCache("mem")
    cache.record(64, 96, 4, spec,
                 {"block_m": 128, "block_k": 128, "block_n": 128,
                  "dispatch": "pipelined", "order": "k_major",
                  "pipelined": True}, density=density)
    autotune.set_cache(cache)
    try:
        routed = ops._resolve_dispatch("auto", plan, spec, 64, 96, 4,
                                       "m_major")
        with_winner = np.asarray(ops.planned_dense_apply(
            plan, x, spec, 64, dispatch="auto", order="m_major"))
    finally:
        autotune.reset_cache()
    heuristic = "sparse" if density <= ops.SPARSE_DENSITY_THRESHOLD \
        else "dense"
    assert routed == heuristic
    free = np.asarray(ops.planned_dense_apply(
        plan, x, spec, 64, dispatch="auto", order="m_major"))
    np.testing.assert_array_equal(with_winner, free)


def test_auto_dispatch_honors_pipelined_cache_winner(rng):
    """A measured autotune winner with dispatch='pipelined' routes 'auto'
    through the pipelined kernels — bit-identical to the heuristic route."""
    x = jnp.asarray(rng.normal(0, 1, size=(4, 96)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.05, size=(96, 64)).astype(np.float32))
    spec = QuantSpec(planes=2, impl="pallas_pipelined")
    plan = ops.plan_dense_weight(w, spec, order="k_major")
    density = plan["schedule"].shape[0] / plan["mask"].size
    cache = autotune.AutotuneCache("mem")
    cache.record(64, 96, 4, spec,
                 {"block_m": 128, "block_k": 128, "block_n": 128,
                  "dispatch": "pipelined", "order": "k_major",
                  "pipelined": True}, density=density)
    autotune.set_cache(cache)
    try:
        forced = np.asarray(ops.planned_dense_apply(
            plan, x, spec, 64, dispatch="auto", order="k_major"))
    finally:
        autotune.reset_cache()
    free = np.asarray(ops.planned_dense_apply(
        plan, x, spec, 64, dispatch="auto", order="k_major"))
    np.testing.assert_array_equal(forced, free)


def test_pallas_pipelined_engine_matches_planes_oracle(rng):
    x = jnp.asarray(rng.normal(0, 1, size=(4, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.05, size=(64, 48)).astype(np.float32))
    spec = QuantSpec(planes=3, impl="pallas_pipelined")
    oracle = np.asarray(get_engine("planes").apply(
        w, x, spec.replace(impl="planes"), out_dtype=jnp.float32))
    got = np.asarray(get_engine("pallas_pipelined").apply(
        w, x, spec, interpret=True, out_dtype=jnp.float32))
    assert_cross_context_close(got, oracle)


def test_pipelined_dispatch_inside_jit_and_scan(rng):
    """k_major plans flow through jit and lax.scan: per-layer schedules of
    different lengths are padded to stack, and the padded pipelined walk
    reproduces the eager dense route."""
    x = jnp.asarray(rng.normal(0, 1, size=(4, 96)).astype(np.float32))
    w = rng.normal(0, 0.05, size=(96, 64)).astype(np.float32)
    spec = QuantSpec(planes=3, impl="pallas_pipelined",
                     act_quant="per_token")
    stacked = jnp.asarray(np.stack([w, np.zeros_like(w), w * 3]))
    params, count = ops.plan_params({"lyr": {"w": stacked}}, spec,
                                    order="k_major")
    assert count == 3
    wp = params["lyr"]["w_plan"]
    assert wp["schedule"].ndim == 3      # [layers, L, 9], equal L
    assert wp["schedule"].shape[-1] == len(SCHED_COLS)

    @jax.jit
    def run(wp):
        def body(carry, sl):
            return carry, ops.planned_dense_apply(
                sl, x, spec, 64, dispatch="auto", order="k_major")
        return jax.lax.scan(body, 0.0, wp)[1]

    outs = np.asarray(run(wp))
    single = ops.plan_dense_weight(jnp.asarray(w), spec, use_cache=False,
                                   order="k_major")
    want0 = np.asarray(ops.planned_dense_apply(single, x, spec, 64,
                                               dispatch="dense",
                                               order="k_major"))
    assert_cross_context_close(outs[0], want0)
    assert (outs[1] == 0).all()          # the all-zero layer


# ---------------------------------------------------------------------------
# Overlap-aware cost model + downstream consumers
# ---------------------------------------------------------------------------

def test_cost_b_dma_elided_with_multiple_m_blocks(rng):
    """k_major schedules with several m-blocks per k-block must show
    b_dma_elided > 0, and the elision must shrink dma_bytes below the v2
    per-step B accounting at equal density."""
    w = jnp.asarray(rng.normal(0, 0.02, size=(256, 256)).astype(np.float32))
    spec = QuantSpec(planes=3, impl="pallas_pipelined", block_m=128,
                     block_k=128)
    plan = ops.plan_dense_weight(w, spec, order="k_major")
    eng = get_engine("pallas_pipelined")
    measured = eng.cost(256, 256, 128, spec, plan=plan)
    assert measured["b_dma_elided"] > 0
    sched = np.asarray(plan["schedule"])
    real = int((sched[:, 3] != 0).sum())
    fetches = int(sched[:, 8].sum())
    assert measured["b_dma_elided"] == real - fetches    # nb == 1 here
    v2 = get_engine("pallas_sparse").cost(
        256, 256, 128, spec, density=float(np.asarray(plan["mask"]).mean()))
    assert measured["dma_bytes"] < v2["dma_bytes"]
    assert v2["b_dma_elided"] == 0
    # the density-estimated path (no plan) also reports elision
    estimated = eng.cost(512, 512, 256, spec.replace(block_m=None,
                                                     block_k=None),
                         density=0.75)
    assert estimated["b_dma_elided"] > 0
    assert estimated["dma_bytes"] + 0 < get_engine("pallas_sparse").cost(
        512, 512, 256, spec.replace(block_m=None, block_k=None),
        density=0.75)["dma_bytes"]


def test_roofline_and_step_cost_carry_b_dma_elided():
    from repro.configs.registry import get_config
    from repro.launch.roofline import quantized_gemm_roofline
    from repro.serving import step_cost
    spec = QuantSpec(planes=4, impl="pallas_pipelined")
    eng = get_engine("pallas_pipelined")
    cost = eng.cost(512, 512, 256, spec, density=0.5)
    rl = quantized_gemm_roofline(cost)
    assert rl["b_dma_elided"] == cost["b_dma_elided"] > 0
    cfg = get_config("minicpm-2b", smoke=True)
    agg = step_cost(cfg, 4, spec, density=0.5)
    assert agg["b_dma_elided"] > 0
    # engines without B reuse keep the key at 0 so aggregation stays
    # uniform across tiers
    assert step_cost(cfg, 4, spec.replace(impl="pallas_fused"),
                     density=0.5)["b_dma_elided"] == 0


def test_estimate_step_time_pipelined_comparable_to_sparse():
    """Tier routing stays sane: the pipelined engine's logical int_macs
    match the sparse engine's at equal density (the overlap lives in
    dma_bytes, not in the MAC count the service-time estimate prices)."""
    from repro.configs.registry import get_config
    from repro.serving import estimate_step_time
    cfg = get_config("minicpm-2b", smoke=True)
    pipe = QuantSpec(planes=4, impl="pallas_pipelined",
                     act_quant="per_token")
    sparse = pipe.replace(impl="pallas_sparse")
    assert estimate_step_time(cfg, 4, pipe, density=0.25) == \
        estimate_step_time(cfg, 4, sparse, density=0.25)


def test_serve_tokens_identical_through_pipelined_engine(rng):
    """Served traffic through the pallas_pipelined engine (k_major plans,
    scan-sliced padded schedules, jit'd step) decodes token-for-token what
    the jnp oracle engine decodes."""
    from repro.configs.registry import get_config
    from repro.serving import ServeEngine, ServeRequest
    cfg = get_config("minicpm-2b", smoke=True)
    prompts = [rng.integers(0, cfg.vocab_size, 6).tolist() for _ in range(2)]

    def serve(impl):
        reqs = [ServeRequest(i, list(p), 4) for i, p in enumerate(prompts)]
        eng = ServeEngine(cfg, 2, 16, quant=QuantSpec(
            planes=3, impl=impl, act_quant="per_token"))
        eng.run(reqs)
        return [r.out for r in reqs]

    assert serve("pallas_pipelined") == serve("planes")
