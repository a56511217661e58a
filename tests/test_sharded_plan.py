"""repro.parallel plan/cost layer: shard partitioning of compacted
schedules, the sharded-plan verifier, plan-cache shard keys, the
collective-bytes cost term, and mesh-shape validation.

Everything here is host-side (pure numpy / cost arithmetic / planning on
one device) — the cross-device execution parity lives in
tests/test_sharded_apply.py behind a forced-device subprocess.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _propcheck import given, settings, strategies as st

from repro import analysis
from repro.engine import QuantSpec, get_engine
from repro.kernels import ops
from repro.launch.mesh import parse_mesh_shape, require_devices
from repro.parallel import (ShardedPlan, allreduce_bytes,
                            gemm_collective_bytes, normalize_shards,
                            shard_plan)
from repro.serving.tiers import (Tier, TierRouter, estimate_step_time,
                                 step_cost)

SHARD_GRIDS = ((2, 2), (4, 2), (2, 4))


def _plan(m, k, planes=3, order="m_major", density=None, seed=0):
    rng = np.random.default_rng(seed)
    w = (rng.standard_t(4, size=(k, m)) * 0.02).astype(np.float32)
    if density is not None:
        # thin the weight so the digit planes land near the target density
        keep = rng.random(w.shape) < density
        w = np.where(keep, w, 0.0).astype(np.float32)
    spec = QuantSpec(planes=planes, block_m=128, block_k=128)
    planned, _sw = ops.plan_for(w, spec, order=order)
    return planned, spec


# ---------------------------------------------------------------------------
# partition exactness (the core invariant)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("order", ["m_major", "k_major"])
@pytest.mark.parametrize("shards", SHARD_GRIDS)
def test_shard_schedules_partition_global_mask(order, shards):
    planned, _spec = _plan(512, 512, order=order)
    splan = shard_plan(planned, shards)
    assert isinstance(splan, ShardedPlan)
    assert splan.shards == tuple(shards)

    mask = np.asarray(splan.plan["mask"])
    bw_n, mb, kb = mask.shape
    mb_s, kb_s = mb // splan.s_model, kb // splan.s_data
    visits = np.zeros(mask.shape, dtype=np.int64)
    for i in range(splan.s_model):
        for j in range(splan.s_data):
            sched = np.asarray(splan.schedules[i, j])
            n_real = int(np.asarray(splan.sched_lens)[i, j])
            real = sched[sched[:, 3] != 0]
            assert len(real) <= n_real
            # every entry's row/kblk must stay inside the shard slab
            assert real[:, 1].max(initial=0) < mb_s
            assert real[:, 2].max(initial=0) < kb_s
            np.add.at(visits, (real[:, 0], i * mb_s + real[:, 1],
                               j * kb_s + real[:, 2]), 1)
    # exactly one shard schedules each occupied plane-block; empty blocks
    # are visited by no shard (missing -> wrong sums, dup -> double count)
    assert np.array_equal(visits, mask.astype(np.int64))
    # and the always-on verifier agrees
    assert analysis.verify_sharded_plan(splan).ok


@given(density=st.floats(0.05, 0.9), planes=st.integers(2, 4))
@settings(max_examples=8, deadline=None)
def test_partition_property_random_densities(density, planes):
    planned, _spec = _plan(256, 256, planes=planes, density=density,
                           seed=int(density * 1000) + planes)
    for shards in ((2, 2), (4, 2)):
        splan = shard_plan(planned, shards)
        report = analysis.verify_sharded_plan(splan)
        assert report.ok, str(report)


def test_partition_with_padded_block_grid():
    # m=384 -> 3 row blocks at block_m=128: s_model=2 forces padding to 4
    planned, _spec = _plan(384, 384)
    splan = shard_plan(planned, (2, 2))
    digits = np.asarray(splan.plan["digits"])
    assert digits.shape[1] % (2 * splan.block_m) == 0
    assert analysis.verify_sharded_plan(splan).ok
    # the padded tail rows are identity-permuted zeros
    inv = np.asarray(splan.plan["inv_perm"])
    assert inv.shape[0] == digits.shape[1]
    assert np.array_equal(np.sort(inv), np.arange(digits.shape[1]))


def test_verifier_catches_missing_and_duplicate_visits():
    planned, _spec = _plan(256, 256)
    splan = shard_plan(planned, (2, 2))
    scheds = np.asarray(splan.schedules).copy()
    real = np.flatnonzero(scheds[0, 0][:, 3] != 0)
    assert len(real) > 1

    # drop one visit -> the shard verifier and the partition check both fire
    broken = scheds.copy()
    broken[0, 0, real[0], 3] = 0
    import dataclasses
    bad = dataclasses.replace(splan, schedules=broken)
    codes = analysis.verify_sharded_plan(bad).codes(analysis.ERROR)
    assert "SHARD_BAD_PARTITION" in codes or "SCHED_MISSING_VISIT" in codes

    # duplicate a visit -> double-counted partial sums
    dup = scheds.copy()
    dup[0, 0, real[1]] = dup[0, 0, real[0]]
    bad = dataclasses.replace(splan, schedules=dup)
    codes = analysis.verify_sharded_plan(bad).codes(analysis.ERROR)
    assert "SHARD_BAD_PARTITION" in codes or "SCHED_DUPLICATE_VISIT" in codes


def test_verifier_catches_shape_mismatch():
    planned, _spec = _plan(256, 256)
    splan = shard_plan(planned, (2, 2))
    import dataclasses
    bad = dataclasses.replace(
        splan, schedules=np.asarray(splan.schedules)[:1])
    codes = analysis.verify_sharded_plan(bad).codes(analysis.ERROR)
    assert "SHARD_BAD_SHAPE" in codes


# ---------------------------------------------------------------------------
# plan cache keys / plan_for integration
# ---------------------------------------------------------------------------

def test_plan_cache_keys_split_on_shards():
    rng = np.random.default_rng(3)
    w = (rng.standard_t(4, size=(256, 256)) * 0.02).astype(np.float32)
    spec = QuantSpec(planes=3, block_m=128, block_k=128)
    p_unsharded, _ = ops.plan_for(w, spec)
    p_none, _ = ops.plan_for(w, spec, shards=None)
    p_11, _ = ops.plan_for(w, spec, shards=(1, 1))
    p_22, _ = ops.plan_for(w, spec, shards=(2, 2))
    p_42, _ = ops.plan_for(w, spec, shards=(4, 2))
    # (1, 1) normalizes to the unsharded cache entry
    assert p_11 is p_unsharded and p_none is p_unsharded
    assert p_unsharded.sharded is None
    # distinct shard grids are distinct cache entries with attached plans
    assert p_22 is not p_unsharded and p_42 is not p_22
    assert p_22.sharded.shards == (2, 2)
    assert p_42.sharded.shards == (4, 2)


def test_shard_plan_rejects_bad_inputs():
    planned, _spec = _plan(256, 256)
    with pytest.raises(ValueError):
        normalize_shards((2, 0))
    with pytest.raises(ValueError):
        normalize_shards((2, 2, 2))
    with pytest.raises(ValueError, match="radix"):
        # record dicts carry no order/radix metadata
        shard_plan({"digits": None}, (2, 2))
    with pytest.raises(ValueError, match="order"):
        shard_plan(planned, (2, 2), order="diagonal")


# ---------------------------------------------------------------------------
# collective-bytes cost term
# ---------------------------------------------------------------------------

def test_allreduce_bytes_formulas():
    assert allreduce_bytes(1000, 1) == 0
    assert allreduce_bytes(1000, 4) == 2 * 3 * 1000 // 4
    assert allreduce_bytes(1000, 4, reduce="psum_scatter") == 3 * 1000 // 4
    with pytest.raises(ValueError):
        allreduce_bytes(1000, 4, reduce="alltoall")


def test_gemm_collective_bytes():
    # no K sharding -> no reduce at all, whatever the model split
    assert gemm_collective_bytes(128, 1024, 1, 4) == 0
    full = gemm_collective_bytes(128, 1024, 4, 1)
    split = gemm_collective_bytes(128, 1024, 4, 2)
    assert full > 0 and split == full // 2
    scat = gemm_collective_bytes(128, 1024, 4, 1, reduce="psum_scatter")
    assert scat == full // 2


@pytest.mark.parametrize("impl", ["pallas_fused", "pallas_sparse",
                                  "pallas_pipelined"])
def test_engine_cost_shard_axis(impl):
    spec = QuantSpec(planes=3, block_m=128, block_k=128,
                     impl=impl if impl != "pallas_fused" else "pallas_fused")
    eng = get_engine(impl)
    c1 = eng.cost(128, 1024, 1024, spec, density=0.4)
    assert c1["collective_bytes"] == 0
    c4 = eng.cost(128, 1024, 1024, spec, density=0.4, shards=(4, 2))
    assert c4["collective_bytes"] == \
        gemm_collective_bytes(128, 1024, 4, 2)
    # per-shard arithmetic shrinks with the grid
    assert c4["int_macs"] < c1["int_macs"]
    assert c4["dma_bytes"] < c1["dma_bytes"]
    # shards=(1,1) is the unsharded cost
    assert eng.cost(128, 1024, 1024, spec, density=0.4,
                    shards=(1, 1)) == c1


def test_step_cost_and_estimate_prefer_sharding():
    from repro.configs.registry import get_config
    cfg = get_config("minicpm-2b", smoke=True)
    spec = QuantSpec(planes=3, impl="pallas_sparse", act_quant="per_token")
    c1 = step_cost(cfg, 4, spec)
    c8 = step_cost(cfg, 4, spec, shards=(4, 2))
    assert c1["collective_bytes"] == 0 and c8["collective_bytes"] > 0
    assert c8["int_macs"] < c1["int_macs"]
    # per-device work shrinks enough that the reduce traffic still wins
    assert estimate_step_time(cfg, 4, spec, shards=(4, 2)) < \
        estimate_step_time(cfg, 4, spec)
    # unquantized tiers pay bf16 partial traffic too
    cu = step_cost(cfg, 4, None, shards=(4, 2))
    assert cu["collective_bytes"] > 0


def test_router_sees_device_count_axis():
    from repro.configs.registry import get_config
    cfg = get_config("minicpm-2b", smoke=True)
    spec = QuantSpec(planes=3, impl="pallas_sparse", act_quant="per_token")
    single = Tier("single", spec, 4)
    sharded = Tier("sharded", spec, 4, shards=(4, 2))
    per_step = {t.name: estimate_step_time(cfg, t.batch, t.spec,
                                           shards=t.shards)
                for t in (single, sharded)}
    assert per_step["sharded"] < per_step["single"]
    router = TierRouter((single, sharded), per_step, policy="fastest")
    from repro.serving import ServeRequest
    req = ServeRequest(0, [1, 2, 3], 4)
    assert router.route(req).name == "sharded"


# ---------------------------------------------------------------------------
# mesh-shape validation
# ---------------------------------------------------------------------------

def test_parse_mesh_shape():
    assert parse_mesh_shape("4x2") == (4, 2)
    assert parse_mesh_shape("8") == (8,)
    for bad in ("", "4x", "axb", "0x2", "-1x2"):
        with pytest.raises(ValueError):
            parse_mesh_shape(bad)


def test_require_devices_names_failing_axis():
    # this test runs on the plain 1-device CPU host (conftest sets no
    # XLA_FLAGS), so any multi-device mesh shape must fail with the axis
    # named in the error
    with pytest.raises(RuntimeError, match=r"mesh axis 'data'"):
        require_devices(8, shape=(2, 4), axes=("data", "model"))
    with pytest.raises(ValueError, match="axis product"):
        require_devices(8, shape=(2, 2), axes=("data", "model"))
    # the trivial mesh always fits
    require_devices(1, shape=(1, 1), axes=("data", "model"))


# ---------------------------------------------------------------------------
# one routing rule: the single-device and the sharded apply agree
# ---------------------------------------------------------------------------

class _Routed(Exception):
    """Raised at the kernel seam with the route a path handed it."""


def _route_taken(monkeypatch, call):
    """The route ``call`` hands ``ops._gemm`` (the one kernel seam of both
    apply paths), or the ValueError it raises before reaching it."""
    def seam(route, *args, **kwargs):
        raise _Routed(route)
    monkeypatch.setattr(ops, "_gemm", seam)
    try:
        call()
    except _Routed as r:
        return r.args[0]
    except ValueError as e:
        return f"ValueError: {e}"
    raise AssertionError("the call never reached a kernel")


@pytest.mark.parametrize("density", ("low", "high"))
@pytest.mark.parametrize("order", ops.SCHEDULE_ORDERS)
@pytest.mark.parametrize("dispatch", ops.DISPATCHES)
def test_single_device_and_sharded_resolve_the_same_route(
        dispatch, order, density, monkeypatch):
    """For every dispatch x schedule order, on a sparse plan (one digit
    plane) and a dense one (all four), ``planned_dense_apply`` and
    ``sharded_planned_apply`` reach the kernel seam with the same route,
    or refuse the same combination with the same error."""
    import jax.numpy as jnp
    from repro.kernels import autotune
    from repro.parallel.apply import make_gemm_mesh, sharded_planned_apply
    rng = np.random.default_rng(3)
    spec = QuantSpec(planes=1 if density == "low" else 4, block_m=128,
                     block_k=128, act_quant="per_token")
    w = rng.normal(0, 0.05, size=(256, 256)).astype(np.float32)
    x = jnp.asarray(rng.normal(0, 1, size=(4, 256)).astype(np.float32))
    planned, sw = ops.plan_for(w, spec, order=order)
    plan = ops.plan_dense_weight(w, spec, order=order)
    splan = shard_plan(planned, (1, 1), sw=sw)
    autotune.set_cache(autotune.AutotuneCache("mem"))   # no measured winner
    try:
        single = _route_taken(monkeypatch, lambda: ops.planned_dense_apply(
            plan, x, spec, 256, fused=False, block_n=128, dispatch=dispatch,
            order=order))
        sharded = _route_taken(monkeypatch, lambda: sharded_planned_apply(
            splan, x, spec, 256, block_n=128, dispatch=dispatch,
            mesh=make_gemm_mesh((1, 1))))
    finally:
        autotune.reset_cache()
    assert single == sharded
    sparse_route = "pipelined" if order == "k_major" else "sparse"
    if dispatch == "sparse" and order == "k_major":
        assert single.startswith("ValueError") and "m_major" in single
    elif dispatch == "auto":
        assert single == (sparse_route if density == "low" else "dense")
    else:
        assert single == dispatch
