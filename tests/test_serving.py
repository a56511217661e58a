"""repro.serving: scheduler policies, slots, tiers, loadgen, metrics, and
the async multi-tier server (virtual-time and realtime modes)."""
import threading

import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.engine import QuantSpec, engine_names, get_engine
from repro.serving import (AsyncServer, DECODE, DONE, PREFILL, QUEUED,
                           REJECTED, Scheduler, ServeEngine, ServeRequest,
                           SlotAllocator, Tier, TierRouter, default_tiers,
                           estimate_step_time, loadgen, step_cost,
                           validate_summary)


def _req(rid, plen=4, max_tokens=4, **kw):
    return ServeRequest(rid, list(range(1, plen + 1)), max_tokens, **kw)


# ---------------------------------------------------------------------------
# request lifecycle
# ---------------------------------------------------------------------------

def test_request_lifecycle_and_timing():
    r = _req(0, arrival=1.0)
    assert r.state == QUEUED and not r.done and r.ttft is None
    r.to(PREFILL, now=1.5)
    r.to(DECODE, now=2.0)
    r.out.extend([5, 6, 7])
    r.to(DONE, now=3.0)
    assert r.done and r.terminal
    assert r.ttft == pytest.approx(1.0)        # 2.0 - 1.0
    assert r.tpot == pytest.approx(0.5)        # (3.0 - 2.0) / (3 - 1)
    assert r.latency == pytest.approx(2.0)


def test_request_illegal_transition():
    r = _req(0)
    with pytest.raises(ValueError, match="illegal transition"):
        r.to(DONE)
    r.to(REJECTED)
    assert r.terminal and not r.done


# ---------------------------------------------------------------------------
# slot allocator
# ---------------------------------------------------------------------------

def test_slots_bind_advance_release():
    alloc = SlotAllocator(2, max_len=16)
    a, b = _req(0, plen=2, max_tokens=2), _req(1, plen=1, max_tokens=1)
    assert alloc.free_slots() == [0, 1]
    assert alloc.bind(0, a) is False            # first use: no rebind
    alloc.bind(1, b)
    assert alloc.occupancy == 1.0
    # step 1: a teacher-forces, b emits its first (and only) token
    fin = alloc.advance(np.array([[7], [9]]))
    assert [r.rid for r in fin] == [1] and b.out == [9]
    assert alloc.free_slots() == [1]
    # slot reuse flags the rebind
    c = _req(2, plen=1, max_tokens=1)
    assert alloc.bind(1, c) is True
    assert int(alloc.generation[1]) == 2


def test_slots_reject_overlong_and_empty_prompt():
    alloc = SlotAllocator(1, max_len=4)
    with pytest.raises(ValueError, match="does not fit max_len"):
        alloc.bind(0, _req(0, plen=4))
    with pytest.raises(ValueError, match="empty prompt"):
        alloc.bind(0, ServeRequest(1, [], 4))


# ---------------------------------------------------------------------------
# admission scheduler
# ---------------------------------------------------------------------------

def test_scheduler_fcfs_order():
    s = Scheduler("fcfs")
    for i in range(3):
        s.submit(_req(i))
    assert [s.pop().rid for _ in range(3)] == [0, 1, 2]
    assert s.pop() is None


def test_scheduler_priority_order():
    s = Scheduler("priority")
    s.submit(_req(0, priority=0))
    s.submit(_req(1, priority=5))
    s.submit(_req(2, priority=5))
    assert [s.pop().rid for _ in range(3)] == [1, 2, 0]  # FCFS among equals


def test_scheduler_deadline_edf_order():
    s = Scheduler("deadline")
    s.submit(_req(0))                           # no deadline: last
    s.submit(_req(1, deadline=9.0))
    s.submit(_req(2, deadline=3.0))
    assert [s.pop().rid for _ in range(3)] == [2, 1, 0]


def test_scheduler_too_long_modes():
    long_req = _req(0, plen=10)
    with pytest.raises(ValueError, match="does not fit max_len"):
        Scheduler("fcfs", max_len=8, on_too_long="error").submit(long_req)
    s = Scheduler("fcfs", max_len=8, on_too_long="reject")
    assert s.submit(_req(1, plen=10)) is False
    assert s.rejected[0].state == REJECTED and s.rejected[0].error
    s = Scheduler("fcfs", max_len=8, on_too_long="truncate")
    r = _req(2, plen=10)
    with pytest.warns(UserWarning, match="truncating prompt"):
        assert s.submit(r) is True
    assert len(r.prompt) == 7                   # max_len - 1


# ---------------------------------------------------------------------------
# loadgen
# ---------------------------------------------------------------------------

def test_loadgen_deterministic_and_sorted():
    a = loadgen.synthesize(100, 8, pattern="poisson", rate=10, seed=3)
    b = loadgen.synthesize(100, 8, pattern="poisson", rate=10, seed=3)
    assert [(r.prompt, r.arrival) for r in a] == \
        [(r.prompt, r.arrival) for r in b]
    arr = [r.arrival for r in a]
    assert arr == sorted(arr) and arr[0] == 0.0


def test_loadgen_patterns_and_deadlines():
    burst = loadgen.arrival_times(6, "burst", burst=3, gap=0.5)
    assert list(burst) == [0.0, 0.0, 0.0, 0.5, 0.5, 0.5]
    uni = loadgen.arrival_times(4, "uniform", rate=2.0)
    assert list(uni) == [0.0, 0.5, 1.0, 1.5]
    assert list(loadgen.arrival_times(3, "none")) == [0.0, 0.0, 0.0]
    reqs = loadgen.synthesize(50, 5, deadline_slack=(1.0, 2.0), seed=0,
                              prompt_len=(2, 4), max_tokens=(1, 3))
    for r in reqs:
        assert r.arrival + 1.0 <= r.deadline <= r.arrival + 2.0
        assert 2 <= len(r.prompt) <= 4 and 1 <= r.max_tokens <= 3
        assert all(0 <= t < 50 for t in r.prompt)


# ---------------------------------------------------------------------------
# tiers: cost model + router
# ---------------------------------------------------------------------------

def test_step_cost_orders_tiers_by_planes():
    cfg = get_config("minicpm-2b", smoke=True)
    fast, quality = default_tiers(2)
    c2 = step_cost(cfg, 4, fast.spec)
    c4 = step_cost(cfg, 4, quality.spec)
    assert c2["int_macs"] < c4["int_macs"]
    assert estimate_step_time(cfg, 4, fast.spec) < \
        estimate_step_time(cfg, 4, quality.spec)
    # unfused pallas pays the accumulator HBM round-trip the fused path
    # keeps in VMEM — the routing estimate must see that too
    unfused = QuantSpec(planes=4, impl="pallas")
    assert step_cost(cfg, 4, unfused)["acc_hbm_bytes"] > \
        c4["acc_hbm_bytes"] == 0


def test_default_tiers_ladder():
    assert [t.name for t in default_tiers(1)] == ["quality"]
    assert [t.name for t in default_tiers(2)] == ["fast", "quality"]
    assert [t.name for t in default_tiers(3)] == \
        ["fast", "balanced", "quality"]
    with pytest.raises(ValueError):
        default_tiers(7)
    for t in default_tiers(3):
        assert t.spec.act_quant == "per_token"  # batch-independent decode


def test_router_policies():
    tiers = default_tiers(2)
    per_step = {"fast": 0.01, "quality": 0.04}
    assert TierRouter(tiers, per_step, "fastest").route(_req(0)).name == \
        "fast"
    assert TierRouter(tiers, per_step, "quality").route(_req(1)).name == \
        "quality"
    rr = TierRouter(tiers, per_step, "round_robin")
    assert [rr.route(_req(i)).name for i in range(4)] == \
        ["fast", "quality", "fast", "quality"]


def test_router_slo_deadline_aware():
    tiers = default_tiers(2)
    router = TierRouter(tiers, {"fast": 0.01, "quality": 0.04}, "slo")
    # no deadline -> quality; ~8 tokens of work
    assert router.route(_req(0, plen=4, max_tokens=4)).name == "quality"
    # loose deadline: quality still fits (8 * 0.04 = 0.32 < 1.0)
    loose = _req(1, plen=4, max_tokens=4, deadline=1.0)
    assert router.route(loose, now=0.0).name == "quality"
    # tight deadline: only fast fits (8 * 0.01 = 0.08 <= 0.1 < 0.32)
    tight = _req(2, plen=4, max_tokens=4, deadline=0.1)
    assert router.route(tight, now=0.0).name == "fast"
    # infeasible deadline falls back to fastest
    hopeless = _req(3, plen=4, max_tokens=4, deadline=1e-6)
    assert router.route(hopeless, now=0.0).name == "fast"
    # queue backlog pushes the estimate past the deadline
    backlogged = _req(4, plen=4, max_tokens=4, deadline=0.4)
    assert router.route(backlogged, now=0.0).name == "quality"
    assert router.route(
        _req(5, plen=4, max_tokens=4, deadline=0.4), now=0.0,
        loads={"quality": (400, 4), "fast": (0, 4)}).name == "fast"


def test_metrics_validate_summary_rejects_bad_shapes():
    with pytest.raises(ValueError, match="missing key"):
        validate_summary({"requests": 1})


# ---------------------------------------------------------------------------
# async server (model-running integration)
# ---------------------------------------------------------------------------

def test_async_server_two_tier_bit_identical_to_standalone():
    """The acceptance run: a fast planes=2 tier and a quality
    planes=4/pallas_fused tier serve a mixed 12-request load with
    overlapping lifetimes; every request's tokens are bit-identical to a
    standalone ServeEngine run under the same spec, and the TTFT/TPOT +
    tier-assignment metrics come back well-formed."""
    cfg = get_config("minicpm-2b", smoke=True)
    tiers = (Tier("fast", QuantSpec(planes=2, impl="planes",
                                    act_quant="per_token"), batch=2),
             Tier("quality", QuantSpec(planes=4, impl="pallas_fused",
                                       act_quant="per_token"), batch=2))
    reqs = loadgen.synthesize(cfg.vocab_size, 12, prompt_len=(3, 6),
                              max_tokens=(3, 6), pattern="poisson",
                              rate=200, deadline_slack=(0.001, 1.0), seed=0)
    prompts = {r.rid: list(r.prompt) for r in reqs}
    server = AsyncServer(cfg, tiers=tiers, max_len=16, router="slo",
                         step_time_scale=5e4)
    stats = validate_summary(server.run(reqs))
    assert stats["completed"] == 12 and stats["rejected"] == 0
    assert sum(stats["tier_requests"].values()) == 12
    assert len(stats["tier_requests"]) == 2     # both tiers took traffic
    assert stats["ttft"]["mean"] > 0 and stats["tpot"]["mean"] > 0
    # overlapping lifetimes: more requests completed than any tier has slots
    assert stats["completed"] > max(t.batch for t in tiers)
    by_tier = {}
    for r in reqs:
        by_tier.setdefault(r.tier, []).append(r)
    for tier in tiers:
        mine = by_tier[tier.name]
        clones = [ServeRequest(r.rid, prompts[r.rid], r.max_tokens)
                  for r in mine]
        ServeEngine(cfg, tier.batch, 16, quant=tier.spec).run(clones)
        assert {c.rid: c.out for c in clones} == \
            {r.rid: r.out for r in mine}, tier.name


def test_async_server_rejects_overlong_requests_and_keeps_serving():
    cfg = get_config("minicpm-2b", smoke=True)
    tiers = (Tier("only", QuantSpec(planes=3, impl="planes"), batch=2),)
    reqs = [_req(0, plen=3, max_tokens=3),
            _req(1, plen=40, max_tokens=3),     # cannot fit max_len=12
            _req(2, plen=3, max_tokens=3)]
    server = AsyncServer(cfg, tiers=tiers, max_len=12)
    stats = validate_summary(server.run(reqs))
    assert stats["completed"] == 2 and stats["rejected"] == 1
    assert reqs[1].state == REJECTED and reqs[1].error
    assert reqs[0].done and reqs[2].done


def test_async_server_realtime_mode_matches_virtual_outputs():
    """Threaded wall-clock mode completes the same load with the same
    per-request tokens as the deterministic virtual-time mode."""
    cfg = get_config("minicpm-2b", smoke=True)

    def fresh():
        return loadgen.synthesize(cfg.vocab_size, 6, prompt_len=(2, 4),
                                  max_tokens=(2, 4), pattern="poisson",
                                  rate=500, seed=5)

    tiers = (Tier("only", None, batch=2),)      # unquantized single tier
    virt_reqs, real_reqs = fresh(), fresh()
    server = AsyncServer(cfg, tiers=tiers, max_len=12, router="fastest")
    v_stats = validate_summary(server.run(virt_reqs))
    r_stats = validate_summary(server.run(real_reqs, realtime=True))
    assert v_stats["completed"] == r_stats["completed"] == 6
    assert r_stats["mode"] == "realtime" and v_stats["mode"] == "virtual"
    assert {r.rid: r.out for r in virt_reqs} == \
        {r.rid: r.out for r in real_reqs}
    assert threading.active_count() < 10        # worker threads joined


# ---------------------------------------------------------------------------
# the GEMM engine decides what the serving engine plans
# ---------------------------------------------------------------------------

def _plan_records(node):
    """Every 'w_plan' record of a param tree."""
    if not isinstance(node, dict):
        return []
    found = [node["w_plan"]] if "w_plan" in node else []
    for key, value in node.items():
        if key != "w_plan":
            found += _plan_records(value)
    return found


@pytest.mark.parametrize("impl", engine_names())
def test_engine_plans_exactly_when_the_gemm_engine_uses_plans(impl):
    """ServeEngine attaches 'w_plan' records exactly when the impl's
    GemmEngine ``uses_plans``, with schedules in that engine's ``order``
    (rebuilt here from each record's mask in both orders: at these widths
    the orders differ)."""
    from repro.kernels import ops
    gemm = get_engine(impl)
    cfg = get_config("minicpm-2b", smoke=True).replace(
        n_layers=2, d_model=256, n_heads=4, n_kv_heads=4, d_ff=512,
        vocab_size=1024)
    spec = QuantSpec(planes=3, impl=impl)
    eng = ServeEngine(cfg, batch=2, max_len=16, quant=spec)
    records = _plan_records(eng.params)
    if not gemm.uses_plans:
        assert records == [] and eng.plan_density is None
        return
    assert records and eng.quant.plan_stats["planned_weights"] > 0
    orders_differ = False
    for rec in records:
        masks = np.asarray(rec["mask"]).reshape(
            (-1,) + np.asarray(rec["mask"]).shape[-3:])
        scheds = np.asarray(rec["schedule"]).reshape(
            (-1,) + np.asarray(rec["schedule"]).shape[-2:])
        for mask, sched in zip(masks, scheds):
            want = {order: ops.pad_schedule(
                        ops.build_schedule(mask, spec.radix, order),
                        sched.shape[0])
                    for order in ops.SCHEDULE_ORDERS}
            np.testing.assert_array_equal(sched, want[gemm.order])
            orders_differ |= not np.array_equal(*want.values())
    assert orders_differ
