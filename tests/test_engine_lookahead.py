"""ServeEngine's lookahead: the next step is dispatched before the one in
flight is read back, fed on the device with the tokens that step samples.
Against an engine that steps in lock step, on seeded smoke-size models:
the same tokens for every request, bindings made or undone while a step
is in flight, the counters of completed steps, and where it is off."""
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.obs import metrics as obs_metrics
from repro.serving import ServeEngine, ServeRequest, Scheduler, TierWorker, \
    Tier

MAX_LEN = 24


def _requests():
    """More requests than slots, of mixed lengths, so that slots finish
    and are refilled while a step is in flight."""
    shapes = [(3, 5), (1, 9), (6, 2), (2, 7), (4, 4), (5, 1), (2, 6)]
    return [ServeRequest(rid, [1 + (7 * rid + j) % 40 for j in range(p)], n)
            for rid, (p, n) in enumerate(shapes)]


def _serve(eng, reqs, audit_ahead=None):
    sched = Scheduler("fcfs", max_len=MAX_LEN)
    for r in reqs:
        sched.submit(r, 0.0)
    done = []
    while eng.has_work(sched):
        eng.admit_from(sched, 0.0)
        done.extend(eng.step())
        if audit_ahead is not None:
            audit_ahead.append(eng._ahead is not None)
    return done


@pytest.mark.parametrize("arch", ["minicpm-2b", "mellum2-12b-a2.5b"])
def test_lookahead_serves_the_same_tokens(arch):
    cfg = get_config(arch, smoke=True)
    sync = ServeEngine(cfg, 3, MAX_LEN, seed=5, lookahead=False)
    ahead = ServeEngine(cfg, 3, MAX_LEN, seed=5, audit=True)
    assert ahead.lookahead and not sync.lookahead
    want = {r.rid: r.out for r in _serve(sync, _requests())}
    was_ahead = []
    got = _serve(ahead, _requests(), was_ahead)
    assert {r.rid: r.out for r in got} == want
    assert sorted(want) == list(range(len(_requests())))
    # most calls left a step in flight; none is left once the work is done
    assert sum(was_ahead) > len(was_ahead) // 2
    assert ahead._ahead is None
    # every binding stepped from position 0, one position a step
    seen = {}
    for ev in ahead.slots.trace:
        key = (ev.slot, ev.generation)
        assert ev.pos == seen.get(key, -1) + 1
        seen[key] = ev.pos


def test_binding_made_in_flight_joins_the_next_step():
    """A request admitted while a step is in flight is first computed by
    the step after it, from position 0, and ends with the tokens a
    lock-step engine gives it."""
    cfg = get_config("minicpm-2b", smoke=True)
    eng = ServeEngine(cfg, 2, MAX_LEN, seed=1, audit=True)
    first, late = ServeRequest(0, [3, 4], 2), ServeRequest(1, [5, 6, 7], 3)
    sched = Scheduler("fcfs", max_len=MAX_LEN)
    sched.submit(first, 0.0)
    eng.admit_from(sched, 0.0)
    eng.step()
    assert eng._ahead is not None
    assert list(eng._ahead.live >= 0) == [True, False]
    sched.submit(late, 0.0)
    eng.admit_from(sched, 0.0)          # binds slot 1 with a step in flight
    eng.step()                          # completes a step without slot 1
    assert late.out == [] and int(eng.slots.pos[1]) == 0
    assert list(eng._ahead.live >= 0) == [True, True]
    while eng.has_work():
        eng.step()
    ref = ServeEngine(cfg, 2, MAX_LEN, seed=1, lookahead=False)
    want = {r.rid: r.out for r in _serve(
        ref, [ServeRequest(0, [3, 4], 2), ServeRequest(1, [5, 6, 7], 3)])}
    assert {0: first.out, 1: late.out} == want
    assert [e.pos for e in eng.slots.trace if e.slot == 1] == [0, 1, 2, 3, 4]


def test_evicted_binding_drops_the_step_in_flight():
    """Evicting a slot with a step in flight leaves that step's token
    unapplied, and a request bound to the slot afterwards is served as on
    a fresh slot."""
    cfg = get_config("minicpm-2b", smoke=True)
    eng = ServeEngine(cfg, 2, MAX_LEN, seed=2)
    gone, other = ServeRequest(0, [2, 9], 6), ServeRequest(1, [4, 4], 4)
    sched = Scheduler("fcfs", max_len=MAX_LEN)
    sched.submit(gone, 0.0)
    sched.submit(other, 0.0)
    eng.admit_from(sched, 0.0)
    for _ in range(3):
        eng.step()
    assert eng._ahead is not None and len(gone.out) == 2
    assert eng.slots.evict(0) is gone
    eng.step()
    assert len(gone.out) == 2           # its step in flight is dropped
    fresh = ServeRequest(2, [6, 1, 3], 3)
    sched.submit(fresh, 0.0)
    eng.admit_from(sched, 0.0)
    while eng.has_work():
        eng.step()
    ref = ServeEngine(cfg, 2, MAX_LEN, seed=2, lookahead=False)
    want = {r.rid: r.out for r in _serve(
        ref, [ServeRequest(1, [4, 4], 4), ServeRequest(2, [6, 1, 3], 3)])}
    assert {1: other.out, 2: fresh.out} == want


def test_counters_count_completed_steps():
    cfg = get_config("minicpm-2b", smoke=True)
    obs_metrics.reset_metrics()
    reg = obs_metrics.get_registry()
    eng = ServeEngine(cfg, 3, MAX_LEN, seed=4, audit=True)
    calls = 0
    sched = Scheduler("fcfs", max_len=MAX_LEN)
    for r in _requests():
        sched.submit(r, 0.0)
    while eng.has_work(sched):
        eng.admit_from(sched, 0.0)
        eng.step()
        calls += 1
    bound = reg.counter("repro_serve_slot_steps_bound_total").value
    live = reg.counter("repro_serve_kv_positions_live_total").value
    read = reg.counter("repro_serve_kv_positions_read_total").value
    # one count per slot a completed step computed: the audit trace's
    assert bound == len(eng.slots.trace)
    assert live == sum(e.pos + 1 for e in eng.slots.trace)
    assert read == calls * 3 * MAX_LEN


def test_lookahead_off_where_it_cannot_hold():
    """Recurrent families, a step function set from outside and the async
    server's workers step in lock step; a step already in flight when the
    step function is replaced is completed first."""
    rwkv = ServeEngine(get_config("rwkv6-3b", smoke=True), 2, MAX_LEN)
    assert not rwkv.lookahead
    worker = TierWorker(Tier("t", None, 2), get_config("minicpm-2b",
                                                       smoke=True), MAX_LEN)
    assert not worker.engine.lookahead
    cfg = get_config("minicpm-2b", smoke=True)
    eng = ServeEngine(cfg, 2, MAX_LEN, seed=3)
    req = ServeRequest(0, [5, 2], 5)
    sched = Scheduler("fcfs", max_len=MAX_LEN)
    sched.submit(req, 0.0)
    eng.admit_from(sched, 0.0)
    eng.step()
    assert eng._ahead is not None
    step, calls = eng.step_fn, []

    def outside(params, tokens, pos, state):
        calls.append(np.asarray(pos).copy())
        return step(params, tokens, pos, state)
    eng.step_fn = outside
    eng.step()                          # completes the step in flight
    assert calls == [] and eng._ahead is None
    while eng.has_work():
        eng.step()
    assert eng._ahead is None and len(calls) == 4
    ref = ServeEngine(cfg, 2, MAX_LEN, seed=3, lookahead=False)
    assert {r.rid: r.out for r in _serve(ref, [ServeRequest(0, [5, 2], 5)])
            } == {0: req.out}
