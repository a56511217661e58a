"""Tests for repro.obs: tracing, metrics, cost-model calibration.

Pins the tentpole contracts:

- span nesting / thread-safety / the disabled-mode no-op fast path
  (NULL_SPAN singleton, zero events, zero gated-metric deltas on the
  serve hot path);
- deterministic histogram snapshots under virtual-time serving;
- Chrome/Perfetto trace-event JSON schema;
- calibration drift ratios, the COST_MODEL_MISCALIBRATED warning and
  the TierRouter / estimate_step_time correction hooks;
- the metrics registry (labels, prometheus exposition, snapshot diff)
  and the ``python -m repro.obs`` CLI.
"""
import json
import threading

import numpy as np
import pytest

from repro import obs
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.__main__ import main as obs_main


@pytest.fixture
def tracing():
    """Enable tracing with a clean buffer; restore disabled-state after."""
    was = obs.enabled()
    obs.enable(clear_events=True)
    yield
    obs.disable() if not was else obs.enable()
    obs.clear_trace()


@pytest.fixture
def no_tracing():
    was = obs.enabled()
    obs.disable()
    obs.clear_trace()
    yield
    if was:
        obs.enable()


# ---------------------------------------------------------------------------
# Tracing
# ---------------------------------------------------------------------------

class TestTrace:
    def test_disabled_span_is_shared_noop(self, no_tracing):
        s1 = obs.span("a", k=1)
        s2 = obs.span("b")
        assert s1 is obs.NULL_SPAN and s2 is obs.NULL_SPAN
        with s1 as s:
            assert s.set(x=1) is s
        assert obs.trace_events() == []

    def test_span_records_complete_event(self, tracing):
        with obs.span("outer", cat="test", m=4):
            pass
        (ev,) = obs.trace_events()
        assert ev["name"] == "outer" and ev["ph"] == "X"
        assert ev["cat"] == "test" and ev["args"] == {"m": 4}
        assert ev["pid"] == obs.PID_RUNTIME
        assert ev["dur"] >= 0 and ev["ts"] >= 0

    def test_span_nesting(self, tracing):
        with obs.span("outer"):
            with obs.span("inner"):
                pass
        inner, outer = obs.trace_events()
        assert inner["name"] == "inner" and outer["name"] == "outer"
        # the inner span lies within the outer span's [ts, ts+dur]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]

    def test_span_set_mid_flight(self, tracing):
        with obs.span("s") as sp:
            sp.set(route="dense")
        (ev,) = obs.trace_events()
        assert ev["args"] == {"route": "dense"}

    def test_thread_safety(self, tracing):
        n_threads, n_spans = 8, 50

        def work(i):
            for j in range(n_spans):
                with obs.span(f"t{i}", j=j):
                    pass

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        evs = obs.trace_events()
        assert len(evs) == n_threads * n_spans
        # each thread's events carry its own tid
        by_name = {}
        for ev in evs:
            by_name.setdefault(ev["name"], set()).add(ev["tid"])
        assert len(by_name) == n_threads
        assert all(len(tids) == 1 for tids in by_name.values())

    def test_complete_event_virtual_clock(self, tracing):
        obs.complete_event("PREFILL", 1.5, 2.0, tid=7, args={"ttft": 0.5})
        (ev,) = obs.trace_events()
        assert ev["pid"] == obs.PID_SERVER and ev["tid"] == 7
        assert ev["ts"] == pytest.approx(1.5e6)
        assert ev["dur"] == pytest.approx(0.5e6)

    def test_chrome_schema_and_save(self, tracing, tmp_path):
        with obs.span("a"):
            pass
        obs.instant("marker", note="x")
        path = tmp_path / "trace.json"
        obs.save(str(path))
        doc = json.loads(path.read_text())
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        evs = doc["traceEvents"]
        # process_name metadata for both clock domains
        meta = [e for e in evs if e["ph"] == "M"]
        assert {e["pid"] for e in meta} == {obs.PID_RUNTIME,
                                            obs.PID_SERVER}
        for ev in evs:
            assert {"name", "ph", "pid", "tid"} <= set(ev)
            if ev["ph"] == "X":
                assert "ts" in ev and "dur" in ev
        assert any(e["ph"] == "i" for e in evs)

    def test_region_counts_without_tracing(self, no_tracing):
        hist = obs_metrics.get_registry().histogram(
            obs_trace.REGION_SECONDS, obs_metrics.REGION_EDGES)
        child = hist.labels(region="test.region_off")
        n0, s0 = child.count, child.total
        with obs.region("test.region_off"):
            pass
        assert child.count == n0 + 1 and child.total >= s0
        assert obs.trace_events() == []

    def test_region_writes_event_when_tracing(self, tracing):
        child = obs_metrics.get_registry().histogram(
            obs_trace.REGION_SECONDS, obs_metrics.REGION_EDGES) \
            .labels(region="test.region_on")
        n0 = child.count
        with obs.region("test.region_on"):
            pass
        (ev,) = obs.trace_events()
        assert ev["name"] == "test.region_on" and ev["ph"] == "X"
        assert ev["pid"] == obs.PID_RUNTIME and ev["dur"] >= 0
        assert child.count == n0 + 1

    def test_region_survives_metrics_reset(self, no_tracing):
        with obs.region("test.region_reset"):
            pass
        obs_metrics.reset_metrics()
        with obs.region("test.region_reset"):
            pass
        values = obs_metrics.snapshot()[obs_trace.REGION_SECONDS]["values"]
        assert values["region=test.region_reset"]["count"] == 1

    def test_enable_clears_on_request(self, tracing):
        with obs.span("a"):
            pass
        assert obs.trace_events()
        obs.enable(clear_events=True)
        assert obs.trace_events() == []


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter_gauge_histogram(self):
        reg = obs_metrics.MetricsRegistry()
        c = reg.counter("c_total", "a counter")
        c.inc()
        c.inc(3)
        assert c.value == 4
        with pytest.raises(ValueError):
            c.inc(-1)
        g = reg.gauge("g")
        g.set(2.5)
        g.inc(0.5)
        assert g.value == 3.0
        h = reg.histogram("h", (1.0, 10.0))
        for v in (0.5, 5.0, 50.0):
            h.observe(v)
        snap = h.snapshot()["values"][""]
        assert snap["edges"] == [1.0, 10.0]
        assert snap["counts"] == [1, 1, 1]        # +Inf overflow bucket
        assert snap["count"] == 3
        assert snap["sum"] == pytest.approx(55.5)

    def test_kind_mismatch_raises(self):
        reg = obs_metrics.MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_labels(self):
        reg = obs_metrics.MetricsRegistry()
        c = reg.counter("dispatch_total")
        c.labels(route="dense").inc(2)
        c.labels(route="sparse").inc()
        snap = c.snapshot()["values"]
        assert snap["route=dense"] == 2
        assert snap["route=sparse"] == 1

    def test_prometheus_text(self):
        reg = obs_metrics.MetricsRegistry()
        reg.counter("req_total", "requests").labels(tier="fast").inc(5)
        h = reg.histogram("lat", (0.1, 1.0), help="latency")
        h.observe(0.05)
        h.observe(0.5)
        text = reg.prometheus_text()
        assert "# TYPE req_total counter" in text
        assert 'req_total{tier="fast"} 5' in text
        assert '# TYPE lat histogram' in text
        # cumulative le buckets and the +Inf total
        assert 'lat_bucket{le="0.1"} 1' in text
        assert 'lat_bucket{le="1.0"} 2' in text
        assert 'lat_bucket{le="+Inf"} 2' in text
        assert "lat_count 2" in text

    def test_default_registry_presets_glossary(self):
        snap = obs_metrics.snapshot()
        for name in obs_metrics.GLOSSARY:
            assert name in snap, name
        # the ISSUE's acceptance series are part of the glossary
        for name in ("repro_plan_cache_hits_total",
                     "repro_autotune_cache_misses_total",
                     "repro_autotune_vmem_rejected_total",
                     "repro_collective_bytes_total",
                     "repro_serve_ttft_seconds",
                     "repro_cost_drift_ratio"):
            assert name in snap, name

    def test_diff_snapshots(self):
        reg = obs_metrics.MetricsRegistry()
        c = reg.counter("n_total")
        a = reg.snapshot()
        c.inc(7)
        b = reg.snapshot()
        d = obs_metrics.diff_snapshots(a, b)
        assert d["n_total"][""] == {"a": 0, "b": 7}
        assert obs_metrics.diff_snapshots(b, b) == {}

    def test_registry_reset_keeps_families(self):
        reg = obs_metrics.MetricsRegistry()
        c = reg.counter("r_total")
        c.inc(3)
        reg.reset()
        assert c.value == 0
        c.inc()                      # pre-bound handle stays usable
        assert reg.counter("r_total").value == 1


# ---------------------------------------------------------------------------
# Disabled-mode fast path on the serve/kernel hot paths
# ---------------------------------------------------------------------------

def _tiny_engine():
    from repro.configs.registry import get_config
    from repro.engine import QuantSpec
    from repro.serving import ServeEngine
    cfg = get_config("minicpm-2b", smoke=True)
    return cfg, ServeEngine(cfg, 2, 12,
                            quant=QuantSpec(planes=2, impl="pallas_fused"))


def _profile_host_events(tmp_path, fn):
    """Run ``fn`` under a JAX profiler session; returns the host plane's
    events as {line id: [(name, start_ns, end_ns)]}."""
    import glob
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                lines[(plane.name, i)] = [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return lines


class TestProfilerSink:
    def test_span_and_region_land_in_profiler_trace(self, tmp_path):
        from repro.serving import Request
        from repro.serving.scheduler import Scheduler
        cfg, eng = _tiny_engine()
        sched = Scheduler("fcfs", max_len=12)
        sched.submit(Request(0, [1, 2, 3], 4))
        eng.admit_from(sched)
        eng.step()                              # compile outside the trace

        def traced():
            obs.enable()
            try:
                with obs.span("test.enabled_span"):
                    pass
            finally:
                obs.disable()
                obs.clear_trace()
            eng.step()

        lines = _profile_host_events(tmp_path, traced)
        names = {n for evs in lines.values() for n, _, _ in evs}
        assert "test.enabled_span" in names
        # serve.step holds its phases, on the thread that ran it
        (evs,) = [evs for evs in lines.values()
                  if any(n == "serve.step" for n, _, _ in evs)]
        (_, s0, e0), = [e for e in evs if e[0] == "serve.step"]
        for child in ("serve.upload", "serve.dispatch", "serve.readback",
                      "serve.advance"):
            (_, s, e), = [ev for ev in evs if ev[0] == child]
            assert s0 <= s <= e <= e0, child
        dispatch = next(ev for ev in evs if ev[0] == "serve.dispatch")
        readback = next(ev for ev in evs if ev[0] == "serve.readback")
        assert dispatch[2] <= readback[1]


class TestEngineCounters:
    def test_counters_tick_without_tracing(self, no_tracing):
        from repro.serving import Request
        from repro.serving.scheduler import Scheduler
        reg = obs_metrics.get_registry()
        names = ("repro_serve_slot_steps_bound_total",
                 "repro_serve_kv_positions_live_total",
                 "repro_serve_kv_positions_read_total")
        cfg, eng = _tiny_engine()
        sched = Scheduler("fcfs", max_len=12)
        sched.submit(Request(0, [1, 2, 3], 3))
        eng.admit_from(sched)
        before = [reg.counter(n).value for n in names]
        steps = 0
        expect_live = 0
        while eng.has_work(sched):
            expect_live += sum(int(eng.slots.pos[i]) + 1
                               for i, _ in eng.slots.bound())
            eng.step()
            steps += 1
        bound, live, read = (reg.counter(n).value - b
                             for n, b in zip(names, before))
        assert steps > 0 and bound == steps     # one request, one slot
        assert live == expect_live
        assert read == steps * eng.batch * eng.max_len
        assert 0 < live <= read
        assert obs.trace_events() == []


class TestDisabledMode:
    def test_serve_step_records_nothing_when_disabled(self, no_tracing):
        from repro.serving import Request
        from repro.serving.scheduler import Scheduler
        cfg, eng = _tiny_engine()
        rng = np.random.default_rng(0)
        sched = Scheduler("fcfs", max_len=12)
        sched.submit(Request(0, rng.integers(
            0, cfg.vocab_size, 4).tolist(), 3))
        eng.admit_from(sched)
        eng.step()                              # jit warm-up
        steps = obs_metrics.get_registry().counter(
            "repro_serve_engine_steps_total")
        n_steps0 = steps.value
        n_events0 = len(obs.trace_events())
        while eng.has_work(sched):
            eng.step()
        assert len(obs.trace_events()) == n_events0
        assert steps.value == n_steps0

    def test_serve_step_records_when_enabled(self, tracing):
        from repro.serving import Request
        from repro.serving.scheduler import Scheduler
        cfg, eng = _tiny_engine()
        rng = np.random.default_rng(0)
        sched = Scheduler("fcfs", max_len=12)
        sched.submit(Request(0, rng.integers(
            0, cfg.vocab_size, 4).tolist(), 3))
        eng.admit_from(sched)
        steps = obs_metrics.get_registry().counter(
            "repro_serve_engine_steps_total")
        n0 = steps.value
        eng.step()
        assert steps.value == n0 + 1
        names = [e["name"] for e in obs.trace_events()]
        assert "serve.step" in names

    def test_kernel_dispatch_span_gated(self, no_tracing):
        from repro.engine import QuantSpec
        from repro.kernels import ops
        rng = np.random.default_rng(0)
        spec = QuantSpec(planes=2, block_m=128, block_k=128)
        w = rng.normal(0, 0.02, size=(128, 128)).astype(np.float32)
        x = rng.normal(0, 1, size=(2, 128)).astype(np.float32)
        plan = ops.plan_dense_weight(w, spec, use_cache=False)
        dispatch = obs_metrics.get_registry().counter(
            "repro_gemm_dispatch_total")
        snap0 = dispatch.snapshot()
        ops.planned_dense_apply(plan, x, spec, 128)
        assert obs.trace_events() == []
        assert dispatch.snapshot() == snap0
        obs.enable(clear_events=True)
        try:
            ops.planned_dense_apply(plan, x, spec, 128)
            names = [e["name"] for e in obs.trace_events()]
            assert "ops.planned_dense_apply" in names
            assert dispatch.snapshot() != snap0
        finally:
            obs.disable()
            obs.clear_trace()


# ---------------------------------------------------------------------------
# Deterministic virtual-time serving snapshots + request lifecycle traces
# ---------------------------------------------------------------------------

def _serve_once(tiers_n=2, trace=False):
    from repro.configs.registry import get_config
    from repro.kernels import ops
    from repro.serving import AsyncServer, default_tiers, loadgen
    cfg = get_config("minicpm-2b", smoke=True)
    reqs = loadgen.synthesize(cfg.vocab_size, 8, prompt_len=(3, 5),
                              max_tokens=(3, 5), pattern="poisson",
                              rate=50, seed=0)
    ops.plan_cache_clear()
    obs_metrics.reset_metrics()
    if trace:
        obs.enable(clear_events=True)
    server = AsyncServer(cfg, tiers=default_tiers(tiers_n, batch=2),
                         max_len=12, step_time_scale=5e4)
    stats = server.run(reqs)
    return stats, obs_metrics.snapshot()


class TestServingIntegration:
    def test_virtual_time_snapshots_deterministic(self, no_tracing):
        stats1, snap1 = _serve_once()
        stats2, snap2 = _serve_once()
        assert stats1["completed"] == stats2["completed"]
        # every serve series (histogram buckets included) is identical
        # across identical virtual-time runs
        for name in snap1:
            if name.startswith("repro_serve") or \
                    name.startswith("repro_schedule"):
                assert snap1[name] == snap2[name], name
        h = snap1["repro_serve_ttft_seconds"]["values"][""]
        assert h["count"] == stats1["completed"] > 0

    def test_request_lifecycle_trace(self):
        was = obs.enabled()
        try:
            stats, _snap = _serve_once(trace=True)
            evs = obs.trace_events()
        finally:
            obs.disable() if not was else obs.enable()
            obs.clear_trace()
        by_name = {}
        for ev in evs:
            by_name.setdefault(ev["name"], []).append(ev)
        assert len(by_name.get("PREFILL", [])) == stats["completed"]
        assert len(by_name.get("DECODE", [])) == stats["completed"]
        assert "serve.step" in by_name
        # lifecycle spans ride the virtual serving clock
        assert all(e["pid"] == obs.PID_SERVER
                   for e in by_name["PREFILL"])
        d = by_name["DECODE"][0]
        assert "tpot" in d["args"] and "tier" in d["args"]

    def test_summary_view_still_validates(self, no_tracing):
        from repro.serving import validate_summary
        stats, _ = _serve_once()
        validate_summary(stats)
        assert stats["completed"] + stats["rejected"] == stats["requests"]


# ---------------------------------------------------------------------------
# Cost-model calibration
# ---------------------------------------------------------------------------

class TestCalibration:
    def test_drift_ratio_geometric_mean(self):
        cal = obs.CostCalibrator(min_samples=2)
        cal.record("pallas_fused", 1.0, 2.0)
        cal.record("pallas_fused", 1.0, 8.0)
        assert cal.drift("pallas_fused") == pytest.approx(4.0)  # sqrt(16)
        assert cal.correction("pallas_fused") == pytest.approx(4.0)
        assert cal.correction("unknown") == 1.0
        assert cal.samples("pallas_fused") == 2

    def test_record_rejects_nonpositive(self):
        cal = obs.CostCalibrator()
        with pytest.raises(ValueError):
            cal.record("x", 0.0, 1.0)
        with pytest.raises(ValueError):
            cal.record("x", 1.0, -1.0)

    def test_uniform_scale_is_not_miscalibration(self):
        # interpret mode: every impl ~1e4x slower — no warning
        cal = obs.CostCalibrator(min_samples=1)
        for impl in ("a", "b", "c"):
            cal.record(impl, 1e-6, 1e-2)
        assert cal.check(warn=False) == {}

    def test_miscalibration_warns_with_code(self):
        cal = obs.CostCalibrator(drift_threshold=4.0, min_samples=1)
        cal.record("a", 1.0, 1.0)
        cal.record("b", 1.0, 1.1)
        cal.record("c", 1.0, 100.0)      # 100x the consensus
        with pytest.warns(obs.CostModelDriftWarning,
                          match=obs.COST_MODEL_MISCALIBRATED):
            bad = cal.check()
        assert "c" in bad and bad["c"] > 4.0
        # warned once per impl
        with warnings_none():
            cal.check()

    def test_seeded_autotune_drift(self):
        # the ISSUE acceptance: drift ratios from autotuner-style timing
        # pairs, seeded and deterministic
        from repro.engine import QuantSpec
        rng = np.random.default_rng(42)
        cal = obs.CostCalibrator(min_samples=3)
        spec = QuantSpec(planes=3)
        pred = obs.predict_gemm_seconds("pallas_fused", 256, 256, 128,
                                        spec, density=1.0)
        assert pred > 0
        for _ in range(5):
            measured = pred * 1e4 * rng.uniform(0.8, 1.25)
            cal.record("pallas_fused", pred, measured,
                       shape=(256, 256, 128), source="autotune")
        rep = cal.report()["pallas_fused"]
        assert rep["samples"] == 5
        assert rep["drift"] == pytest.approx(1e4, rel=0.3)
        assert rep["sources"] == {"autotune": 5}
        gauge = obs_metrics.get_registry().gauge("repro_cost_drift_ratio")
        assert gauge.labels(impl="pallas_fused").value == \
            pytest.approx(rep["drift"])

    def test_estimate_step_time_correction(self):
        from repro.configs.registry import get_config
        from repro.engine import QuantSpec
        from repro.serving.tiers import estimate_step_time
        cfg = get_config("minicpm-2b", smoke=True)
        spec = QuantSpec(planes=3)
        base = estimate_step_time(cfg, 2, spec)
        assert estimate_step_time(cfg, 2, spec, correction=2.5) == \
            pytest.approx(2.5 * base)

    def test_tier_router_apply_calibration(self):
        from repro.serving.tiers import Tier, TierRouter
        from repro.engine import QuantSpec
        fast = Tier("fast", QuantSpec(planes=2, impl="pallas_fused"))
        qual = Tier("quality", QuantSpec(planes=4, impl="pallas_sparse"))
        router = TierRouter((fast, qual), {"fast": 1.0, "quality": 2.0},
                            "fastest")
        cal = obs.CostCalibrator(min_samples=1)
        cal.record("pallas_fused", 1.0, 4.0)     # fast is really 4x slower
        applied = router.apply_calibration(cal)
        assert applied == {"fast": 4.0, "quality": 1.0}
        assert router.per_step["fast"] == pytest.approx(4.0)
        # the corrected estimates flip the fastest tier
        assert router._fastest.name == "quality"

    def test_autotune_records_calibration(self):
        from repro.engine import QuantSpec
        from repro.kernels import autotune
        obs.reset_calibrator()
        cal = obs.get_calibrator()
        autotune.autotune_gemm(192, 256, 128, QuantSpec(planes=2),
                               iters=1, cache=autotune.AutotuneCache())
        assert cal.samples("pallas_fused") > 0
        rep = cal.report()
        assert all(v["drift"] > 0 for v in rep.values())
        obs.reset_calibrator()


class warnings_none:
    """Context asserting no warnings are raised inside."""

    def __enter__(self):
        import warnings
        self._cm = warnings.catch_warnings(record=True)
        self._rec = self._cm.__enter__()
        import warnings as w
        w.simplefilter("always")
        return self

    def __exit__(self, *exc):
        self._cm.__exit__(*exc)
        assert self._rec == [], [str(w.message) for w in self._rec]
        return False


# ---------------------------------------------------------------------------
# Autotune-cache counters (satellite: miss warnings -> metrics)
# ---------------------------------------------------------------------------

class TestAutotuneCounters:
    def test_miss_warning_increments_counter(self):
        from repro.kernels.autotune import AutotuneCache, \
            AutotuneCacheMissWarning, cache_key
        from repro.engine import QuantSpec
        warn_c = obs_metrics.get_registry().counter(
            "repro_autotune_miss_warnings_total")
        miss_c = obs_metrics.get_registry().counter(
            "repro_autotune_cache_misses_total")
        cache = AutotuneCache("probe.json", strict=True)
        spec = QuantSpec(planes=3)
        # strict caches only warn when non-empty: seed one entry
        cache.record(64, 64, 64, spec,
                     {"block_m": 128, "block_k": 128, "block_n": 128,
                      "dispatch": "dense"}, backend="interpret")
        w0, m0 = warn_c.value, miss_c.value
        with pytest.warns(AutotuneCacheMissWarning):
            assert cache.lookup(512, 512, 512, spec) is None
        assert warn_c.value == w0 + 1
        assert miss_c.value == m0 + 1
        # the second miss on the same key is not re-warned
        assert cache.lookup(512, 512, 512, spec) is None
        assert warn_c.value == w0 + 1
        assert miss_c.value == m0 + 2
        assert cache.stats()["misses"] == 2
        assert cache_key(512, 512, 512, spec)  # key fn stays importable

    def test_hit_increments_counter(self):
        from repro.kernels.autotune import AutotuneCache
        from repro.engine import QuantSpec
        hit_c = obs_metrics.get_registry().counter(
            "repro_autotune_cache_hits_total")
        spec = QuantSpec(planes=3)
        cache = AutotuneCache()
        cache.record(64, 64, 64, spec,
                     {"block_m": 128, "block_k": 128, "block_n": 128,
                      "dispatch": "dense"}, backend="interpret")
        h0 = hit_c.value
        assert cache.lookup(64, 64, 64, spec) is not None
        assert hit_c.value == h0 + 1


# ---------------------------------------------------------------------------
# CLI: python -m repro.obs
# ---------------------------------------------------------------------------

class TestCli:
    def _snap_file(self, tmp_path, name, n):
        reg = obs_metrics.MetricsRegistry()
        reg.counter("repro_demo_total", "demo").inc(n)
        h = reg.histogram("repro_demo_seconds", (0.1, 1.0), help="demo h")
        h.observe(0.5)
        path = tmp_path / name
        path.write_text(json.dumps(reg.snapshot()))
        return str(path)

    def test_render_text(self, tmp_path, capsys):
        path = self._snap_file(tmp_path, "a.json", 3)
        assert obs_main(["render", path]) == 0
        out = capsys.readouterr().out
        assert "repro_demo_total" in out and "3" in out

    def test_render_prom(self, tmp_path, capsys):
        path = self._snap_file(tmp_path, "a.json", 3)
        assert obs_main(["render", path, "--format", "prom"]) == 0
        out = capsys.readouterr().out
        assert "# TYPE repro_demo_total counter" in out
        assert 'repro_demo_seconds_bucket{le="+Inf"} 1' in out

    def test_diff(self, tmp_path, capsys):
        a = self._snap_file(tmp_path, "a.json", 3)
        b = self._snap_file(tmp_path, "b.json", 5)
        assert obs_main(["diff", a, b]) == 1      # differences found
        out = capsys.readouterr().out
        assert "repro_demo_total" in out
        assert obs_main(["diff", a, a]) == 0

    def test_trace_summary(self, tmp_path, capsys, tracing):
        with obs.span("ops.planned_dense_apply"):
            pass
        path = tmp_path / "t.json"
        obs.save(str(path))
        assert obs_main(["trace", str(path)]) == 0
        out = capsys.readouterr().out
        assert "ops.planned_dense_apply" in out

    def test_bad_input(self, tmp_path, capsys):
        missing = str(tmp_path / "nope.json")
        assert obs_main(["render", missing]) == 2


# ---------------------------------------------------------------------------
# Package-level exports
# ---------------------------------------------------------------------------

def test_obs_exports():
    for name in ("span", "enable", "disable", "enabled", "NULL_SPAN",
                 "snapshot", "prometheus_text", "diff_snapshots",
                 "GLOSSARY", "CostCalibrator", "get_calibrator",
                 "predict_gemm_seconds", "COST_MODEL_MISCALIBRATED"):
        assert hasattr(obs, name), name
    assert obs_trace.ENV_TRACE == "REPRO_TRACE"
