"""The per-layer metrics that read the serving engine's own regions and
counters from the program's metrics registry: each against the registry
after a few steps of a smoke-size engine, and each silent on an empty
registry."""
import pytest

from benchkit import smoke_cell

import run
from repro.obs import metrics as obs_metrics

READERS = ("engine_host_ms", "kv_live_share", "plan_weights_s")


@pytest.fixture(scope="module")
def served():
    """A smoke-size engine built and stepped on a freshly reset registry;
    returns the readers' values and the registry's snapshot."""
    from repro.serving import Request, ServeEngine
    from repro.serving.scheduler import Scheduler
    from repro.engine import QuantSpec
    cell = smoke_cell()
    serve = cell["config"]["serve"]
    cfg = run.model_config(cell["config"])
    obs_metrics.reset_metrics()
    eng = ServeEngine(cfg, serve["batch"], serve["max_len"], seed=3,
                      quant=QuantSpec.parse(serve["quant_spec"]))
    sched = Scheduler("fcfs", max_len=serve["max_len"])
    for rid in range(serve["batch"] + 1):
        sched.submit(Request(rid, [1 + rid, 2, 3], 2 + rid))
    for _ in range(6):
        eng.admit_from(sched)
        eng.step()
    values = {name: run.load_metric(name).read(None) for name in READERS}
    return values, obs_metrics.snapshot()


def _regions(snap):
    return {k[len("region="):]: v for k, v in
            snap["repro_region_seconds"]["values"].items()}


def test_engine_host_ms(served):
    served, snap = served
    regions = _regions(snap)
    parts = sum(regions[r]["sum"] for r in ("serve.admit", "serve.upload",
                                            "serve.dispatch",
                                            "serve.advance"))
    assert regions["serve.step"]["count"] == 6
    # the first step traces and compiles: recorded apart, left out
    assert regions["serve.compile"]["count"] == 1
    assert regions["serve.dispatch"]["count"] == 5
    assert served["engine_host_ms"] == pytest.approx(1e3 * parts / 6)
    assert 0 < served["engine_host_ms"] < \
        1e3 * (regions["serve.step"]["sum"] +
               regions["serve.admit"]["sum"]) / 6


def test_kv_live_share(served):
    served, snap = served
    live, read = (snap[f"repro_serve_kv_positions_{k}_total"]["values"][""]
                  for k in ("live", "read"))
    assert read == 6 * 4 * 48                  # steps x batch x max_len
    assert served["kv_live_share"] == pytest.approx(100.0 * live / read)
    assert 0 < served["kv_live_share"] <= 100


def test_plan_weights_s(served):
    served, snap = served
    regions = _regions(snap)
    assert regions["serve.plan_params"]["count"] == 1
    assert served["plan_weights_s"] == regions["serve.plan_params"]["sum"]
    # planning is a part of the engine build
    assert 0 < served["plan_weights_s"] <= regions["serve.build"]["sum"]


@pytest.mark.parametrize("name", READERS)
def test_reader_silent_on_empty_registry(served, name):
    obs_metrics.reset_metrics()
    assert run.load_metric(name).read(None) is None
