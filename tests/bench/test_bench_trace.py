"""The reduction from a profiler trace to the per-layer metrics, on two
decode steps of minicpm-2b.decode recorded on a v5e (19 layers, batch
32) and on hand-made events."""
import gzip
import json
import os
import types

import pytest

from benchkit import V5E

import profile_trace
import run
import work

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "minicpm_decode_trace.json.gz")
CELL = run.load_cell("minicpm-2b.decode")


@pytest.fixture(scope="module")
def summary():
    with gzip.open(FIXTURE, "rt") as f:
        return profile_trace.summarize(json.load(f))


def _run(summary, steps=()):
    # the fixture was recorded with 19 layers
    return types.SimpleNamespace(
        model=dict(CELL["config"]["model"], n_layers=19),
        serve=CELL["config"]["serve"],
        bits=8, work=work, peaks=run.load_peaks(V5E),
        window_steps=list(steps),
        traced_steps=list(steps), trace=summary)


def test_recorded_trace(summary):
    assert summary["steps"] == 2
    assert 0 < summary["busy_s"] <= summary["window_s"]
    gemm = profile_trace.matching_time(summary, ("bw_gemm*",))
    # 7 GEMMs x 19 layers x 2 steps, each a few hundred microseconds
    assert 0.01 < gemm < summary["busy_s"]
    labels = dict(summary["device_ops"])
    assert "copy bf16[1,32,640,36,64]" in labels
    assert not any(k.startswith("while") for k in labels)
    assert summary["idle_by_host"][0][0] == "bench.step"
    idle = sum(s for _, s in summary["idle_by_host"])
    assert idle == pytest.approx(summary["window_s"] - summary["busy_s"],
                                 rel=1e-6)


def test_metrics_on_recorded_trace(summary):
    steps = [{"bound": 32, "context": 32 * 300}] * 2
    got = {m["name"]: run.load_metric(m["name"]).read(_run(summary, steps))
           for m in CELL["per_layer"]}
    assert got["slot_occupancy"] == 100.0
    assert 0 < got["device_idle_share"] < 100
    assert got["step_device_ms"] == pytest.approx(
        1e3 * summary["busy_s"] / 2)
    for share in ("bw_gemm_roofline", "gemm_device_share", "step_mfu"):
        assert 0 < got[share] <= 100
    # the readings of this trace before the work counts were named by
    # the configuration: the same code and the same counts
    assert got["step_mfu"] == 0.37740371738997897
    assert got["bw_gemm_roofline"] == 9.245312292184499


def test_readers_return_nothing_without_a_trace():
    for m in CELL["per_layer"]:
        if m["source"] == "device_trace":
            assert run.load_metric(m["name"]).read(_run(None)) is None


def test_union_and_gaps():
    ev = {"devices": {"/device:TPU:0": [
        ["a.1", 100, 50, "a f32[2]"],
        ["b.2", 120, 50, "b f32[2]"],        # overlaps a: busy 100-170
        ["while.3", 100, 70, "while"],      # a container: busy, not time
        ["c.4", 200, 20, "c f32[2]"],
    ]}, "host": [["bench.traced", 90, 150], ["bench.step", 95, 140],
                 ["bench.admit", 175, 20]]}
    s = profile_trace.summarize(ev)
    assert s["window_s"] == pytest.approx(150e-9)
    assert s["busy_s"] == pytest.approx(90e-9)
    assert s["steps"] == 1
    assert s["op_s"] == pytest.approx({"a.1": 50e-9, "b.2": 50e-9,
                                       "c.4": 20e-9})
    assert s["label_s"] == pytest.approx({"a f32[2]": 50e-9,
                                          "b f32[2]": 50e-9,
                                          "c f32[2]": 20e-9})
    # gaps 90-100 and 220-240 under the step, 170-200 under admission
    assert dict(s["idle_by_host"]) == pytest.approx(
        {"bench.step": 30e-9, "bench.admit": 30e-9})
    assert profile_trace.summarize({"devices": ev["devices"],
                                    "host": []}) is None


def test_op_name():
    text = ("%bw_gemm_fused.69 = f32[2304,128]{1,0:T(8,128)} "
            "custom-call(s32[1656]{0} %reshape.583)")
    assert profile_trace.op_name(text) == ("bw_gemm_fused.69",
                                           "bw_gemm_fused f32[2304,128]")
    assert profile_trace.op_name("%while.4 = (s32[], bf16[2]) while()")[1] \
        == "while"
