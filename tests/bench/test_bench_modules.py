"""A configuration names its reference, its work counts and its smoke
sizes: a second configuration, present only as new files under
``fixtures`` and new BENCHMARK.json entries, resolves to its own toy
modules and rehearses through ``run.run_cell``; names that leave the
directory or name no file are refused; the default path is unchanged."""
import gzip
import json
import os
import shutil
import types

import numpy as np
import pytest

from benchkit import CHIP_DIR, DENSE_SMOKE, ROOT, V5E, smoke_cell

import profile_trace
import reference
import run
import work

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
SEED = 2 ** 31 + 12345
TOY = "toy-2x.decode"


def _toy_root(tmp_path, **config):
    """A checkout root whose BENCHMARK.json is the repo's with the toy
    configuration and its cell added, and the toy's file beside it, with
    ``config``'s entries set in it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-2x", "source": "a test fixture",
                             "file": "toy-2x.json", "reduced": ["n_layers"],
                             "why": "a block the dense reference lacks"})
    bench["workloads"].append({"name": TOY, "config": "toy-2x",
                               "traffic": "decode", "chips": 1,
                               "why": "a test fixture"})
    for m in bench["per_layer"]:
        if m["name"] in ("step_mfu", "bw_gemm_roofline"):
            m["workloads"].append(TOY)
    with open(os.path.join(tmp_path, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    with open(os.path.join(FIXTURES, "toy-2x.json")) as f:
        toy = dict(json.load(f), **config)
    with open(os.path.join(tmp_path, "toy-2x.json"), "w") as f:
        json.dump(toy, f)
    return str(tmp_path)


def _recorded_trace():
    with gzip.open(os.path.join(FIXTURES, "minicpm_decode_trace.json.gz"),
                   "rt") as f:
        return profile_trace.summarize(json.load(f))


def test_default_modules_are_the_dense_ones():
    cell = run.load_cell("minicpm-2b.decode")
    config = cell["config"]
    assert "reference" not in config and "work" not in config
    assert cell["modules"] == CHIP_DIR
    for key in ("reference", "work"):
        assert run.module_path(config, key, CHIP_DIR) == \
            os.path.join(CHIP_DIR, key + ".py")


def test_named_modules_resolve(tmp_path):
    cell = run.load_cell(TOY, _toy_root(tmp_path), FIXTURES)
    assert cell["modules"] == FIXTURES
    for key in ("reference", "work"):
        assert run.module_path(cell["config"], key, FIXTURES) == \
            os.path.join(FIXTURES, f"toy_{key}.py")
    assert {m["name"] for m in cell["per_layer"]} == {"step_mfu",
                                                      "bw_gemm_roofline"}


@pytest.mark.parametrize("key, name", [
    ("reference", "../reference"),
    ("reference", "fixtures/toy_reference"),
    ("work", "toy_work.py"),
    ("work", "no_such_module"),
    ("reference", "reference"),       # the harness's, not in FIXTURES
])
def test_bad_module_names_are_refused(tmp_path, key, name):
    root = _toy_root(tmp_path, **{key: name})
    with pytest.raises(SystemExit, match=key):
        run.load_cell(TOY, root, FIXTURES)
    with open(os.path.join(root, "toy-2x.json")) as f:
        config = json.load(f)
    with pytest.raises(SystemExit, match=key):
        run.module_path(config, key, FIXTURES)


@pytest.mark.parametrize("name", ["minicpm-2b.decode", TOY])
def test_smoke_sizes_come_from_the_configuration(tmp_path, name):
    cell = smoke_cell(name, _toy_root(tmp_path), FIXTURES) if name == TOY \
        else smoke_cell(name)
    model = cell["config"]["model"]
    with open(os.path.join(FIXTURES, "toy-2x.json")) as f:
        toy_smoke = json.load(f)["smoke"]
    want = toy_smoke if name == TOY else dict(DENSE_SMOKE, n_kv_heads=4)
    assert {k: model[k] for k in want} == want
    assert set(cell["config"]["reduced"]) == set(model)
    assert cell["config"]["serve"]["batch"] == 4


def test_check_dispatches_to_the_named_reference(tmp_path):
    cell = smoke_cell(TOY, _toy_root(tmp_path), FIXTURES)
    config = cell["config"]
    rng = np.random.default_rng(3)
    picked = [types.SimpleNamespace(
        rid=i, prompt=rng.integers(1, 4000, 6).tolist(),
        out=rng.integers(1, 4000, 10).tolist()) for i in range(2)]
    toy = run.check(config, SEED, picked, False, FIXTURES)
    dense = dict(config)
    del dense["reference"]
    plain = run.check(dense, SEED, picked, False, CHIP_DIR)
    assert toy["tokens"] == plain["tokens"] > 0
    assert toy["max_logit_gap"] != plain["max_logit_gap"]


@pytest.mark.parametrize("planned_head", [False, True],
                         ids=["tied_head", "planned_head_mqa"])
def test_default_reference_is_bit_identical(planned_head):
    # the refactored dense reference against its frozen copy
    before = run.load_module(os.path.join(FIXTURES, "reference_before.py"))
    model = smoke_cell()["config"]["model"]
    if planned_head:
        model.update(tie_embeddings=False, n_kv_heads=1)
    rng = np.random.default_rng(5)
    lens = rng.integers(8, 48, run.SAMPLE_REQUESTS)
    seqs = [rng.integers(1, 8000, n).tolist() for n in lens]
    starts = [int(n) // 2 for n in lens]
    args = (SEED, model, seqs, starts, 48, reference.plane_qmax(3),
            reference.plane_qmax(2))
    got, want = reference.logit_gaps(*args), before.logit_gaps(*args)
    for key in ("served", "control"):
        assert got[key].dtype == want[key].dtype
        assert np.array_equal(got[key], want[key]), key


def _read_recorded(mod):
    """step_mfu and bw_gemm_roofline on the recorded trace (19 layers,
    two steps of 32 slots) with ``mod`` as the work counts."""
    summary = _recorded_trace()
    steps = [{"bound": 32, "context": 32 * 300}] * 2
    model = dict(run.load_cell("minicpm-2b.decode")["config"]["model"],
                 n_layers=19)
    peaks = run.load_peaks(V5E)
    ns = types.SimpleNamespace(
        model=model, serve={"batch": 32}, bits=8, work=mod, peaks=peaks,
        window_steps=steps, traced_steps=steps, trace=summary)
    kernel_s = profile_trace.matching_time(summary, ("bw_gemm*",
                                                     "quant_gemm*"))
    mfu = run.load_metric("step_mfu").read(ns)
    roofline = run.load_metric("bw_gemm_roofline").read(ns)
    assert mfu == 100 * mod.useful_least_time(model, 64, 64 * 300, peaks) \
        / summary["window_s"]
    assert roofline == 100 * 2 * mod.step_gemm_least_time(
        model, 32, peaks, 8) / kernel_s
    return mfu, roofline


def test_metrics_read_the_configurations_work():
    dense = _read_recorded(work)
    toy = _read_recorded(run.load_module(os.path.join(FIXTURES,
                                                      "toy_work.py")))
    # the toy counts each layer's MLP twice: more work in the same time
    assert toy[0] > dense[0] and toy[1] > dense[1]


def test_toy_cell_rehearses_through_run_cell(tmp_path, monkeypatch):
    # a traced run on the CPU: the device part of the trace is the
    # recorded one, so that the per-layer metrics have something to read
    cell = smoke_cell(TOY, _toy_root(tmp_path), FIXTURES)
    summary = _recorded_trace()
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))
    monkeypatch.setattr(profile_trace, "load", lambda trace_dir: None)
    monkeypatch.setattr(profile_trace, "summarize", lambda events: summary)
    rec = run.run_cell(cell, SEED, run.TRACE_SECONDS, trace=True,
                       log=lambda msg: None)
    shutil.rmtree(tmp_path / "trace", ignore_errors=True)
    # the program serves the dense decoder; the toy's reference runs each
    # MLP twice, so the check that went to it reads the run as wrong
    assert rec["check"]["tokens"] > 0
    assert rec["check"]["max_logit_gap"] > rec["limit"]
    assert not rec["correct"]
    toy = run.load_module(os.path.join(FIXTURES, "toy_work.py"))
    model, peaks = cell["config"]["model"], cell["peaks"]
    steps = rec["window_steps"]          # all of the window is traced
    kernel_s = profile_trace.matching_time(summary, ("bw_gemm*",
                                                     "quant_gemm*"))
    assert rec["per_layer"]["bw_gemm_roofline"] == pytest.approx(
        100 * summary["steps"] * toy.step_gemm_least_time(model, 4, peaks, 8)
        / kernel_s, rel=1e-12)
    assert rec["per_layer"]["step_mfu"] == pytest.approx(
        100 * toy.useful_least_time(model, sum(s["bound"] for s in steps),
                                    sum(s["context"] for s in steps), peaks)
        / summary["window_s"], rel=1e-12)
