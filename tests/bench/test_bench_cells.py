"""BENCHMARK.json: every cell resolves to its files, and the file keeps the
shape its readers expect."""
import json
import os
import re

import pytest

from benchkit import CHIP_DIR, ROOT

import run
import traffic

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = run.load_cell(name)
    cfg = run.model_config(cell["config"])
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == cell["config"]["name"])
    # the keys cut from the published model are the ones BENCHMARK.json
    # lists, and the depth is a constant of the file
    assert sorted(cell["config"]["reduced"]) == sorted(entry["reduced"])
    assert cfg.n_layers == cell["config"]["model"]["n_layers"]
    pool = traffic.shape_pool(cell["mix"])
    assert pool.sum(axis=1).max() <= cell["config"]["serve"]["max_len"]
    assert {m["name"] for m in cell["end_to_end"]} >= \
        {"setup_s", "output_tok_s"}
    assert cell["per_layer"], "every cell reports a per-layer metric"
    for m in cell["per_layer"]:
        assert callable(run.load_metric(m["name"]).read)


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for path in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    assert os.path.relpath(CHIP_DIR, ROOT) in BENCH["paths"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [c["name"] for c in BENCH["configs"]] + CELLS + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in CELLS
            assert cell in e2e[m["moves"]].get("workloads", CELLS)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
