"""Dry-run machinery units that need no 512-device mesh: the loop-cost
extrapolation, record rendering, cell registry."""
import importlib
import json

import pytest

from repro.configs import registry


def _dr():
    # importing repro.launch.dryrun sets XLA_FLAGS *in this process's env*
    # but jax is already initialized with 1 device here, so device state is
    # unaffected; we only use its pure helpers.
    return importlib.import_module("repro.launch.dryrun")


def test_extrapolate_linear_costs():
    dr = _dr()
    c1 = {"flops": 10.0, "bytes": 100.0, "coll": 4.0, "transcendentals": 0.0,
          "coll_by_op": {"all-reduce": 4}}
    c2 = {"flops": 16.0, "bytes": 140.0, "coll": 7.0, "transcendentals": 0.0,
          "coll_by_op": {"all-reduce": 7}}
    out = dr._extrapolate(c1, c2, n_layers=10)
    # body = 6/40/3, base = 4/60/1 -> total = base + 10*body
    assert out["flops"] == pytest.approx(4 + 60)
    assert out["bytes"] == pytest.approx(60 + 400)
    assert out["coll"] == pytest.approx(1 + 30)
    assert out["coll_by_op"]["all-reduce"] == pytest.approx(4 + 9 * 3)


def test_extrapolate_clamps_negative_body():
    dr = _dr()
    c1 = {"flops": 10.0, "bytes": 0.0, "coll": 0.0, "transcendentals": 0.0,
          "coll_by_op": {}}
    c2 = {"flops": 8.0, "bytes": 0.0, "coll": 0.0, "transcendentals": 0.0,
          "coll_by_op": {}}
    out = dr._extrapolate(c1, c2, n_layers=5)
    assert out["flops"] >= 0.0


def test_registry_cells_complete():
    cells = list(registry.all_cells(include_skips=True))
    assert len(cells) == 44                       # 11 archs x 4 shapes
    runnable = [c for c in cells if c[2]]
    skipped = [c for c in cells if not c[2]]
    assert len(skipped) == 9                      # long_500k x full-attn
    assert all(s == "long_500k" for _, s, ok in skipped if not ok)
    assert {"rwkv6-3b", "hymba-1.5b"} == {
        a for a, s, ok in runnable if s == "long_500k"}


def test_registry_overrides_and_errors():
    cfg = registry.get_config("minicpm-2b", quant_planes=3)
    assert cfg.quant_planes == 3
    with pytest.raises(ValueError):
        registry.get_config("not-an-arch")
    with pytest.raises(ValueError):
        registry.get_shape("not-a-shape")


def test_report_renders_records(tmp_path, capsys):
    from repro.launch import report
    rec = {"arch": "x", "shape": "train_4k", "mesh": "single",
           "status": "ok", "kind": "train", "chips": 256,
           "memory": {"argument_bytes": 2 << 30, "output_bytes": 0,
                      "temp_bytes": 1 << 30, "generated_code_bytes": 0,
                      "alias_bytes": 0},
           "roofline": {"t_compute_s": 1.0, "t_memory_s": 2.0,
                        "t_collective_s": 0.5, "bottleneck": "memory",
                        "useful_ratio": 0.5, "roofline_fraction": 0.25},
           }
    skip = {"arch": "y", "shape": "long_500k", "mesh": "single",
            "status": "skipped", "reason": "full attention"}
    p = tmp_path / "rec.json"
    p.write_text(json.dumps([rec, skip]))
    assert report.main([str(p), "--md"]) == 0
    out = capsys.readouterr().out
    assert "| x | train_4k" in out
    assert "SKIP" in out
    assert "25.00%" in out
