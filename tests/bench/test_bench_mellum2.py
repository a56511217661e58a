"""Mellum2-12B-A2.5B's cell: its configuration file, its reference
(``mellum2_reference.py``) against the program, its work counts
(``mellum2_work.py``) and the two expert metrics, and the served smoke
cell through ``run.run_cell``, correct while its control is not."""
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchkit import CHIP_DIR, ROOT, SMOKE_LIMIT, V5E, smoke_cell

import profile_trace
import run
from repro.obs import metrics as obs_metrics

CELL = "mellum2-12b-a2.5b.decode-long"
SEED = 2 ** 31 + 12345
EXPERT_METRICS = ("expert_gemm_roofline", "expert_device_share")


def _module(name):
    return run.load_module(os.path.join(CHIP_DIR, name + ".py"))


def test_configuration_file_and_entries():
    """The file keeps the published config at its top level, cut only in
    the keys it and BENCHMARK.json list; the cell reports the listed
    per-layer metrics (not kv_live_share, whose counters know no
    window)."""
    cell = run.load_cell(CELL)
    config = cell["config"]
    published = config["published"]
    cut = {"num_hidden_layers": 12, "num_experts": 8}
    for key, value in published.items():
        assert config[key] == cut.get(key, value), key
    assert set(cut) <= set(config["reduced"])
    cfg = run.model_config(config)
    assert (cfg.n_layers, cfg.held_experts, cfg.n_experts) == (12, 8, 64)
    assert cfg.experts_per_token == published["num_experts_per_tok"]
    assert cfg.d_ff == published["moe_intermediate_size"]
    assert cfg.sliding_window == published["sliding_window"]
    assert cfg.layer_pattern == "SSSF"
    assert [cfg.layer_kind(i) for i in range(28)] == [
        "S" if t == "sliding_attention" else "F"
        for t in published["layer_types"]]
    yarn = published["rope_parameters"]["full_attention"]
    assert (cfg.yarn_factor, cfg.yarn_original_max_position,
            cfg.rope_theta) == (yarn["factor"],
                                yarn["original_max_position_embeddings"],
                                yarn["rope_theta"])
    # the betas and attention factor yarn_inv_freq takes by default
    from repro.models import layers as L
    _, scale = L.yarn_inv_freq(cfg.head_dim, cfg.rope_theta,
                               cfg.yarn_factor,
                               cfg.yarn_original_max_position)
    assert (yarn["beta_fast"], yarn["beta_slow"], yarn["attention_factor"]) \
        == (32, 1, scale)
    names = {m["name"] for m in cell["per_layer"]}
    assert set(EXPERT_METRICS) <= names and "kv_live_share" not in names
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "mellum2-12b-a2.5b")
    assert entry["source"] == config["source"]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One traced smoke run of the cell with the control read, on a fresh
    registry.  The device part of the trace is a made-up summary (no chip
    here) that holds the grouped expert kernel, so that the per-layer
    metrics have something to read."""
    tmp = tmp_path_factory.mktemp("trace")
    cell = smoke_cell(CELL)
    summary = {"window_s": 4.0, "busy_s": 3.6, "steps": 0,
               "op_s": {"bw_gemm_fused.1": 1.0, "bw_gemm_experts.2": 0.5,
                        "fusion.3": 2.1},
               "label_s": {}, "idle_by_host": [], "device_ops": []}
    mp = pytest.MonkeyPatch()
    mp.setattr(run, "TRACE_DIR", str(tmp / "trace"))
    mp.setattr(profile_trace, "load", lambda trace_dir: None)
    mp.setattr(profile_trace, "summarize", lambda events: summary)
    obs_metrics.reset_metrics()
    try:
        rec = run.run_cell(cell, SEED, run.TRACE_SECONDS, trace=True,
                           control=True, log=lambda msg: None)
    finally:
        mp.undo()
    # every step of the window is traced at this size
    summary["steps"] = len(rec["window_steps"])
    ns = types.SimpleNamespace(
        model=cell["config"]["model"], serve=cell["config"]["serve"],
        bits=8, work=_module("mellum2_work"), peaks=cell["peaks"],
        window_steps=rec["window_steps"], traced_steps=rec["window_steps"],
        trace=summary)
    values = {m["name"]: run.load_metric(m["name"]).read(ns)
              for m in cell["per_layer"]}
    return cell, rec, ns, values, obs_metrics.snapshot()


def test_smoke_cell_is_correct_and_its_control_is_not(served):
    cell, rec, _, _, _ = served
    assert rec["limit"] == SMOKE_LIMIT
    assert rec["correct"] and rec["failed"] == 0
    assert rec["check"]["tokens"] > 0
    assert rec["check"]["max_logit_gap"] <= SMOKE_LIMIT
    assert not rec["check"]["control_correct"]
    assert rec["check"]["control_max_logit_gap"] > SMOKE_LIMIT
    # the sample runs past the smoke window
    assert cell["config"]["model"]["sliding_window"] < \
        cell["config"]["serve"]["max_len"] // 2


def test_expert_metrics_read_the_counters_and_the_kernel(served):
    cell, rec, ns, values, snap = served
    steps = snap["repro_region_seconds"]["values"][
        "region=serve.step"]["count"]
    routes, active = (snap[f"repro_moe_{k}_total"]["values"][""]
                      for k in ("routes_held", "experts_active"))
    model = cell["config"]["model"]
    held, k = model["experts_held"], model["experts_per_token"]
    batch = cell["config"]["serve"]["batch"]
    # every slot's token is routed, bound or not
    assert 0 < routes <= steps * model["n_layers"] * batch * k
    assert 0 < active <= steps * model["n_layers"] * held
    assert routes >= active
    work = ns.work
    assert values["expert_gemm_roofline"] == pytest.approx(
        100 * ns.trace["steps"] * work.expert_gemm_least_time(
            model, routes / steps, active / steps, cell["peaks"], 8) / 0.5)
    assert values["expert_device_share"] == pytest.approx(100 * 0.5 / 3.6)
    assert values["gemm_device_share"] == pytest.approx(100 * 1.5 / 3.6)
    for name, v in values.items():
        assert v is not None, name
        if name.endswith(("_roofline", "_share")) or "mfu" in name:
            assert 0 < v <= 100, name


def test_expert_metrics_silent_without_counters_or_kernel(served):
    _, _, ns, _, _ = served
    no_kernel = types.SimpleNamespace(**vars(ns))
    no_kernel.trace = dict(ns.trace, op_s={"bw_gemm_fused.1": 1.0})
    for name in EXPERT_METRICS:
        assert run.load_metric(name).read(no_kernel) is None
    dense = types.SimpleNamespace(**vars(ns))
    dense.work = _module("work")
    assert run.load_metric("expert_gemm_roofline").read(dense) is None
    obs_metrics.reset_metrics()
    assert run.load_metric("expert_gemm_roofline").read(ns) is None


def test_work_counts_only_routed_experts():
    work = _module("mellum2_work")
    model = run.load_cell(CELL)["config"]["model"]
    peaks = run.load_peaks(V5E)
    routes, active = work.expected_routes(model, 64)
    assert routes == pytest.approx(8 * 64 * 8 / 64)
    assert 7.99 < active < 8.0
    # no routes: no expert work; fewer active experts: fewer bytes
    assert work.expert_gemm_least_time(model, 0.0, 0.0, peaks) == 0.0
    full = work.expert_gemm_least_time(model, 12 * routes, 12 * 8, peaks)
    fewer = work.expert_gemm_least_time(model, 12 * routes, 12 * 4, peaks)
    assert fewer < full
    # memory bound at decode: 8 experts' int8 weights a layer and
    # matrix, the routed rows' int8 inputs and float32 results
    nbytes = 12 * (3 * 8 * 2304 * 896 + routes * (2 * 2304 + 896)
                   + routes * 4 * (2 * 896 + 2304))
    assert full == pytest.approx(nbytes / peaks["hbm_bytes_per_s"],
                                 rel=1e-12)
    # the step's quantized GEMMs hold the routed experts' part
    dense_part = work.step_gemm_least_time(dict(model, experts_held=0,
                                                n_experts=64), 64, peaks)
    assert work.step_gemm_least_time(model, 64, peaks) < dense_part
    ops = work.token_ops(model)
    # q, k, v, o at every token; one routed held expert expected (8 of 64
    # routes land on 8 held experts); the untied head
    assert ops["int8"] == 12 * (2 * 2304 * (2 * 4096 + 2 * 512)
                                + 1.0 * 3 * 2 * 2304 * 896) + \
        2 * 2304 * 98304


def _smoke_model():
    return smoke_cell(CELL)["config"]["model"]


def test_reference_weights_are_the_programs():
    """The reference draws, by itself, the weights the program serves."""
    from repro.models.api import get_api
    from repro.parallel.sharding import unbox
    ref = _module("mellum2_reference")
    model = _smoke_model()
    cfg = run.model_config(dict(smoke_cell(CELL)["config"]))
    params = unbox(get_api(cfg).init(jax.random.PRNGKey(SEED), cfg))
    blocks = params["blocks"]
    for layer in (0, 3):
        w = ref.weights(SEED, model, layer)
        pairs = [(w[n], blocks["attn"][n]["w"][layer])
                 for n in ("wq", "wk", "wv", "wo")]
        pairs += [(w["router"], blocks["moe"]["router"]["w"][layer])]
        pairs += [(w[n], blocks["moe"]["w_" + n][layer])
                  for n in ("up", "down", "gate")]
        for a, b in pairs:
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


def test_reference_shares_add_up_to_the_uncut_layer():
    ref = _module("mellum2_reference")
    model = dict(_smoke_model(), experts_first=0, experts_held=16)
    whole = ref.weights(SEED, model, 1)
    y = jnp.asarray(np.random.default_rng(0).standard_normal(
        (2, 9, model["d_model"])).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        want = ref.moe(whole, y, model, 42)
        total = 0
        for first in range(0, 16, 2):
            share = ref.weights(SEED, dict(model, experts_first=first,
                                           experts_held=2), 1)
            np.testing.assert_array_equal(np.asarray(share["up"]),
                                          np.asarray(whole["up"][first:
                                                                 first + 2]))
            total = total + ref.moe(share, y, dict(
                model, experts_first=first, experts_held=2), 42)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_decode_through_the_cache_matches_the_reference():
    """The program, decoding token by token through its cache on the
    configuration's integer grid (the jnp engine, float32 activations),
    against the reference's full forward, at positions past the window."""
    from repro.engine import QuantSpec
    from repro.models import transformer as T
    from repro.parallel.sharding import unbox
    import reference as R
    ref = _module("mellum2_reference")
    config = smoke_cell(CELL)["config"]
    model = config["model"]
    spec = QuantSpec.parse("planes=3,encoding=ent,impl=ref,"
                           "act_quant=per_token")
    cfg = run.model_config(config).replace(
        dtype="float32", quant=spec, quant_planes=3)
    params = unbox(T.lm_init(jax.random.PRNGKey(SEED), cfg))
    b, t = 2, 24
    assert t > 2 * model["sliding_window"]
    tokens = np.random.default_rng(1).integers(0, model["vocab_size"],
                                               (b, t)).astype(np.int32)
    caches = unbox(T.init_caches(cfg, b, t, jnp.float32))
    got = []
    for i in range(t):
        li, caches = T.lm_decode_step(params, jnp.asarray(tokens[:, i:i + 1]),
                                      jnp.full((b,), i, jnp.int32), caches,
                                      cfg)
        got.append(np.asarray(li[:, 0], np.float32))
    got = np.stack(got, 1)[..., :model["vocab_size"]]
    qmax = R.plane_qmax(3)
    hid = R.final_hidden(SEED, model, tokens, [qmax], ref.weights,
                         ref.block)[qmax]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(R.qdense(hid, R.lm_head(SEED, model), qmax))
    want = want[..., :model["vocab_size"]]
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-3 * scale)
