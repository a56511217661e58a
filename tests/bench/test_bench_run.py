"""The measurement path refuses the CPU; the rest of a run, at smoke size
on the CPU (Pallas in interpret mode), decides ``correct`` by the plain
reference, fails its controls (the reference, and the program, one digit
plane coarser) and fails every fault the cell can have."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchkit import CHIP_DIR, ROOT, smoke_cell

import reference
import run

SEED = 2 ** 31 + 12345          # benchmark seeds may pass 32 signed bits


def _rehearse(engine_hook=None, control=False, seed=SEED, cell=None):
    return run.run_cell(cell or smoke_cell(), seed, 4.0, trace=False,
                        control=control, engine_hook=engine_hook,
                        log=lambda msg: None)


def test_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(CHIP_DIR, "run.py"), "--workload",
         "minicpm-2b.decode", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not p.stdout.strip()


def test_reference_draws_the_programs_weights():
    from repro.models.api import get_api
    from repro.parallel.sharding import unbox
    cell = smoke_cell("minicpm-2b.decode")
    cfg = run.model_config(cell["config"])
    params = unbox(get_api(cfg).init(jax.random.PRNGKey(SEED), cfg))
    model = cell["config"]["model"]
    table = reference.embedding(SEED, model)
    assert np.array_equal(np.asarray(table),
                          np.asarray(params["embed"]["table"]))
    w = reference.layer_weights(SEED, model, 0)
    blocks = params["blocks"]
    for name, got in (("wq", blocks["attn"]["wq"]["w"][0]),
                      ("down", blocks["mlp"]["down"]["w"][0]),
                      ("gate", blocks["mlp"]["gate"]["w"][0])):
        assert np.array_equal(np.asarray(w[name]), np.asarray(got)), name


@pytest.mark.parametrize("planned_head", [False, True],
                         ids=["tied_head", "planned_head_mqa"])
def test_sound_run_and_control(planned_head):
    cell = smoke_cell()
    if planned_head:
        # the other path of the model code and the reference: an untied
        # LM head planned into digit planes, and one KV head (MQA)
        cell["config"]["model"].update(tie_embeddings=False, n_kv_heads=1)
    rec = _rehearse(control=True, cell=cell)
    c = rec["check"]
    assert rec["correct"], c
    assert rec["compiles_in_window"] == 0
    assert c["tokens"] > 0
    assert rec["e2e"]["output_tok_s"] > 0 and "itl_p95_ms" in rec["e2e"]
    # the control, one digit plane coarser, is not correct by the
    # cell's limit, and reads well above the sound run
    assert c["control_max_logit_gap"] > rec["limit"]
    assert c["control_correct"] is False
    assert c["control_max_logit_gap"] >= 3 * c["max_logit_gap"]
    line = run.result_line(cell, rec, {"platform": "cpu"}, False)
    assert list(line)[-1] == "check"
    assert line["check"]["control_max_logit_gap"]["correct"] is False
    json.dumps(line)


def test_program_one_plane_coarser_is_not_correct():
    # the program's own lower-precision path, one digit plane fewer,
    # served through the timed loop and judged against the reference
    c = smoke_cell("minicpm-2b.decode")
    spec = c["config"]["serve"]["quant_spec"]
    c["config"]["serve"]["quant_spec"] = spec.replace("planes=3", "planes=2")
    rec = run.run_cell(c, SEED, 4.0, trace=False, log=lambda msg: None)
    assert not rec["correct"], rec["check"]
    assert rec["check"]["max_logit_gap"] > rec["limit"]


def _token_altered(eng):
    step, vocab = eng.step_fn, eng.cfg.padded_vocab

    def broken(*args):
        nxt, state = step(*args)
        return (nxt + 1) % vocab, state
    eng.step_fn = broken


def _state_unchanged(eng):
    step = eng.step_fn

    def broken(params, tokens, pos, state):
        nxt, _ = step(params, tokens, pos, state)
        return nxt, state
    eng.step_fn = broken


def _half_batch(eng):
    step = eng.step_fn

    def broken(*args):
        nxt, state = step(*args)
        half = nxt.shape[0] // 2
        return jnp.concatenate([nxt[:half], nxt[:nxt.shape[0] - half]]), \
            state
    eng.step_fn = broken


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch_left_out"])
def test_fault_is_not_correct(fault):
    rec = _rehearse(engine_hook=fault)
    assert not rec["correct"], rec["check"]
    assert rec["check"]["max_logit_gap"] > rec["limit"]


def test_stall_report_splits_the_longest_gap():
    # steps every 10 ms; one step call stalls 50 ms with a collection
    # of 40 ms inside it, one admission stalls 20 ms
    steps, t = [], 0.0
    for i in range(10):
        admit = t + 0.001 + (0.020 if i == 7 else 0.0)
        start = admit + 0.001
        end = start + 0.008 + (0.050 if i == 4 else 0.0)
        steps.append({"admit": admit, "start": start, "end": end,
                      "step_cpu": 0.001})
        t = end
    gc_pauses = [(steps[4]["start"] + 0.005, 0.040, 2)]
    lines = run.stall_report(steps, gc_pauses, steps[0]["end"], top=2)
    assert lines[0].startswith("[run] step gaps: median 10.0 ms")
    assert "1 collections, 40.0 ms" in lines[0]
    assert "gap 60.0 = before admit 1.0 + admit 1.0 + step 58.0" in lines[1]
    assert "('40.0', 2)" in lines[1]
    assert "gap 30.0 = before admit 21.0" in lines[2]
    assert "gc []" in lines[2]
    assert run.stall_report(steps[:1], [], 0.0) == []
