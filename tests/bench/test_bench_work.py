"""work.py: the operations and bytes of the served GEMMs, against values
worked out by hand from the published widths."""
import pytest

from benchkit import V5E

import run
import work

MINICPM = run.load_cell("minicpm-2b.decode")["config"]["model"]
GRANITE = dict(MINICPM, n_layers=3, d_model=6144, n_heads=48, n_kv_heads=1,
               head_dim=128, d_ff=24576, vocab_size=49152,
               tie_embeddings=False)


@pytest.mark.parametrize("k, n, ops, nbytes", [
    # minicpm-2b up/gate: 2304 -> 5760 at 32 tokens
    (2304, 5760, 849_346_560, 13_271_040 + 73_728 + 737_280),
    # minicpm-2b down: 5760 -> 2304
    (5760, 2304, 849_346_560, 13_271_040 + 184_320 + 294_912),
    # granite-34b up/gate: 6144 -> 24576
    (6144, 24576, 9_663_676_416, 150_994_944 + 196_608 + 3_145_728),
    # granite-34b K/V under MQA: 6144 -> 128
    (6144, 128, 50_331_648, 786_432 + 196_608 + 16_384),
])
def test_gemm_ops_and_bytes(k, n, ops, nbytes):
    assert work.gemm_ops(k, n, 32) == ops
    assert work.gemm_bytes(k, n, 32) == nbytes


def test_step_gemms():
    layer = [("wq", 2304, 2304), ("wk", 2304, 2304), ("wv", 2304, 2304),
             ("wo", 2304, 2304), ("up", 2304, 5760), ("down", 5760, 2304),
             ("gate", 2304, 5760)]
    assert work.layer_gemms(MINICPM) == layer
    # a tied head is not a quantized GEMM
    assert len(work.step_gemms(MINICPM)) == 7 * MINICPM["n_layers"]
    granite = work.step_gemms(GRANITE)
    assert len(granite) == 7 * 3 + 1
    assert granite[-1] == ("lm_head", 6144, 49152)
    assert ("wk", 6144, 128) in granite


def test_token_ops():
    per_layer = 2 * (4 * 2304 * 2304 + 3 * 2304 * 5760)
    ops = work.token_ops(MINICPM)
    assert ops["int8"] == MINICPM["n_layers"] * per_layer
    assert ops["bf16"] == 2 * 2304 * 122753
    # an untied head counts at int8 over the unpadded vocabulary
    granite = work.token_ops(GRANITE)
    assert granite["bf16"] == 0
    assert granite["int8"] == 3 * 2 * (2 * 6144 * 6144 + 2 * 6144 * 128 +
                                       3 * 6144 * 24576) + \
        2 * 6144 * 49152
    assert work.attention_ops(MINICPM, 100) == \
        MINICPM["n_layers"] * 4 * 36 * 64 * 100


def test_least_times():
    peaks = run.load_peaks(V5E)
    # a decode GEMM is bound by its bytes
    k, n = 2304, 5760
    t = work.least_time(work.gemm_ops(k, n, 32), work.gemm_bytes(k, n, 32),
                        peaks["int8_ops"], peaks["hbm_bytes_per_s"])
    assert t == pytest.approx(work.gemm_bytes(k, n, 32) / 819e9)
    step = work.step_gemm_least_time(MINICPM, 32, peaks)
    per_layer_bytes = sum(work.gemm_bytes(k, n, 32)
                          for _, k, n in work.layer_gemms(MINICPM))
    assert step == pytest.approx(MINICPM["n_layers"] * per_layer_bytes
                                 / 819e9)
    useful = work.useful_least_time(MINICPM, 32, 32 * 300, peaks)
    assert useful == pytest.approx(
        32 * work.token_ops(MINICPM)["int8"] / 393e12 +
        (32 * 2 * 2304 * 122753 + work.attention_ops(MINICPM, 9600))
        / 197e12)
