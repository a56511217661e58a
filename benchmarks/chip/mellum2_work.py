"""The work a decode step of Mellum2-12B-A2.5B requires, counted from
shapes: the ``work`` contract of ``work.py`` (its GEMM roofline, bytes
and peaks), plus the expert counts ``expert_gemm_roofline`` reads.

Counted:

- the attention projections (q, k, v, o) of every layer, at every token;
- the router, a float32 matmul over all experts: useful work at the
  bf16 peak, not a quantized GEMM;
- the held experts' three GEMMs (gate, up, down) for the tokens routed to
  them, never for those that were not.  At ``tokens`` rows the routes to
  each held expert are expected to be tokens x k / n_experts (uniform
  routing, as the counters' load reads), and an expert's weight bytes
  count once if it has any route: held x (1 - (1 - k / n_experts) **
  tokens) experts are expected to;
- the untied LM head.

``useful_least_time`` is given only the context summed over tokens, so it
cannot cap a sliding layer's attention at its window: it counts every
layer's attention over the whole context.  Over the decode-long mix's
steady state that overstates the sliding layers' attention by about 7%
(5% of all attention work: three layers in four slide).
"""
from __future__ import annotations

from typing import List, Tuple

from work import (gemm_bytes, gemm_ops, least_time,  # noqa: F401
                  pad_vocab)


def attention_gemms(model: dict) -> List[Tuple[str, int, int]]:
    """(name, K, N) of one layer's attention projections."""
    d, hd = model["d_model"], model["head_dim"]
    q, kv = model["n_heads"] * hd, model["n_kv_heads"] * hd
    return [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d)]


def expert_gemms(model: dict) -> List[Tuple[str, int, int]]:
    """(name, K, N) of one expert's GEMMs."""
    d, f = model["d_model"], model["d_ff"]
    return [("gate", d, f), ("up", d, f), ("down", f, d)]


def held(model: dict) -> int:
    return model.get("experts_held") or model["n_experts"]


def expected_routes(model: dict, tokens: int) -> Tuple[float, float]:
    """(token-routes to held experts, held experts with a route) of one
    layer at ``tokens`` rows, under uniform routing."""
    share = model["experts_per_token"] / model["n_experts"]
    return (held(model) * tokens * share,
            held(model) * (1.0 - (1.0 - share) ** tokens))


def expert_gemm_least_time(model: dict, routes: float, active: float,
                           peaks: dict, bits: int = 8) -> float:
    """Least time of one step's routed expert GEMMs, from the step's
    token-routes to held experts and held experts with a route, each
    summed over layers (the engine's counters, per step).  Each layer's
    grouped call of one matrix is one GEMM: 2 x routes x K x N over the
    int8 peak, or the active experts' weight bytes with the routed rows'
    activations in and results out over the HBM bandwidth."""
    n = model["n_layers"]
    r, a = routes / n, active / n
    total = 0.0
    for _, k, m in expert_gemms(model):
        nbytes = a * k * m * bits // 8 + r * k * 1 + r * m * 4
        total += least_time(2 * r * k * m, nbytes, peaks["int8_ops"],
                            peaks["hbm_bytes_per_s"])
    return n * total


def step_gemm_least_time(model: dict, tokens: int, peaks: dict,
                         bits: int = 8) -> float:
    """Least time of one step's quantized GEMMs at ``tokens`` rows: the
    attention projections, the held experts' routed GEMMs at the expected
    routes, and the untied LM head, each bounded on its own."""
    def one(k, n):
        return least_time(gemm_ops(k, n, tokens),
                          gemm_bytes(k, n, tokens, bits),
                          peaks["int8_ops"], peaks["hbm_bytes_per_s"])
    layers = model["n_layers"]
    routes, active = expected_routes(model, tokens)
    total = layers * sum(one(k, n) for _, k, n in attention_gemms(model))
    total += expert_gemm_least_time(model, layers * routes, layers * active,
                                    peaks, bits)
    if not model.get("tie_embeddings", False):
        total += one(model["d_model"], pad_vocab(model["vocab_size"]))
    return total


def token_ops(model: dict) -> dict:
    """Operations one token needs, apart from attention: int8 for the
    attention projections, its expected routed expert GEMMs (k x held /
    n_experts experts) and the untied head over the unpadded vocabulary;
    bf16 for the router."""
    layers = model["n_layers"]
    attn = sum(gemm_ops(k, n, 1) for _, k, n in attention_gemms(model))
    routed = model["experts_per_token"] * held(model) / model["n_experts"]
    expert = routed * sum(gemm_ops(k, n, 1) for _, k, n in
                          expert_gemms(model))
    head = 2 * model["d_model"] * model["vocab_size"]
    router = 2 * model["d_model"] * model["n_experts"]
    return {"int8": layers * (attn + expert) + head,
            "bf16": layers * router}


def attention_ops(model: dict, context: int) -> int:
    """bf16 operations of one token's attention (scores and values) over
    ``context`` positions, all layers (no window cap: see above)."""
    return model["n_layers"] * 4 * model["n_heads"] * model["head_dim"] * \
        context


def useful_least_time(model: dict, tokens: int, context_sum: int,
                      peaks: dict) -> float:
    """Least time of the useful work of ``tokens`` token positions whose
    attention spans ``context_sum`` positions in all."""
    ops = token_ops(model)
    return tokens * ops["int8"] / peaks["int8_ops"] + \
        (tokens * ops["bf16"] + attention_ops(model, context_sum)) / \
        peaks["bf16_flops"]
