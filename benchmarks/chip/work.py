"""The work a decode step of the dense decoder requires, counted from
shapes.

These counts are the same whatever implements a GEMM: a digit-plane
kernel, a plain int8 matmul or a bf16 one.  The planes a kernel streams
(4 bytes per parameter today) are the implementation's overhead, not the
work, so they are not counted.

This is the default work module of a configuration.  One of another
block names its own module of this directory under ``"work"``; the
metrics call it as ``run.work``, and it provides, with these signatures:

- ``step_gemm_least_time(model, tokens, peaks, bits=8)``: the least time
  of one step's quantized GEMMs for ``tokens`` token rows
  (``bw_gemm_roofline``);
- ``useful_least_time(model, tokens, context_sum, peaks)``: the least
  time of the useful work of ``tokens`` token positions whose attention
  spans ``context_sum`` positions in all (``step_mfu``).

The same rule holds there: work is counted from shapes, whatever
implements a GEMM.  A routed layer counts the experts its tokens were
routed to (and the router), never all the experts it holds.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

ACT_BYTES = 1      # int8 activations on the kernel's input
OUT_BYTES = 4      # float32 accumulator written by the kernel epilogue


def pad_vocab(v: int, multiple: int = 128) -> int:
    return -(-v // multiple) * multiple


def layer_gemms(model: dict) -> List[Tuple[str, int, int]]:
    """(name, K, N) of the quantized GEMMs of one decoder layer."""
    d, hd = model["d_model"], model["head_dim"]
    q, kv, ff = model["n_heads"] * hd, model["n_kv_heads"] * hd, \
        model["d_ff"]
    gemms = [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
             ("up", d, ff), ("down", ff, d)]
    if model.get("gated_mlp", True):
        gemms.append(("gate", d, ff))
    return gemms


def step_gemms(model: dict) -> List[Tuple[str, int, int]]:
    """(name, K, N) of every quantized GEMM call of one decode step: the
    layers' GEMMs, and the LM head where it is not tied to the embedding
    (a tied head is a bf16 matmul, not a quantized GEMM)."""
    out = [g for _ in range(model["n_layers"]) for g in layer_gemms(model)]
    if not model.get("tie_embeddings", False):
        out.append(("lm_head", model["d_model"],
                    pad_vocab(model["vocab_size"])))
    return out


def gemm_ops(k: int, n: int, tokens: int) -> int:
    return 2 * tokens * k * n


def gemm_bytes(k: int, n: int, tokens: int, bits: int = 8) -> int:
    """The weight at ``bits`` per element, the int8 activations in and
    the float32 results out."""
    return k * n * bits // 8 + tokens * k * ACT_BYTES + \
        tokens * n * OUT_BYTES


def least_time(ops: float, nbytes: float, peak_ops: float,
               peak_bw: float) -> float:
    """Roofline: the larger of the compute bound and the memory bound."""
    return max(ops / peak_ops, nbytes / peak_bw)


def step_gemm_least_time(model: dict, tokens: int, peaks: dict,
                         bits: int = 8) -> float:
    """Least time of one step's quantized GEMMs at the int8 peak and the
    HBM bandwidth, each GEMM bounded on its own."""
    return sum(least_time(gemm_ops(k, n, tokens),
                          gemm_bytes(k, n, tokens, bits),
                          peaks["int8_ops"], peaks["hbm_bytes_per_s"])
               for _, k, n in step_gemms(model))


def token_ops(model: dict) -> Dict[str, int]:
    """Operations one token needs, apart from attention: the quantized
    linear layers (an untied LM head over the unpadded vocabulary), and
    a tied LM head in bf16."""
    tied = model.get("tie_embeddings", False)
    linear = sum(gemm_ops(k, n, 1) for name, k, n in step_gemms(model)
                 if name != "lm_head")
    head = 2 * model["d_model"] * model["vocab_size"]
    return {"int8": linear + (0 if tied else head),
            "bf16": head if tied else 0}


def attention_ops(model: dict, context: int) -> int:
    """bf16 operations of one token's attention (scores and values) over
    ``context`` positions, all layers."""
    return model["n_layers"] * 4 * model["n_heads"] * model["head_dim"] * \
        context


def useful_least_time(model: dict, tokens: int, context_sum: int,
                      peaks: dict) -> float:
    """Least time of the useful work of ``tokens`` token positions whose
    attention spans ``context_sum`` positions in all: int8 work at the
    int8 peak, bf16 work at the bf16 peak."""
    ops = token_ops(model)
    return tokens * ops["int8"] / peaks["int8_ops"] + \
        (tokens * ops["bf16"] + attention_ops(model, context_sum)) / \
        peaks["bf16_flops"]
