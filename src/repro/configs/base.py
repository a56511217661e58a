"""Config schema: model architecture + input-shape + run configuration.

One ModelConfig per assigned architecture lives in repro/configs/<arch>.py;
each also provides a reduced `smoke()` config of the same family for CPU
tests.  Shapes are the four assigned input-shape cells.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.engine.spec import QuantSpec

__all__ = ["ModelConfig", "ShapeConfig", "SHAPES", "pad_vocab"]


def pad_vocab(v: int, multiple: int = 128) -> int:
    """Round vocab up for MXU alignment and clean mesh divisibility."""
    return -(-v // multiple) * multiple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | rwkv | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int                # raw (pre-padding) vocabulary
    head_dim: int = 0              # 0 -> d_model // n_heads
    # --- MoE ---
    n_experts: int = 0
    experts_per_token: int = 0
    capacity_factor: float = 1.25
    moe_shard: str = "expert"      # 'expert' (EP) or 'mlp' (TP over d_ff)
    moe_dispatch_groups: int = 1   # >1: DP-shard-local dispatch (no gathers)
    router_aux_coef: float = 0.01
    # the experts this chip holds, [experts_first, experts_first +
    # experts_held) of each layer (0: all): the router still spans all
    # n_experts, and the layer returns its held experts' part of the result
    experts_held: int = 0
    experts_first: int = 0
    # --- RWKV / SSM ---
    rwkv_head_size: int = 0
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_conv: int = 4
    # --- encoder-decoder ---
    n_encoder_layers: int = 0
    # --- frontends (modality stubs: precomputed embeddings) ---
    frontend: Optional[str] = None  # 'vision' | 'audio'
    frontend_tokens: int = 0        # patches / frames per example
    # --- layer details ---
    qkv_bias: bool = False
    act: str = "silu"
    gated_mlp: bool = True
    rope_theta: float = 1e4
    # layer kinds, as one period repeated over the layers: "S" a sliding-
    # window layer (a query at p sees keys p - sliding_window < j <= p,
    # default RoPE), "F" a full layer (YaRN RoPE when yarn_factor > 0:
    # interpolation by 1 / yarn_factor below the original context's
    # correction range, transformers' default betas and attention factor).
    # "" keeps every layer full, with the default RoPE unless yarn_factor
    # is set.  Scalars and strings only: a configuration file compares them.
    layer_pattern: str = ""
    sliding_window: int = 0
    yarn_factor: float = 0.0       # 0: no YaRN
    yarn_original_max_position: int = 0
    norm: str = "rms"              # rms | layer
    tie_embeddings: bool = False
    logit_softcap: float = 0.0     # grok-style tanh soft capping
    # --- numerics / execution ---
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    opt_state_dtype: str = "float32"   # bf16 moments for the huge models
    attn_chunk: int = 2048         # switch to flash-chunked above this seq
    remat: bool = True
    scan_unroll: int = 1           # layer-scan unroll (dry-run cost variants)
    quant_planes: int = 0          # >0: BW-decomposed int8 linear path
    # full quantized-GEMM configuration; None defers to the quant_planes
    # sugar above (launchers materialize an explicit spec at startup so
    # concurrent engines with different specs never interfere)
    quant: Optional[QuantSpec] = None
    # --- parallelism policy ---
    fsdp: bool = True
    fsdp_over_pod: bool = False    # shard weights over the pod axis too
    # long-context support (sub-quadratic sequence mixing)
    subquadratic: bool = False

    def quant_spec(self) -> Optional[QuantSpec]:
        """The QuantSpec the model layers should execute under.

        An explicit ``quant`` field wins; otherwise the legacy
        ``quant_planes`` int is sugar for a default-grid spec whose impl
        comes from the deprecated global shim (preserving the old
        global-switch semantics for un-migrated callers).  Returns None
        when quantization is disabled.
        """
        if self.quant is not None:
            return self.quant if self.quant.enabled else None
        if self.quant_planes:
            from repro.engine import _compat
            return QuantSpec(planes=self.quant_planes,
                             impl=_compat.default_impl())
        return None

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        return pad_vocab(self.vocab_size)

    @property
    def held_experts(self) -> int:
        """Experts of each layer held here (all unless experts_held)."""
        return self.experts_held or self.n_experts

    @property
    def has_layer_kinds(self) -> bool:
        """Whether layers differ in window or RoPE (per-layer tables ride
        in the layer scans); False keeps the uniform dense path."""
        return bool(self.layer_pattern) or self.yarn_factor > 0

    def layer_kind(self, layer: int) -> str:
        """"S" (sliding window) or "F" (full) for ``layer``."""
        pattern = self.layer_pattern or "F"
        return pattern[layer % len(pattern)]

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks)."""
        d, hd = self.d_model, self.resolved_head_dim
        attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) \
            + self.n_heads * hd * d
        if self.family == "rwkv":
            attn = 5 * d * d + d * d  # r,k,v,w(g) projections + out
        mlp_mats = 3 if self.gated_mlp else 2
        mlp = mlp_mats * d * self.d_ff
        if self.n_experts:
            mlp = mlp * self.held_experts + d * self.n_experts
        block = attn + mlp
        n_blocks = self.n_layers + self.n_encoder_layers
        emb = self.padded_vocab * d * (1 if self.tie_embeddings else 2)
        return n_blocks * block + emb

    def active_param_count(self) -> int:
        if not self.n_experts:
            return self.param_count()
        dense_like = self.replace(n_experts=0, d_ff=self.d_ff *
                                  self.experts_per_token)
        return dense_like.param_count()


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str           # 'train' | 'prefill' | 'decode'


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}
