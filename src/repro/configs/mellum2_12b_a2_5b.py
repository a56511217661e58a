"""mellum2-12b-a2.5b — JetBrains Mellum2-12B-A2.5B-Instruct
[huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct, config.json].

28L d_model=2304, GQA 32 query / 4 KV heads of 128, untied vocab=98304.
Every layer is sparse: 64 experts of width 896, top-8 softmax router
renormalised over the 8 (norm_topk_prob), no shared expert.  Layers
repeat (sliding, sliding, sliding, full): window 1024 with the default
RoPE (theta 500000), full layers with YaRN (factor 16 over 8192
positions, beta_fast 32, beta_slow 1, attention factor 1.27726 =
0.1 ln 16 + 1: the defaults ``layers.yarn_inv_freq`` takes).
RMSNorm eps 1e-6, no attention bias.  The config names no QK-norm and no
MTP module, so neither is built.
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    d_ff=896,                     # moe_intermediate_size, the expert width
    vocab_size=98304,
    n_experts=64,
    experts_per_token=8,
    moe_shard="expert",
    act="silu",
    gated_mlp=True,
    norm="rms",
    rope_theta=500000.0,
    tie_embeddings=False,
    layer_pattern="SSSF",
    sliding_window=1024,
    yarn_factor=16.0,
    yarn_original_max_position=8192,
)


def smoke() -> ModelConfig:
    # one period of the pattern, a window the smoke sequences pass, and
    # YaRN over a short original context so that its ramp is non-trivial
    # at head_dim 16
    return CONFIG.replace(n_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
                          head_dim=16, d_ff=32, vocab_size=512, n_experts=8,
                          experts_per_token=2, sliding_window=6,
                          rope_theta=10000.0, yarn_factor=4.0,
                          yarn_original_max_position=64, remat=False)
