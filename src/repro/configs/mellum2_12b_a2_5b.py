"""mellum2-12b-a2.5b — window and full attention, 64 routed experts top-8.
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct]

28L d_model=2304 32H (GQA kv=4) head 128 vocab=98304, untied head,
RMSNorm eps 1e-6, no attention bias. ``layer_types`` repeats three
sliding-window layers (window 1024, plain RoPE at theta 500000) then one
full-attention layer (YaRN: theta 500000, factor 16 over 8192 original
positions, beta 32/1, attention factor 1.27726). Every MLP is sparse:
64 experts of width 896, 8 per token, top-k weights renormalised, no
shared experts; ``intermediate_size`` 7168 is unused.
"""
from repro.configs.base import ModelConfig, MoEConfig, YarnRope

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    d_head=128,
    d_ff=7168,
    vocab_size=98304,
    sliding_window=1024,
    layer_pattern=("window", "window", "window", "full"),
    rope="standard",
    rope_theta=500_000.0,
    yarn=YarnRope(factor=16.0, original_max_position=8192, beta_fast=32.0,
                  beta_slow=1.0, attention_factor=1.2772588722239782),
    norm="rmsnorm",
    mlp="swiglu",
    moe=MoEConfig(n_experts=64, experts_per_token=8, d_expert=896),
)
