"""Mellum2-12B-A2.5B [moe; hf:JetBrains/Mellum2-12B-A2.5B-Instruct,
config.json]: 28 layers, three sliding-window layers (window 1024,
default rotary) then one full-attention layer (YaRN rotary), repeated;
every MLP sparse, 64 experts top-8 of width 896, softmax router with
normalised top-k weights, no shared expert."""
from repro.configs.base import ArchConfig, AttnKind, Yarn, register

SLIDING = AttnKind(window=1024)
FULL = AttnKind(yarn=Yarn(
    factor=16.0, original_max_position=8192, beta_fast=32.0,
    beta_slow=1.0, attention_factor=1.2772588722239782))

register(ArchConfig(
    name="mellum2_12b", family="moe", n_layers=28, d_model=2304,
    vocab=98304, n_heads=32, n_kv_heads=4, head_dim=128, d_ff=896,
    n_experts=64, top_k=8, moe_every=1, act="silu", gated=True, norm="rms",
    rope_base=500000.0, attn_pattern=(SLIDING, SLIDING, SLIDING, FULL),
))
