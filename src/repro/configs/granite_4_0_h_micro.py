"""Granite 4.0-H Micro (3B) — Mamba-2 / NoPE-attention hybrid, no experts.
[hf:ibm-granite/granite-4.0-h-micro]

40 layers: 36 Mamba-2 mixers and 4 GQA attention mixers, attention at
layers 5, 15, 25 and 35 (``layer_types``: period 10, offset 5); every
layer has a SwiGLU MLP of 8192 (``shared_intermediate_size``). Mamba-2:
d_inner = 2 * 2048 = 4096 in 64 heads of 64, d_state 128, one group,
conv width 4 with bias, chunk 256, no projection bias. The attention
layers carry no rotary embedding (``position_embedding_type`` "nope")
and scale scores by ``attention_multiplier`` 1/64. Granite's other
multipliers: embeddings x12, every mixer and MLP branch x0.22 before the
residual add, logits / 8. Tied embeddings over a vocabulary of 100352.
"""
from repro.configs.base import MambaConfig, ModelConfig

CONFIG = ModelConfig(
    arch_id="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,
    vocab=100352,
    head_dim=64,
    qkv_bias=False,
    rope_theta=10000.0,
    nope=True,
    mamba=MambaConfig(d_state=128, expand=2, head_dim=64, n_groups=1,
                      conv_width=4, chunk=256),
    attn_period=10,
    attn_offset=5,
    embedding_multiplier=12.0,
    attention_multiplier=0.015625,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    norm_eps=1e-5,
    tie_embeddings=True,
    source="hf:ibm-granite/granite-4.0-h-micro",
)
