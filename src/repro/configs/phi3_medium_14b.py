"""phi3-medium-14b — Phi-3 medium (4k context): RoPE + SwiGLU + GQA.

[dense] 40L d_model=5120 40H (GQA kv=10) d_ff=17920 vocab=32064
RMSNorm eps 1e-5, untied head, rope_theta 10000
[arXiv:2404.14219; microsoft/Phi-3-medium-4k-instruct config.json]
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="phi3-medium-14b",
    family="dense",
    source="arXiv:2404.14219; https://huggingface.co/microsoft/"
           "Phi-3-medium-4k-instruct/blob/main/config.json",
    num_layers=40,
    d_model=5120,
    num_heads=40,
    num_kv_heads=10,
    head_dim=128,
    d_ff=17920,
    vocab_size=32064,
    norm="rmsnorm",
    norm_eps=1e-5,
    act="silu",
    rope_theta=10000.0,
)
