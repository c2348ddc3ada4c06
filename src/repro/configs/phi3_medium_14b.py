"""Phi-3-medium-4k: dense RoPE SwiGLU GQA, RMSNorm, untied head.
[arXiv:2404.14219; hf microsoft/Phi-3-medium-4k-instruct config.json]"""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="phi3-medium-14b",
    family="dense",
    num_layers=40,
    d_model=5120,
    num_heads=40,
    num_kv_heads=10,
    head_dim=128,
    d_ff=17920,
    vocab_size=32064,
    mlp_type="swiglu",
    norm_type="rmsnorm",
    rope_theta=10000.0,
    tie_embeddings=False,
    source="https://huggingface.co/microsoft/Phi-3-medium-4k-instruct; arXiv:2404.14219",
)
