"""phi4-mini-3.8b [arXiv:2412.08905; hf:microsoft/Phi-4-mini-instruct]: 32L d3072 24H (GQA kv=8) ff8192 V=200064, SwiGLU, RoPE on 96 of 128 head dims, RMSNorm eps 1e-5."""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="phi4-mini-3.8b", family="dense",
    num_layers=32, d_model=3072, num_heads=24, num_kv_heads=8,
    d_ff=8192, vocab_size=200064, head_dim=128, mlp="swiglu", rope=True,
    rope_fraction=0.75, norm_eps=1e-5,
)
