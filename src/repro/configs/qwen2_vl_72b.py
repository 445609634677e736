"""qwen2-vl-72b — VLM backbone with M-RoPE [arXiv:2409.12191].

Vision encoder (ViT) is a STUB: the model takes patch embeddings and
3-D (t/h/w) M-RoPE position ids.
"""
from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="qwen2-vl-72b",
    arch_type="vlm",
    num_layers=80,
    d_model=8192,
    num_heads=64,
    num_kv_heads=8,
    head_dim=128,
    d_ff=29_568,
    vocab_size=152_064,
    qkv_bias=True,
    rope_theta=1_000_000.0,
    mrope_sections=(16, 24, 24),     # t/h/w frequency channels (sum = 64)
    frontend="vision",
    num_frontend_tokens=1024,
    tie_embeddings=False,
    long_context_window=8_192,
    source="arXiv:2409.12191 (Qwen2-VL, M-RoPE + dynamic resolution)",
)
