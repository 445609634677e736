"""qwen2-0.5b — dense, GQA (kv=2), QKV bias [arXiv:2407.10671]."""
from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="qwen2-0.5b",
    arch_type="dense",
    num_layers=24,
    d_model=896,
    num_heads=14,
    num_kv_heads=2,
    head_dim=64,
    d_ff=4864,
    vocab_size=151_936,
    qkv_bias=True,
    rope_theta=1_000_000.0,
    tie_embeddings=True,
    # serving variant: ring-buffer decode cache window
    long_context_window=8_192,
    source="arXiv:2407.10671 (Qwen2 technical report)",
)
