"""whisper-small — encoder-decoder audio backbone [arXiv:2212.04356].

Conv/mel frontend is a STUB: the model takes precomputed frame
embeddings.
"""
from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="whisper-small",
    arch_type="audio",
    num_layers=12,              # decoder layers
    encoder_layers=12,
    d_model=768,
    num_heads=12,
    num_kv_heads=12,
    head_dim=64,
    d_ff=3072,
    vocab_size=51_865,
    cross_attention=True,
    qkv_bias=True,
    norm="layernorm",
    act="gelu",
    frontend="audio",
    num_frontend_tokens=1500,
    tie_embeddings=True,
    source="arXiv:2212.04356 (Whisper)",
)
