"""granite-4.0-h-micro — IBM's 3B hybrid: Mamba-2 and NoPE GQA layers in
sequence, a gated MLP in every layer (``models/mixed.py``).

``HF`` is the published ``config.json``
(https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json),
less the keys that say nothing about the model's shape: 40 layers in
periods of 10 (9 ``mamba``, ``attention`` at position 5), hidden 2048;
Mamba-2 mixers of 64 heads of 64, state 128, one group, conv 4, chunk
256; attention of 32 query and 8 KV heads, scores scaled by 1/64; an MLP
of 8192 in every layer; vocabulary 100,352, tied.
"""
from repro.models.mixed import config_from_hf

_PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4

HF = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": _PERIOD * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352,
}

CONFIG = config_from_hf(HF, name="granite-4.0-h-micro")
