"""Arch-id → config lookup."""
from repro.configs.gemma3_27b import CONFIG as gemma3_27b
from repro.configs.granite_20b import CONFIG as granite_20b
from repro.configs.hymba_1_5b import CONFIG as hymba_1_5b
from repro.configs.kimi_k2 import CONFIG as kimi_k2
from repro.configs.llama4_maverick import CONFIG as llama4_maverick
from repro.configs.mamba2_2_7b import CONFIG as mamba2_2_7b
from repro.configs.qwen2_0_5b import CONFIG as qwen2_0_5b
from repro.configs.qwen2_vl_72b import CONFIG as qwen2_vl_72b
from repro.configs.qwen3_32b import CONFIG as qwen3_32b
from repro.configs.whisper_small import CONFIG as whisper_small

ARCHS = {
    c.name: c
    for c in [
        qwen2_0_5b, llama4_maverick, hymba_1_5b, whisper_small,
        qwen2_vl_72b, gemma3_27b, mamba2_2_7b, granite_20b, kimi_k2,
        qwen3_32b,
    ]
}


def get_arch(name: str):
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(ARCHS)}")
    return ARCHS[name]
