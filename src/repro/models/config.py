"""Architecture configuration shared by the whole model zoo.

One frozen dataclass covers all 6 assigned architecture families
(dense / MoE / SSM / hybrid / VLM / audio); per-arch files in
``repro/configs`` instantiate it with the exact assigned hyperparameters.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclass(frozen=True)
class ArchConfig:
    name: str
    arch_type: str                 # dense | moe | hybrid | ssm | vlm | audio
    num_layers: int
    d_model: int
    d_ff: int
    vocab_size: int

    # ---- attention ----
    num_heads: int = 0             # 0 => attention-free (pure SSM)
    num_kv_heads: int = 0
    head_dim: int = 0              # 0 => d_model // num_heads
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    global_rope_theta: Optional[float] = None   # gemma3 global layers
    mrope_sections: Optional[Tuple[int, int, int]] = None  # qwen2-vl M-RoPE
    # per-layer attention pattern: window size of each layer in the repeating
    # unit (None = global/full). e.g. gemma3: (1024,)*5 + (None,) — 5:1.
    layer_windows: Optional[Tuple[Optional[int], ...]] = None
    # explicit full-attention layers overriding the cyclic pattern
    # (e.g. hymba: first / middle / last)
    global_layer_indices: Tuple[int, ...] = ()
    # serving override: window applied to *all* full-attention layers for the
    # long_500k shape (beyond-paper sliding-window serving variant)
    long_context_window: Optional[int] = None

    # ---- MoE ----
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_d_ff: int = 0
    num_shared_experts: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    # "gather" (GSPMD-inferred movement) | "a2a" (explicit shard_map
    # all_to_all dispatch — serving only, §Perf HC1 structural fix)
    moe_dispatch: str = "gather"

    # ---- SSM (mamba2 / hybrid) ----
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_chunk: int = 128
    ssm_conv: int = 4

    # ---- block layout ----
    # "attn" | "ssm" | "hybrid" (parallel attn+ssm a la Hymba) | "mixed"
    # (one mixer per layer, named by layer_types: ``models/mixed.py``)
    block_type: str = "attn"
    layer_types: Optional[Tuple[str, ...]] = None   # "mamba" | "attention"
    # score scale of attention; None = 1 / sqrt(head_dim)
    attn_scale: Optional[float] = None
    # Granite multipliers: embeddings times the first, each mixer's and
    # MLP's output times the second before its residual add, logits
    # divided by the third
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    # ---- encoder-decoder (whisper) ----
    encoder_layers: int = 0
    cross_attention: bool = False

    # ---- modality frontend stub ----
    frontend: Optional[str] = None          # 'vision' | 'audio'
    num_frontend_tokens: int = 0            # patch/frame embeddings provided

    # ---- misc ----
    act: str = "silu"
    norm: str = "rmsnorm"
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    remat: bool = True                      # activation checkpoint each layer
    # "full" = recompute everything; "dots" = save matmul outputs
    # (jax dots_with_no_batch_dims_saveable policy) — recompute only the
    # cheap elementwise ops, skip re-running matmuls & their collectives
    remat_policy: str = "full"
    # Roofline-analysis knobs: XLA's cost_analysis counts while-loop bodies
    # ONCE, so scanned-layer FLOPs/bytes/collectives are undercounted
    # ~trip_count x.  The dry-run lowers twice (layer_unroll=1 and =4) and
    # extrapolates the per-layer body cost to num_layers.  scan_unroll
    # additionally unrolls the small aux scans (chunked CE loss) fully.
    layer_unroll: int = 1
    scan_unroll: bool = False
    # online-softmax KV-block attention (never materializes [Sq, Skv]);
    # None = reference einsum attention. Used by the §Perf prefill hillclimb.
    attn_block: Optional[int] = None
    source: str = ""                        # citation per assignment

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def param_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.ssm_d_inner // self.ssm_headdim

    def layer_window(self, layer_idx: int) -> Optional[int]:
        """Window of layer ``layer_idx`` under the repeating pattern."""
        if self.layer_windows is None:
            return None
        return self.layer_windows[layer_idx % len(self.layer_windows)]

    def with_overrides(self, **kw) -> "ArchConfig":
        return replace(self, **kw)

    def reduced(self) -> "ArchConfig":
        """Smoke-test variant: ≤2 layers, d_model ≤ 512, ≤4 experts."""
        d_model = min(self.d_model, 256)
        n_heads = min(self.num_heads, 4) if self.num_heads else 0
        n_kv = min(self.num_kv_heads, max(1, n_heads // 2)) if self.num_kv_heads else 0
        if n_heads and n_kv:
            n_kv = max(1, min(n_kv, n_heads))
            while n_heads % n_kv:
                n_kv -= 1
        kw = dict(
            num_layers=2,
            d_model=d_model,
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 512),
            num_heads=n_heads,
            num_kv_heads=n_kv,
            head_dim=64 if self.num_heads else 0,
            encoder_layers=2 if self.encoder_layers else 0,
            num_frontend_tokens=min(self.num_frontend_tokens, 16),
            remat=False,
            dtype="float32",
        )
        if self.is_moe:
            kw.update(
                num_experts=4,
                num_experts_per_tok=min(self.num_experts_per_tok, 2),
                moe_d_ff=min(self.moe_d_ff, 256),
                num_shared_experts=min(self.num_shared_experts, 1),
                capacity_factor=4.0,   # dropless at smoke scale → exact
            )
        if self.ssm_state:
            kw.update(ssm_state=min(self.ssm_state, 16), ssm_headdim=32,
                      ssm_chunk=32)
        if self.layer_windows is not None:
            kw.update(layer_windows=tuple(
                min(w, 64) if w else None for w in self.layer_windows[:2]
            ) or (None,))
        if self.mrope_sections is not None:
            # keep t/h/w proportions, scaled to the reduced head_dim
            half = kw["head_dim"] // 2
            t = half // 4
            kw.update(mrope_sections=(t, (half - t) // 2,
                                      half - t - (half - t) // 2))
        if self.global_layer_indices:
            kw.update(global_layer_indices=(0,))
        return self.with_overrides(**kw)
