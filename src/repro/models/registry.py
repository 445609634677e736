"""Uniform model API over decoder-only and encoder-decoder backbones.

``bundle(cfg)`` returns a :class:`ModelBundle` with a single calling
convention used by the LM example and the smoke tests — independent of
architecture family.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import encdec, transformer
from repro.models.config import ArchConfig

Params = Any


@dataclass(frozen=True)
class ModelBundle:
    cfg: ArchConfig
    init: Callable[[jax.Array], Params]
    loss: Callable[..., Any]                 # (params, batch) -> (loss, metrics)
    init_cache: Callable[..., Any]           # (batch, max_len, layout) -> cache
    prefill: Callable[..., Any]              # (params, batch, cache) -> (logits, cache)
    decode_step: Callable[..., Any]          # (params, token, index, cache) -> ...


def bundle(cfg: ArchConfig) -> ModelBundle:
    if cfg.arch_type == "audio":
        def init(rng):
            return encdec.init_encdec_params(rng, cfg)

        def loss(params, batch, use_pallas: bool = False):
            return encdec.encdec_loss(params, cfg, batch, use_pallas)

        def init_cache(batch: int, max_len: int, layout: str = "full"):
            enc_len = cfg.num_frontend_tokens
            return encdec.init_encdec_cache(cfg, batch, max_len, enc_len)

        def prefill(params, batch, cache, layout: str = "full"):
            return encdec.encdec_prefill(
                params, cfg, batch["frames"], batch["tokens"], cache
            )

        def decode_step(params, token, index, cache, layout: str = "full"):
            return encdec.encdec_decode_step(params, cfg, token, index, cache)

        return ModelBundle(cfg, init, loss, init_cache, prefill, decode_step)

    def init(rng):
        return transformer.init_lm_params(rng, cfg)

    def loss(params, batch, use_pallas: bool = False):
        return transformer.lm_loss(params, cfg, batch, use_pallas)

    def init_cache(batch: int, max_len: int, layout: str = "full"):
        return transformer.init_cache(cfg, batch, max_len, layout)

    def prefill(params, batch, cache, layout: str = "full"):
        return transformer.lm_prefill(
            params, cfg, batch["tokens"], cache,
            extra_embeds=batch.get("extra_embeds"),
            mrope_positions=batch.get("mrope_positions"),
            cache_layout=layout,
        )

    def decode_step(params, token, index, cache, layout: str = "full"):
        return transformer.lm_decode_step(params, cfg, token, index, cache,
                                          cache_layout=layout)

    return ModelBundle(cfg, init, loss, init_cache, prefill, decode_step)
