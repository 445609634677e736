"""(G/M)QA attention block with qk-norm, bias, RoPE/M-RoPE, KV cache.

Supports every attention variant in the assigned pool:

* GQA (qwen2/3, gemma3, llama4, kimi), MQA (granite, kv=1), MHA (whisper)
* optional QKV bias (qwen2 family), optional q/k RMS-norm (qwen3, gemma3)
* per-layer sliding windows (gemma3 5:1, serving long-context variant)
* full and ring-buffer (windowed) KV caches for decode
* cross-attention (whisper decoder)

Computation is routed through :func:`repro.kernels.ops.attention` so the
Pallas flash kernel and the jnp oracle are interchangeable.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models.config import ArchConfig
from repro.models.layers import apply_rope, dense_init, rmsnorm
from repro.models.sharding import shard, shard_heads, BATCH_AXES, MODEL_AXIS

Params = Dict[str, jax.Array]


def attention_init(rng: jax.Array, cfg: ArchConfig, d_model: Optional[int] = None,
                   dtype=None) -> Params:
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim
    dt = dtype or cfg.param_dtype
    ks = jax.random.split(rng, 4)
    p: Params = {
        "wq": dense_init(ks[0], d, cfg.num_heads * hd, dt),
        "wk": dense_init(ks[1], d, cfg.num_kv_heads * hd, dt),
        "wv": dense_init(ks[2], d, cfg.num_kv_heads * hd, dt),
        "wo": dense_init(ks[3], cfg.num_heads * hd, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dt)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dt)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dt)
        p["k_norm"] = jnp.zeros((hd,), dt)
    return p


def _split_heads(x: jax.Array, n: int, hd: int) -> jax.Array:
    B, S, _ = x.shape
    return x.reshape(B, S, n, hd).transpose(0, 2, 1, 3)  # [B, H, S, hd]


def _merge_heads(x: jax.Array) -> jax.Array:
    B, H, S, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, H * hd)


def attention_apply(
    params: Params,
    cfg: ArchConfig,
    x: jax.Array,                       # [B, S, D]
    angles: Optional[jax.Array],        # [B, S, hd/2] rope angles (None = NoPE)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    cache: Optional[Dict[str, jax.Array]] = None,
    cache_index: Optional[jax.Array] = None,  # scalar: #tokens already cached
    cache_layout: str = "full",               # "full" | "ring" (static)
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    use_pallas: bool = False,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Returns (output [B, S, D], updated cache).

    Cache layouts (created by :func:`init_cache`):
      * full: k/v ``[B, Hkv, S_max, hd]``, absolute slots.
      * ring: k/v ``[B, Hkv, W, hd]``, slot = pos % W (windowed layers).
    """
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim

    q = x @ params["wq"]
    if cross_kv is None:
        k = x @ params["wk"]
        v = x @ params["wv"]
    else:
        k_src, v_src = cross_kv
        k = k_src @ params["wk"]
        v = v_src @ params["wv"]
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]

    q = _split_heads(q, cfg.num_heads, hd)
    k = _split_heads(k, cfg.num_kv_heads, hd)
    v = _split_heads(v, cfg.num_kv_heads, hd)
    q, k, v = shard_heads(q), shard_heads(k), shard_heads(v)

    if cfg.qk_norm:
        q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, params["k_norm"], cfg.norm_eps)
    if cfg.attn_scale is not None:
        # every attention path below scales scores by 1 / sqrt(hd)
        q = q * (cfg.attn_scale * math.sqrt(hd))

    if angles is not None and cross_kv is None:
        q = apply_rope(q, angles)
        k = apply_rope(k, angles)
    elif angles is not None:
        q = apply_rope(q, angles)

    new_cache = None
    if cache is not None:
        idx = cache_index if cache_index is not None else jnp.zeros((), jnp.int32)
        if cache_layout == "ring":
            # windowed layers at decode: slot = pos % W (S is 1 at decode)
            W = cache["k"].shape[2]
            slots = (idx + jnp.arange(S)) % W
            ck = cache["k"].at[:, :, slots].set(k)
            cv = cache["v"].at[:, :, slots].set(v)
            new_cache = {"k": ck, "v": cv}
            out = _ring_attention(q, ck, cv, idx + S - 1, W)
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, idx, axis=2)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, idx, axis=2)
            new_cache = {"k": ck, "v": cv}
            if cfg.attn_block is not None and S > 1:
                from repro.kernels.ref import attention_chunked

                out = attention_chunked(
                    q, ck, cv, causal=causal, window=window, q_offset=idx,
                    block=cfg.attn_block, k_valid=idx + S,
                    unroll=cfg.scan_unroll,
                )
            else:
                out = _cached_attention(q, ck, cv, idx, causal, window)
        out = _merge_heads(out)
        out = out @ params["wo"]
        return shard(out, BATCH_AXES, None, None), new_cache

    if cfg.attn_block is not None and S > 1:
        from repro.kernels.ref import attention_chunked

        out = attention_chunked(q, k, v, causal=causal, window=window,
                                block=cfg.attn_block, unroll=cfg.scan_unroll)
    else:
        out = kops.attention(q, k, v, causal=causal, window=window,
                             use_pallas=use_pallas)
    out = _merge_heads(out)
    out = out @ params["wo"]
    return shard(out, BATCH_AXES, None, None), new_cache


def _cached_attention(q, k, v, idx, causal: bool, window: Optional[int]):
    """Attention against a full-layout cache with a *traced* offset ``idx``.

    Equivalent to ``attention_ref`` with ``q_offset=idx`` but ``idx`` is a
    traced scalar (decode step counter), so masking is built inline.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    group = Hq // Hkv
    q_ = q.reshape(B, Hkv, group, Sq, D).astype(jnp.float32)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", q_, k.astype(jnp.float32))
    logits = logits / jnp.sqrt(jnp.asarray(D, jnp.float32))
    q_pos = idx + jnp.arange(Sq)
    k_pos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v.astype(jnp.float32))
    return out.reshape(B, Hq, Sq, D).astype(q.dtype)


def _ring_attention(q, ck, cv, last_pos, W: int):
    """Attention over a ring-buffer cache of size W.

    Slot ``i`` holds absolute position ``p_i = last - ((last - i) mod W)``;
    a slot is valid iff ``p_i >= 0`` (within-window holds by construction).
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, _, _ = ck.shape
    group = Hq // Hkv
    slots = jnp.arange(W)
    p = last_pos - jnp.mod(last_pos - slots, W)
    valid = p >= 0
    q_ = q.reshape(B, Hkv, group, Sq, D).astype(jnp.float32)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", q_, ck.astype(jnp.float32))
    logits = logits / jnp.sqrt(jnp.asarray(D, jnp.float32))
    logits = jnp.where(valid[None, None, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, cv.astype(jnp.float32))
    return out.reshape(B, Hq, Sq, D).astype(q.dtype)
