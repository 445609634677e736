"""Shared neural-net building blocks (pure functional JAX, no flax).

Parameters are plain dict pytrees; initializers take explicit PRNG keys.
All matmul params carry logical sharding metadata via
``repro.models.sharding`` (applied at placement time, not here).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def dense_init(rng, in_dim: int, out_dim: int, dtype, scale: float = 1.0):
    std = scale / (in_dim ** 0.5)
    return (jax.random.normal(rng, (in_dim, out_dim), jnp.float32) * std).astype(dtype)


def embed_init(rng, vocab: int, dim: int, dtype):
    return (jax.random.normal(rng, (vocab, dim), jnp.float32) * 0.02).astype(dtype)


class LoRA:
    """A frozen projection ``w`` with a low-rank adapter: ``x @ LoRA(w, a,
    b, scale)`` is ``x @ w + scale * (x @ a) @ b`` (arXiv:2106.09685), so
    a block that multiplies by ``params[name]`` takes an adapted weight
    unchanged.  ``a``: ``[in, r]``, ``b``: ``[r, out]``, ``scale``:
    ``alpha / r``.  Not a pytree: made inside the traced function from
    the base's and the adapter's leaves."""

    def __init__(self, w: jax.Array, a: jax.Array, b: jax.Array,
                 scale: float):
        self.w, self.a, self.b, self.scale = w, a, b, scale

    def __rmatmul__(self, x: jax.Array) -> jax.Array:
        return x @ self.w + self.scale * ((x @ self.a) @ self.b)


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(var + eps)
    return (normed * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)


def layernorm(x: jax.Array, weight: jax.Array, bias: jax.Array,
              eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (out * weight + bias).astype(x.dtype)


def apply_norm(kind: str, x, params, eps):
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"], eps)
    return layernorm(x, params["scale"], params["bias"], eps)


def norm_init(kind: str, dim: int, dtype):
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((dim,), dtype)}
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


_ACTS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
}


def gated_mlp_init(rng, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "w_gate": dense_init(k1, d_model, d_ff, dtype),
        "w_up": dense_init(k2, d_model, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d_model, dtype),
    }


def gated_mlp(params, x: jax.Array, act: str = "silu") -> jax.Array:
    g = _ACTS[act](x @ params["w_gate"])
    h = g * (x @ params["w_up"])
    return h @ params["w_down"]


# ---------------------------------------------------------------------------
# RoPE (standard + dual-theta select + M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies [head_dim // 2] (f32)."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / head_dim))


def rope_angles(positions: jax.Array, head_dim: int, theta: float) -> jax.Array:
    """positions [..., S] -> angles [..., S, head_dim // 2]."""
    return positions[..., None].astype(jnp.float32) * rope_freqs(head_dim, theta)


def apply_rope(x: jax.Array, angles: jax.Array) -> jax.Array:
    """x: [B, H, S, D]; angles: [B, S, D/2] or [S, D/2] (half-split layout)."""
    if angles.ndim == 2:
        angles = angles[None]
    cos = jnp.cos(angles)[:, None]   # [B, 1, S, D/2]
    sin = jnp.sin(angles)[:, None]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def mrope_angles(
    positions_3d: jax.Array, head_dim: int, theta: float,
    sections: Tuple[int, int, int],
) -> jax.Array:
    """M-RoPE (Qwen2-VL): 3 position streams share the rotary channels.

    ``positions_3d``: [3, B, S] (temporal, height, width).
    ``sections`` gives how many *frequency channels* (out of head_dim/2)
    each stream owns; channels are assigned blockwise t|h|w.
    Returns angles [B, S, head_dim/2].
    """
    assert sum(sections) == head_dim // 2, (sections, head_dim)
    freqs = rope_freqs(head_dim, theta)                        # [D/2]
    ang = positions_3d[..., None].astype(jnp.float32) * freqs  # [3, B, S, D/2]
    idx = jnp.repeat(
        jnp.arange(3), jnp.asarray(sections), total_repeat_length=head_dim // 2
    )                                                          # [D/2] stream id
    onehot = jax.nn.one_hot(idx, 3, dtype=jnp.float32).T       # [3, D/2]
    return jnp.einsum("sbld,sd->bld", ang, onehot)
