"""Pure-jnp oracles for every Pallas kernel (the correctness references).

Each kernel in this package must match its oracle here to float tolerance
across a sweep of shapes/dtypes (see ``tests/test_kernels_*.py``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def weighted_agg_ref(stacked: jax.Array, weights: jax.Array) -> jax.Array:
    """``out[n] = sum_k w[k] * x[k, n]`` accumulated in f32.

    ``stacked``: [K, N] (any float dtype); ``weights``: [K] f32.
    Returns the same dtype as ``stacked``.
    """
    acc = jnp.sum(
        weights.astype(jnp.float32)[:, None] * stacked.astype(jnp.float32),
        axis=0,
    )
    return acc.astype(stacked.dtype)


def divergence_ref(stacked: jax.Array, global_vec: jax.Array) -> jax.Array:
    """Per-client squared L2 distance to the global vector, f32.

    ``stacked``: [K, N]; ``global_vec``: [N] → out [K] f32.
    """
    d = global_vec.astype(jnp.float32)[None, :] - stacked.astype(jnp.float32)
    return jnp.sum(d * d, axis=1)


def trimmed_agg_ref(stacked: jax.Array, weights: jax.Array,
                    trim: int) -> jax.Array:
    """Coordinate-wise weighted trimmed mean, accumulated in f32.

    ``stacked``: [K, N] (any float dtype); ``weights``: [K] f32;
    ``trim``: values removed *per side* per coordinate (``2*trim < K``).

    Per coordinate the ``trim`` smallest and ``trim`` largest values are
    discarded (stable ascending order, so among duplicates the lowest
    client indices trim at the bottom and the highest at the top — the
    Pallas kernel's peel order matches this tie rule exactly) and the
    survivors are combined by their normalized weights.  If the surviving
    weight mass is ~0 (e.g. every participant of a sparse round got
    trimmed) the unweighted mean of the survivors is used instead, so the
    output stays finite; the engine's all-dropped guard handles the
    no-participant case above this layer.

    Returns the same dtype as ``stacked``.
    """
    K, _ = stacked.shape
    if not 0 <= 2 * trim < K:
        raise ValueError(f"need 0 <= 2*trim < K, got trim={trim} K={K}")
    x = stacked.astype(jnp.float32)
    order = jnp.argsort(x, axis=0)                      # stable by default
    xs = jnp.take_along_axis(x, order, axis=0)
    ws = weights.astype(jnp.float32)[order]
    keep = jnp.zeros((K, 1), jnp.float32).at[trim:K - trim].set(1.0)
    num = jnp.sum(xs * ws * keep, axis=0)
    den = jnp.sum(ws * keep, axis=0)
    fallback = jnp.sum(xs * keep, axis=0) / float(K - 2 * trim)
    out = jnp.where(den > 1e-12, num / jnp.maximum(den, 1e-12), fallback)
    return out.astype(stacked.dtype)


def pairwise_sq_dists_ref(stacked: jax.Array) -> jax.Array:
    """All pairwise squared L2 distances ``[S, S]`` f32 from explicit row
    differences — no Gram cancellation, no matmul — one row at a time,
    so the ``[S, S, N]`` difference tensor never materializes.

    ``stacked``: [S, N] (any float dtype) → [S, S] f32, zero diagonal.
    """
    x = stacked.astype(jnp.float32)
    return jax.lax.map(lambda xi: jnp.sum(jnp.square(x - xi), axis=1), x)


def krum_agg_ref(stacked: jax.Array, weights: jax.Array, f: int, m: int):
    """Multi-Krum reference: explicit pairwise differences, no Gram trick.

    ``stacked``: [S, N] (any float dtype); ``weights``: [S] f32; ``f``:
    assumed Byzantine bound (``f <= S - 3``); ``m``: selection size
    (``m = 1`` is plain Krum).

    Per client ``i`` the score sums the squared distances to its
    ``S - f - 2`` nearest *other* clients (self excluded); zero-weight
    rows score ``+inf`` (a dropped upload can serve as a neighbor but
    can never be selected).  The ``m`` lowest-score clients are averaged
    by their renormalized weights; if the surviving weight mass is ~0
    (a starved round where every pick was a dropped upload) the
    aggregate is the zero vector — never an average of dropped clients'
    updates — and the caller must no-op the round (the engine's
    all-dropped guard does).  ``lax.top_k`` tie-breaks toward lower
    client indices — the kernel path shares the rule.

    Returns ``(aggregate [N] in stacked's dtype, scores [S] f32)``.
    """
    S, _ = stacked.shape
    if not (f >= 0 and S - f - 2 >= 1):
        raise ValueError(f"need 0 <= f <= S-3 for S={S}, got f={f}")
    if not 1 <= m <= S:
        raise ValueError(f"need 1 <= m <= S={S}, got m={m}")
    x = stacked.astype(jnp.float32)
    diff = x[:, None, :] - x[None, :, :]
    d2 = jnp.sum(diff * diff, axis=-1)
    d2 = jnp.where(jnp.eye(S, dtype=bool), jnp.inf, d2)
    nn = jnp.sort(d2, axis=1)[:, :S - f - 2]
    w = weights.astype(jnp.float32)
    scores = jnp.where(w > 0, jnp.sum(nn, axis=1), jnp.inf)
    _, idx = jax.lax.top_k(-scores, m)
    sel = jnp.zeros((S,), jnp.float32).at[idx].set(1.0)
    wk = w * sel
    den = jnp.sum(wk)
    num = wk @ x
    out = jnp.where(den > 1e-12, num / jnp.maximum(den, 1e-12),
                    jnp.zeros_like(num))
    return out.astype(stacked.dtype), scores


def attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Reference (G)QA attention with optional causal/sliding-window mask.

    ``q``: [B, Hq, Sq, D]; ``k``/``v``: [B, Hkv, Skv, D] with Hq % Hkv == 0.
    ``q_offset`` is the absolute position of q[:, :, 0] (decode: cache_len).
    ``window``: if set, query at absolute position i attends to keys in
    ``(i - window, i]`` — i.e. a sliding window of size ``window``.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    group = Hq // Hkv
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    s = scale if scale is not None else 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), kk.astype(jnp.float32)
    ) * s
    q_pos = jnp.arange(Sq) + q_offset
    k_pos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    # fully-masked rows (can happen with tiny windows) -> zeros, not NaN
    probs = jnp.where(jnp.any(mask, -1)[None, None, :, None], probs, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vv.astype(jnp.float32))
    return out.astype(q.dtype)


def attention_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window=None,
    q_offset=0,
    block: int = 1024,
    k_valid=None,
    unroll: bool = False,
) -> jax.Array:
    """Flash-style attention in pure XLA: online softmax over KV blocks.

    Never materializes the [Sq, Skv] score matrix — peak intermediate is
    [B, Hq, Sq, block].  This is the "XLA-level flash" used by the serving
    prefill path (the Pallas kernel is the TPU-kernel-level equivalent).
    ``window``/``q_offset`` may be traced scalars; ``k_valid`` optionally
    masks cache positions ≥ its value (prefill against a larger cache).
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    group = Hq // Hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    pad = (-Skv) % block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nb = (Skv + pad) // block
    kb = jnp.moveaxis(k.reshape(B, Hkv, nb, block, D), 2, 0)
    vb = jnp.moveaxis(v.reshape(B, Hkv, nb, block, D), 2, 0)

    q32 = q.reshape(B, Hkv, group, Sq, D).astype(jnp.float32) * scale
    q_pos = q_offset + jnp.arange(Sq)

    def body(carry, inp):
        m, l, acc = carry
        kc, vc, start = inp
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", q32, kc.astype(jnp.float32))
        k_pos = start + jnp.arange(block)
        mask = k_pos[None, :] < (Skv if k_valid is None else k_valid)
        if causal:
            mask = mask & (q_pos[:, None] >= k_pos[None, :])
        if window is not None:
            mask = mask & ((q_pos[:, None] - k_pos[None, :]) < window)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        p = jnp.where(mask[None, None, None], p, 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.einsum("bhgqk,bhkd->bhgqd", p,
                                       vc.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, group, Sq, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hkv, group, Sq, 1), jnp.float32)
    a0 = jnp.zeros((B, Hkv, group, Sq, D), jnp.float32)
    starts = jnp.arange(nb) * block
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, starts),
                                  unroll=nb if unroll else 1)
    out = acc / jnp.where(l > 0, l, 1.0)
    return out.reshape(B, Hq, Sq, D).astype(q.dtype)
