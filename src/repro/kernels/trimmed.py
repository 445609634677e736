"""Pallas TPU kernel: fused coordinate-wise weighted trimmed mean.

Robust aggregation (``TrimmedMeanStrategy``) needs, per coordinate of the
``[S, N]`` flat client matrix, the weighted mean of the values that
survive removing the ``trim`` largest and ``trim`` smallest entries.  A
sort-based formulation would materialize a full ``[S, N]`` permutation in
HBM; on TPU a sort along the *sublane* axis is also a poor fit for the
VPU.  Instead the kernel peels extremes: ``trim`` is small (a quarter of
the cohort at most), so per ``[S, block_n]`` tile it runs ``trim``
max-peel + min-peel passes that knock one survivor out of the keep-mask
each — ``O(trim · S · block_n)`` streaming work, no sort, no scatter.
The peels run as a loop, not unrolled, so a large cohort's trim budget
does not grow the kernel.

Tie-breaking matches the stable-argsort oracle (``ref.trimmed_agg_ref``)
exactly: the max peel evicts the *last* duplicate (stable ascending sort
places higher client indices later, so they fall in the top-``trim``
slice first) and the min peel evicts the *first*.  This keeps the set of
trimmed *weights* identical between kernel and oracle even when client
values collide, which the duplicate-value kernel tests pin down.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import block_cols


def _kernel(x_ref, w_ref, o_ref, *, trim: int):
    x = x_ref[...].astype(jnp.float32)          # [K, bn]
    w = w_ref[...].astype(jnp.float32)          # [K, 1]
    K = x.shape[0]
    # Mosaic's iota is integer-only; row indices are exact in f32
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0).astype(jnp.float32)

    def peel(_, keep):
        # peel the current max; last duplicate wins (stable-sort tie rule)
        hi = jnp.max(jnp.where(keep > 0, x, -jnp.inf), axis=0, keepdims=True)
        at_hi = (keep > 0) & (x == hi)
        idx = jnp.max(jnp.where(at_hi, row, -1.0), axis=0, keepdims=True)
        keep = keep * (1.0 - (row == idx).astype(jnp.float32))
        # peel the current min; first duplicate wins
        lo = jnp.min(jnp.where(keep > 0, x, jnp.inf), axis=0, keepdims=True)
        at_lo = (keep > 0) & (x == lo)
        idx = jnp.min(jnp.where(at_lo, row, float(K)), axis=0, keepdims=True)
        return keep * (1.0 - (row == idx).astype(jnp.float32))

    keep = jax.lax.fori_loop(0, trim, peel, jnp.ones_like(x))
    wk = w * keep
    num = jnp.sum(x * wk, axis=0, keepdims=True)
    den = jnp.sum(wk, axis=0, keepdims=True)
    fallback = jnp.sum(x * keep, axis=0, keepdims=True) / float(K - 2 * trim)
    out = jnp.where(den > 1e-12, num / jnp.maximum(den, 1e-12), fallback)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("trim", "interpret"))
def trimmed_agg(
    stacked: jax.Array,
    weights: jax.Array,
    trim: int,
    interpret: bool = True,
) -> jax.Array:
    """Coordinate-wise weighted trimmed mean ``[N]`` over ``[S, N]``.

    Semantics match :func:`repro.kernels.ref.trimmed_agg_ref` (including
    the zero-surviving-weight fallback to the unweighted kept mean).
    Padded columns are all-zero ties and get sliced away, so zero padding
    is harmless; the tile width is sized from the VMEM budget for this
    ``K`` and dtype, clamped to the lane-aligned width the input needs.
    """
    K, N = stacked.shape
    if not 0 <= 2 * trim < K:
        raise ValueError(f"need 0 <= 2*trim < K, got trim={trim} K={K}")
    block_n = block_cols(K, N, stacked.dtype.itemsize, f32_temps=6)
    n_pad = (-N) % block_n
    if n_pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, n_pad)))
    padded_n = N + n_pad

    out = pl.pallas_call(
        functools.partial(_kernel, trim=trim),
        grid=(padded_n // block_n,),
        in_specs=[
            pl.BlockSpec((K, block_n), lambda i: (0, i)),   # client tiles
            pl.BlockSpec((K, 1), lambda i: (0, 0)),         # resident weights
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, padded_n), stacked.dtype),
        interpret=interpret,
    )(stacked, weights.astype(jnp.float32).reshape(K, 1))
    return out[0, :N]
