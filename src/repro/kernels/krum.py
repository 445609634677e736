"""Pallas TPU kernel: fused pairwise-distance scoring for Krum/multi-Krum.

Distance-based robust aggregation (``KrumStrategy``) needs, for the
round's ``[S, N]`` flat client matrix, every pairwise squared distance
``d2[i, j] = ||x_i - x_j||^2`` — an ``[S, S]`` matrix whose naive
materialization streams the wave ``S`` times.  The kernel instead
accumulates the Gram matrix ``G = X @ X.T`` over ``[S, block_n]``
feature tiles (one MXU contraction per tile, the ``[S, S]`` accumulator
resident in VMEM across the grid) and recovers the distances from the
polarization identity ``d2[i, j] = G[i, i] + G[j, j] - 2 G[i, j]`` —
one streaming pass over the wave regardless of ``S``.

Scoring and selection are ``O(S^2 log S)`` on a tiny matrix and stay in
plain jnp: score ``i`` sums its ``S - f - 2`` smallest distances to
*other* clients (self excluded via an inf diagonal), zero-weight rows
(dropped uploads) are forced to ``+inf`` so selection never picks them,
and the ``m`` lowest-score rows are averaged by their renormalized
aggregation weights.  Distances are computed over *all* rows — a dropped
client's honest-trained vector is still a useful neighbor — only
selection is weight-gated.

The oracle (``ref.krum_agg_ref``) computes the same scores from explicit
row differences — no Gram cancellation — which pins the kernel's
numerics in the equivalence sweep (rtol 1e-5 on CPU interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import block_cols, lane_width, padded_rows


def _gram_kernel(x_ref, o_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)          # [S, bn]
    part = jax.lax.dot_general(
        x, x, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                           # [S, S] tile partial

    @pl.when(i == 0)
    def _init():
        o_ref[...] = part

    @pl.when(i != 0)
    def _acc():
        o_ref[...] += part


@functools.partial(jax.jit, static_argnames=("interpret",))
def pairwise_sq_dists(
    stacked: jax.Array,
    interpret: bool = True,
) -> jax.Array:
    """All pairwise squared L2 distances ``[S, S]`` f32 over ``[S, N]``.

    Gram-based: zero feature padding contributes zero to every inner
    product, so padding to the lane-aligned block width is harmless.
    The diagonal is clamped to exactly 0 and negatives from float
    cancellation are floored away.  The tile width comes from the VMEM
    budget, net of the resident ``[S, S]`` accumulator (two buffers and
    the partial).
    """
    S, N = stacked.shape
    acc_bytes = 3 * padded_rows(S, 4) * lane_width(S) * 4
    block_n = block_cols(S, N, stacked.dtype.itemsize, f32_temps=1,
                         fixed_bytes=acc_bytes)
    n_pad = (-N) % block_n
    if n_pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, n_pad)))
    padded_n = N + n_pad

    gram = pl.pallas_call(
        _gram_kernel,
        grid=(padded_n // block_n,),
        in_specs=[pl.BlockSpec((S, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((S, S), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((S, S), jnp.float32),
        interpret=interpret,
        name="pairwise_sq_dists",
    )(stacked)
    sq = jnp.diagonal(gram)
    d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * gram, 0.0)
    return d2 * (1.0 - jnp.eye(S, dtype=jnp.float32))


def gram_sq_dists(gram: jax.Array) -> jax.Array:
    """Squared distances from an ``[S, S]`` f32 Gram matrix (shared by the
    sharded collective, which assembles the Gram from local GEMM blocks)."""
    S = gram.shape[0]
    sq = jnp.diagonal(gram)
    d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * gram, 0.0)
    return d2 * (1.0 - jnp.eye(S, dtype=jnp.float32))


def krum_scores(d2: jax.Array, weights: jax.Array, f: int) -> jax.Array:
    """Krum score per client: sum of its ``S - f - 2`` nearest neighbors.

    ``d2`` is the ``[S, S]`` squared-distance matrix (diagonal ignored),
    ``weights`` the ``[S]`` aggregation-weight vector whose zero rows
    (dropped uploads) are pushed to ``+inf`` so they can never be
    selected.  Lower is better: an honest client surrounded by the
    honest cluster has small nearest-neighbor distances, an outlier pays
    for every neighbor it lacks.
    """
    S = d2.shape[0]
    k_nn = S - f - 2
    if not (f >= 0 and k_nn >= 1):
        raise ValueError(f"need 0 <= f <= S-3 for S={S}, got f={f}")
    d2 = jnp.where(jnp.eye(S, dtype=bool), jnp.inf, d2)
    nn = jnp.sort(d2, axis=1)[:, :k_nn]
    scores = jnp.sum(nn, axis=1)
    return jnp.where(weights.astype(jnp.float32) > 0, scores, jnp.inf)


def krum_select(scores: jax.Array, weights: jax.Array, m: int):
    """``(wsel, sel)``: normalized aggregation weights over the ``m``
    lowest-score clients, plus the raw 0/1 selection mask.

    ``lax.top_k`` tie-breaks toward lower client indices, matching the
    oracle.  If the selected rows carry no weight mass (every pick was a
    zero-weight straggler in a starved round) the weights are all zero —
    the aggregate built from them is the zero vector, never an average of
    dropped clients' updates.  Callers must treat a starved round as a
    no-op: the engine's all-dropped guard (``sum(contrib) > 0``) keeps
    the previous params in exactly this case, and any future caller of
    ``flat_krum_agg``/``tree_krum_agg`` owes the same guard.
    """
    S = scores.shape[0]
    if not 1 <= m <= S:
        raise ValueError(f"need 1 <= m <= S={S}, got m={m}")
    _, idx = jax.lax.top_k(-scores, m)
    sel = jnp.zeros((S,), jnp.float32).at[idx].set(1.0)
    wk = weights.astype(jnp.float32) * sel
    den = jnp.sum(wk)
    return jnp.where(den > 1e-12, wk / jnp.maximum(den, 1e-12),
                     jnp.zeros_like(wk)), sel


@functools.partial(jax.jit,
                   static_argnames=("f", "m", "interpret"))
def krum_agg(
    stacked: jax.Array,
    weights: jax.Array,
    f: int,
    m: int,
    interpret: bool = True,
):
    """Multi-Krum aggregate ``([N], scores [S])`` over ``[S, N]``.

    Semantics match :func:`repro.kernels.ref.krum_agg_ref`; ``m = 1`` is
    plain Krum (the single best-scored client's update), ``m > 1``
    multi-Krum (renormalized weighted mean of the ``m`` best).
    """
    d2 = pairwise_sq_dists(stacked, interpret=interpret)
    scores = krum_scores(d2, weights, f)
    wsel, _ = krum_select(scores, weights, m)
    agg = (wsel @ stacked.astype(jnp.float32)).astype(stacked.dtype)
    return agg, scores
