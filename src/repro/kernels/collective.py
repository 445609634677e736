"""Collective-finishing variants of the flat server kernels.

The cross-shard form of the flat server kernels in :mod:`ops`, for
the round block under a mesh: every shard holds its own wave block
``stacked_loc = stacked[i*S_loc:(i+1)*S_loc]`` of the round's ``[S, N]``
flat client matrix, runs the *same* fused kernel from :mod:`ops` on its
block, and a single ``psum`` / ``all_gather`` / ``all_to_all`` over the
client axes of the mesh finishes the reduction.  Each function takes a
:class:`~repro.utils.sharding.ShardSpec` and must be called inside a
``shard_map`` body over those axes; with ``num_shards == 1`` they reduce
to the plain :mod:`ops` call.

Numerics: the shard-local partial sums commute with the collective up to
f32 reduction order, so results match the single-device kernels to
~1e-7 relative (the mesh equivalence gate pins rtol 1e-5).  The trimmed
mean is *exact* (same client set trimmed per coordinate) because the
``all_to_all`` transpose preserves global row order.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import krum as krum_kernel
from repro.kernels import ops
from repro.utils.sharding import ShardSpec


def flat_weighted_agg_shard(
    stacked_loc: jax.Array,
    weights_loc: jax.Array,
    shard: ShardSpec,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``Σ_k p_k · stacked[k]`` with rows sharded over the client axes.

    ``weights_loc`` is this shard's row block of the *globally
    normalized* weight vector (slice, don't renormalize): the local
    fused matvec produces a partial ``[N]`` and one ``psum`` finishes.
    """
    part = ops.flat_weighted_agg(stacked_loc, weights_loc,
                                 interpret=interpret)
    return shard.psum(part)


def flat_qagg_shard(
    q_loc: jax.Array,
    scales_loc: jax.Array,
    weights_loc: jax.Array,
    block: int,
    shard: ShardSpec,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``Σ_k p_k · deq(q_k)`` with quantized rows sharded over client axes.

    The compressed-wave commit: each shard runs the fused
    dequantize-reduce (:func:`ops.flat_qagg`) on its int8
    ``[S_loc, N]`` block + ``[S_loc, nb]`` scale sidecar, and one
    ``psum`` over the *dequantized f32 partials* finishes the reduction
    — so only the f32 ``[N]`` partial crosses shards, never a dequantized
    wave.  ``weights_loc`` is this shard's row slice of the globally
    normalized weight vector (slice, don't renormalize).
    """
    part = ops.flat_qagg(q_loc, scales_loc, weights_loc, block=block,
                         interpret=interpret)
    return shard.psum(part)


def flat_divergence_sq_shard(
    stacked_loc: jax.Array,
    global_vec: jax.Array,
    shard: ShardSpec,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Per-client squared L2 divergence, gathered back to full ``[S]``.

    The streaming kernel runs on the local ``[S_loc, N]`` block (each
    row's reduction is shard-local, so values are *identical* to the
    single-device kernel); ``all_gather`` restores wave order so the
    replicated criteria pipeline downstream sees the full vector.
    """
    part = ops.flat_divergence_sq(stacked_loc, global_vec,
                                  interpret=interpret)
    return shard.all_gather(part)


def flat_candidate_sweep_shard(
    weights_loc: jax.Array,
    stacked_loc: jax.Array,
    shard: ShardSpec,
) -> jax.Array:
    """Algorithm-1 candidate sweep ``[m!, S] @ [S, N]`` across shards.

    ``weights_loc`` is the ``[n_perm, S_loc]`` column block of the
    per-permutation weight matrix matching this shard's wave rows; the
    local GEMM's partial ``[n_perm, N]`` finishes with one ``psum``.
    """
    part = (weights_loc.astype(jnp.float32)
            @ stacked_loc.astype(jnp.float32))
    return shard.psum(part).astype(stacked_loc.dtype)


def flat_trimmed_agg_shard(
    stacked_loc: jax.Array,
    weights: jax.Array,
    trim: int,
    shard: ShardSpec,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Coordinate-wise trimmed mean with rows sharded over client axes.

    Trimming needs *all* S client values per coordinate, so the rows
    cannot stay put: an ``all_to_all`` transposes the layout from
    row-sharded ``[S_loc, N]`` to column-sharded ``[S, N/n]`` (N padded
    to a multiple of the shard count), the fused single-device kernel
    trims the full client column locally, and a tiled ``all_gather``
    reassembles ``[N]``.  ``weights`` is the full ``[S]`` vector —
    tiled ``all_to_all`` stacks source blocks in axis order, which *is*
    global wave order, so weights line up without reindexing.  Falls
    back to a row ``all_gather`` when the client dimension spans more
    than one mesh axis (host meshes have a single ``data`` axis).
    """
    n = shard.num_shards
    if n == 1:
        return ops.flat_trimmed_agg(stacked_loc, weights, trim,
                                    interpret=interpret)
    if len(shard.axes) == 1:
        axis = shard.axes[0]
        n_feat = stacked_loc.shape[1]
        pad = (-n_feat) % n
        x = jnp.pad(stacked_loc, ((0, 0), (0, pad)))
        x = jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=0,
                               tiled=True)
        out = ops.flat_trimmed_agg(x, weights, trim, interpret=interpret)
        out = jax.lax.all_gather(out, axis, axis=0, tiled=True)
        return out[:n_feat]
    full = shard.all_gather(stacked_loc)
    return ops.flat_trimmed_agg(full, weights, trim, interpret=interpret)


def flat_krum_agg_shard(
    stacked_loc: jax.Array,
    weights: jax.Array,
    f: int,
    m: int,
    shard: ShardSpec,
    interpret: Optional[bool] = None,
):
    """Multi-Krum with wave rows sharded over the client axes.

    The ``[S, S]`` Gram matrix splits by *row block*: each shard gathers
    the full wave once and contributes its ``G_block = X_loc @ X.T``
    strip — 1/n of the total contraction FLOPs — and an ``all_gather``
    over the strips (combined-index order == global wave order)
    assembles the full Gram.  Distances, scores and the ``m``-best
    selection are then tiny ``[S, S]``/``[S]`` computations replicated
    bit-identically on every shard (``weights`` is the full replicated
    vector), so every shard agrees on the selected client set.  The
    final average stays shard-local: each shard reduces its own rows
    with its slice of the selection weights and one ``psum`` finishes —
    the wave never crosses shards twice.

    Returns ``(aggregate [N], scores [S])``, both replicated.  Shares
    ``ops.flat_krum_agg``'s guard contract: a starved round aggregates
    to the zero vector and must be no-opped by the caller.
    """
    n = shard.num_shards
    if n == 1:
        return ops.flat_krum_agg(stacked_loc, weights, f, m,
                                 interpret=interpret)
    full = shard.all_gather(stacked_loc).astype(jnp.float32)
    g_block = stacked_loc.astype(jnp.float32) @ full.T     # [S_loc, S]
    gram = shard.all_gather(g_block)                       # [S, S]
    d2 = krum_kernel.gram_sq_dists(gram)
    scores = krum_kernel.krum_scores(d2, weights, f)
    wsel, _ = krum_kernel.krum_select(scores, weights, m)
    part = (shard.slice_rows(wsel)
            @ stacked_loc.astype(jnp.float32))             # local partial [N]
    agg = shard.psum(part).astype(stacked_loc.dtype)
    return agg, scores

