"""Jit'd public wrappers over the Pallas kernels (+ pytree/flat adapters).

Two kinds of entry points:

* ``tree_*`` — pytree adapters that view each stacked leaf as ``[K, N]``
  and run the kernel per leaf (``interpret=True`` everywhere in this
  container; on a real TPU the launch scripts pass ``interpret=False``).
* ``flat_*`` — the flat-vector server hot path: one ``[S, N]`` matrix for
  the whole model, dispatched through :func:`resolve_kernel_mode` — the
  compiled Mosaic kernel on TPU, the fused jnp reference elsewhere
  (interpret-mode Pallas emulation is orders of magnitude slower than an
  XLA fusion on CPU, so it is never picked implicitly).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import krum as krum_kernel
from repro.kernels import ref
from repro.kernels.divergence import divergence_sq
from repro.kernels.flash_attention import flash_attention
from repro.kernels.quantize import QBLOCK, qagg, qagg_ref
from repro.kernels.trimmed import trimmed_agg
from repro.kernels.weighted_agg import weighted_agg
from repro.utils.pytree import PyTree


def resolve_kernel_mode(interpret: Optional[bool] = None) -> Tuple[bool, bool]:
    """Shared backend-aware kernel dispatch: ``(use_pallas, interpret)``.

    * ``interpret=None`` (auto, the hot-path default): on TPU run the
      compiled Mosaic kernels (``(True, False)``); on every other backend
      use the jnp reference path (``(False, True)``) — XLA fuses it into
      one streaming pass, while interpret-mode Pallas would emulate the
      grid in Python.
    * an explicit bool *forces* the Pallas kernel with that interpret
      setting — tests use ``interpret=True`` to validate kernel bodies on
      CPU.
    """
    if interpret is not None:
        return True, bool(interpret)
    on_tpu = jax.default_backend() == "tpu"
    return on_tpu, not on_tpu


def flat_weighted_agg(
    stacked: jax.Array,
    weights: jax.Array,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``w_G[n] = Σ_k p_k · stacked[k, n]`` on the flat representation.

    ``stacked`` is the round's ``[S, N]`` flat client matrix.  One fused
    weighted reduction: the streaming Pallas kernel on TPU, a BLAS
    ``weights @ stacked`` matvec elsewhere (f32 accumulation either way).
    """
    use_pallas, interp = resolve_kernel_mode(interpret)
    if use_pallas:
        return weighted_agg(stacked, weights, interpret=interp)
    out = weights.astype(jnp.float32) @ stacked.astype(jnp.float32)
    return out.astype(stacked.dtype)


def flat_qagg(
    q: jax.Array,
    scales: jax.Array,
    weights: jax.Array,
    block: int = QBLOCK,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``Σ_k p_k · deq(q_k)`` without materializing the dequantized wave.

    The compressed-path counterpart of :func:`flat_weighted_agg`: ``q``
    is the round's int8 ``[S, N]`` quantized client matrix and ``scales``
    its ``[S, nb]`` per-block absmax sidecar (``kernels.quantize``).  One
    fused dequantize-reduce — the streaming Pallas kernel on TPU (int8
    tiles, a quarter of the f32 HBM traffic), the einsum oracle
    elsewhere.  Returns f32 ``[N]``.
    """
    use_pallas, interp = resolve_kernel_mode(interpret)
    if use_pallas:
        return qagg(q, scales, weights, block=block, interpret=interp)
    return qagg_ref(q, scales, weights, block=block)


def flat_divergence_sq(
    stacked: jax.Array,
    global_vec: jax.Array,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Per-client squared L2 distance ``[S]`` on the flat representation.

    One streaming subtract→square→reduce pass over ``[S, N]`` — the Md
    criterion's input without ever materializing an ``[S, params]``
    update pytree.  The jnp fallback is the broadcast reference form
    (``sum(square(g - x), axis=1)``): a row-mapped BLAS ``dot(d, d)``
    variant is ~3x faster on *standalone* arrays on XLA CPU, but inside
    the fused round block the broadcast form wins because XLA folds it
    into the surrounding passes while ``lax.map`` forces a while-loop
    barrier — measured on the ``hotpath`` bench before settling here.
    """
    use_pallas, interp = resolve_kernel_mode(interpret)
    if use_pallas:
        return divergence_sq(stacked, global_vec, interpret=interp)
    return ref.divergence_ref(stacked, global_vec)


def flat_trimmed_agg(
    stacked: jax.Array,
    weights: jax.Array,
    trim: int,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Coordinate-wise weighted trimmed mean ``[N]`` on the flat path.

    The robust-aggregation reduction: per coordinate drop the ``trim``
    largest and smallest client values, weighted-mean the survivors.  One
    fused peel-reduce pass (see ``kernels/trimmed.py``) on TPU, the
    stable-argsort jnp reference elsewhere — both share tie rules, so the
    two backends trim identical client sets even on duplicate values.
    """
    use_pallas, interp = resolve_kernel_mode(interpret)
    if use_pallas:
        return trimmed_agg(stacked, weights, trim, interpret=interp)
    return ref.trimmed_agg_ref(stacked, weights, trim)


def tree_trimmed_agg(stacked: PyTree, weights: jax.Array, trim: int,
                     interpret: Optional[bool] = None) -> PyTree:
    """Per-leaf coordinate-wise trimmed mean over a stacked-client pytree.

    Each leaf ``[K, ...]`` is viewed as ``[K, N]`` and reduced with
    :func:`flat_trimmed_agg`; tiny leaves (< 1 lane row) go straight to
    the jnp reference.  Because the reduction is independent per
    coordinate, this matches the flat-path result leaf-slice for
    leaf-slice — the basis of the flat-vs-pytree equivalence gate for
    ``TrimmedMeanStrategy``.
    """
    def _one(leaf: jax.Array) -> jax.Array:
        K = leaf.shape[0]
        n = int(leaf.size) // K
        flat = leaf.reshape(K, n)
        if n < 128:
            out = ref.trimmed_agg_ref(flat, weights, trim)
        else:
            out = flat_trimmed_agg(flat, weights, trim, interpret=interpret)
        return out.reshape(leaf.shape[1:])

    return jax.tree.map(_one, stacked)


def flat_krum_agg(
    stacked: jax.Array,
    weights: jax.Array,
    f: int,
    m: int,
    interpret: Optional[bool] = None,
):
    """Multi-Krum aggregate ``([N], scores [S])`` on the flat path.

    The distance-based robust reduction: Gram-accumulated pairwise
    squared distances (one streaming pass over ``[S, N]``, see
    ``kernels/krum.py``), neighbor-sum scoring, and a renormalized
    weighted mean over the ``m`` best-scored clients.  The jnp fallback
    uses the same Gram identity (one BLAS ``X @ X.T``) with scoring and
    selection shared with the kernel path, so both backends select
    identical client sets.

    Guard contract: a starved round (every selected row has zero
    weight) aggregates to the zero vector — the caller must gate the
    commit on some participant having contributed (the engine's
    ``sum(contrib) > 0`` alive guard) rather than commit the result.
    """
    use_pallas, interp = resolve_kernel_mode(interpret)
    if use_pallas:
        return krum_kernel.krum_agg(stacked, weights, f, m,
                                    interpret=interp)
    x = stacked.astype(jnp.float32)
    d2 = krum_kernel.gram_sq_dists(x @ x.T)
    scores = krum_kernel.krum_scores(d2, weights, f)
    wsel, _ = krum_kernel.krum_select(scores, weights, m)
    return (wsel @ x).astype(stacked.dtype), scores


def tree_krum_agg(stacked: PyTree, weights: jax.Array, f: int, m: int,
                  interpret: Optional[bool] = None):
    """Multi-Krum over a stacked-client pytree.

    Unlike the coordinate-wise reductions, Krum's selection is *global*:
    per-leaf squared distances are summed into one ``[S, S]`` matrix
    (exactly the flat path's distances, accumulated leaf by leaf), one
    score/selection is computed, and every leaf is averaged with the same
    selection weights — so flat and pytree paths pick the same clients.
    Tiny leaves (< 1 lane row) contribute via the jnp Gram form directly.
    Shares :func:`flat_krum_agg`'s guard contract: starved rounds
    aggregate to zero and must be no-opped by the caller.
    """
    use_pallas, interp = resolve_kernel_mode(interpret)
    leaves = jax.tree.leaves(stacked)
    S = leaves[0].shape[0]
    d2 = jnp.zeros((S, S), jnp.float32)
    for leaf in leaves:
        n = int(leaf.size) // S
        flat = leaf.reshape(S, n)
        if use_pallas and n >= 128:
            d2 = d2 + krum_kernel.pairwise_sq_dists(flat, interpret=interp)
        else:
            x = flat.astype(jnp.float32)
            d2 = d2 + krum_kernel.gram_sq_dists(x @ x.T)
    scores = krum_kernel.krum_scores(d2, weights, f)
    wsel, _ = krum_kernel.krum_select(scores, weights, m)
    out = jax.tree.map(
        lambda leaf: jnp.tensordot(
            wsel, leaf.astype(jnp.float32), axes=(0, 0)
        ).astype(leaf.dtype),
        stacked,
    )
    return out, scores


def tree_weighted_agg(stacked: PyTree, weights: jax.Array,
                      interpret: bool = True) -> PyTree:
    """Kernel-backed ``w_G = Σ_k p_k w_k`` over a stacked-client pytree.

    Each leaf ``[K, ...]`` is viewed as ``[K, N]`` and aggregated in one
    fused pass; tiny leaves (< 1 lane row) fall back to jnp.
    """
    def _one(leaf: jax.Array) -> jax.Array:
        K = leaf.shape[0]
        n = int(jnp.prod(jnp.asarray(leaf.shape[1:]))) if leaf.ndim > 1 else 1
        flat = leaf.reshape(K, n)
        if n < 128:
            return ref.weighted_agg_ref(flat, weights).reshape(leaf.shape[1:])
        out = weighted_agg(flat, weights, interpret=interpret)
        return out.reshape(leaf.shape[1:])

    return jax.tree.map(_one, stacked)


def tree_divergence_sq(stacked: PyTree, global_params: PyTree,
                       interpret: bool = True) -> jax.Array:
    """Per-client squared L2 distance ``[K]`` summed over every leaf."""
    leaves = jax.tree.leaves(stacked)
    g_leaves = jax.tree.leaves(global_params)
    K = leaves[0].shape[0]
    total = jnp.zeros((K,), jnp.float32)
    for x, g in zip(leaves, g_leaves):
        n = int(x.size) // K
        flat = x.reshape(K, n)
        gflat = g.reshape(n)
        if n < 128:
            total = total + ref.divergence_ref(flat, gflat)
        else:
            total = total + divergence_sq(flat, gflat, interpret=interpret)
    return total


def attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = True, window: Optional[int] = None, q_offset: int = 0,
    use_pallas: bool = False, interpret: bool = True,
    block_q: int = 128, block_k: int = 128,
) -> jax.Array:
    """Dispatch between the Pallas flash kernel and the jnp reference.

    The model zoo calls this everywhere; the dry-run path (host backend)
    uses ``use_pallas=False`` since Mosaic kernels only lower on TPU.
    """
    if use_pallas:
        return flash_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset, block_q=block_q,
                               block_k=block_k, interpret=interpret)
    return ref.attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=q_offset)
