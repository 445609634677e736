"""Pallas TPU kernel: fused multi-client weighted parameter aggregation.

The server-side hot loop of the paper's protocol is ``w_G = Σ_k p_k · w_k``
over K stacked client parameter vectors — a purely memory-bound pass over
``K × N`` values producing ``N``.  A naive per-tensor jnp implementation
reads each leaf K times through HBM *and* materializes a broadcast
``w[:, None] * x`` intermediate; the kernel streams one ``[K, block_n]``
VMEM tile per grid step, multiplies by the K weights held in VMEM, and
writes one ``[block_n]`` output tile — a single HBM pass at roofline
bandwidth with f32 accumulation regardless of the storage dtype.

TPU mapping notes:
* ``block_n`` is a multiple of 128 (lane width); K rides the sublane dim.
  By default it is sized from a VMEM budget (``kernels/tiling.py``), so
  the tile stays 2048 lanes for small cohorts and narrows for large K.
* weights are tiny ([K]) and pinned via a ``(K, 1)`` block that maps to the
  same tile every grid step (compiler keeps it resident).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import block_cols, lane_width


def _kernel(w_ref, x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # [K, bn]
    w = w_ref[...].astype(jnp.float32)          # [K, 1]
    o_ref[...] = jnp.sum(x * w, axis=0, keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def weighted_agg(
    stacked: jax.Array,
    weights: jax.Array,
    block_n: Optional[int] = None,
    interpret: bool = True,
) -> jax.Array:
    """``out[n] = Σ_k weights[k] * stacked[k, n]``.

    ``stacked``: [K, N] any float dtype; ``weights``: [K].
    ``interpret=True`` runs the kernel body in Python on CPU (validation
    mode for this container); on TPU pass ``interpret=False``.

    Any ``K >= 1`` / ``N >= 1`` works: ``block_n`` (``None``: sized
    from the VMEM budget for this ``K`` and dtype) is clamped to the
    lane-aligned width the input actually needs, so a 257-element vector
    pads to 384 columns (one grid step), not 2048.  Accumulation is f32
    regardless of the storage dtype (bf16 in, bf16 out, f32 math).
    """
    K, N = stacked.shape
    if block_n is None:
        block_n = block_cols(K, N, stacked.dtype.itemsize, f32_temps=2)
    block_n = min(block_n, lane_width(N))
    n_pad = (-N) % block_n
    if n_pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, n_pad)))
    padded_n = N + n_pad
    w2 = weights.reshape(K, 1).astype(jnp.float32)

    out = pl.pallas_call(
        _kernel,
        grid=(padded_n // block_n,),
        in_specs=[
            pl.BlockSpec((K, 1), lambda i: (0, 0)),        # weights, resident
            pl.BlockSpec((K, block_n), lambda i: (0, i)),  # client tile
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, padded_n), stacked.dtype),
        interpret=interpret,
    )(w2, stacked)
    return out[0, :N]
