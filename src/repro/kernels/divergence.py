"""Pallas TPU kernel: fused per-client L2 divergence (criterion Md).

The model-divergence criterion needs ``||w_G − w_k||₂`` for every client k.
Doing this with jnp materializes a ``[K, N]`` diff tensor in HBM; the
kernel fuses subtract → square → reduce into one streaming pass, keeping a
``[K]`` f32 accumulator resident in the output tile across grid steps
(TPU grids execute sequentially, so cross-step accumulation into the same
output block is the canonical reduction pattern).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import block_cols, lane_width


def _kernel(g_ref, x_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    g = g_ref[...].astype(jnp.float32)          # [1, bn]
    x = x_ref[...].astype(jnp.float32)          # [K, bn]
    d = g - x
    o_ref[...] += jnp.sum(d * d, axis=1, keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def divergence_sq(
    stacked: jax.Array,
    global_vec: jax.Array,
    block_n: Optional[int] = None,
    interpret: bool = True,
) -> jax.Array:
    """Per-client squared L2 distance ``[K]`` (f32) to ``global_vec [N]``.

    Zero padding is harmless: padded columns contribute ``(0-0)^2``.
    ``block_n`` (``None``: sized from the VMEM budget for this ``K`` and
    dtype) is clamped to the lane-aligned width the input needs, so
    small vectors are not padded to a full default tile; any ``K >= 1`` /
    ``N >= 1`` works, with f32 accumulation for every storage dtype.
    """
    K, N = stacked.shape
    if block_n is None:
        block_n = block_cols(K, N, stacked.dtype.itemsize, f32_temps=2)
    block_n = min(block_n, lane_width(N))
    n_pad = (-N) % block_n
    if n_pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, n_pad)))
        global_vec = jnp.pad(global_vec, (0, n_pad))
    padded_n = N + n_pad

    out = pl.pallas_call(
        _kernel,
        grid=(padded_n // block_n,),
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),   # global tile
            pl.BlockSpec((K, block_n), lambda i: (0, i)),   # client tiles
        ],
        out_specs=pl.BlockSpec((K, 1), lambda i: (0, 0)),   # resident acc
        out_shape=jax.ShapeDtypeStruct((K, 1), jnp.float32),
        interpret=interpret,
        name="divergence_sq",
    )(global_vec.reshape(1, padded_n), stacked)
    return out[:, 0]
