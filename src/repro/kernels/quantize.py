"""Blockwise absmax quantization + fused dequantize-reduce kernel.

The compressed-update streaming layer (``FedSimConfig(compress=...)``):
each client's flat update is quantized to int8 or int4 with one absmax
scale per ``block`` contiguous coordinates — 2048 by default, the widest
tile the flat server kernels stream (``kernels/tiling.py``) — so the server
aggregates *storage-dtype* tiles and the scales ride along as an
``[S, nb]`` sidecar that is ~0.2% of the payload.

Three layers, mirroring ``weighted_agg.py`` / ``ref.py``:

* :func:`quantize_blockwise` / :func:`dequantize_blockwise` — the lossy
  round-trip primitives.  Deterministic (round-half-to-even, no
  stochastic rounding): identical inputs quantize identically on every
  shard, which is what lets the mesh gate pin sharded == single-device
  compressed runs at rtol 1e-5.
* :func:`qagg_ref` — the pure-jnp oracle for the fused reduction
  ``out[n] = Σ_k w_k · scale[k, n//block] · q[k, n]`` (f32 accumulation).
* :func:`qagg` — the Pallas kernel: one ``[K, bn]`` int8 tile (``bn``
  divides ``block``) + its scale block's ``[K, 1]`` scale column per
  grid step, weights resident in VMEM, one f32 ``[bn]`` output tile.
  Reads a quarter (int8) of the HBM bytes the f32 ``weighted_agg`` pass
  moves.

Wire format: :func:`wire_bytes` accounts one client upload as the packed
payload (``ceil(N·bits/8)`` value bytes — int4 packs two values per byte,
see :func:`pack_int4` — plus one f32 scale per block).  The simulation
keeps int4 values unpacked in int8 storage, so one int8 ``qagg`` kernel
serves both modes; the nibble packing is the tested wire format and the
byte accounting everywhere reflects it.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import LANE, block_cols

#: quantized range per compress mode: values live in [-qmax, qmax]
QMAX = {"int8": 127, "int4": 7}
#: wire bits per value per compress mode
QBITS = {"int8": 8, "int4": 4}
#: default scale-block size — the flat kernels' widest streaming tile
QBLOCK = 2048


def _check_mode(compress: str) -> int:
    if compress not in QMAX:
        raise ValueError(
            f"unknown compress mode {compress!r}; expected one of "
            f"{sorted(QMAX)}"
        )
    return QMAX[compress]


def num_blocks(n: int, block: int = QBLOCK) -> int:
    """Scale blocks covering an ``n``-coordinate vector."""
    return -(-n // block)


def quantize_blockwise(
    x: jax.Array, compress: str, block: int = QBLOCK
) -> Tuple[jax.Array, jax.Array]:
    """Per-block absmax quantization along the last axis.

    ``x``: ``[..., N]`` float → ``(q, scales)`` with ``q`` int8
    ``[..., N]`` in ``[-qmax, qmax]`` and ``scales`` f32 ``[..., nb]``
    (``nb = ceil(N / block)``).  Per block ``scale = absmax / qmax``; an
    all-zero block gets scale 0 and quantizes to zeros.  Elementwise
    guarantees (property-tested in ``tests/test_quant.py``):

    * round-trip error ``|x - q·scale| <= scale / 2``,
    * the reconstruction never flips sign (``x · q·scale >= 0``),
    * exact zeros map to exact zeros,
    * fully deterministic — no rounding noise, so identical inputs give
      identical bytes on every shard/backend.
    """
    qmax = _check_mode(compress)
    n = x.shape[-1]
    nb = num_blocks(n, block)
    pad = nb * block - n
    xf = x.astype(jnp.float32)
    if pad:
        widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
        xf = jnp.pad(xf, widths)
    xb = xf.reshape(*x.shape[:-1], nb, block)
    scales = jnp.max(jnp.abs(xb), axis=-1) / qmax
    safe = jnp.where(scales > 0, scales, 1.0)
    q = jnp.clip(jnp.round(xb / safe[..., None]), -qmax, qmax)
    q = q.astype(jnp.int8).reshape(*x.shape[:-1], nb * block)
    return q[..., :n], scales


def dequantize_blockwise(
    q: jax.Array, scales: jax.Array, block: int = QBLOCK
) -> jax.Array:
    """Reconstruct ``q · scale`` back to f32 along the last axis.

    ``q``: int8 ``[..., N]``; ``scales``: ``[..., nb]`` → f32 ``[..., N]``.
    """
    n = q.shape[-1]
    nb = scales.shape[-1]
    pad = nb * block - n
    qf = q.astype(jnp.float32)
    if pad:
        widths = [(0, 0)] * (q.ndim - 1) + [(0, pad)]
        qf = jnp.pad(qf, widths)
    qb = qf.reshape(*q.shape[:-1], nb, block)
    out = qb * scales.astype(jnp.float32)[..., None]
    return out.reshape(*q.shape[:-1], nb * block)[..., :n]


def qagg_ref(
    q: jax.Array, scales: jax.Array, weights: jax.Array,
    block: int = QBLOCK,
) -> jax.Array:
    """Oracle for the fused dequantize-reduce:
    ``out[n] = Σ_k w[k] · scales[k, n // block] · q[k, n]``, f32 accumulated.

    ``q``: int8 ``[K, N]``; ``scales``: ``[K, nb]``; ``weights``: ``[K]``
    → ``[N]`` f32.
    """
    K, n = q.shape
    nb = scales.shape[1]
    pad = nb * block - n
    qf = q.astype(jnp.float32)
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, pad)))
    qb = qf.reshape(K, nb, block)
    acc = jnp.einsum(
        "k,kb,kbn->bn",
        weights.astype(jnp.float32), scales.astype(jnp.float32), qb,
    )
    return acc.reshape(-1)[:n]


def _qagg_kernel(w_ref, s_ref, q_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)          # [K, bn]
    w = w_ref[...].astype(jnp.float32)          # [K, 1]
    s = s_ref[...].astype(jnp.float32)          # [K, 1] this block's scales
    o_ref[...] = jnp.sum(q * (w * s), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def qagg(
    q: jax.Array,
    scales: jax.Array,
    weights: jax.Array,
    block: int = QBLOCK,
    interpret: bool = True,
) -> jax.Array:
    """Fused dequantize-reduce Pallas kernel (see :func:`qagg_ref`).

    Streams ``[K, bn]`` int8 tiles, each with its scale block's ``[K, 1]``
    scale column, multiplies by the resident ``[K, 1]`` weights and
    writes one f32 ``[bn]`` output tile.  ``block`` must be the
    quantizer's scale-block size.  The tile width ``bn`` is the widest
    divisor of ``block`` that fits the VMEM budget for this ``K``
    (``kernels/tiling.py``) — the whole scale block for small cohorts —
    so the tiling never changes the scale granularity or the per-column
    sum.  The scales ride as ``[nb, K, 1]``, so each grid step's block
    spans the array's full trailing dims, as Mosaic requires.
    ``interpret=True`` runs the body in Python on CPU; on TPU pass
    ``interpret=False``.
    """
    K, n = q.shape
    nb = scales.shape[1]
    pad = nb * block - n
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad)))
    w2 = weights.reshape(K, 1).astype(jnp.float32)
    s3 = scales.T.reshape(nb, K, 1)
    bn = (block_cols(K, block, q.dtype.itemsize, f32_temps=2, divides=block)
          if block % LANE == 0 else block)
    per = block // bn                          # tiles per scale block

    out = pl.pallas_call(
        _qagg_kernel,
        grid=(nb * per,),
        in_specs=[
            pl.BlockSpec((K, 1), lambda i: (0, 0)),              # weights
            pl.BlockSpec((pl.squeezed, K, 1),
                         lambda i: (i // per, 0, 0)),            # scales
            pl.BlockSpec((K, bn), lambda i: (0, i)),             # int8 tile
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, nb * block), jnp.float32),
        interpret=interpret,
    )(w2, s3, q)
    return out[0, :n]


# ---------------------------------------------------------------- wire format
def pack_int4(q: jax.Array) -> jax.Array:
    """Pack int4-range values (two per byte) along the last axis.

    ``q``: int8 ``[..., N]`` with values in ``[-7, 7]`` → uint8
    ``[..., ceil(N/2)]``; even indices ride the low nibble.  ``N`` odd
    pads the last high nibble with zero.
    """
    n = q.shape[-1]
    if n % 2:
        widths = [(0, 0)] * (q.ndim - 1) + [(0, 1)]
        q = jnp.pad(q, widths)
    lo = q[..., 0::2].astype(jnp.uint8) & 0xF
    hi = q[..., 1::2].astype(jnp.uint8) & 0xF
    return lo | (hi << 4)


def unpack_int4(packed: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`pack_int4`: uint8 ``[..., ceil(n/2)]`` → int8
    ``[..., n]`` with nibbles sign-extended back to ``[-8, 7]``."""
    lo = (packed & 0xF).astype(jnp.int8)
    hi = (packed >> 4).astype(jnp.int8)
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = jnp.where(hi > 7, hi - 16, hi)
    out = jnp.stack([lo, hi], axis=-1).reshape(*packed.shape[:-1], -1)
    return out[..., :n]


def wire_bytes(num_params: int, compress: str = "none",
               block: int = QBLOCK) -> int:
    """Bytes one client upload costs on the wire.

    ``"none"`` is the f32 baseline (``4·N``); quantized modes pay the
    packed payload (``ceil(N·bits/8)``) plus one f32 scale per block.
    """
    if compress == "none":
        return 4 * num_params
    _check_mode(compress)
    payload = -(-num_params * QBITS[compress] // 8)
    return payload + 4 * num_blocks(num_params, block)
