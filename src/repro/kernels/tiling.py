"""Lane-block sizing for the streaming ``[S, N]`` server kernels.

Every flat kernel streams ``[S, block_n]`` column tiles of the round's
client matrix through VMEM: two pipelined input buffers in the storage
dtype plus the kernel body's f32 temporaries, all ``S`` rows tall.  A
fixed 2048-lane tile is 8 MiB of f32 at ``S = 1024``; double-buffered,
with its temporaries, that overflows the 16 MiB of VMEM a v5e kernel is
given by default.  :func:`block_cols` therefore sizes the tile from a
VMEM budget that depends on ``S`` and the dtype, keeping the 2048-lane
tile wherever it fits (every cohort up to a few hundred clients).
"""
from __future__ import annotations

from typing import Optional

#: lane width: the minor dimension of a VMEM tile
LANE = 128
#: the widest tile any kernel streams (the pre-budget fixed width)
MAX_BLOCK_N = 2048
#: VMEM bytes one grid step's live ``[S, block_n]`` tiles may occupy —
#: half the 16 MiB default scoped limit, leaving room for Mosaic's own
VMEM_TILE_BUDGET = 8 * 2**20


def padded_rows(rows: int, itemsize: int) -> int:
    """Rows a ``[rows, ·]`` VMEM tile occupies: sublanes pack 8 rows of
    32-bit values, 16 of bf16, 32 of int8."""
    pack = 8 * max(1, 4 // itemsize)
    return -(-rows // pack) * pack


def lane_width(n: int) -> int:
    """``n`` rounded up to whole lanes."""
    return -(-n // LANE) * LANE


def block_cols(rows: int, n: int, itemsize: int, f32_temps: int,
               fixed_bytes: int = 0, divides: Optional[int] = None) -> int:
    """Widest lane-aligned ``block_n`` whose working set fits the budget.

    ``rows`` is the tile height (the cohort ``S``), ``itemsize`` the
    storage dtype's, ``f32_temps`` how many ``[rows, block_n]`` f32
    temporaries the kernel body keeps live, and ``fixed_bytes`` VMEM the
    kernel holds regardless of ``block_n`` (a resident accumulator).
    The result is at most :data:`MAX_BLOCK_N`, at least one lane, never
    wider than ``n`` needs, and — with ``divides`` — a divisor of it.
    """
    per_col = (2 * padded_rows(rows, itemsize) * itemsize
               + f32_temps * padded_rows(rows, 4) * 4)
    fit = max(0, VMEM_TILE_BUDGET - fixed_bytes) // per_col
    bn = max(LANE, min(MAX_BLOCK_N, fit // LANE * LANE, lane_width(n)))
    if divides is not None:
        while divides % bn:
            bn -= LANE
    return bn
