"""Client-axis sharding context for the mesh-parallel flat server path.

The flat server hot path (PR 4) reduces one round to dense ops over a
single ``[S, N]`` update matrix plus a handful of O(K) state vectors.
Sharding it over a mesh follows one rule:

* **small stays replicated** — selection, participation, criteria
  normalization and weights are O(S) or O(K) *vectors*; every shard
  recomputes them from the same PRNG keys, so they are bit-identical
  across shards and no collective is needed;
* **big gets sharded** — the ``[S, N]`` stacked updates split by wave
  position (shard ``i`` trains rows ``[i*S_loc, (i+1)*S_loc)``) and the
  O(K·C)/O(K) server tables split by client block; shard-local partial
  reductions finish with one ``psum``/``all_gather``.

:class:`ShardSpec` carries the *static* description of the client axes
(names + sizes) and provides the handful of collectives the engine
needs.  Its methods are only valid inside a ``jax.shard_map`` body over
a mesh containing those axes (the engine passes ``check_vma=False``:
the round step is *deterministically* replicated — same PRNG keys on
every shard — in ways the static checker cannot prove); with ``num_shards == 1`` they
degrade to (near) no-ops, so the same code path runs on one device.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax


@dataclass(frozen=True)
class ShardSpec:
    """Static description of the mesh axes the client dimension spans.

    ``axes`` are ordered major-to-minor (e.g. ``("pod", "data")``): the
    combined shard index, row slicing and ``all_gather`` ordering all
    follow that convention, matching ``PartitionSpec((axes,))`` layout.
    """

    axes: Tuple[str, ...]
    sizes: Tuple[int, ...]

    @property
    def num_shards(self) -> int:
        return math.prod(self.sizes)

    def index(self):
        """Combined (row-major over ``axes``) shard index, traced."""
        idx = jax.lax.axis_index(self.axes[0])
        for a, s in zip(self.axes[1:], self.sizes[1:]):
            idx = idx * s + jax.lax.axis_index(a)
        return idx

    def psum(self, x):
        return jax.lax.psum(x, self.axes)

    def pmax(self, x):
        return jax.lax.pmax(x, self.axes)

    def all_gather(self, x):
        """Gather shard blocks along axis 0, in combined-index order."""
        for a in reversed(self.axes):
            x = jax.lax.all_gather(x, a, axis=0, tiled=True)
        return x

    def slice_rows(self, x, axis: int = 0):
        """This shard's block of a *replicated* array along ``axis``.

        ``x.shape[axis]`` must be divisible by :attr:`num_shards`; the
        block order matches :meth:`index` / :meth:`all_gather`, so
        ``all_gather(slice_rows(x)) == x``.
        """
        per = x.shape[axis] // self.num_shards
        return jax.lax.dynamic_slice_in_dim(x, self.index() * per, per,
                                            axis=axis)

    def partition_spec(self, *trailing) -> "jax.sharding.PartitionSpec":
        """``PartitionSpec`` sharding dim 0 over the client axes."""
        from jax.sharding import PartitionSpec

        head = self.axes[0] if len(self.axes) == 1 else self.axes
        return PartitionSpec(head, *trailing)
