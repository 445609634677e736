"""Mode-B distributed federated training (DESIGN.md §3).

The production mapping of the paper's protocol onto the TPU mesh:

* ``shard_map`` is *manual* over the client axes (``pod``, ``data``) — each
  mesh group along those axes is one federated client holding its data
  shard; the ``model`` axis stays *auto* (GSPMD tensor-parallels each
  client's local compute).
* Each client computes its local gradient (1 local step ≡ FedAvg local
  update, see DESIGN.md §3 equivalence), measures its criteria, and the
  "server" is a criteria-weighted ``psum`` over the client axes — the
  paper's Eq. 2–4 as a single collective.
* Criteria (production adaptations of §3's):
    - Ds: valid-token count share,
    - Ld: distinct-label count share (vocab-histogram based),
    - Md: inverse update-divergence share, phi = 1/sqrt(lr*||g|| + 1).
* ``adjust=True`` adds Algorithm 1: all m! permutation candidates are
  aggregated and scored by validation loss inside the same XLA program
  (the vectorized variant of ``core.adjust``), with the accept/backtrack
  rule applied with ``jnp.where`` — zero host round-trips per round.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.criteria import ClientContext, get_criterion
from repro.core.operators import all_permutations, prioritized_score
from repro.launch.mesh import client_axes, num_clients
from repro.models.registry import ModelBundle
from repro.utils.pytree import PyTree, tree_sq_norm

CRITERIA_NAMES = ("Ds", "Ld", "Md")


def _batch_in_specs(batch: Dict[str, jax.Array], caxes) -> Dict[str, P]:
    """Batch arrays split over the client axes on their batch dim."""
    out = {}
    for k, v in batch.items():
        if k == "mrope_positions":                   # [3, B, S]
            out[k] = P(None, caxes, *([None] * (v.ndim - 2)))
        else:                                        # [B, ...]
            out[k] = P(caxes, *([None] * (v.ndim - 1)))
    return out


def _client_criteria(
    batch: Dict[str, jax.Array], grads: PyTree, lr: float, vocab_size: int,
    caxes: Tuple[str, ...], part: Optional[jax.Array] = None,
    stale: Optional[jax.Array] = None,
) -> jax.Array:
    """Per-client normalized criteria vector [m] (sums to 1 over clients).

    ``part`` is this client's scalar participation (scenario mask): 0
    excludes it from the round's normalizing constant entirely.  ``stale``
    is this client's scalar staleness (rounds since its update was last
    committed, from the engine's ``ServerState.last_sync`` clocks): when
    given, the registered ``staleness`` criterion is appended as a fourth
    column, so async stale-gradient runs down-weight late arrivals with
    the same machinery on the mesh as on one host.
    """
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)

    ds_raw = jnp.sum(mask)
    hist = jnp.zeros((vocab_size,), jnp.int32).at[labels.reshape(-1)].add(1)
    ld_raw = jnp.sum((hist > 0).astype(jnp.float32))
    gnorm = jnp.sqrt(tree_sq_norm(grads))
    md_raw = 1.0 / jnp.sqrt(lr * gnorm + 1.0)

    cols = [ds_raw, ld_raw, md_raw]
    if stale is not None:
        cols.append(get_criterion("staleness")(
            ClientContext(staleness=stale)
        ))
    raw = jnp.stack(cols)                            # [m]
    if part is not None:
        raw = raw * part
    total = jax.lax.psum(raw, caxes)
    return raw / jnp.maximum(total, 1e-12)


def _sgd(params: PyTree, grads: PyTree, lr: float) -> PyTree:
    """The server update: w_G ← w_G − lr·(Σ_k p_k g_k) — the Mode-B
    equivalent of the paper's weighted model average (DESIGN.md §3)."""
    return jax.tree.map(
        lambda p, g: (p.astype(jnp.float32)
                      - lr * g.astype(jnp.float32)).astype(p.dtype),
        params, grads,
    )


def _agg_rs_ag_bf16(weighted: jax.Array, caxes, K: int) -> jax.Array:
    """f32 reduce-scatter + bf16 all-gather server reduction.

    Ring all-reduce moves ~2x f32 bytes; RS(f32) + AG(bf16) moves
    ~1x f32 + 0.5x f32 = 25% less ICI traffic, with the sum still
    accumulated in f32 (the bf16 rounding happens once, after the
    reduction).

    The scatter happens along an *existing* dimension divisible by the
    client count — a flattening reshape would destroy the auto model-axis
    sharding of the other dims and force GSPMD to fully rematerialize the
    gradient (measured: 7x memory blow-up; see EXPERIMENTS.md §Perf HC3
    iteration 1).  Leaves with no divisible dim fall back to plain psum.
    """
    dim = next((d for d, n in enumerate(weighted.shape) if n % K == 0 and n >= K),
               None)
    if dim is None:
        return jax.lax.psum(weighted, caxes)
    shard = jax.lax.psum_scatter(weighted, caxes, scatter_dimension=dim,
                                 tiled=True)            # dim shrunk by K, f32
    shard = shard.astype(jnp.bfloat16)
    full = jax.lax.all_gather(shard, caxes, axis=dim, tiled=True)
    return full.astype(jnp.float32)


def make_federated_train_step(
    bundle: ModelBundle,
    mesh,
    lr: float = 0.01,
    priority: Optional[Tuple[int, ...]] = None,
    fedavg_baseline: bool = False,
    agg_mode: str = "allreduce",
    with_participation: bool = False,
    with_staleness: bool = False,
) -> Callable:
    """Jitted federated train step: ``step(params, batch) -> (params, stats)``.

    ``fedavg_baseline=True`` reproduces plain FedAvg (weights = Ds share
    only) — the paper's baseline, kept for A/B comparison.
    ``agg_mode``: "allreduce" (f32 psum, paper-faithful baseline) or
    "rs_ag_bf16" (f32 reduce-scatter + bf16 all-gather — beyond-paper
    collective optimization, §Perf).
    ``with_participation=True`` appends a ``participation`` argument: the
    ``[K]`` per-client scenario mask/contribution
    (``repro.federated.scenarios.participation``): 0 excludes a client
    from criteria normalization and the weighted psum, fractional values
    down-weight stragglers; an all-dropped round degenerates to a no-op
    update (all weights 0).  The same argument is how client *selection*
    reaches the mesh: ``repro.federated.selection.round_participation``
    scatters any :class:`~repro.federated.selection.SelectionPolicy`'s
    pick into this ``[K]`` gate, so deadline-aware/bias/oracle policies
    drive Mode-B rounds exactly like the single-host engine.
    ``with_staleness=True`` appends a ``staleness`` argument: the ``[K]``
    per-client rounds-since-last-sync vector (the engine's
    ``ServerState.last_sync`` clocks), measured through the registered
    ``staleness`` criterion as a fourth criteria column — async runs on
    the mesh down-weight stale updates exactly like the single-host
    engine.  The full signature with both flags is
    ``step(params, batch, participation, staleness)``.
    ``priority`` defaults to identity order over however many criteria
    are active (3, or 4 with staleness).
    """
    caxes = client_axes(mesh)
    K = num_clients(mesh)
    cfg = bundle.cfg
    m = len(CRITERIA_NAMES) + (1 if with_staleness else 0)
    if priority is None:
        priority = tuple(range(m))
    if len(priority) != m:
        raise ValueError(
            f"priority {priority} must permute all {m} active criteria"
        )

    def per_client(params, batch, *extra):
        extra = list(extra)
        part = extra.pop(0) if with_participation else None
        stale = extra.pop(0) if with_staleness else None
        pm = None if part is None else part.reshape(())
        st = None if stale is None else stale.reshape(())
        (loss, _), grads = jax.value_and_grad(
            lambda p: bundle.loss(p, batch), has_aux=True
        )(params)
        # criteria normalize over *participants* (binary mask); the
        # fractional straggler contribution is applied once, to the score —
        # same semantics as the single-host round loop (scenarios.py)
        bin_pm = None if pm is None else (pm > 0).astype(jnp.float32)
        c = _client_criteria(batch, grads, lr, cfg.vocab_size, caxes, bin_pm,
                             st)

        s = c[0] if fedavg_baseline else prioritized_score(c, priority)
        if pm is not None:
            s = s * pm
        z = jax.lax.psum(s, caxes)
        p_k = s / jnp.maximum(z, 1e-12)

        # reductions in f32: avoids bf16 all-reduce promotion (XLA CPU
        # crash) and keeps the server reduction numerically exact
        if agg_mode == "rs_ag_bf16":
            agg = jax.tree.map(
                lambda g: _agg_rs_ag_bf16(
                    p_k * g.astype(jnp.float32), caxes, K
                ).astype(g.dtype),
                grads,
            )
        else:
            agg = jax.tree.map(
                lambda g: jax.lax.psum(
                    p_k * g.astype(jnp.float32), caxes
                ).astype(g.dtype),
                grads,
            )
        mean_loss = jax.lax.psum(loss, caxes) / K
        # client-varying outputs carry a leading length-1 axis that shard_map
        # concatenates into [K] / [K, m] global views
        stats = {
            "loss": mean_loss,
            "weight": p_k[None],
            "criteria": c[None, :],
        }
        return agg, stats

    out_specs = (
        P(),
        {"loss": P(), "weight": P(caxes), "criteria": P(caxes, None)},
    )

    n_extra = int(with_participation) + int(with_staleness)

    def train_step(params, batch, *extra):
        if len(extra) != n_extra:
            raise TypeError(
                f"step expects {n_extra} extra [K] argument(s) "
                f"(participation={with_participation}, "
                f"staleness={with_staleness}), got {len(extra)}"
            )
        agg, stats = jax.shard_map(
            per_client,
            mesh=mesh,
            in_specs=(P(), _batch_in_specs(batch, caxes),
                      *(P(caxes) for _ in extra)),
            out_specs=out_specs,
            axis_names=set(caxes),
            check_vma=False,
        )(params, batch, *extra)
        return _sgd(params, agg, lr), stats

    return train_step


def make_federated_adjust_step(
    bundle: ModelBundle,
    mesh,
    lr: float = 0.01,
) -> Callable:
    """Algorithm-1 round at scale: every priority permutation's candidate is
    built and validated inside one lowered program.

    ``step(params, batch, val_batch, prev_quality, priority_idx)``
    → ``(params, stats)`` with the accepted permutation index.
    """
    caxes = client_axes(mesh)
    K = num_clients(mesh)
    cfg = bundle.cfg
    perms = all_permutations(len(CRITERIA_NAMES))

    def per_client(params, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda p: bundle.loss(p, batch), has_aux=True
        )(params)
        c = _client_criteria(batch, grads, lr, cfg.vocab_size, caxes)
        cands = []
        for perm in perms:                            # static m! unroll
            s = prioritized_score(c, perm)
            z = jax.lax.psum(s, caxes)
            p_k = s / jnp.maximum(z, 1e-12)
            cands.append(jax.tree.map(
                lambda g: jax.lax.psum(
                    p_k * g.astype(jnp.float32), caxes
                ).astype(g.dtype),
                grads,
            ))
        mean_loss = jax.lax.psum(loss, caxes) / K
        return tuple(cands), mean_loss

    def adjust_step(params, batch, val_batch, prev_quality, priority_idx):
        cands, mean_loss = jax.shard_map(
            per_client,
            mesh=mesh,
            in_specs=(P(), _batch_in_specs(batch, caxes)),
            out_specs=(tuple(P() for _ in perms), P()),
            axis_names=set(caxes),
            check_vma=False,
        )(params, batch)

        qualities = []
        for agg in cands:                             # lines 13–16 per cand.
            vloss, _ = bundle.loss(_sgd(params, agg, lr), val_batch)
            qualities.append(-vloss)                  # higher = better
        qualities = jnp.stack(qualities)

        n = len(perms)
        cur_q = qualities[priority_idx]
        ok = qualities >= prev_quality
        not_cur = jnp.arange(n) != priority_idx
        first_ok = jnp.argmax(jnp.where(ok & not_cur, 1.0, 0.0))
        any_ok = jnp.any(ok & not_cur)
        fallback = jnp.argmax(qualities)
        chosen = jnp.where(cur_q >= prev_quality, priority_idx,
                           jnp.where(any_ok, first_ok, fallback))

        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *cands)
        agg = jax.tree.map(lambda s: s[chosen], stacked)
        return _sgd(params, agg, lr), {
            "loss": mean_loss,
            "quality": qualities[chosen],
            "priority_idx": chosen,
            "backtracked": cur_q < prev_quality,
        }

    return adjust_step
