"""Pluggable client-selection policies (scenario-aware sampling).

The paper's protocol *weights* client updates by multi-criteria scores but
still *selects* participants uniformly at random (FedAvg's C-fraction,
McMahan et al., 2017).  On a heterogeneous fleet that leaves easy wins on
the table: the engine already predicts per-client completion times and
tracks staleness clocks, so *which* clients start a round can itself be a
criteria-driven policy — the selection-side analogue of Prioritized
Multi-Criteria aggregation (Anelli et al., 2020).

This module mirrors :class:`repro.federated.engine.AggregationStrategy`
on the selection side:

* :class:`SelectionContext` — everything a policy may look at when
  drawing a round: the selection PRNG key, the round id, the engine's
  ``last_sync`` staleness clocks, the device fleet, and the strategy's
  in-flight ``avoid`` mask,
* :class:`SelectionPolicy` — the protocol (``select(ctx) -> (sel, dt)``),
* four implementations:

  - :class:`UniformPolicy` — FedAvg's uniform draw; bit-for-bit the
    pre-refactor ``sample_clients_jax`` call (golden-tested),
  - :class:`BiasPolicy` — availability-biased Gumbel top-k (the old
    ``ScenarioConfig.bias_sampling=True`` path, ported),
  - :class:`DeadlineAwarePolicy` — Gumbel top-k over a log-utility that
    prefers devices predicted to finish *before the straggler deadline*
    (low ``slowdown``), pulls in long-unsynced clients (staleness bonus,
    the fairness/coverage term) and can mix in any registered criterion
    computable from fleet state,
  - :class:`OracleCompletionPolicy` — selects on the *true* sampled
    completion times of the round (an upper bound for benchmarks: no
    real server can see the future).

Everything is pure jnp on traced values — policies run inside the
engine's ``jax.lax.scan`` round block and under jit.  ``num_clients`` and
``n`` are Python ints (static under jit); all other context fields are
traced arrays.

Adding a policy: subclass :class:`SelectionPolicy`, implement
``select``, register it in :data:`POLICIES` — the engine, the benchmark
sweep (``benchmarks/roundloop.py``) and the ``[K]`` mask helper
(:func:`round_participation`) pick it up by name.

Corruption blindness (hostile-fleet contract): policies may read the
fleet's *device* profile — ``slowdown``, ``expected_availability()``,
``last_sync`` — but MUST NOT read ``DeviceFleet.corrupt`` or the attack
metadata.  A real server cannot observe which clients are Byzantine, and
the ``byzantine`` preset deliberately plants its attackers in the fastest
tier with perfect availability — exactly the clients a latency-greedy
policy prefers — so any policy that "defends" by peeking at the mask is
cheating and any policy that *learns to prefer* fast attackers is working
as designed: the defense belongs to the aggregation layer
(``TrimmedMeanStrategy`` / ``ClippedDPStrategy`` + the ``update_norm``
criterion).  ``tests/test_robust.py`` pins this down by asserting every
registered policy draws identical rounds with and without the corrupt
mask present.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.criteria import ClientContext, measure_criteria
from repro.federated.sampler import (
    gumbel_top_k,
    sample_clients_jax,
    soft_avoid,
)
from repro.federated.scenarios import (
    COMPLETION_BASE,
    COMPLETION_JITTER,
    DeviceFleet,
    completion_time,
)


@dataclass
class SelectionContext:
    """Everything a policy may inspect when drawing one round.

    * ``key``         selection PRNG key (one fold per round)
    * ``num_clients`` fleet size ``K`` — Python int, static under jit
    * ``n``           round size ``S`` — Python int, static under jit
    * ``rnd``         round id (i32 scalar, traced)
    * ``last_sync``   ``[K]`` i32 round of each client's last committed
                      sync (the engine's staleness clocks)
    * ``fleet``       device profiles, or ``None`` outside scenarios
    * ``avoid``       optional ``[K]`` 0/1 in-flight mask from the
                      aggregation strategy (clients whose updates are
                      still buffered must not start a second local run)
    * ``time_key``    the round's completion-time PRNG key — the same
                      stream the engine uses for ``completion_time``, so
                      an oracle policy can peek at the true ``dt``

    Under ``FedSimConfig(mesh=...)`` policies run replicated on every
    shard: ``last_sync`` (and ``avoid``) are the *all-gathered* full
    ``[K]`` vectors, not this shard's block, so any registered policy
    works on the mesh unchanged — as long as it draws randomness only
    from ``key``/``time_key`` (see ``sampler.py``'s mesh note).
    """

    key: jax.Array
    num_clients: int
    n: int
    rnd: jax.Array
    last_sync: jax.Array
    fleet: Optional[DeviceFleet] = None
    avoid: Optional[jax.Array] = None
    time_key: Optional[jax.Array] = None


def overprovisioned_round_size(base: int, overprovision: float,
                               num_clients: int) -> int:
    """Round size with fault-tolerance headroom: ``ceil(S·(1+o))``.

    Deadline rounds (``FedSimConfig(deadline=..., overprovision=...)``)
    select more clients than the target cohort so that crashed / timed-
    out uploads can be absorbed without starving the quorum — the
    standard production over-provisioning trick (cf. the system design
    in Bonawitz et al., 2019).  Every policy sees the inflated ``n``
    through :class:`SelectionContext`; the result is clamped to the
    fleet size.  Static (a Python int): the wave shape is fixed at
    trace time like every other round dimension.
    """
    import math

    if overprovision < 0:
        raise ValueError(
            f"overprovision must be >= 0, got {overprovision}")
    return min(num_clients, math.ceil(base * (1.0 + overprovision)))


class SelectionPolicy:
    """Protocol: how one round's participants are drawn.

    ``select(ctx)`` returns ``(sel, dt)``:

    * ``sel`` — sorted ``[n]`` int32 client indices,
    * ``dt`` — optional ``[n]`` float32 completion times.  ``None`` for
      every realistic policy (the engine then samples
      ``scenarios.completion_time`` from ``ctx.time_key`` as usual); a
      clairvoyant policy that *selected on* sampled times returns them so
      the virtual clock charges the times it actually saw.
    """

    #: policy cannot run without a scenario fleet (e.g. availability bias).
    requires_fleet: bool = False

    def select(
        self, ctx: SelectionContext
    ) -> Tuple[jax.Array, Optional[jax.Array]]:
        raise NotImplementedError


class UniformPolicy(SelectionPolicy):
    """FedAvg's uniform draw — bit-for-bit the pre-policy engine.

    With no ``avoid`` mask this is a truncated ``jax.random.permutation``
    (exactly the pre-refactor call, golden-tested); with one it is the
    soft-excluding Gumbel draw of ``sample_clients_jax(avoid=...)``.
    """

    def select(self, ctx):
        return sample_clients_jax(ctx.key, ctx.num_clients, ctx.n,
                                  avoid=ctx.avoid), None


class BiasPolicy(SelectionPolicy):
    """Availability-biased sampling (the old ``bias_sampling=True`` path).

    Gumbel top-k with weights ``fleet.expected_availability()`` — clients
    whose duty cycle and network make their uploads likely to arrive are
    preferred.  Requires a scenario fleet.
    """

    requires_fleet = True

    def select(self, ctx):
        if ctx.fleet is None:
            raise ValueError("BiasPolicy needs a scenario fleet "
                             "(FedSimConfig.scenario)")
        w = ctx.fleet.expected_availability()
        return sample_clients_jax(ctx.key, ctx.num_clients, ctx.n, w,
                                  avoid=ctx.avoid), None


@dataclass(frozen=True)
class DeadlineAwarePolicy(SelectionPolicy):
    """Deadline-aware Gumbel top-k over predicted completion time.

    Each client gets a log-utility

    .. code-block:: text

        u_k = - deadline_weight  * log(predicted_dt_k)
              + staleness_weight * log1p(rnd - last_sync_k)
              + criteria_weight  * sum_i log(c_i^k)        (optional)

    and the round is a Gumbel top-k draw over ``u / temperature`` —
    without-replacement sampling ∝ ``exp(u/T)``, so the sync straggler
    barrier ``max_k dt_k`` shrinks (slow tiers are rarely drawn) while the
    staleness bonus keeps pulling long-unselected clients back in, bounding
    the coverage loss of a pure fastest-first rule.  ``predicted_dt_k`` is
    the *deterministic* part of the completion-time model,
    ``base * slowdown_k`` — the server knows device tiers, not the
    per-round jitter (see :class:`OracleCompletionPolicy` for that bound).

    ``criteria`` names any registered criterion computable from fleet
    state — the :class:`~repro.core.criteria.ClientContext` here carries
    ``flops_per_sec`` (``1/slowdown``), ``staleness`` and
    ``availability``, so ``("availability",)`` or
    ``("compute_capability",)`` work out of the box; criteria needing
    data shards do not apply at selection time.

    * ``temperature`` → 0 degenerates to deterministic top-k (pure
      exploitation); large T → uniform.
    * honours ``ctx.avoid`` with the standard backfill contract.
    * with no fleet the deadline term vanishes and the policy becomes
      staleness-weighted sampling — still well defined.
    """

    deadline_weight: float = 1.0
    staleness_weight: float = 0.5
    criteria: Tuple[str, ...] = ()
    criteria_weight: float = 1.0
    temperature: float = 1.0
    base: float = COMPLETION_BASE

    def scores(self, ctx: SelectionContext) -> jax.Array:
        """``[K]`` log-utilities — monotone non-increasing in
        ``predicted_dt`` (property-tested)."""
        K = ctx.num_clients
        if ctx.fleet is not None:
            pred_dt = self.base * ctx.fleet.slowdown
            avail = ctx.fleet.expected_availability()
            flops = 1.0 / ctx.fleet.slowdown
        else:
            pred_dt = jnp.full((K,), self.base, jnp.float32)
            avail = jnp.ones((K,), jnp.float32)
            flops = jnp.ones((K,), jnp.float32)
        stale = jnp.maximum(
            (ctx.rnd - ctx.last_sync).astype(jnp.float32), 0.0)
        u = (-self.deadline_weight * jnp.log(jnp.maximum(pred_dt, 1e-12))
             + self.staleness_weight * jnp.log1p(stale))
        if self.criteria:
            cctx = ClientContext(flops_per_sec=flops, staleness=stale,
                                 availability=avail)
            raw = jax.vmap(
                lambda c: measure_criteria(self.criteria, c))(cctx)
            # raw, not share-normalized: normalization divides each
            # column by a client-independent constant, which is a pure
            # shift after log — invisible to (Gumbel) top-k
            u = u + self.criteria_weight * jnp.sum(
                jnp.log(jnp.maximum(raw, 1e-12)), axis=1)
        return u

    def select(self, ctx):
        u = self.scores(ctx)
        n = min(int(ctx.n), ctx.num_clients)
        if self.temperature <= 0.0:                  # deterministic top-k
            _, idx = jax.lax.top_k(soft_avoid(u, ctx.avoid), n)
            return jnp.sort(idx.astype(jnp.int32)), None
        return gumbel_top_k(ctx.key, u / self.temperature, n,
                            ctx.avoid), None


@dataclass(frozen=True)
class OracleCompletionPolicy(SelectionPolicy):
    """Selects on the round's *true* sampled completion times.

    Draws every client's ``dt`` from ``ctx.time_key`` (the same lognormal
    model as :func:`repro.federated.scenarios.completion_time`, including
    the per-round jitter no real server can observe), deterministically
    keeps the ``n`` fastest eligible clients, and returns their true
    ``dt`` so the virtual clock charges exactly the times selection saw.
    An upper bound on what any deadline-aware policy can achieve — use it
    in benchmarks to separate "better prediction" headroom from "better
    policy" headroom.
    """

    # defaults shared with scenarios.completion_time, so an
    # OracleCompletionPolicy() selects on the same dt distribution the
    # engine charges every other policy with
    base: float = COMPLETION_BASE
    jitter: float = COMPLETION_JITTER

    def select(self, ctx):
        K = ctx.num_clients
        if ctx.fleet is not None:
            dt_all = completion_time(ctx.fleet, jnp.arange(K), ctx.time_key,
                                     self.base, self.jitter)
        else:
            eps = jax.random.normal(ctx.time_key, (K,))
            dt_all = self.base * jnp.exp(self.jitter * eps)
        score = soft_avoid(-jnp.log(jnp.maximum(dt_all, 1e-12)), ctx.avoid)
        _, idx = jax.lax.top_k(score, min(int(ctx.n), K))
        sel = jnp.sort(idx.astype(jnp.int32))
        return sel, dt_all[sel]


POLICIES: Dict[str, object] = {
    "uniform": UniformPolicy,
    "bias": BiasPolicy,
    "deadline": DeadlineAwarePolicy,
    "oracle": OracleCompletionPolicy,
}


def make_policy(name: str, **kwargs) -> SelectionPolicy:
    """Policy factory for configs/CLIs: ``make_policy("deadline",
    staleness_weight=1.0)``."""
    if name not in POLICIES:
        raise KeyError(
            f"unknown selection policy {name!r}; available: "
            f"{sorted(POLICIES)}"
        )
    return POLICIES[name](**kwargs)


def round_participation(
    policy: SelectionPolicy,
    key: jax.Array,
    num_clients: int,
    n: int,
    rnd: jax.Array | int = 0,
    last_sync: Optional[jax.Array] = None,
    fleet: Optional[DeviceFleet] = None,
    avoid: Optional[jax.Array] = None,
    time_key: Optional[jax.Array] = None,
) -> jax.Array:
    """Run ``policy`` and scatter its pick to a ``[K]`` 0/1 mask.

    For a caller that keeps *every* client resident and gates the
    non-participants with a ``[K]`` mask: the same policies that drive
    the round engine produce that mask, from the same context.  Nothing
    in the round engine calls it; its tests pin it to the engine's
    policies.  Pure jnp, jit-safe.
    """
    if last_sync is None:
        last_sync = jnp.zeros((num_clients,), jnp.int32)
    if time_key is None:
        time_key = jax.random.fold_in(key, 1)
    ctx = SelectionContext(
        key=key, num_clients=num_clients, n=n,
        rnd=jnp.asarray(rnd, jnp.int32), last_sync=last_sync,
        fleet=fleet, avoid=avoid, time_key=time_key,
    )
    sel, _ = policy.select(ctx)
    return jnp.zeros((num_clients,), jnp.float32).at[sel].set(1.0)
