"""Round-engine core: one server loop, pluggable aggregation strategies.

The server-side machinery of the round engine
(``federated/simulation.py``): criteria weighting, the commit and the
Algorithm-1 state live here once, so sync, buffered-async and
FedAvg-baseline execution are *policies* over one engine rather than
three copies of it:

* :class:`ServerState` — the scan carry: global params, Algorithm-1
  quality/priority, per-client ``last_sync`` staleness clocks, the async
  update buffer, and the virtual clock,
* :class:`RoundInputs` — everything one round produced on the "client
  side" (locally-trained models, normalized criteria, scenario masks,
  per-client virtual completion times),
* :class:`AggregationStrategy` — the protocol a policy implements, with
  three implementations:

  - :class:`SyncStrategy` — the paper's synchronous round: every
    participant's model is aggregated immediately (optionally through
    Algorithm-1 online priority adjustment).  Bit-for-bit identical to
    the pre-engine round loop on the ``uniform`` preset.
  - :class:`BufferedAsyncStrategy` — FedBuff-style buffered async
    (Nguyen et al., 2022): arrivals accumulate score-weighted *updates*
    in a buffer and the server commits one global step whenever
    ``buffer_size`` arrivals are in.  Staleness (rounds since a client's
    last committed sync) feeds the registered ``staleness`` criterion,
    so stale updates are down-weighted by the same prioritized
    multi-criteria machinery that weights everything else.
  - :class:`FedAvgStrategy` — dataset-size-only weighting (McMahan et
    al., 2017), the paper's baseline, for A/B against either of the
    above.
  - :class:`TrimmedMeanStrategy` — Byzantine-robust sync: coordinate-wise
    weighted trimmed mean over the round's client matrix (one fused
    peel-reduce on the flat path — ``kernels/trimmed.py``), composing
    with the prioritized criteria weights.
  - :class:`KrumStrategy` / :class:`MultiKrumStrategy` — distance-based
    Byzantine-robust sync (Blanchard et al., 2017): nearest-neighbor
    distance scores over the round's client matrix (one Gram-accumulating
    streaming pass on the flat path — ``kernels/krum.py``) select the
    ``m`` most-central clients; catches the colluding within-trim-band
    payloads a coordinate-wise trim absorbs.
  - :class:`ClippedDPStrategy` — DP-FedAvg-style hardening: per-client
    L2 clipping plus calibrated Gaussian noise on the committed mean;
    pairs with the registered ``update_norm`` criterion so oversized
    updates lose weight *before* the clip engages.  The noise knob is a
    real privacy budget: ``federated.privacy`` accounts the subsampled-
    Gaussian RDP of every commit and the simulation reports/enforces the
    spent ``(epsilon, delta)``.

Virtual time: scenario fleets assign each selected client a completion
time ``dt_k`` (``scenarios.completion_time``).  A sync round lasts
``max_k dt_k`` — the server waits for its slowest participant — while an
async tick lasts ``n / sum_k(1/dt_k)`` (``n`` arrivals at the fleet's
aggregate arrival rate): the server never barriers on stragglers.  Both
advance ``ServerState.sim_time``, which is what the round-loop benchmark
compares for time-to-target.

Everything here is pure jnp on traced values — strategies run unchanged
inside ``jax.lax.scan`` round blocks and under jit.

Strategies are *representation-agnostic*: every step is pytree math, so
the carry's ``params``/``buffer`` may be the model pytree (the default,
golden-pinned path) or the flat ``[N]`` vector of the hot path
(``FedSimConfig(flat_params=True)``), in which case ``RoundInputs.stacked``
is the ``[S, N]`` client matrix, ``aggregate_models`` dispatches to one
fused weighted reduction, and the async buffer fold is a single matvec —
no strategy code changes between the two.

Mesh parallelism: under ``FedSimConfig(mesh=...)`` the same strategies
run inside a ``shard_map`` over the mesh's client axes.
``RoundInputs.shard`` carries the static
:class:`~repro.utils.sharding.ShardSpec`; ``stacked`` is then this
shard's ``[S_loc, N]`` wave block and ``ServerState.last_sync`` /
``in_buffer`` are ``[K_loc]`` client blocks, while every O(S) vector
(criteria, weights, masks, dt) stays replicated.  Each strategy's
reduction becomes a shard-local kernel finished by one collective
(:mod:`repro.kernels.collective`); with ``shard=None`` (the default)
every code path below is byte-for-byte the single-device one, which is
what the bit-for-bit golden pins.

Compressed streaming: under ``FedSimConfig(compress="int8"|"int4")`` the
round body hands strategies a quantized wave (``RoundInputs.quant``)
alongside the dequantized reconstruction in ``stacked``.  Linear commits
(sync, fedavg, the async buffer fold) consume the int8 tiles through the
fused dequantize-reduce kernel (:func:`_quant_agg`); the nonlinear
defenses (trimmed mean, clipped-DP) and the Algorithm-1 candidate sweep
dequantize first — they read ``stacked`` unchanged.  The per-client
error-feedback residuals live in ``ServerState.error_fb`` and are
maintained by the simulation round body, not by strategies.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import (
    AggregationConfig,
    adjust_round_vectorized,
    aggregate_models,
    compute_scores,
    compute_weights,
)
from repro.core.criteria import resolve
from repro.kernels import collective as kcoll
from repro.kernels import ops as kops
from repro.utils.pytree import PyTree
from repro.utils.sharding import ShardSpec

# Candidate evaluation (Algorithm-1 lines 13-16): params -> scalar quality.
EvalFn = Callable[[PyTree], jax.Array]


@jax.tree_util.register_pytree_node_class
@dataclass
class ServerState:
    """The engine's scan carry — everything the server remembers.

    Shapes (``K`` = fleet size, fixed at ``init_state``; everything is a
    traced array, nothing here is static under jit):

    * ``params``        — global model ``w_G`` (pytree, or flat ``[N]``
      vector under the flat-vector hot path)
    * ``quality``       — Algorithm-1 previous round quality (f32 scalar)
    * ``priority_idx``  — index into ``all_permutations`` (i32 scalar)
    * ``last_sync``     — ``[K]`` i32, round of each client's last
      committed sync; ``rnd - last_sync[k]`` is client ``k``'s staleness
      and also feeds :class:`~repro.federated.selection.
      DeadlineAwarePolicy`'s fairness bonus
    * ``sim_time``      — virtual clock (f32 scalar, time units — see
      ``benchmarks/README.md``)
    * ``commits``       — global updates committed so far (i32 scalar)

    Buffer fields are ``None`` for strategies that never buffer (sync,
    fedavg); ``None`` children are empty pytree subtrees, so the same
    carry structure threads through ``lax.scan`` for every strategy.

    ``error_fb`` is the compressed-streaming error-feedback carry
    (``FedSimConfig(compress=..., error_feedback=True)``): ``[K, N]``
    f32 — or this shard's ``[K_loc, N]`` client block under a mesh —
    holding each client's quantization residual, re-injected into its
    next participating upload by the simulation round body (strategies
    never touch it; ``replace``-based steps carry it through).  ``None``
    on uncompressed runs, keeping the golden-pinned carry structure.

    ``deadline`` is the deadline-round backoff carry
    (``FedSimConfig(deadline=...)``): the f32 scalar *effective* arrival
    deadline for the next round — reset to the configured base whenever
    a round meets its quorum, multiplied by the backoff factor (capped)
    whenever it does not (:func:`deadline_backoff_step`).  Maintained by
    the simulation round body, replicated under a mesh, serialized with
    the rest of the carry by the checkpoint layer.  ``None`` on runs
    without deadlines, keeping the golden-pinned carry structure.
    """

    params: PyTree
    quality: jax.Array                 # Algorithm-1 previous quality (f32)
    priority_idx: jax.Array            # index into all_permutations (i32)
    last_sync: jax.Array               # [K] round of last committed sync (i32)
    sim_time: jax.Array                # virtual clock (f32, time units)
    commits: jax.Array                 # global updates committed so far (i32)
    buffer: Optional[PyTree] = None    # score-weighted update sum (async)
    buffer_weight: Optional[jax.Array] = None  # sum of buffered scores (f32)
    buffer_count: Optional[jax.Array] = None   # buffered arrivals (i32)
    in_buffer: Optional[jax.Array] = None      # [K] 0/1 pending-arrival mask
    error_fb: Optional[jax.Array] = None       # [K, N] quantization residuals
    deadline: Optional[jax.Array] = None       # effective round deadline (f32)

    def tree_flatten(self):
        children = (self.params, self.quality, self.priority_idx,
                    self.last_sync, self.sim_time, self.commits,
                    self.buffer, self.buffer_weight, self.buffer_count,
                    self.in_buffer, self.error_fb, self.deadline)
        return children, None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@dataclass
class RoundInputs:
    """One round's client-side products, handed to the strategy.

    ``S`` is the round size (static under jit); ``m`` the number of
    criteria in ``AggregationConfig.criteria``.  ``mask`` is binary
    participation (scenario availability x upload survival x in-flight
    eligibility); ``contrib = mask / slowdown`` additionally down-weights
    stragglers and is what aggregation weights see.  An all-zero ``mask``
    round must be (and is, for every built-in strategy) a no-op.
    """

    rnd: jax.Array        # round id (i32 scalar)
    sel: jax.Array        # [S] selected client indices
    stacked: PyTree       # [S, ...] locally-trained client models
    criteria: jax.Array   # [S, m] normalized criteria matrix
    mask: jax.Array       # [S] binary participation
    contrib: jax.Array    # [S] mask / slowdown (straggler down-weighting)
    dt: jax.Array         # [S] virtual completion times (time units)
    #: static sharding context under FedSimConfig(mesh=...): ``stacked``
    #: is then the [S_loc, N] wave block of this shard while sel /
    #: criteria / mask / contrib / dt remain the full replicated [S]
    #: vectors, and ServerState's [K] fields are [K_loc] client blocks.
    shard: Optional[ShardSpec] = None
    #: compressed wave (``FedSimConfig(compress=...)``): the round's
    #: quantized ``(q int8 [S, N], scales f32 [S, nb])`` pair — this
    #: shard's row blocks under a mesh.  When set, ``stacked`` is the
    #: *dequantized reconstruction* ``w_G + deq(q)``: linear commits
    #: (sync/fedavg/async) consume ``quant`` through the fused
    #: dequantize-reduce kernel instead, while the nonlinear defenses
    #: (trimmed mean, clipped-DP) and Algorithm-1 sweep consume the
    #: dequantized ``stacked`` — the server dequantizes *before* those
    #: defenses, so a hostile payload cannot hide behind its scales.
    quant: Optional[Tuple[jax.Array, jax.Array]] = None
    #: static scale-block size of ``quant`` (0 when uncompressed)
    qblock: int = 0


def _scatter_round(last_sync: jax.Array, sel: jax.Array, mask: jax.Array,
                   rnd: jax.Array, gate: jax.Array,
                   shard: Optional[ShardSpec] = None) -> jax.Array:
    """``last_sync[sel] = rnd`` where ``mask`` and ``gate`` hold.

    With ``shard``, ``last_sync`` is this shard's ``[K_loc]`` client
    block while ``sel`` is the full replicated wave: each shard updates
    only the entries it owns.  Non-owned indices clip into valid slots,
    which can collide with owned ones, so the sharded form scatters
    ``max(rnd, ...)`` with a ``-1`` sentinel instead of ``set`` —
    equivalent because ``last_sync`` is monotone non-decreasing, and
    deterministic where duplicate-index ``set`` is not.
    """
    if shard is None:
        upd = jnp.where(gate * mask > 0, rnd, last_sync[sel])
        return last_sync.at[sel].set(upd.astype(last_sync.dtype))
    k_loc = last_sync.shape[0]
    lo = shard.index() * k_loc
    owned = (sel >= lo) & (sel < lo + k_loc)
    idx = jnp.clip(sel - lo, 0, k_loc - 1)
    val = jnp.where(owned & (gate * mask > 0), rnd, -1)
    return last_sync.at[idx].max(val.astype(last_sync.dtype))


def _entropy(p: jax.Array) -> jax.Array:
    return -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-12)))


def deadline_backoff_step(eff_deadline: jax.Array, quorum_met: jax.Array,
                          base: float, factor: float,
                          cap: float) -> jax.Array:
    """Next round's effective arrival deadline (exponential retry backoff).

    A round that meets its quorum resets the deadline to the configured
    ``base``; a quorum failure retries the next round with the deadline
    multiplied by ``factor`` (>= 1), saturating at ``cap`` — the server
    waits longer and longer for a struggling fleet, but never unboundedly.
    Pure jnp on a traced carry scalar, so the backoff state lives in
    ``ServerState.deadline`` and survives scan blocks and checkpoints.
    Property-tested in ``tests/test_faults.py``: monotone non-decreasing
    under consecutive failures, capped at ``max(base, cap)``, reset on
    success.
    """
    backed = jnp.minimum(eff_deadline * factor, cap)
    return jnp.where(quorum_met, base, jnp.maximum(backed, eff_deadline))


def _weighted_agg(stacked: PyTree, p: jax.Array,
                  shard: Optional[ShardSpec]) -> PyTree:
    """``aggregate_models``, shard-aware on the flat path.

    ``p`` is the full globally-normalized ``[S]`` weight vector; under a
    shard the local kernel consumes this shard's row slice of it and one
    psum finishes the reduction.
    """
    if shard is None:
        return aggregate_models(stacked, p)
    return kcoll.flat_weighted_agg_shard(stacked, shard.slice_rows(p), shard)


def _quant_agg(quant: Tuple[jax.Array, jax.Array], p: jax.Array,
               qblock: int, shard: Optional[ShardSpec]) -> jax.Array:
    """``Σ_k p_k · deq(q_k)`` — the fused dequantize-reduce commit.

    ``p`` is the full ``[S]`` weight vector; under a shard the local
    kernel consumes this shard's row slice and one psum over the
    dequantized f32 partials finishes (``kernels.collective``).
    """
    q, scales = quant
    if shard is None:
        return kops.flat_qagg(q, scales, p, block=qblock)
    return kcoll.flat_qagg_shard(q, scales, shard.slice_rows(p),
                                 qblock, shard)


def _model_agg(state_params: jax.Array, inp: "RoundInputs",
               p: jax.Array) -> PyTree:
    """``Σ_k p_k · w_k`` — fused over the quantized wave when present.

    With ``inp.quant``, ``w_k = w_G + deq(q_k)`` by construction, so the
    model aggregate is ``(Σ_k p_k) · w_G + Σ_k p_k · deq(q_k)`` — the
    second term is one :func:`_quant_agg` pass over int8 tiles, and the
    dequantized ``[S, N]`` reconstruction never enters the reduction.
    Without it, this is exactly :func:`_weighted_agg`.
    """
    if inp.quant is None:
        return _weighted_agg(inp.stacked, p, inp.shard)
    return (jnp.sum(p) * state_params
            + _quant_agg(inp.quant, p, inp.qblock, inp.shard))


class AggregationStrategy:
    """Protocol: how a round's client products become a server update."""

    #: criteria (canonical names) this strategy reads from the matrix.
    requires: Tuple[str, ...] = ()
    #: whether Algorithm-1 online adjustment is meaningful under this policy.
    supports_online_adjust: bool = True

    def init_state(self, params: PyTree, num_clients: int,
                   priority_idx: int) -> ServerState:
        return ServerState(
            params=params,
            quality=jnp.asarray(0.0, jnp.float32),
            priority_idx=jnp.asarray(priority_idx, jnp.int32),
            last_sync=jnp.zeros((num_clients,), jnp.int32),
            sim_time=jnp.asarray(0.0, jnp.float32),
            commits=jnp.asarray(0, jnp.int32),
        )

    def avoid_mask(self, state: ServerState) -> Optional[jax.Array]:
        """Optional [K] 0/1 mask of clients to avoid re-selecting
        (``sample_clients_jax(avoid=...)``)."""
        return None

    def step(self, state: ServerState, inp: RoundInputs,
             cfg: AggregationConfig, online_adjust: bool,
             eval_fn: EvalFn) -> Tuple[ServerState, dict]:
        """One engine tick: fold a round's client products into the carry.

        ``cfg`` and ``online_adjust`` are static under jit (they shape
        the traced program); ``state``/``inp`` are traced.  Must be pure
        jnp — it runs inside ``lax.scan``.  Returns the new carry plus a
        per-round metrics dict (``entropy``, ``priority_idx``,
        ``backtracked``, ``num_evaluated``) that the driver stacks per
        scan block.
        """
        raise NotImplementedError


class SyncStrategy(AggregationStrategy):
    """The paper's synchronous round — aggregate every participant now.

    Reproduces the pre-engine round loop bit for bit (regression-tested
    against a recorded pre-refactor trajectory on the ``uniform``
    preset): same weighting, same Algorithm-1 path, same all-dropped
    no-op guard.  The round's virtual duration is the straggler barrier
    ``max_k dt_k`` over participants.
    """

    def step(self, state, inp, cfg, online_adjust, eval_fn):
        params, prev_q, prio_idx = state.params, state.quality, state.priority_idx
        c, contrib = inp.criteria, inp.contrib

        if online_adjust:
            res = adjust_round_vectorized(
                c, inp.stacked, cfg, prio_idx, prev_q,
                eval_fn=eval_fn, mask=contrib, shard=inp.shard,
            )
            new_params, p = res.global_params, res.weights
            new_q = res.quality
            new_prio = res.priority.astype(jnp.int32)
            backtracked = res.backtracked
            n_eval = jnp.asarray(res.num_evaluated, jnp.int32)
        else:
            p = compute_weights(c, cfg, tuple(cfg.priority), mask=contrib)
            new_params = _model_agg(params, inp, p)
            new_q, new_prio = prev_q, prio_idx
            backtracked = jnp.asarray(False)
            n_eval = jnp.asarray(1, jnp.int32)

        # If every selected client dropped out, the round is a no-op:
        # keep the previous global model and adjustment state.
        alive = jnp.sum(contrib) > 0
        new_params = jax.tree.map(
            lambda a, b: jnp.where(alive, a, b), new_params, params
        )
        new_q = jnp.where(alive, new_q, prev_q)
        new_prio = jnp.where(alive, new_prio, prio_idx)
        backtracked = jnp.where(alive, backtracked, False)

        alive_f = alive.astype(jnp.float32)
        barrier = jnp.max(inp.dt * inp.mask)      # server waits for stragglers
        new_state = replace(
            state,
            params=new_params,
            quality=new_q,
            priority_idx=new_prio,
            last_sync=_scatter_round(state.last_sync, inp.sel, inp.mask,
                                     inp.rnd, alive_f, inp.shard),
            sim_time=state.sim_time + jnp.where(alive, barrier, 1.0),
            commits=state.commits + alive.astype(jnp.int32),
        )
        ys = {
            "entropy": _entropy(p),
            "priority_idx": new_prio,
            "backtracked": backtracked,
            "num_evaluated": n_eval,
        }
        return new_state, ys


class FedAvgStrategy(AggregationStrategy):
    """Dataset-size-only weighting — the FedAvg baseline, for A/B runs.

    Slices the ``dataset_size`` column out of whatever criteria matrix
    the round measured, so a multi-criteria config can be A/B'd against
    its own Ds-only shadow without re-measuring anything.
    """

    requires = ("dataset_size",)
    supports_online_adjust = False

    _DS_CFG = AggregationConfig(criteria=("Ds",), priority=(0,))

    def step(self, state, inp, cfg, online_adjust, eval_fn):
        names = tuple(resolve(n) for n in cfg.criteria)
        ds = names.index("dataset_size")
        p = compute_weights(inp.criteria[:, ds:ds + 1], self._DS_CFG, (0,),
                            mask=inp.contrib)
        new_params = _model_agg(state.params, inp, p)

        alive = jnp.sum(inp.contrib) > 0
        new_params = jax.tree.map(
            lambda a, b: jnp.where(alive, a, b), new_params, state.params
        )
        barrier = jnp.max(inp.dt * inp.mask)
        new_state = replace(
            state,
            params=new_params,
            last_sync=_scatter_round(state.last_sync, inp.sel, inp.mask,
                                     inp.rnd, alive.astype(jnp.float32),
                                     inp.shard),
            sim_time=state.sim_time + jnp.where(alive, barrier, 1.0),
            commits=state.commits + alive.astype(jnp.int32),
        )
        ys = {
            "entropy": _entropy(p),
            "priority_idx": state.priority_idx,
            "backtracked": jnp.asarray(False),
            "num_evaluated": jnp.asarray(1, jnp.int32),
        }
        return new_state, ys


@dataclass(frozen=True)
class BufferedAsyncStrategy(AggregationStrategy):
    """FedBuff-style buffered asynchronous aggregation.

    Each engine tick is an *arrival wave*: the selected clients train
    from the current committed model and their updates ``w_k - w_G``
    enter the buffer weighted by their multi-criteria scores.  Every
    arrival buys one buffer "slot" — a wave of ``n`` participants buffers
    total weight ``n``, split across its arrivals in proportion to their
    scores — so criteria decide relative weight *within* a wave while
    sparse and full waves contribute in proportion to their arrivals.
    When ``buffer_size`` arrivals have accumulated — possibly
    across several waves — the server commits one global step, the
    weighted mean of everything buffered, scaled by ``server_lr``:

        w_G <- w_G + server_lr * (sum_k w_k' (w_k - w_G)) / (sum_k w_k')

    Staleness: ``last_sync[k]`` records the round whose commit last
    absorbed client ``k``; a new arrival carries ``rnd - last_sync[k]``,
    which the round measures through the registered ``staleness``
    criterion (``1 / (1 + s)``).  Put ``"staleness"`` in the
    ``AggregationConfig.criteria`` tuple (e.g. first in the priority
    order) and stale updates are attenuated by exactly the machinery the
    paper uses for Ds/Ld/Md — no special-cased staleness discount.

    In-flight clients (buffered, not yet committed) are excluded from
    re-selection through :meth:`avoid_mask` — a device still uploading
    does not start a second local run.

    A wave's virtual duration is ``n / sum(1/dt_k)`` over its ``n``
    participants: arrivals stream in at the fleet's aggregate rate, so
    (unlike the sync barrier ``max dt_k``) one 4x straggler costs 4x
    *its own* slot, not 4x everyone's round.

    Algorithm-1 online adjustment is a synchronous-quality feedback loop
    and is not supported here.
    """

    buffer_size: int = 8
    server_lr: float = 1.0

    supports_online_adjust = False

    def init_state(self, params, num_clients, priority_idx):
        base = super().init_state(params, num_clients, priority_idx)
        return replace(
            base,
            buffer=jax.tree.map(jnp.zeros_like, params),
            buffer_weight=jnp.asarray(0.0, jnp.float32),
            buffer_count=jnp.asarray(0, jnp.int32),
            in_buffer=jnp.zeros((num_clients,), jnp.float32),
        )

    def avoid_mask(self, state):
        # soft-exclude in-flight clients from the next wave's sample
        return state.in_buffer

    def step(self, state, inp, cfg, online_adjust, eval_fn):
        n_part = jnp.sum(inp.mask)
        # Criteria columns are *shares* normalized within the wave (a lone
        # survivor of a sparse wave scores ~1.0 where a full wave's clients
        # score ~1/n), so raw scores are not comparable across the waves a
        # commit may span.  Each arrival therefore buys one "slot": a wave
        # buffers total weight n_part, split across its arrivals by their
        # multi-criteria scores — criteria (incl. staleness) set relative
        # weight within the wave, arrival counts set it across waves.
        s = compute_scores(inp.criteria, cfg, tuple(cfg.priority)) * inp.contrib
        p_wave = s / jnp.maximum(jnp.sum(s), 1e-12)
        wave_w = p_wave * n_part
        if inp.quant is not None:
            # compressed wave: the buffered deltas *are* the dequantized
            # uploads (stacked = w_G + deq(q)), so the wave fold is one
            # fused dequantize-reduce over the int8 tiles — shard-local
            # with a psum over the dequantized f32 partials under a mesh.
            buffer = state.buffer + _quant_agg(inp.quant, wave_w,
                                               inp.qblock, inp.shard)
        elif inp.shard is None:
            delta = jax.tree.map(
                lambda w, g: w - g[None], inp.stacked, state.params
            )
            buffer = jax.tree.map(
                lambda b, d: b + jnp.tensordot(wave_w, d, axes=(0, 0)),
                state.buffer, delta,
            )
        else:
            # cross-shard buffer fold: each shard folds its own wave rows
            # (delta is the [S_loc, N] block), one psum merges the partial
            # sums, and the replicated buffer absorbs the full wave — the
            # commit below then needs no further collective.
            delta = jax.tree.map(
                lambda w, g: w - g[None], inp.stacked, state.params
            )
            wave_loc = inp.shard.slice_rows(wave_w)
            buffer = state.buffer + inp.shard.psum(
                jnp.tensordot(wave_loc, delta, axes=(0, 0))
            )
        buffer_weight = state.buffer_weight + jnp.sum(wave_w)
        buffer_count = state.buffer_count + jnp.sum(inp.mask).astype(jnp.int32)
        if inp.shard is None:
            in_buffer = state.in_buffer.at[inp.sel].max(inp.mask)
        else:
            # [K_loc] block: mark only owned arrivals; clipped non-owned
            # indices write 0, which max() ignores.
            k_loc = state.in_buffer.shape[0]
            lo = inp.shard.index() * k_loc
            owned = ((inp.sel >= lo) & (inp.sel < lo + k_loc))
            idx = jnp.clip(inp.sel - lo, 0, k_loc - 1)
            in_buffer = state.in_buffer.at[idx].max(
                inp.mask * owned.astype(inp.mask.dtype)
            )

        commit = buffer_count >= self.buffer_size
        scale = jnp.where(
            commit, self.server_lr / jnp.maximum(buffer_weight, 1e-12), 0.0
        )
        new_params = jax.tree.map(
            lambda p, b: p + scale * b, state.params, buffer
        )

        keep = 1.0 - commit.astype(jnp.float32)
        last_sync = jnp.where(
            commit & (in_buffer > 0), inp.rnd, state.last_sync
        ).astype(jnp.int32)

        rate = jnp.sum(inp.mask / jnp.maximum(inp.dt, 1e-6))
        wave_time = jnp.where(n_part > 0, n_part / jnp.maximum(rate, 1e-12),
                              1.0)

        new_state = replace(
            state,
            params=new_params,
            last_sync=last_sync,
            sim_time=state.sim_time + wave_time,
            commits=state.commits + commit.astype(jnp.int32),
            buffer=jax.tree.map(lambda b: b * keep, buffer),
            buffer_weight=buffer_weight * keep,
            buffer_count=buffer_count * keep.astype(jnp.int32),
            in_buffer=in_buffer * keep,
        )
        ys = {
            "entropy": _entropy(p_wave),
            "priority_idx": state.priority_idx,
            "backtracked": jnp.asarray(False),
            "num_evaluated": jnp.asarray(1, jnp.int32),
        }
        return new_state, ys


def _is_flat(stacked: PyTree) -> bool:
    """Flat-path detection, mirroring ``aggregate_models``'s contract:
    a bare 2-D array is the ``[S, N]`` client matrix, anything else a
    stacked pytree."""
    return isinstance(stacked, jax.Array) and stacked.ndim == 2


@dataclass(frozen=True)
class TrimmedMeanStrategy(AggregationStrategy):
    """Byzantine-robust sync: coordinate-wise weighted trimmed mean.

    Per coordinate of the round's ``[S, N]`` client matrix, the ``trim``
    largest and ``trim`` smallest values are discarded and the survivors
    combined by their (renormalized) prioritized multi-criteria weights —
    so the defense composes with Ds/Ld/Md weighting instead of replacing
    it.  Classical breakdown property: up to ``trim`` arbitrarily-corrupt
    clients per coordinate cannot move the commit outside the honest
    value range (property-tested in ``tests/test_robust.py``).

    Notes on masks: a dropped client keeps weight 0 (it cannot pull the
    mean) but its honest-looking local model still occupies a value slot
    and may absorb part of the trim budget; size ``trim`` for the
    round cohort ``S``, not the fleet.  Needs ``2 * trim < S``.

    The reduction runs as one fused peel-reduce Pallas kernel on the flat
    path (``kernels.ops.flat_trimmed_agg``) and per-leaf on the pytree
    path — both share exact tie rules, and the two representations match
    to the flat-vs-pytree gate's tolerance.

    Algorithm-1 online adjustment is a sync-quality feedback loop over
    *linear* candidate sweeps and does not compose with a non-linear
    robust reduction; not supported.
    """

    trim: int = 1

    supports_online_adjust = False

    def step(self, state, inp, cfg, online_adjust, eval_fn):
        S = int(inp.mask.shape[0])
        if not 0 <= 2 * self.trim < S:
            raise ValueError(
                f"TrimmedMeanStrategy needs 0 <= 2*trim < round size; "
                f"got trim={self.trim} for S={S}"
            )
        p = compute_weights(inp.criteria, cfg, tuple(cfg.priority),
                            mask=inp.contrib)
        if inp.shard is not None:
            new_params = kcoll.flat_trimmed_agg_shard(
                inp.stacked, p, self.trim, inp.shard
            )
        elif _is_flat(inp.stacked):
            new_params = kops.flat_trimmed_agg(inp.stacked, p, self.trim)
        else:
            new_params = kops.tree_trimmed_agg(inp.stacked, p, self.trim)

        alive = jnp.sum(inp.contrib) > 0
        new_params = jax.tree.map(
            lambda a, b: jnp.where(alive, a, b), new_params, state.params
        )
        barrier = jnp.max(inp.dt * inp.mask)
        new_state = replace(
            state,
            params=new_params,
            last_sync=_scatter_round(state.last_sync, inp.sel, inp.mask,
                                     inp.rnd, alive.astype(jnp.float32),
                                     inp.shard),
            sim_time=state.sim_time + jnp.where(alive, barrier, 1.0),
            commits=state.commits + alive.astype(jnp.int32),
        )
        ys = {
            "entropy": _entropy(p),
            "priority_idx": state.priority_idx,
            "backtracked": jnp.asarray(False),
            "num_evaluated": jnp.asarray(1, jnp.int32),
        }
        return new_state, ys


@dataclass(frozen=True)
class KrumStrategy(AggregationStrategy):
    """Distance-based Byzantine-robust sync: Krum / multi-Krum selection.

    Blanchard et al. (2017): score every client by the summed squared
    distances to its ``S - f - 2`` nearest cohort neighbors and commit
    the weighted mean of the ``m`` best-scored clients' models (``m = 1``
    is plain Krum; this class defaults to it, the ``multi-krum`` registry
    entry to ``m = S - f - 2``).  Where the coordinate-wise trimmed mean
    absorbs a *small per-coordinate bias* from colluders hiding inside
    the trim band (the ALIE failure mode), Krum is coordinate-blind: a
    colluding cohort shifted ``z`` standard deviations from the honest
    mean pays that offset in every pairwise distance and scores worse
    than the honest cluster, so the commit simply excludes it.

    Breakdown point: the scoring is sound for ``f < (S - 2) / 2``
    corrupt clients in the round cohort (the neighbor count must exceed
    the corrupt count so every honest score is anchored by honest
    neighbors).  ``f = None`` defaults to the largest admissible bound
    ``(S - 3) // 2``; the constructor cannot check ``S``, so the bound
    is validated at trace time in :meth:`step` and property-tested in
    ``tests/test_robust.py``.

    Selected clients are averaged by their renormalized prioritized
    multi-criteria weights, so device-awareness composes with the
    defense exactly as it does for the trimmed mean.  Dropped uploads
    (zero contribution) score ``+inf`` and are never selected, but their
    honest-trained vectors still serve as neighbors; a fully starved
    selection aggregates to the zero vector by the kernel's guard
    contract, and this strategy's alive guard (``sum(contrib) > 0``)
    keeps the previous params in that case.  The pairwise
    distances run as one Gram-accumulating streaming pass on the flat
    path (``kernels/krum.py``), as summed per-leaf distances feeding a
    single shared selection on the pytree path, and as shard-local
    ``X_loc @ X.T`` strips finished by ``all_gather``/``psum`` under a
    mesh — all three pick identical client sets.

    Algorithm-1 online adjustment is a linear-sweep feedback loop and
    does not compose with a selection-based reduction; not supported.
    """

    f: Optional[int] = None
    m: int = 1

    supports_online_adjust = False

    def _resolve(self, S: int) -> Tuple[int, int]:
        f = self.f if self.f is not None else max(0, (S - 3) // 2)
        if not (0 <= f and 2 * f + 2 < S):
            raise ValueError(
                f"KrumStrategy needs f < (S - 2) / 2; got f={f} for S={S}"
            )
        m = self.m if self.m is not None else max(1, S - f - 2)
        if not 1 <= m <= S - f - 2:
            raise ValueError(
                f"KrumStrategy needs 1 <= m <= S - f - 2; got m={m} "
                f"for S={S}, f={f}"
            )
        return f, m

    def step(self, state, inp, cfg, online_adjust, eval_fn):
        S = int(inp.mask.shape[0])
        f, m = self._resolve(S)
        p = compute_weights(inp.criteria, cfg, tuple(cfg.priority),
                            mask=inp.contrib)
        if inp.shard is not None:
            new_params, _ = kcoll.flat_krum_agg_shard(
                inp.stacked, p, f, m, inp.shard
            )
        elif _is_flat(inp.stacked):
            new_params, _ = kops.flat_krum_agg(inp.stacked, p, f, m)
        else:
            new_params, _ = kops.tree_krum_agg(inp.stacked, p, f, m)

        alive = jnp.sum(inp.contrib) > 0
        new_params = jax.tree.map(
            lambda a, b: jnp.where(alive, a, b), new_params, state.params
        )
        barrier = jnp.max(inp.dt * inp.mask)
        new_state = replace(
            state,
            params=new_params,
            last_sync=_scatter_round(state.last_sync, inp.sel, inp.mask,
                                     inp.rnd, alive.astype(jnp.float32),
                                     inp.shard),
            sim_time=state.sim_time + jnp.where(alive, barrier, 1.0),
            commits=state.commits + alive.astype(jnp.int32),
        )
        ys = {
            "entropy": _entropy(p),
            "priority_idx": state.priority_idx,
            "backtracked": jnp.asarray(False),
            "num_evaluated": jnp.asarray(1, jnp.int32),
        }
        return new_state, ys


@dataclass(frozen=True)
class MultiKrumStrategy(KrumStrategy):
    """Multi-Krum: ``m = None`` resolves to ``S - f - 2`` at trace time —
    average every client whose score the Krum criterion trusts, instead
    of committing a single model.  Registered as ``"multi-krum"``."""

    m: Optional[int] = None


@dataclass(frozen=True)
class ClippedDPStrategy(AggregationStrategy):
    """Per-client L2 clip + calibrated Gaussian noise (DP-FedAvg style).

    Each participant's update ``delta_k = w_k - w_G`` is clipped to at
    most ``clip_norm`` in L2, the clipped updates are averaged with the
    prioritized multi-criteria weights, and (for ``noise_multiplier > 0``)
    isotropic Gaussian noise is added to the committed mean:

        w_G <- w_G + sum_k p_k c_k delta_k + sigma * N(0, I),
        c_k = min(1, clip_norm / ||delta_k||),
        sigma = noise_multiplier * clip_norm / max(n_participants, 1)

    — the standard calibration for a mean of ``n`` contributions each of
    sensitivity ``clip_norm / n`` (McMahan et al., 2018), where ``n``
    counts the clients that actually contributed this round (the same
    set the weights normalize over).  With ``noise_multiplier = 0`` this
    is pure robust clipping: the commit's step is norm-bounded by
    ``clip_norm`` regardless of what any client sends, which already
    defuses magnitude attacks (scaled/sign-flip payloads get truncated
    to the same length as honest updates).

    ``uniform_weights=True`` replaces the prioritized criteria weights
    with the uniform mean over contributors (``p_k = 1 / n``).  This is
    the *DP-safe* mode and a precondition of accounting
    (``FedSimConfig(dp_delta=...)`` refuses a non-uniform strategy): the
    criteria weights are computed from un-noised client statistics such
    as ``update_norm``, so a weighted commit both gives some client a
    coefficient ``p_k > 1 / n`` (sensitivity above what the accountant
    charges) and leaks client data through the weights themselves.  The
    reported weights entropy is likewise the uniform one in this mode —
    metrics are released alongside the model and must not carry the
    un-noised criteria either.

    Noise is deterministic per ``(noise_seed, round)`` — drawn from
    ``fold_in(key(noise_seed), rnd)`` as one flat ``[N]`` vector that the
    pytree path slices per leaf in ravel order, so the flat and pytree
    representations see *bit-identical* noise and stay equivalent under
    the flat-vs-pytree gate.

    Declares ``requires = ("update_norm",)``: configs must measure the
    norm criterion, closing the feedback loop — the operator down-weights
    the very clients whose updates keep hitting the clip.
    """

    clip_norm: float = 1.0
    noise_multiplier: float = 0.0
    noise_seed: int = 0
    uniform_weights: bool = False

    requires = ("update_norm",)
    supports_online_adjust = False

    def step(self, state, inp, cfg, online_adjust, eval_fn):
        params = state.params
        contributors = (inp.contrib > 0).astype(jnp.float32)
        n_contrib = jnp.sum(contributors)
        if self.uniform_weights:
            p = contributors / jnp.maximum(n_contrib, 1.0)
        else:
            p = compute_weights(inp.criteria, cfg, tuple(cfg.priority),
                                mask=inp.contrib)
        if inp.shard is not None:
            num_params = int(inp.stacked.shape[1])
            sq = kcoll.flat_divergence_sq_shard(inp.stacked, params,
                                                inp.shard)
        elif _is_flat(inp.stacked):
            num_params = int(inp.stacked.shape[1])
            sq = kops.flat_divergence_sq(inp.stacked, params)
        else:
            leaves = jax.tree.leaves(inp.stacked)
            g_leaves = jax.tree.leaves(params)
            num_params = sum(int(g.size) for g in g_leaves)
            S = leaves[0].shape[0]
            sq = jnp.zeros((S,), jnp.float32)
            for x, g in zip(leaves, g_leaves):
                d = x.astype(jnp.float32) - g.astype(jnp.float32)[None]
                sq = sq + jnp.sum(d.reshape(S, -1) ** 2, axis=1)
        clip = jnp.minimum(
            1.0, self.clip_norm / jnp.maximum(jnp.sqrt(sq), 1e-12)
        )
        q = p * clip                     # combined coefficient on deltas
        q_sum = jnp.sum(q)
        if inp.shard is not None:
            step_vec = kcoll.flat_weighted_agg_shard(
                inp.stacked, inp.shard.slice_rows(q), inp.shard
            ) - q_sum * params
            new_params = params + step_vec
        elif _is_flat(inp.stacked):
            step_vec = kops.flat_weighted_agg(inp.stacked, q) - q_sum * params
            new_params = params + step_vec
        else:
            new_params = jax.tree.map(
                lambda s, g: g + jnp.tensordot(q, s, axes=(0, 0)) - q_sum * g,
                inp.stacked, params,
            )
        if self.noise_multiplier > 0.0:
            # calibrate against the contributing count — the denominator
            # of the committed mean — not the raw participation mask
            sigma = self.noise_multiplier * self.clip_norm \
                / jnp.maximum(n_contrib, 1.0)
            nkey = jax.random.fold_in(
                jax.random.key(self.noise_seed), inp.rnd
            )
            z = jax.random.normal(nkey, (num_params,), jnp.float32)
            if _is_flat(inp.stacked):
                new_params = new_params + sigma * z
            else:
                g_leaves, treedef = jax.tree.flatten(new_params)
                noisy, off = [], 0
                for g in g_leaves:
                    zl = z[off:off + g.size].reshape(g.shape)
                    noisy.append(g + (sigma * zl).astype(g.dtype))
                    off += int(g.size)
                new_params = jax.tree.unflatten(treedef, noisy)

        # all-dropped guard also suppresses the noise: a no-op round must
        # not random-walk the global model
        alive = jnp.sum(inp.contrib) > 0
        new_params = jax.tree.map(
            lambda a, b: jnp.where(alive, a, b), new_params, params
        )
        barrier = jnp.max(inp.dt * inp.mask)
        new_state = replace(
            state,
            params=new_params,
            last_sync=_scatter_round(state.last_sync, inp.sel, inp.mask,
                                     inp.rnd, alive.astype(jnp.float32),
                                     inp.shard),
            sim_time=state.sim_time + jnp.where(alive, barrier, 1.0),
            commits=state.commits + alive.astype(jnp.int32),
        )
        ys = {
            "entropy": _entropy(p),
            "priority_idx": state.priority_idx,
            "backtracked": jnp.asarray(False),
            "num_evaluated": jnp.asarray(1, jnp.int32),
        }
        return new_state, ys


STRATEGIES = {
    "sync": SyncStrategy,
    "buffered-async": BufferedAsyncStrategy,
    "fedavg": FedAvgStrategy,
    "trimmed-mean": TrimmedMeanStrategy,
    "krum": KrumStrategy,
    "multi-krum": MultiKrumStrategy,
    "clipped-dp": ClippedDPStrategy,
}


def make_strategy(name: str, **kwargs) -> AggregationStrategy:
    """Strategy factory for configs/CLIs: ``make_strategy("buffered-async",
    buffer_size=16)``."""
    if name not in STRATEGIES:
        raise KeyError(
            f"unknown aggregation strategy {name!r}; available: "
            f"{sorted(STRATEGIES)}"
        )
    return STRATEGIES[name](**kwargs)
