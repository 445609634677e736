"""Paper-faithful federated simulation (FedAvg + device-aware extension).

Implements the experimental protocol of §3 end-to-end on one host:

* a server holding the global model ``w_G``,
* per-round client selection through a pluggable
  :class:`~repro.federated.selection.SelectionPolicy` — the paper's
  uniform draw (fraction 0.1) by default; availability-biased,
  deadline-aware Gumbel top-k and oracle policies are available and run
  inside the same jitted round step,
* per-client local SGD (batch 10, 5 local epochs, lr 0.01) — run for *all*
  selected clients at once via ``vmap(lax.scan(...))``,
* criteria measurement through the ``core.criteria`` registry (Ds / Ld /
  Md and any registered extension criterion, normalized across the
  round's participants),
* aggregation through a pluggable :class:`~repro.federated.engine.
  AggregationStrategy` — synchronous rounds (the paper's protocol,
  optionally with Algorithm-1 online priority adjustment), FedBuff-style
  buffered async with staleness-aware weighting, or the Ds-only FedAvg
  baseline — all driven by the same round block,
* device-heterogeneity scenarios (``repro.federated.scenarios``): per-round
  participation masks exclude dropped/unavailable clients, stragglers are
  down-weighted, and per-client completion times advance the engine's
  virtual clock (sync rounds barrier on the slowest participant; async
  waves do not),
* LEAF-style evaluation: each eval point the global model is tested on
  every client's local test set; we track the fraction of devices above
  the target accuracy and the size-weighted global accuracy.

The round loop is **on-device**: all randomness comes from ``jax.random``
keys folded per round, client sampling and batch-plan construction happen
inside the jitted round step, and ``eval_every`` consecutive rounds are
driven by one ``jax.lax.scan`` so a whole block lowers to a single XLA
program (eval/metrics hoisted to block boundaries).  ``use_scan=False``
falls back to a host-driven per-round loop (same round body, same
trajectory) — kept for A/B benchmarking of the dispatch overhead.

Two server-side representations share that round body:

* the default **pytree path** — per-leaf math, bit-for-bit pinned by the
  recorded goldens,
* the **flat-vector hot path** (``FedSimConfig(flat_params=True)``) —
  client results are raveled to one ``[S, N]`` matrix at the
  ``local_train`` boundary and the carry holds flat ``[N]`` vectors, so
  criteria (streaming divergence), aggregation (one fused weighted
  reduction), the async buffer (one axpy) and the Algorithm-1 candidate
  sweep (one ``[m!, S] @ [S, N]`` matmul) are single streaming passes
  dispatched through ``repro.kernels.ops`` (Pallas on TPU, BLAS on CPU).
  The ``hotpath`` section of ``BENCH_roundloop.json`` tracks the win.

``FedSimConfig(mesh=...)`` shards the flat path over the mesh's client
axes (``launch.mesh.client_axes``): the round block runs inside one
``shard_map``, each shard trains only its ``[S_loc, N]`` block of the
wave and owns a ``[K_loc]`` block of the staleness clocks / async
arrival mask and a ``[K_loc, C]`` block of the label table, and every
strategy finishes its reduction with one collective
(``repro.kernels.collective``).  Selection, participation and criteria
normalization are O(S)/O(K)-vector work and run *replicated* from the
same PRNG keys, so the sharded trajectory matches the single-device
flat path to matvec reduction order (rtol 1e-5, gated in
``tests/test_flatpath.py``).  See ``docs/ARCHITECTURE.md`` for the
full placement table.

The engine is model-agnostic: it takes ``loss_fn(params, x, y)`` and
``acc_fn(params, x, y, mask)`` plus initial params.  A model whose
clients train only part of it (adapters over a frozen base) hands the
rest over as ``shared``: an argument of every compiled round block and
evaluation, never a constant compiled into them, that both functions
then take as ``shared=``.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AggregationConfig
from repro.core.criteria import (
    ClientContext,
    criterion_needs,
    measure_criteria,
    normalize_criteria,
    resolve,
)
from repro.core.operators import all_permutations
from repro.data.pipeline import device_batch_plans
from repro.data.synthetic import FederatedDataset
from repro.federated.engine import (
    AggregationStrategy,
    RoundInputs,
    ServerState,
    SyncStrategy,
    deadline_backoff_step,
)
from repro.federated.sampler import num_selected
from repro.federated.scenarios import (
    DeviceFleet,
    ScenarioConfig,
    completion_time,
    make_fleet,
    participation,
)
from repro.federated.selection import (
    BiasPolicy,
    SelectionContext,
    SelectionPolicy,
    UniformPolicy,
    overprovisioned_round_size,
)
from repro.kernels import collective as kcoll
from repro.kernels import ops as kops
from repro.kernels import quantize as kquant
from repro.launch.mesh import client_sharding
from repro.optim.optimizers import sgd
from repro.utils import spans
from repro.utils.pytree import FlatSpec, PyTree
from repro.utils.sharding import ShardSpec


def _shared_kw(shared) -> dict:
    """The keyword ``loss_fn``, ``acc_fn`` and ``_eval_global`` take
    shared weights by: none where the model has none, so such a model
    (or an override written without them) is called as before."""
    return {} if shared is None else {"shared": shared}


@dataclass
class FedSimConfig:
    """Simulation hyper-parameters.  Every field is static under jit —
    changing any of them recompiles the round block.

    ``selection=None`` resolves to :class:`UniformPolicy` (the paper's
    uniform draw), or :class:`BiasPolicy` when the scenario sets the
    legacy ``bias_sampling=True`` flag; ``strategy=None`` resolves to
    :class:`SyncStrategy` (the paper's synchronous round).

    ``flat_params=True`` selects the flat-vector server hot path: the
    engine carry holds the global model as one ``[N]`` f32 vector and a
    round's client results as one ``[S, N]`` matrix, so criteria,
    aggregation, the async buffer and the Algorithm-1 candidate sweep run
    as fused streaming passes (kernel-dispatched — see
    ``docs/ARCHITECTURE.md``).  Numerically equivalent to the default
    pytree path within float tolerance (regression-tested), but not bit
    for bit — reduction orders differ — so the golden-pinned default
    stays ``False``.

    ``donate=True`` donates the :class:`ServerState` carry to each block
    dispatch, letting XLA reuse the params/buffer storage instead of
    copying it per call.

    ``mesh`` (a ``jax.sharding.Mesh``, e.g. from
    ``launch.mesh.make_host_mesh``) runs the
    round block sharded over the mesh's client axes — requires
    ``flat_params=True`` and ``use_scan=True``, and both the fleet size
    ``K`` and the round size ``S`` must be divisible by the product of
    the client-axis sizes.  ``mesh=None`` (default) is the plain
    single-device program.

    ``dp_delta``/``dp_epsilon`` turn the :class:`ClippedDPStrategy` noise
    knob into a real privacy budget: with ``dp_delta`` set (and a noised
    clipped-DP strategy configured) every eval point reports the spent
    ``(epsilon, dp_delta)`` of the run so far — fixed-size-WOR
    subsampled-Gaussian RDP composed over the commits actually made, for
    both the sync and the buffered-async commit schedules
    (``federated.privacy``).  Accounting demands a DP-safe
    configuration: ``ClippedDPStrategy(uniform_weights=True)`` (the
    uniform mean over contributors — criteria-derived weights break the
    sensitivity bound and leak) and uniform client selection (the
    amplification theorem does not cover weighted policies); anything
    else raises at construction.  Setting ``dp_epsilon`` additionally
    makes the budget *enforced*: the affordable commit count is
    precomputed from the monotone accountant, each scan block is capped
    at the commits still affordable, and the run stops — flagged
    ``budget_exhausted`` — *before* a commit would spend past the
    target, so the final model never contains over-budget noised state.

    ``compress`` turns on compressed update streaming (flat path only):
    each client's flat update is quantized to int8/int4 with per-block
    absmax scales (``kernels.quantize``, block size ``quant_block`` —
    the kernel streaming tile) *inside* the vmapped ``local_train``
    boundary, and linear commits consume the quantized wave through the
    fused dequantize-reduce kernel.  ``error_feedback=True`` carries
    per-client quantization residuals (``ServerState.error_fb``,
    ``[K, N]`` f32 — a ``[K_loc, N]`` client block under a mesh) that
    are re-injected into each client's next participating upload — the
    standard EF trick that stops quantization bias accumulating across
    rounds.  ``compress="none"`` (default) traces the exact golden
    program: no quantization code enters the round step.

    ``deadline`` turns on fault-tolerant deadline rounds: the server
    over-provisions the cohort (``ceil(S·(1+overprovision))`` clients
    selected, clamped to the fleet), waits ``deadline`` simulated-time
    units, and commits the partial wave of on-time arrivals — uploads
    whose sampled ``completion_time`` exceeds the effective deadline are
    dropped and the prioritized-criteria weights renormalize over the
    survivors (an all-timed-out round is a no-op, mirroring the
    all-dropped contract).  When fewer than ``ceil(quorum·S)`` arrivals
    make it (``S`` the *base* cohort, pre-over-provisioning), the round
    is abandoned and the *effective* deadline — carried in
    ``ServerState.deadline`` — backs off by ``deadline_backoff``×
    (capped at ``deadline_cap``, default ``8·deadline``), resetting to
    the base once a quorum lands.  The virtual clock charges
    ``min(deadline, max arrival dt)`` per committed round (and the full
    effective deadline for an abandoned one) instead of the unbounded
    straggler barrier.  ``deadline=None`` (default) traces the exact
    golden program.  Incompatible with DP accounting: deadline drops
    make the committed cohort data-dependent, voiding the
    fixed-size-WOR subsampling bound.

    ``eval_block`` evaluates the packed test rows that many at a time
    (``lax.map``), so that a model whose rows are long sequences never
    holds every row's activations at once; ``None`` runs every row in
    one batch.

    ``checkpoint_every``/``checkpoint_dir`` write crash-recovery
    checkpoints of the full engine carry (plus run metadata: metrics
    history, targets hit, DP-accountant parameters) at scan-block
    boundaries — ``checkpoint_every`` must be a multiple of
    ``eval_every``.  Because all round randomness folds from per-round
    keys, ``run(resume_from=...)`` reproduces the uninterrupted
    trajectory bit for bit (gated in ``tests/test_checkpoint.py``).
    """

    fraction: float = 0.1          # paper: 10% of clients per round
    batch_size: int = 10           # paper: B = 10
    local_epochs: int = 5          # paper: E = 5
    lr: float = 0.01               # paper: eta = 0.01
    max_rounds: int = 1000         # paper cap
    aggregation: AggregationConfig = field(default_factory=AggregationConfig)
    online_adjust: bool = False    # study C switch
    eval_every: int = 1            # also the lax.scan round-block size
    seed: int = 0
    scenario: Optional[ScenarioConfig] = None  # device-heterogeneity preset
    use_scan: bool = True          # False: host-driven per-round dispatch
    strategy: Optional[AggregationStrategy] = None  # None -> SyncStrategy()
    selection: Optional[SelectionPolicy] = None     # None -> UniformPolicy()
    flat_params: bool = False      # flat [S, N] server hot path
    donate: bool = True            # donate the carry to block dispatches
    mesh: Optional[object] = None  # jax Mesh: shard the flat path's client axis
    compress: str = "none"         # "none" | "int8" | "int4" update streaming
    error_feedback: bool = True    # carry per-client EF residuals (compressed)
    quant_block: int = kquant.QBLOCK  # absmax scale granularity (kernel tile)
    dp_delta: Optional[float] = None    # account (eps, delta) spent per commit
    dp_epsilon: Optional[float] = None  # halt when spent eps reaches this
    deadline: Optional[float] = None    # per-round completion-time budget
    overprovision: float = 0.0     # select ceil(S*(1+o)) to absorb timeouts
    quorum: float = 0.0            # min on-time fraction of the base cohort
    deadline_backoff: float = 2.0  # deadline multiplier on quorum failure
    deadline_cap: Optional[float] = None   # backoff ceiling (None -> 8x)
    checkpoint_every: Optional[int] = None  # rounds between state snapshots
    checkpoint_dir: Optional[str] = None    # where snapshots land
    eval_block: Optional[int] = None  # test rows per evaluation pass (None: all)


@dataclass
class RoundMetrics:
    round: int
    global_acc: float              # size-weighted mean of local accuracies
    frac_above: Dict[float, float] # target acc -> fraction of devices above
    priority: Tuple[int, ...]
    backtracked: bool
    num_evaluated: int
    weights_entropy: float
    participants: int              # clients surviving the scenario mask
    sim_time: float = 0.0          # virtual clock at this eval point
    commits: int = 0               # global updates committed so far
    epsilon_spent: Optional[float] = None  # DP budget so far (accounting on)
    # deadline-round telemetry (all zero unless cfg.deadline is set)
    arrivals: float = 0.0          # on-time uploads over this eval block
    timeouts: float = 0.0          # trained-but-late uploads dropped
    retries: int = 0               # quorum-failed (backed-off) rounds
    deadline: float = 0.0          # effective deadline after this block


@dataclass
class SimResult:
    """``final_params`` is always the model *pytree* (unraveled if the run
    used ``flat_params=True``); ``final_state`` is the raw engine carry —
    under the flat path its ``params``/buffer fields are flat vectors."""

    metrics: List[RoundMetrics]
    final_params: PyTree
    rounds_to_target: Dict[Tuple[float, float], Optional[int]]
    # (target_acc, frac_devices) -> first round achieving it (None if never)
    final_state: Optional[ServerState] = None
    budget_exhausted: bool = False  # run halted on the dp_epsilon target


class FederatedSimulation:
    """Server-side simulation of the paper's experiments.

    ``acc_fn(params, x, y, mask)`` must be a masked mean of per-row
    scores, ``sum(score * mask) / max(sum(mask), 1)`` (``cnn_accuracy``
    and ``mlp_accuracy`` are): evaluation scores each test row on its own
    and averages the scores per client.  ``eval_rows`` is the number of
    test rows one evaluation runs, ``sum(test_counts)``.

    ``shared`` is a pytree of weights every client holds and none trains
    (a frozen base under trained adapters).  It is an argument of the
    compiled round block and evaluation, and ``loss_fn`` and ``acc_fn``
    get it as ``shared=``; with ``None`` they are called without it."""

    def __init__(
        self,
        data: FederatedDataset,
        init_params: PyTree,
        loss_fn: Callable,
        acc_fn: Callable,
        config: FedSimConfig,
        shared: Optional[PyTree] = None,
    ):
        self.data = data
        self.cfg = config
        self.loss_fn = loss_fn
        self.acc_fn = acc_fn
        self.params = init_params
        self.shared = shared
        # what the round block and the evaluation take after their own
        # arguments: nothing where the model has no shared weights
        self._shared_args = () if shared is None else (shared,)
        if config.eval_block is not None and config.eval_block < 1:
            raise ValueError(
                f"FedSimConfig.eval_block must be >= 1, got "
                f"{config.eval_block}")
        self.strategy: AggregationStrategy = (
            config.strategy if config.strategy is not None else SyncStrategy()
        )
        if config.online_adjust and not self.strategy.supports_online_adjust:
            raise ValueError(
                f"{type(self.strategy).__name__} does not support Algorithm-1 "
                "online adjustment (it is a synchronous-quality feedback loop)"
            )
        canon = tuple(resolve(n) for n in config.aggregation.criteria)
        for req in self.strategy.requires:
            if resolve(req) not in canon:
                raise ValueError(
                    f"{type(self.strategy).__name__} requires criterion "
                    f"{req!r} in AggregationConfig.criteria, got {canon}"
                )
        self.fleet: Optional[DeviceFleet] = (
            make_fleet(config.scenario, data.num_clients)
            if config.scenario is not None else None
        )
        if config.selection is not None:
            self.policy: SelectionPolicy = config.selection
        elif config.scenario is not None and config.scenario.bias_sampling:
            self.policy = BiasPolicy()     # legacy bias_sampling flag
        else:
            self.policy = UniformPolicy()
        if self.policy.requires_fleet and self.fleet is None:
            raise ValueError(
                f"{type(self.policy).__name__} requires a device fleet — "
                "set FedSimConfig.scenario"
            )
        # DP accounting: host-side RDP accountant over the commit schedule.
        # q is the per-commit sampling rate — S / K for sync-style commits
        # (one commit per surviving round over the round cohort), or
        # buffer_size / K for strategies that commit a client buffer.
        self._accountant = None
        self._dp_max_commits: Optional[int] = None
        if config.dp_epsilon is not None and config.dp_delta is None:
            raise ValueError(
                "FedSimConfig.dp_epsilon needs dp_delta — an epsilon "
                "target is only meaningful at a fixed delta"
            )
        if config.dp_delta is not None:
            from repro.federated.privacy import (GaussianAccountant,
                                                 commit_sampling_rate)

            noise = float(getattr(self.strategy, "noise_multiplier", 0.0))
            if noise <= 0.0:
                raise ValueError(
                    "DP accounting (dp_delta/dp_epsilon) requires a noised "
                    "strategy — ClippedDPStrategy with noise_multiplier > 0; "
                    f"got {type(self.strategy).__name__}"
                )
            # the accountant charges the sensitivity of the *uniform* mean
            # over contributors; prioritized criteria weights give some
            # client p_k > 1/n and are themselves computed from un-noised
            # client statistics, so a weighted commit voids the bound
            if not getattr(self.strategy, "uniform_weights", False):
                raise ValueError(
                    "DP accounting (dp_delta/dp_epsilon) requires "
                    "ClippedDPStrategy(uniform_weights=True): criteria-"
                    "derived aggregation weights are data-dependent and "
                    "unprotected, so the accountant's sensitivity "
                    "assumption (clip_norm / n per client) does not hold "
                    "for a weighted commit"
                )
            # amplification-by-subsampling assumes the cohort is a uniform
            # draw; capability/availability-weighted policies have non-
            # uniform, state-dependent inclusion probabilities the WOR
            # bound does not cover
            if type(self.policy) is not UniformPolicy:
                raise ValueError(
                    "DP accounting (dp_delta/dp_epsilon) requires uniform "
                    "client selection (FedSimConfig.selection=None or "
                    f"UniformPolicy); got {type(self.policy).__name__}"
                )
            q = commit_sampling_rate(
                data.num_clients,
                num_selected(data.num_clients, config.fraction),
                buffer_size=getattr(self.strategy, "buffer_size", None),
            )
            # scheme="wor" (the default): the engine's cohorts are fixed-
            # size without-replacement draws, not Poisson samples
            self._accountant = GaussianAccountant(
                q=q, noise_multiplier=noise, delta=float(config.dp_delta)
            )
            if config.dp_epsilon is not None:
                # pure monotone function of the commit count, so the
                # affordable commit budget is known before the run starts
                self._dp_max_commits = self._accountant.max_commits(
                    float(config.dp_epsilon))

        # Deadline rounds: static quorum size and backoff cap; the
        # effective deadline itself is dynamic (ServerState.deadline).
        self._deadline_on = config.deadline is not None
        self._quorum_n = 0
        self._deadline_cap = 0.0
        if not self._deadline_on:
            if config.overprovision:
                raise ValueError(
                    "FedSimConfig.overprovision requires deadline=... — "
                    "headroom only means something when late uploads are "
                    "dropped at a deadline"
                )
            if config.quorum:
                raise ValueError(
                    "FedSimConfig.quorum requires deadline=... — a quorum "
                    "is counted over the deadline's on-time arrivals"
                )
        else:
            if config.deadline <= 0:
                raise ValueError(
                    f"FedSimConfig.deadline must be > 0, got "
                    f"{config.deadline}"
                )
            if not 0.0 <= config.quorum <= 1.0:
                raise ValueError(
                    f"FedSimConfig.quorum must be in [0, 1], got "
                    f"{config.quorum}"
                )
            if config.deadline_backoff < 1.0:
                raise ValueError(
                    f"FedSimConfig.deadline_backoff must be >= 1, got "
                    f"{config.deadline_backoff} (a shrinking retry "
                    "deadline can never recover a failed quorum)"
                )
            self._deadline_cap = (
                float(config.deadline_cap)
                if config.deadline_cap is not None
                else 8.0 * float(config.deadline)
            )
            if self._deadline_cap < config.deadline:
                raise ValueError(
                    f"FedSimConfig.deadline_cap={config.deadline_cap} is "
                    f"below the base deadline {config.deadline}"
                )
            if config.dp_delta is not None:
                raise ValueError(
                    "FedSimConfig(deadline=...) is incompatible with DP "
                    "accounting: deadline drops make the committed cohort "
                    "depend on sampled completion times, so the fixed-"
                    "size-WOR subsampling rate the accountant assumes no "
                    "longer holds"
                )

        # Crash-recovery checkpointing (see run(resume_from=...)).
        if config.checkpoint_every is not None:
            if config.checkpoint_dir is None:
                raise ValueError(
                    "FedSimConfig.checkpoint_every requires "
                    "checkpoint_dir=... to write into"
                )
            if config.checkpoint_every <= 0:
                raise ValueError(
                    f"FedSimConfig.checkpoint_every must be >= 1, got "
                    f"{config.checkpoint_every}"
                )
            if config.checkpoint_every % max(1, config.eval_every):
                raise ValueError(
                    f"FedSimConfig.checkpoint_every="
                    f"{config.checkpoint_every} must be a multiple of "
                    f"eval_every={config.eval_every}: snapshots are only "
                    "consistent at scan-block boundaries"
                )

        self._base_key = jax.random.key(config.seed)
        self._perms = all_permutations(config.aggregation.num_criteria())
        self._prio_init = self._perms.index(tuple(config.aggregation.priority))

        # flat-vector hot path: cached ravel/unravel plan for the model
        self._flat = bool(config.flat_params)
        self._fspec = FlatSpec(init_params)

        # compressed update streaming: static mode ("int8"/"int4" or None)
        # plus whether the error-feedback residual carry is live.  With
        # compress="none" nothing below traces — the golden program is
        # untouched.
        if config.compress not in ("none", *kquant.QMAX):
            raise ValueError(
                f"FedSimConfig.compress={config.compress!r}: expected "
                f"'none' or one of {sorted(kquant.QMAX)}"
            )
        self._compress: Optional[str] = (
            None if config.compress == "none" else config.compress
        )
        if self._compress is not None and not self._flat:
            raise ValueError(
                "FedSimConfig(compress=...) requires flat_params=True — "
                "updates quantize as one flat vector per client on the "
                "[S, N] hot path"
            )
        if config.quant_block < 1:
            raise ValueError(
                f"FedSimConfig.quant_block must be >= 1, got "
                f"{config.quant_block}"
            )
        self._ef_on = self._compress is not None and config.error_feedback

        # mesh-parallel flat path: static sharding context over the
        # mesh's client axes (ShardSpec); None = plain single-device.
        self._shard: Optional[ShardSpec] = None
        if config.mesh is not None:
            if not self._flat:
                raise ValueError(
                    "FedSimConfig(mesh=...) requires flat_params=True — the "
                    "client axis only shards on the flat [S, N] hot path"
                )
            if not config.use_scan:
                raise ValueError(
                    "FedSimConfig(mesh=...) requires use_scan=True (the "
                    "sharded round block is one shard_map'd lax.scan)"
                )
            self._shard = client_sharding(config.mesh)
            n_shards = self._shard.num_shards
            if data.num_clients % n_shards:
                raise ValueError(
                    f"fleet size K={data.num_clients} must be divisible by "
                    f"the mesh's client-shard count {n_shards} "
                    f"(axes {self._shard.axes} of shape {self._shard.sizes})"
                )
        # Laziness: the expensive update context (an [S, params] pytree, or
        # its streamed [S] squared norm on the flat path) is only built
        # when a configured criterion declares it needs updates.  A
        # criterion registered *without* a needs declaration (needs=None)
        # is treated conservatively: the pytree path still materializes
        # updates for it (pre-laziness behavior), and the flat path —
        # which can only offer the streamed squared norm — refuses it.
        declared = {n: criterion_needs(n) for n in canon}
        self._needs_update = any(d is None or "update" in d
                                 for d in declared.values())
        if self._flat:
            undeclared = [n for n, d in declared.items() if d is None]
            if undeclared:
                raise ValueError(
                    "flat_params=True requires criteria registered with an "
                    f"explicit needs declaration; {undeclared} have none. "
                    "Re-register with needs=() (no update context) or "
                    "needs=('update',) — update consumers receive the "
                    "streamed update_sq_norm on the flat path, not an "
                    "update pytree (see core.criteria.model_divergence)."
                )

        # device-resident copies of the client shards
        self.images = jnp.asarray(data.images)
        self.labels = jnp.asarray(data.labels)
        self.counts = jnp.asarray(data.counts)
        self.t_counts = jnp.asarray(data.test_counts)
        # The test sets packed on the host: each client's first
        # test_counts[k] rows in client order, and the client owning each
        # row, so an evaluation runs the real rows and no padding.
        real = (np.arange(data.test_labels.shape[1])[None, :]
                < np.asarray(data.test_counts)[:, None])
        self._t_rows = jnp.asarray(data.test_images[real])
        self._t_row_labels = jnp.asarray(data.test_labels[real])
        self._t_owner = jnp.asarray(np.nonzero(real)[0].astype(np.int32))
        self.eval_rows = int(real.sum())   # rows one evaluation runs

        # Static per-client features: the [K, C] label-histogram table is
        # fixed by the dataset, so one exact integer-count table gathered
        # by `sel` replaces the per-round [S, max_n, C] one-hot reduction.
        # Stored in the narrowest integer dtype that holds the largest
        # count (usually uint8/uint16 — 4-16x smaller than f32 at fleet
        # scale, where this table is the dominant O(K·C) resident) and
        # cast to f32 only on the gathered [S, C] wave slice.
        # Its width C comes from the data: one past the largest label.
        width = data.num_classes
        hist = np.stack([data.label_histogram(k, width)
                         for k in range(data.num_clients)])
        self._label_table = jnp.asarray(
            hist, np.min_scalar_type(int(hist.max(initial=0))))

        # Fixed per-round shapes -> every jitted program compiles once.
        # Deadline rounds inflate the wave with over-provisioning headroom
        # (still static — the timeout gate is a mask, not a reshape); the
        # quorum threshold counts against the *base* cohort size.
        base_sel = num_selected(data.num_clients, config.fraction)
        if self._deadline_on:
            self._num_sel = overprovisioned_round_size(
                base_sel, config.overprovision, data.num_clients)
            self._quorum_n = max(1, math.ceil(config.quorum * base_sel))
        else:
            self._num_sel = base_sel
        if self._shard is not None and self._num_sel % self._shard.num_shards:
            raise ValueError(
                f"round size S={self._num_sel} (fraction={config.fraction} "
                f"of K={data.num_clients}) must be divisible by the mesh's "
                f"client-shard count {self._shard.num_shards} — adjust "
                f"fraction so each shard trains an equal wave block"
            )
        self._fixed_steps = max(
            1, int(data.counts.max()) // config.batch_size
        ) * config.local_epochs

        # Donating the ServerState carry lets XLA update params/buffer in
        # place per block dispatch instead of copying them; run() copies
        # externally-held buffers into the first carry, so donation never
        # invalidates caller arrays.
        donate = (0,) if config.donate else ()
        if self._shard is None:
            self._round_step = self._build_round_step()
            self._run_block = jax.jit(self._build_run_block(),
                                      donate_argnums=donate)
            self._run_one = jax.jit(self._round_step, donate_argnums=donate)
        else:
            self._round_step = self._run_one = None
            self._run_block = jax.jit(self._build_run_block_mesh(),
                                      donate_argnums=donate)
        self._eval_all = jax.jit(self._eval_boundary)

    # ------------------------------------------------------------------
    def init_state(self) -> ServerState:
        """Fresh engine carry for the current ``self.params`` (flat-path
        runs carry the raveled ``[N]`` vector)."""
        params = self._fspec.ravel(self.params) if self._flat else self.params
        state = self.strategy.init_state(
            params, self.data.num_clients, self._prio_init
        )
        if self._ef_on:
            state = replace(state, error_fb=jnp.zeros(
                (self.data.num_clients, self._fspec.num_params), jnp.float32
            ))
        if self._deadline_on:
            state = replace(state, deadline=jnp.asarray(
                self.cfg.deadline, jnp.float32))
        return state

    def op_layers(self) -> Tuple[str, Dict[str, str]]:
        """``(module name, {instruction name: layer})`` of the compiled
        round block (:mod:`repro.utils.spans`), for attributing the ops of
        a profiler trace to layers.  Compiles ``_run_block`` for the carry
        ``run`` passes it; where the persistent compilation cache holds
        that program, the compile loads it from there (the cache's key
        leaves metadata out: an entry compiled without the scopes comes
        back with every op unscoped)."""
        n = max(1, self.cfg.eval_every)
        round_ids = jnp.arange(1, n + 1, dtype=jnp.int32)
        lowered = self._run_block.lower(self.init_state(), round_ids,
                                        *self._shared_args)
        text = lowered.compile().as_text()
        return spans.module_name(text), spans.op_layers(text)

    # ------------------------------------------------------------------
    def _eval_global(self, params, shared=None):
        """Per-client test accuracies [K] + size-weighted global accuracy.

        Scores every packed test row alone (``vmap`` folds the rows into
        one batch, or into batches of ``eval_block`` rows run one after
        another) and sums the scores per client: for an ``acc_fn`` that
        is a masked mean of per-row scores this is the masked mean over
        each client's padded rows, and for 0/1 scores it is that bit for
        bit."""
        one = jnp.ones((1,), jnp.float32)
        kw = _shared_kw(shared)

        def score(xy):
            x, y = xy
            return self.acc_fn(params, x[None], y[None], one, **kw)

        rows = (self._t_rows, self._t_row_labels)
        block = self.cfg.eval_block
        if block is None or block >= self.eval_rows:
            scores = jax.vmap(score)(rows)
        else:
            scores = jax.lax.map(score, rows, batch_size=block)
        w = self.t_counts.astype(jnp.float32)
        hits = jax.ops.segment_sum(scores, self._t_owner,
                                   num_segments=w.shape[0],
                                   indices_are_sorted=True)
        # the barrier keeps XLA from rewriting a division by the constant
        # counts as a product with their reciprocals, an ulp off the mean
        accs = hits / jax.lax.optimization_barrier(jnp.maximum(w, 1.0))
        return accs, jnp.sum(accs * w) / jnp.sum(w)

    def _eval_params(self, params, shared=None):
        """:meth:`_eval_global` accepting either representation."""
        if self._flat:
            params = self._fspec.unravel(params)
        return self._eval_global(params, **_shared_kw(shared))

    def _eval_boundary(self, params, shared=None):
        """The evaluation that closes a block, under the ``eval`` layer
        (Algorithm-1's candidate evaluations stay under ``adjust``)."""
        with spans.layer("eval"):
            return self._eval_params(params, shared)

    def _measure_criteria(
        self, stacked: PyTree, sel: jax.Array, params: PyTree,
        mask: jax.Array, last_sync: jax.Array, rnd: jax.Array,
        label_counts: jax.Array,
        shard: Optional[ShardSpec] = None,
    ) -> jax.Array:
        """[S, m] criteria matrix, normalized over the round's participants.

        Every criterion goes through the ``core.criteria`` registry: a
        batched :class:`ClientContext` is built from the client shards,
        the fleet profile and the engine's staleness clocks, and
        :func:`measure_criteria` is vmapped over it — so any registered
        criterion whose context fields are available here (everything
        except MoE ``expert_counts``) works without touching this module.

        The update context is *lazy*: it is only built when a configured
        criterion declares ``needs=("update",)``, and on the flat path
        it is the streamed ``[S]`` squared-norm vector
        (``kernels.flat_divergence_sq``) rather than an ``[S, params]``
        update pytree.  ``stacked``/``params`` are the flat ``[S, N]`` /
        ``[N]`` arrays when ``flat_params=True``, pytrees otherwise.

        ``label_counts`` is the pre-gathered ``[S, C]`` f32 wave slice of
        the label table (the caller owns the gather because under a mesh
        it is a distributed owned-rows psum over the ``[K_loc, C]``
        shards); ``last_sync`` is likewise the *full* ``[K]`` clock.
        With ``shard``, ``stacked`` is the local ``[S_loc, N]`` block and
        the streamed divergence is all-gathered back to ``[S]``.
        """
        names = self.cfg.aggregation.criteria
        fleet = self.fleet
        n_examples = self.counts[sel].astype(jnp.float32)
        stale = (rnd - last_sync[sel]).astype(jnp.float32)
        if fleet is not None:
            flops = 1.0 / fleet.slowdown[sel]      # relative capability
            avail = fleet.expected_availability()[sel]
        else:
            flops = jnp.ones_like(n_examples)
            avail = jnp.ones_like(n_examples)

        updates = upd_sq = None
        if self._needs_update:
            if shard is not None:
                upd_sq = kcoll.flat_divergence_sq_shard(stacked, params,
                                                        shard)
            elif self._flat:
                upd_sq = kops.flat_divergence_sq(stacked, params)
            else:
                updates = jax.tree.map(lambda s, p: s - p[None],
                                       stacked, params)
        ctx = ClientContext(
            num_examples=n_examples, label_counts=label_counts,
            update=updates, flops_per_sec=flops, staleness=stale,
            availability=avail, update_sq_norm=upd_sq,
        )
        raw = jax.vmap(lambda c: measure_criteria(names, c))(ctx)
        return normalize_criteria(raw, mask)

    # ------------------------------------------------------------------
    def _build_round_step(self, shard: Optional[ShardSpec] = None,
                          label_table=None):
        """Pure round body ``(state, round_idx) -> (state, ys)``.

        Carry is a :class:`ServerState`; everything — sampling, batch
        plans, local SGD, criteria, scenario masks, and the strategy's
        aggregation policy — happens in one traced program.

        With ``shard`` the body is traced *inside* a ``shard_map`` over
        the mesh's client axes: selection/masks/criteria run replicated
        (same keys on every shard → identical values), each shard trains
        only its positional ``[S_loc, N]`` wave block, the carry's
        ``[K]`` fields arrive as ``[K_loc]`` blocks, and ``label_table``
        is the traced ``[K_loc, C]`` shard of the label table (it must
        be a shard_map *argument*, not a captured constant, to actually
        live sharded).
        """
        cfg = self.cfg
        fleet = self.fleet
        strategy = self.strategy
        policy = self.policy
        S = self._num_sel
        opt = sgd(cfg.lr)
        loss_fn = self.loss_fn
        flat = self._flat
        fspec = self._fspec

        # Byzantine injection is static: only fleets carrying a corrupt
        # mask trace the attack (honest runs keep their exact programs and
        # PRNG streams).  A *static* attack rewrites the client's trained
        # pytree inside the vmapped client, before the flat path ravels,
        # so both representations see bit-identical corruption from one
        # injection point.  A *colluding* attack needs the corrupt
        # cohort's pooled update statistics first, so the wave trains
        # honestly and a second vmapped pass (``collude`` below, still
        # pre-ravel/pre-quantize semantics) swaps the crafted payloads in.
        corrupt_on = fleet is not None and fleet.corrupt is not None
        colluding_on = False
        if corrupt_on:
            from repro.federated.attacks import (apply_attack,
                                                 apply_colluding_attack,
                                                 cohort_stats, is_colluding)

            attack_name = fleet.attack
            attack_scale = float(fleet.attack_scale)
            colluding_on = is_colluding(attack_name)
        if corrupt_on and not colluding_on:
            def one_client(global_params, shared, images, labels, plan,
                           corrupt_k, attack_key):
                trained = _one_client_honest(global_params, shared, images,
                                             labels, plan)
                return apply_attack(attack_name, trained, global_params,
                                    corrupt_k, attack_scale, attack_key)

            train_axes = (None, None, 0, 0, 0, 0, 0)
        else:
            one_client = None
            train_axes = (None, None, 0, 0, 0)

        if colluding_on:
            def collude(wave, gparams, corrupt_loc, keys_loc, corrupt_full,
                        psum):
                """Second injection pass over the honest wave: pool the
                corrupt rows' deltas into (mu, sigma) — psum-finished
                under a mesh, with the replicated full-selection count as
                denominator — then vmap the payload swap with the shared
                statistics broadcast.  Honest rows pass through
                bit-identical (the select is on the untouched row)."""
                delta = jax.tree.map(lambda s, g: s - g[None], wave, gparams)
                mu, sigma = cohort_stats(delta, corrupt_loc,
                                         total=jnp.sum(corrupt_full),
                                         psum=psum)

                def one(trained_k, corrupt_k, key_k):
                    return apply_colluding_attack(
                        attack_name, trained_k, gparams, corrupt_k,
                        attack_scale, key_k, mu, sigma)

                return jax.vmap(one)(wave, corrupt_loc, keys_loc)

        def loss_of(params, xb, yb, shared):
            return loss_fn(params, xb, yb, **_shared_kw(shared))

        def _one_client_honest(global_params, shared, images, labels, plan):
            opt_state = opt.init(global_params)

            def step(carry, idx):
                params, opt_state = carry
                xb = jnp.take(images, idx, axis=0)
                yb = jnp.take(labels, idx, axis=0)
                grads = jax.grad(loss_of)(params, xb, yb, shared)
                updates, opt_state = opt.update(grads, opt_state, params)
                params = jax.tree.map(lambda p, u: p + u, params, updates)
                return (params, opt_state), None

            (params, _), _ = jax.lax.scan(step, (global_params, opt_state), plan)
            return params

        if one_client is None:
            one_client = _one_client_honest

        compress = self._compress
        qblock = cfg.quant_block
        ef_on = self._ef_on
        n_flat = fspec.num_params

        # deadline rounds: static quorum/backoff parameters (the dynamic
        # effective deadline rides in the carry)
        deadline_on = self._deadline_on
        if deadline_on:
            quorum_n = self._quorum_n
            deadline_base = float(cfg.deadline)
            backoff_factor = float(cfg.deadline_backoff)
            deadline_cap = self._deadline_cap

        if flat and compress is not None and not colluding_on:
            # Compressed streaming: quantize inside the vmapped client,
            # so local_train's direct output is the int8 wave + its
            # per-block scale sidecar + the client's new error-feedback
            # residual — the uncompressed f32 [S, N] update matrix is
            # never a local_train output.  ``ef_row`` is the residual
            # re-injected into this upload (zeros when EF is off).
            def one_client_quant(global_params, g_flat, ef_row, *rest):
                w = fspec.ravel(one_client(global_params, *rest))
                carried = (w - g_flat) + ef_row
                q_row, s_row = kquant.quantize_blockwise(
                    carried, compress, qblock)
                resid = carried - kquant.dequantize_blockwise(
                    q_row, s_row, qblock)
                return q_row, s_row, resid

            local_train = jax.vmap(one_client_quant,
                                   in_axes=(None, None, 0) + train_axes[1:])
        elif flat:
            # ravel inside the vmapped client so the [S, N] matrix is
            # local_train's direct output — the stacked pytree never
            # materializes as a separate buffer (an extra S*N-sized copy
            # per round otherwise)
            def one_client_flat(global_params, *rest):
                return fspec.ravel(one_client(global_params, *rest))

            local_train = jax.vmap(one_client_flat, in_axes=train_axes)
        else:
            local_train = jax.vmap(one_client, in_axes=train_axes)

        def round_step(state: ServerState, rnd, shared=None):
            params = state.params
            # the flat carry holds [N]; local SGD needs the model pytree
            model_params = fspec.unravel(params) if flat else params
            key = jax.random.fold_in(self._base_key, rnd)
            k_sel, k_batch, k_scen = jax.random.split(key, 3)
            # derived, not split: keeps k_sel/k_batch/k_scen bit-identical
            # to the pre-engine loop (which never sampled completion times)
            k_time = jax.random.fold_in(key, 3)

            # Under a mesh, every O(K)/O(S) *vector* below is computed
            # replicated from the replicated keys — only the [S_loc, N]
            # training block and the [K_loc] state blocks are per-shard.
            last_sync = state.last_sync
            avoid = strategy.avoid_mask(state)
            if shard is not None:
                last_sync = shard.all_gather(last_sync)
                if avoid is not None:
                    avoid = shard.all_gather(avoid)
            sel, dt_policy = policy.select(SelectionContext(
                key=k_sel, num_clients=self.data.num_clients, n=S, rnd=rnd,
                last_sync=last_sync, fleet=fleet, avoid=avoid,
                time_key=k_time,
            ))
            plans = device_batch_plans(k_batch, self.counts[sel],
                                       self._fixed_steps, cfg.batch_size)
            # flat mode: local_train already emits the [S, N] matrix —
            # everything downstream (criteria, weighting, aggregation,
            # the candidate sweep) streams over it.  Under a mesh each
            # shard trains only its positional block of the wave, so the
            # full [S, N] matrix never exists on one device.
            if shard is not None:
                sel_t = shard.slice_rows(sel)
                plans_t = shard.slice_rows(plans)
            else:
                sel_t, plans_t = sel, plans
            train_args = (shared, self.images[sel_t], self.labels[sel_t],
                          plans_t)
            corrupt_t = atk_keys = corrupt_sel = None
            if corrupt_on:
                # dedicated stream (fold index 4) so hostile runs perturb
                # no existing randomness; one key per (round, client)
                atk_keys = jax.random.split(jax.random.fold_in(key, 4), S)
                if shard is not None:
                    atk_keys = shard.slice_rows(atk_keys)
                corrupt_t = fleet.corrupt[sel_t]
                if not colluding_on:
                    train_args = train_args + (corrupt_t, atk_keys)
                else:
                    # replicated full-selection mask: the cohort size must
                    # be identical on every shard (stats denominators)
                    corrupt_sel = fleet.corrupt[sel]
            if compress is not None:
                # Error-feedback rows for this wave: a direct [S, N]
                # gather on one device.  Under a mesh each row lives on
                # its *owner* shard while the wave position that trains
                # it may sit on another, so an owned-rows psum rebuilds
                # the wave's rows replicated (the label-table pattern at
                # [S, N] cost — a simulation artifact: on a real fleet
                # the residual lives on the device, not the server) and
                # each shard slices its positional block.
                ef_wave = None
                if not ef_on:
                    s_rows = S if shard is None else S // shard.num_shards
                    ef_sel = jnp.zeros((s_rows, n_flat), jnp.float32)
                elif shard is None:
                    ef_sel = state.error_fb[sel]
                else:
                    k_loc = state.error_fb.shape[0]
                    lo = shard.index() * k_loc
                    owned_ef = (sel >= lo) & (sel < lo + k_loc)
                    rows = state.error_fb[jnp.clip(sel - lo, 0, k_loc - 1)]
                    ef_wave = shard.psum(
                        jnp.where(owned_ef[:, None], rows, 0.0))
                    ef_sel = shard.slice_rows(ef_wave)
                with spans.layer("local_train"):
                    if colluding_on:
                        # colluding + compressed: the wave trains honestly
                        # (flat rows), the collusion pass swaps the crafted
                        # payloads in, and only then does the wire quantize —
                        # the attacker corrupts what it uploads, the
                        # quantizer compresses it like any honest payload
                        # (same carried = delta + EF ordering as the fused
                        # per-client path).
                        wave = local_train(model_params, *train_args)
                        wave = collude(
                            wave, params, corrupt_t, atk_keys, corrupt_sel,
                            shard.psum if shard is not None else None)
                        carried = (wave - params[None, :]) + ef_sel
                        q_wave, q_scales = kquant.quantize_blockwise(
                            carried, compress, qblock)
                        resid = carried - kquant.dequantize_blockwise(
                            q_wave, q_scales, qblock)
                    else:
                        q_wave, q_scales, resid = local_train(
                            model_params, params, ef_sel, *train_args)
                    # the dequantized reconstruction w_G + deq(q) — what the
                    # server actually "received"; criteria and the nonlinear
                    # strategies consume this, linear commits use the int8
                    # wave through the fused kernel instead.
                    stacked = params[None, :] + kquant.dequantize_blockwise(
                        q_wave, q_scales, qblock)
            else:
                with spans.layer("local_train"):
                    stacked = local_train(model_params, *train_args)
                    if colluding_on:
                        stacked = collude(
                            stacked, params if flat else model_params,
                            corrupt_t, atk_keys, corrupt_sel,
                            shard.psum if shard is not None else None)

            if fleet is not None:
                mask, contrib = participation(fleet, sel, rnd, k_scen)
                dt = (dt_policy if dt_policy is not None
                      else completion_time(fleet, sel, k_time))
            else:
                mask = contrib = jnp.ones((S,), jnp.float32)
                dt = dt_policy if dt_policy is not None else mask
            if avoid is not None:
                # Soft-excluded in-flight clients can backfill a thin draw,
                # but must not contribute twice: gate them out of the wave
                # entirely.  All clients in flight -> a no-op round.
                elig = 1.0 - avoid[sel]
                mask = mask * elig
                contrib = contrib * elig

            if deadline_on:
                # Deadline gate: uploads later than the effective deadline
                # never reach the server.  A wave whose on-time arrivals
                # miss the quorum is abandoned wholesale — mask/contrib
                # zero out, so every strategy's all-dropped guard makes
                # the round a no-op — and the effective deadline backs
                # off exponentially (capped), resetting to the base the
                # next time a quorum lands.  Gating happens *before* the
                # error-feedback fold and criteria normalization: a
                # timed-out upload neither settles its quantization debt
                # nor enters the weight denominator.
                eff_deadline = state.deadline
                on_time = (dt <= eff_deadline).astype(jnp.float32)
                arrivals = jnp.sum(mask * on_time)
                timeouts = jnp.sum(mask) - arrivals
                quorum_met = arrivals >= quorum_n
                live = quorum_met.astype(jnp.float32)
                mask = mask * on_time * live
                contrib = contrib * on_time * live
                state = replace(state, deadline=deadline_backoff_step(
                    eff_deadline, quorum_met, deadline_base,
                    backoff_factor, deadline_cap))

            if ef_on:
                # Fold this wave's residuals into the carry: participants
                # (mask > 0) replace their row, everyone else keeps
                # theirs — a dropped upload never reached the server, so
                # its quantization error is not yet the server's debt and
                # re-injects on the client's next surviving round.
                if shard is None:
                    dr = jnp.where(mask[:, None] > 0, resid - ef_sel, 0.0)
                    new_ef = state.error_fb.at[sel].add(dr)
                else:
                    # owner-side scatter: residual rows were computed on
                    # the shard that trained them; all_gather restores
                    # wave order and each shard folds only rows it owns.
                    # Non-owned indices clip into valid slots but add
                    # exact zeros, so clip collisions are harmless and
                    # the update stays deterministic (cf. _scatter_round,
                    # which needs a max/sentinel for the same reason).
                    r_full = shard.all_gather(resid)
                    k_loc = state.error_fb.shape[0]
                    lo = shard.index() * k_loc
                    owned_ef = (sel >= lo) & (sel < lo + k_loc)
                    idx = jnp.clip(sel - lo, 0, k_loc - 1)
                    dr = jnp.where((owned_ef & (mask > 0))[:, None],
                                   r_full - ef_wave, 0.0)
                    new_ef = state.error_fb.at[idx].add(dr)
                state = replace(state, error_fb=new_ef)

            # [S, C] label-count slice for the Ld criterion: a direct
            # gather on one device, a distributed owned-rows psum over the
            # [K_loc, C] table shards on a mesh.
            table = label_table if label_table is not None \
                else self._label_table
            if shard is None:
                label_counts = table[sel].astype(jnp.float32)
            else:
                k_loc = table.shape[0]
                lo = shard.index() * k_loc
                owned = (sel >= lo) & (sel < lo + k_loc)
                rows = table[jnp.clip(sel - lo, 0, k_loc - 1)]
                label_counts = shard.psum(
                    jnp.where(owned[:, None], rows.astype(jnp.float32), 0.0)
                )

            with spans.layer("criteria"):
                c = self._measure_criteria(stacked, sel, params, mask,
                                           last_sync, rnd, label_counts,
                                           shard)

            inp = RoundInputs(rnd=rnd, sel=sel, stacked=stacked, criteria=c,
                              mask=mask, contrib=contrib, dt=dt, shard=shard,
                              quant=((q_wave, q_scales)
                                     if compress is not None else None),
                              qblock=qblock if compress is not None else 0)
            with spans.layer("aggregate"):
                state, ys = strategy.step(
                    state, inp, cfg.aggregation, cfg.online_adjust,
                    eval_fn=lambda cand: self._eval_params(cand, shared)[1],
                )
            ys["participants"] = jnp.sum(mask)
            if deadline_on:
                # the strategy charged the dead-round unit cost (1.0) for
                # an abandoned wave; the server actually waited out the
                # whole effective deadline before giving up
                state = replace(state, sim_time=state.sim_time + jnp.where(
                    quorum_met, 0.0, eff_deadline - 1.0))
                ys["arrivals"] = arrivals
                ys["timeouts"] = timeouts
                ys["retried"] = 1.0 - live
            return state, ys

        return round_step

    def _build_run_block(self):
        """``eval_every`` rounds as one lax.scan + one boundary eval."""

        def run_block(state: ServerState, round_ids, shared=None):
            step = functools.partial(self._round_step, shared=shared)
            state, ys = jax.lax.scan(step, state, round_ids)
            accs, global_acc = self._eval_boundary(state.params, shared)
            return state, ys, accs, global_acc

        return run_block

    def _build_run_block_mesh(self):
        """The mesh-parallel run block: one ``shard_map`` per scan block.

        Placement: the carry's ``last_sync``/``in_buffer`` and the label
        table are sharded over the client axes (``PartitionSpec`` on dim
        0); params, buffer, scalars, the round ids and every dataset
        array captured by the round body are replicated.  Eval runs
        outside the ``shard_map`` on the replicated global params.
        """
        from jax.sharding import PartitionSpec as P

        shard = self._shard
        mesh = self.cfg.mesh
        k_spec = shard.partition_spec()
        # Spec pytree mirroring ServerState; leaf specs broadcast over
        # whole subtrees (params may be any pytree) and buffer slots that
        # are None for this strategy match the empty subtree.
        state_spec = ServerState(
            params=P(), quality=P(), priority_idx=P(),
            last_sync=k_spec, sim_time=P(), commits=P(),
            buffer=P(), buffer_weight=P(), buffer_count=P(),
            in_buffer=k_spec,
            # EF residuals shard like the other per-client state: each
            # shard owns the [K_loc, N] client block of the [K, N] carry
            error_fb=k_spec if self._ef_on else P(),
            # the effective deadline is a replicated scalar (every shard
            # sees the same masks from the same keys)
            deadline=P(),
        )

        def block(state, round_ids, table, shared):
            step = functools.partial(
                self._build_round_step(shard=shard, label_table=table),
                shared=shared)
            return jax.lax.scan(step, state, round_ids)

        # the shared weights are replicated, like the params
        sharded = jax.shard_map(
            block, mesh=mesh,
            in_specs=(state_spec, P(), k_spec, P()),
            out_specs=(state_spec, P()),
            check_vma=False,
        )

        def run_block(state: ServerState, round_ids, shared=None):
            state, ys = sharded(state, round_ids, self._label_table, shared)
            accs, global_acc = self._eval_boundary(state.params, shared)
            return state, ys, accs, global_acc

        return run_block

    # ------------------------------------------------------------------
    # crash-recovery checkpoints
    @staticmethod
    def _metrics_to_meta(metrics: List[RoundMetrics]) -> list:
        """Msgpack-safe encoding of the metrics history.  ``frac_above``
        has float keys (illegal as msgpack map keys), so it rides as an
        item list; floats round-trip exactly (msgpack doubles)."""
        out = []
        for m in metrics:
            d = dict(vars(m))
            d["frac_above"] = [[t, v] for t, v in m.frac_above.items()]
            d["priority"] = list(m.priority)
            out.append(d)
        return out

    @staticmethod
    def _metrics_from_meta(items: list) -> List[RoundMetrics]:
        out = []
        for d in items:
            d = dict(d)
            d["frac_above"] = {float(t): float(v)
                               for t, v in d["frac_above"]}
            d["priority"] = tuple(int(p) for p in d["priority"])
            out.append(RoundMetrics(**d))
        return out

    def _run_fingerprint(self) -> dict:
        """The static identity of a trajectory: resuming under any other
        value of these would silently diverge from the original run, so
        the restore path refuses a mismatch."""
        cfg = self.cfg
        return {
            "seed": cfg.seed,
            "fraction": cfg.fraction,
            "max_rounds": cfg.max_rounds,
            "eval_every": cfg.eval_every,
            "batch_size": cfg.batch_size,
            "local_epochs": cfg.local_epochs,
            "lr": cfg.lr,
            "flat_params": bool(self._flat),
            "compress": cfg.compress,
            "strategy": type(self.strategy).__name__,
            "selection": type(self.policy).__name__,
            "scenario": (cfg.scenario.preset
                         if cfg.scenario is not None else None),
            "deadline": cfg.deadline,
            "overprovision": cfg.overprovision,
            "quorum": cfg.quorum,
        }

    def _accountant_meta(self) -> Optional[dict]:
        """DP-accountant parameters carried in the checkpoint — the spent
        epsilon is a pure function of these and ``state.commits``, so
        storing (q, noise, delta) makes the accountant itself
        recoverable."""
        if self._accountant is None:
            return None
        a = self._accountant
        return {"q": float(a.q),
                "noise_multiplier": float(a.noise_multiplier),
                "delta": float(a.delta)}

    def _save_checkpoint(self, rnd: int, state: ServerState,
                         metrics: List[RoundMetrics],
                         rounds_to: dict) -> str:
        """One atomic snapshot of the engine carry + run metadata at a
        block boundary.  A method (not inlined in ``run``) so the crash-
        recovery gate can hook the write and kill the process right
        after it."""
        from repro.checkpoint import checkpoint_path, save_server_state

        path = checkpoint_path(self.cfg.checkpoint_dir, rnd)
        save_server_state(path, state, {
            "round": int(rnd),
            "metrics": self._metrics_to_meta(metrics),
            "rounds_to": [[t, f, r] for (t, f), r in rounds_to.items()],
            "fingerprint": self._run_fingerprint(),
            "accountant": self._accountant_meta(),
        })
        return path

    # ------------------------------------------------------------------
    def run(
        self,
        targets: Tuple[float, ...] = (0.75, 0.80),
        device_fracs: Tuple[float, ...] = (0.2, 0.3, 0.4, 0.5, 0.7, 0.75),
        log_every: int = 10,
        verbose: bool = True,
        resume_from: Optional[str] = None,
    ) -> SimResult:
        """Drive up to ``cfg.max_rounds`` rounds and evaluate every block.

        Rounds run in ``cfg.eval_every``-sized ``lax.scan`` blocks (one
        XLA dispatch per block; ``use_scan=False`` keeps a host-driven
        per-round loop with an identical trajectory).  After each block
        the global model is evaluated on every client's local test set.

        ``targets`` are global-accuracy goals; ``device_fracs`` are
        fraction-of-devices goals — ``rounds_to_target[(t, f)]`` records
        the first round where at least ``f`` of the devices score ≥ ``t``
        (``None`` if never), and the loop early-stops once every goal is
        met.  Returns a :class:`SimResult` whose ``metrics`` carry one
        :class:`RoundMetrics` per eval point, including the virtual-clock
        reading ``sim_time`` (see ``benchmarks/README.md`` for units).

        ``resume_from`` restores a crash-recovery checkpoint (written by
        ``checkpoint_every``/``checkpoint_dir`` at block boundaries) and
        continues the run from its round: because every round's
        randomness folds from the absolute round index, the resumed
        trajectory — params, metrics, targets hit — is bit-for-bit the
        uninterrupted one.  The checkpoint's config fingerprint must
        match this simulation's, and ``targets``/``device_fracs`` must
        match the original call.
        """
        cfg = self.cfg
        block = max(1, cfg.eval_every)
        metrics: List[RoundMetrics] = []
        rounds_to: Dict[Tuple[float, float], Optional[int]] = {
            (t, f): None for t in targets for f in device_fracs
        }

        budget_exhausted = False
        state = self.init_state()
        rnd = 0
        if resume_from is not None:
            from repro.checkpoint import restore_server_state

            state, meta = restore_server_state(resume_from, like=state)
            fp = meta.get("fingerprint")
            if fp != self._run_fingerprint():
                raise ValueError(
                    f"checkpoint {resume_from!r} was written by a "
                    f"different configuration: {fp} vs "
                    f"{self._run_fingerprint()}"
                )
            if meta.get("accountant") != self._accountant_meta():
                raise ValueError(
                    f"checkpoint {resume_from!r} carries DP-accountant "
                    f"parameters {meta.get('accountant')} but this run "
                    f"accounts with {self._accountant_meta()}"
                )
            meta_rt = {(float(t), float(f)): (None if r is None else int(r))
                       for t, f, r in meta["rounds_to"]}
            if set(meta_rt) != set(rounds_to):
                raise ValueError(
                    "resume_from: targets/device_fracs differ from the "
                    "checkpointed run's goals"
                )
            rounds_to = meta_rt
            metrics = self._metrics_from_meta(meta["metrics"])
            rnd = int(meta["round"])
        ckpt_every = cfg.checkpoint_every
        next_ckpt = (((rnd // ckpt_every) + 1) * ckpt_every
                     if ckpt_every is not None else None)
        if self.cfg.donate:
            # donated dispatches consume the carry's buffers in place —
            # copy so arrays the caller still holds (self.params and, for
            # resumed runs, a prior final_state) survive this run
            state = jax.tree.map(lambda x: jnp.array(x, copy=True), state)

        while rnd < cfg.max_rounds:
            # host spans on the profiler's clock (no cost without a
            # profiler): which host step the device waits on at each
            # block boundary; ``round`` is the block's first round
            with jax.profiler.StepTraceAnnotation("fedsim.block",
                                                  step_num=rnd):
                n = min(block, cfg.max_rounds - rnd)
                if self._dp_max_commits is not None:
                    # enforce the budget *before* running: each round
                    # commits at most once, so capping the block at the
                    # remaining affordable commits guarantees the spent
                    # epsilon stays below dp_epsilon — over-budget noised
                    # state is never committed, not rolled back after the
                    # fact
                    with jax.profiler.TraceAnnotation("fedsim.dp_check",
                                                      round=rnd + 1):
                        remaining = self._dp_max_commits - int(state.commits)
                    if remaining <= 0:
                        budget_exhausted = True
                        if verbose:
                            print(
                                f"[round {rnd:4d}] privacy budget "
                                f"exhausted: one more commit would spend "
                                f"past eps={cfg.dp_epsilon} at "
                                f"delta={cfg.dp_delta} "
                                f"({int(state.commits)} commits)"
                            )
                        break
                    n = min(n, remaining)
                blk_arrivals = blk_timeouts = 0.0
                blk_retries = 0
                with jax.profiler.TraceAnnotation("fedsim.dispatch",
                                                  round=rnd + 1):
                    round_ids = jnp.arange(rnd + 1, rnd + n + 1,
                                           dtype=jnp.int32)
                    if cfg.use_scan:
                        state, ys, accs, global_acc = self._run_block(
                            state, round_ids, *self._shared_args)
                        last = jax.tree.map(lambda a: a[-1], ys)
                    else:
                        for rid in round_ids:
                            state, last = self._run_one(
                                state, rid, *self._shared_args)
                            if self._deadline_on:
                                blk_arrivals += float(last["arrivals"])
                                blk_timeouts += float(last["timeouts"])
                                blk_retries += int(last["retried"])
                        accs, global_acc = self._eval_all(
                            state.params, *self._shared_args)
                with jax.profiler.TraceAnnotation("fedsim.pull",
                                                  round=rnd + 1):
                    if cfg.use_scan and self._deadline_on:
                        blk_arrivals = float(jnp.sum(ys["arrivals"]))
                        blk_timeouts = float(jnp.sum(ys["timeouts"]))
                        blk_retries = int(jnp.sum(ys["retried"]))
                    first, rnd = rnd + 1, rnd + n
                    accs = np.asarray(accs)
                    frac_above = {t: float(np.mean(accs >= t))
                                  for t in targets}
                    for t in targets:
                        for f in device_fracs:
                            if (rounds_to[(t, f)] is None
                                    and frac_above[t] >= f):
                                rounds_to[(t, f)] = rnd
                    priority = self._perms[int(last["priority_idx"])]
                    backtracked = bool(last["backtracked"])
                    commits = int(state.commits)
                    epsilon = (self._accountant.epsilon(commits)
                               if self._accountant is not None else None)
                    metrics.append(RoundMetrics(
                        round=rnd, global_acc=float(global_acc),
                        frac_above=frac_above, priority=priority,
                        backtracked=backtracked,
                        num_evaluated=int(last["num_evaluated"]),
                        weights_entropy=float(last["entropy"]),
                        participants=int(last["participants"]),
                        sim_time=float(state.sim_time),
                        commits=commits,
                        epsilon_spent=epsilon,
                        arrivals=blk_arrivals,
                        timeouts=blk_timeouts,
                        retries=blk_retries,
                        deadline=(float(state.deadline) if self._deadline_on
                                  else 0.0),
                    ))
                if next_ckpt is not None and rnd >= next_ckpt:
                    with jax.profiler.TraceAnnotation("fedsim.checkpoint",
                                                      round=first):
                        self._save_checkpoint(rnd, state, metrics, rounds_to)
                    next_ckpt = ((rnd // ckpt_every) + 1) * ckpt_every
                if verbose and (rnd % log_every == 0 or rnd >= cfg.max_rounds):
                    print(
                        f"[round {rnd:4d}] acc={float(global_acc):.4f} "
                        f"frac>= {targets[0]:.0%}: "
                        f"{frac_above[targets[0]]:.2f} "
                        f"priority={priority} bt={backtracked}"
                    )
                # backstop only: the pre-run commit cap above keeps the
                # spent epsilon strictly below the target, so this cannot
                # fire for the capped schedules; it guards any future
                # commit schedule that beats the one-commit-per-round bound
                if (epsilon is not None and cfg.dp_epsilon is not None
                        and epsilon >= cfg.dp_epsilon):
                    budget_exhausted = True
                    if verbose:
                        print(
                            f"[round {rnd:4d}] privacy budget exhausted: "
                            f"eps={epsilon:.3f} >= {cfg.dp_epsilon} at "
                            f"delta={cfg.dp_delta} after {commits} commits"
                        )
                    break
                # early stop when the strictest goal is met
                if all(v is not None for v in rounds_to.values()):
                    break

        self.params = (self._fspec.unravel(state.params) if self._flat
                       else state.params)
        return SimResult(metrics=metrics, final_params=self.params,
                         rounds_to_target=rounds_to, final_state=state,
                         budget_exhausted=budget_exhausted)
