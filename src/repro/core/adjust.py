"""Algorithm 1 — online adjustment of the priority order with backtracking.

The server keeps the priority permutation used in the previous round while
the (weighted) global accuracy keeps improving.  When a candidate global
model *regresses*, the server backtracks: it re-aggregates the same local
models under the other permutations, accepting the first that beats the
previous accuracy; if none does, it falls back to the least-worst candidate
(the permutation with maximum candidate accuracy).

Two implementations:

* :func:`adjust_round` — faithful sequential search (Python control flow,
  jitted evaluation per candidate; evaluation of later permutations is
  *lazy*, exactly like the paper's `while` loop).
* :func:`adjust_round_vectorized` — evaluates every permutation in one
  lowered computation (vmap over the m! candidate aggregates) and applies
  the same acceptance rule with `jnp.where`.  This is what the round
  engine uses: a single XLA program per round, no host round-trips.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import operators
from repro.core.aggregate import AggregationConfig, aggregate_models, compute_weights
from repro.core.operators import Permutation
from repro.utils import spans
from repro.utils.pytree import PyTree

# Candidate evaluation: global-model pytree → scalar quality (higher=better).
EvalFn = Callable[[PyTree], jax.Array]


@dataclass
class AdjustResult:
    global_params: PyTree
    quality: jax.Array               # accepted candidate's quality
    priority: Permutation | jax.Array  # accepted permutation (static or index)
    num_evaluated: int               # how many candidates were built/tested
    backtracked: bool | jax.Array
    weights: Optional[jax.Array] = None  # accepted candidate's p[K]


def _candidate(
    c: jax.Array,
    stacked: PyTree,
    cfg: AggregationConfig,
    priority: Permutation,
    mask: Optional[jax.Array],
) -> PyTree:
    p = compute_weights(c, cfg, priority, mask)
    return aggregate_models(stacked, p)


def adjust_round(
    c: jax.Array,
    stacked_models: PyTree,
    cfg: AggregationConfig,
    current_priority: Permutation,
    prev_quality: float,
    eval_fn: EvalFn,
    mask: Optional[jax.Array] = None,
) -> AdjustResult:
    """Paper Algorithm 1, lines 8–29 (sequential, lazy backtracking).

    ``eval_fn`` plays the role of lines 13–16 (weighted local test
    accuracies of the candidate).  Permutations are tried in a fixed
    lexicographic order, skipping the current one, exactly once each.
    """
    perms = operators.all_permutations(cfg.num_criteria())
    candidate = _candidate(c, stacked_models, cfg, current_priority, mask)
    quality = eval_fn(candidate)
    n_eval = 1
    if bool(quality >= prev_quality):
        return AdjustResult(
            candidate, quality, current_priority, n_eval, False,
            weights=compute_weights(c, cfg, current_priority, mask),
        )

    best_q, best_cand, best_perm = quality, candidate, current_priority
    for perm in perms:
        if perm == tuple(current_priority):
            continue
        cand = _candidate(c, stacked_models, cfg, perm, mask)
        q = eval_fn(cand)
        n_eval += 1
        if bool(q >= prev_quality):
            return AdjustResult(
                cand, q, perm, n_eval, True,
                weights=compute_weights(c, cfg, perm, mask),
            )
        if bool(q > best_q):
            best_q, best_cand, best_perm = q, cand, perm
    # least-worst fallback (lines 22–25)
    return AdjustResult(
        best_cand, best_q, best_perm, n_eval, True,
        weights=compute_weights(c, cfg, best_perm, mask),
    )


@spans.layer("adjust")
def adjust_round_vectorized(
    c: jax.Array,
    stacked_models: PyTree,
    cfg: AggregationConfig,
    current_priority_idx: jax.Array,
    prev_quality: jax.Array,
    eval_fn: EvalFn,
    mask: Optional[jax.Array] = None,
    shard=None,
) -> AdjustResult:
    """Algorithm 1 as one XLA computation (all permutations evaluated).

    Semantics match :func:`adjust_round` given the same fixed permutation
    enumeration order: keep the current permutation if it does not regress;
    otherwise accept the first non-regressing permutation; otherwise the
    argmax candidate.  ``current_priority_idx`` is a traced index into
    :func:`operators.all_permutations`.

    Eager evaluation of all m! candidates trades FLOPs for zero host
    round-trips — on the mesh each candidate is just one weighted psum of
    scalars plus a cheap re-weighting, so this is the right trade at scale.

    When ``stacked_models`` is the flat ``[K, N]`` client matrix (a bare
    2-D array is *by contract* the flat representation — see
    :func:`~repro.core.aggregate.aggregate_models`), the whole candidate
    sweep collapses to one ``[m!, K] @ [K, N]`` matmul (one streaming
    pass over the round's models) instead of ``m!`` sequential pytree
    aggregations; same acceptance rule, float-tolerance-identical
    candidates.

    With ``shard`` (a :class:`~repro.utils.sharding.ShardSpec`, flat
    path only, inside ``shard_map``): ``c``/``mask`` are the full
    replicated vectors while ``stacked_models`` is this shard's
    ``[K_loc, N]`` wave block; the candidate sweep becomes the
    shard-local ``[m!, K_loc] @ [K_loc, N]`` GEMM finished by one psum
    (:func:`repro.kernels.collective.flat_candidate_sweep_shard`), and
    evaluation/acceptance run replicated on identical candidates.
    """
    perms = operators.all_permutations(cfg.num_criteria())
    n = len(perms)

    # scores for every permutation: [n, K]
    weights = jnp.stack(
        [compute_weights(c, cfg, perm, mask) for perm in perms], axis=0
    )

    flat = isinstance(stacked_models, jax.Array) and stacked_models.ndim == 2
    if shard is not None and not flat:
        raise ValueError(
            "adjust_round_vectorized(shard=...) requires the flat [K, N] "
            "client matrix (flat_params=True)"
        )
    if flat:
        # Flat-vector hot path: all m! candidate aggregates as ONE
        # [n, K] @ [K, N] matmul — a single streaming pass over the
        # stacked client matrix instead of n sequential weighted sums.
        if shard is not None:
            from repro.kernels.collective import flat_candidate_sweep_shard

            w_loc = shard.slice_rows(weights, axis=1)    # [n, K_loc]
            cands = flat_candidate_sweep_shard(
                w_loc, stacked_models, shard)            # [n, N]
        else:
            cands = (weights.astype(jnp.float32)
                     @ stacked_models.astype(jnp.float32)
                     ).astype(stacked_models.dtype)      # [n, N]
        qualities = jax.lax.map(eval_fn, cands)          # [n]
    else:
        def build_and_eval(w):
            return eval_fn(aggregate_models(stacked_models, w))

        qualities = jax.lax.map(build_and_eval, weights)  # [n]

    cur_q = qualities[current_priority_idx]
    ok = qualities >= prev_quality
    # first non-regressing permutation in enumeration order (excluding cur,
    # which is handled by the outer where)
    not_cur = jnp.arange(n) != current_priority_idx
    first_ok = jnp.argmax(jnp.where(ok & not_cur, 1.0, 0.0))
    any_ok = jnp.any(ok & not_cur)
    fallback = jnp.argmax(qualities)
    chosen = jnp.where(
        cur_q >= prev_quality,
        current_priority_idx,
        jnp.where(any_ok, first_ok, fallback),
    )
    w_chosen = weights[chosen]
    # the flat path already built every candidate in the matmul — pick a
    # row; the pytree path re-aggregates with the chosen weights
    if flat:
        global_params = cands[chosen]
    else:
        global_params = aggregate_models(stacked_models, w_chosen)
    return AdjustResult(
        global_params=global_params,
        quality=qualities[chosen],
        priority=chosen,
        num_evaluated=n,
        # "did the search leave the happy path" — matches adjust_round,
        # which reports True even when the least-worst fallback lands back
        # on the current permutation
        backtracked=cur_q < prev_quality,
        weights=w_chosen,
    )
