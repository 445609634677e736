"""Microbenchmarks: aggregation operators + the flat aggregation kernel.

Prints ``name,us_per_call,derived`` CSV rows (benchmark harness contract).
On CPU these measure the *algorithmic* layers (operators, oracles).  The
kernel row goes through the hot path's dispatch
(``kernels.ops.resolve_kernel_mode``): the compiled Mosaic kernel on a
TPU, the fused jnp twin elsewhere — its ``derived`` column names which
ran, and on which device.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AggregationConfig, compute_weights
from repro.core.operators import (
    all_permutations,
    choquet_score,
    lambda_fuzzy_measure,
    owa_quantifier_weights,
    owa_score,
    prioritized_score,
)
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.utils.compile_cache import use_compile_cache


def bench(fn, *args, iters=50, warmup=5) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def main() -> None:
    rng = np.random.default_rng(0)
    rows = []

    # --- operators over a realistic round (37 clients, 3 criteria) -----
    c = jnp.asarray(rng.uniform(0.0, 1.0, (37, 3)), jnp.float32)
    f_prio = jax.jit(lambda c: prioritized_score(c, (2, 0, 1)))
    rows.append(("operator_prioritized_37x3", bench(f_prio, c), "37 clients"))
    w_owa = owa_quantifier_weights(3, 2.0)
    f_owa = jax.jit(lambda c: owa_score(c, w_owa))
    rows.append(("operator_owa_37x3", bench(f_owa, c), "37 clients"))
    mu = lambda_fuzzy_measure([1 / 3] * 3, -0.3)
    f_cho = jax.jit(lambda c: choquet_score(c, mu))
    rows.append(("operator_choquet_37x3", bench(f_cho, c), "37 clients"))

    # full weight computation incl. normalization
    cfg = AggregationConfig()
    f_w = jax.jit(lambda c: compute_weights(c, cfg))
    rows.append(("weights_prioritized_37x3", bench(f_w, c), "eq3+eq4"))

    # --- server aggregation over the paper's CNN size ------------------
    K, N = 37, 6_603_710 // 32  # 1/32 of the CNN for CPU-tractable timing
    stacked = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32))
    weights = jnp.asarray(rng.uniform(size=K).astype(np.float32))
    weights = weights / weights.sum()
    f_jnp = jax.jit(lambda s, w: ref.weighted_agg_ref(s, w))
    us = bench(f_jnp, stacked, weights, iters=10)
    gbps = K * N * 4 / (us / 1e6) / 1e9
    rows.append(("agg_jnp_37x206k", us, f"{gbps:.1f}GB/s"))
    use_pallas, _ = kops.resolve_kernel_mode()
    f_kernel = jax.jit(lambda s, w: kops.flat_weighted_agg(s, w))
    dev = jax.devices()[0]
    rows.append(("agg_flat_dispatch_37x206k",
                 bench(f_kernel, stacked, weights, iters=10),
                 f"{'pallas' if use_pallas else 'jnp'} on "
                 f"{dev.platform}/{dev.device_kind}"))

    f_div = jax.jit(lambda s, g: ref.divergence_ref(s, g))
    g = stacked[0]
    rows.append(("divergence_jnp_37x206k", bench(f_div, stacked, g, iters=10),
                 "Md criterion"))

    # --- Algorithm-1 overhead: candidates per round ---------------------
    stacked_models = {"w": jnp.asarray(rng.normal(size=(8, 100_000)), jnp.float32)}
    from repro.core import adjust_round_vectorized
    f_adj = jax.jit(lambda c8, sm: adjust_round_vectorized(
        c8, sm, cfg, jnp.asarray(0), jnp.asarray(-1e9),
        eval_fn=lambda p: -jnp.mean(p["w"] ** 2)).quality)
    c8 = jnp.asarray(rng.uniform(0.0, 1.0, (8, 3)), jnp.float32)
    rows.append(("adjust_vectorized_6perm_8x100k",
                 bench(f_adj, c8, stacked_models, iters=10), "6 candidates"))

    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    use_compile_cache()
    main()
