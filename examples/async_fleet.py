"""Buffered-async vs sync on a flaky network: the round engine's
strategies A/B'd on one fleet.

Runs the same federated workload on the ``flaky-network`` preset (uniform
compute, always-on devices, heavy-tailed per-round upload loss) under
**every** aggregation strategy registered in
``repro.federated.engine.STRATEGIES`` — a strategy added to the registry
is swept here automatically.  The registry currently holds:

* ``sync``           — the paper's synchronous round: the server barriers
  on every surviving participant each round,
* ``buffered-async`` — FedBuff-style buffered aggregation: arrivals
  stream into a buffer, the server commits whenever ``--buffer`` updates
  are in, and each arrival's weight is attenuated by the registered
  ``staleness`` criterion (rounds since that client's last committed
  sync) through the same prioritized multi-criteria operator as Ds/Ld/Md,
* ``fedavg``         — dataset-size-only weighting, the FedAvg baseline,
* ``trimmed-mean``   — byzantine-robust sync: coordinate-wise weighted
  trimmed mean (run ``--preset byzantine`` to watch it shrug off the
  sign-flip cohort that poisons plain sync),
* ``krum`` / ``multi-krum`` — distance-based byzantine-robust selection:
  commit the client(s) with the smallest summed distance to their
  nearest neighbors (run ``--preset byzantine-colluding`` to see them
  hold where coordinate-wise trimming degrades),
* ``clipped-dp``     — per-client L2 clip + calibrated Gaussian noise
  (DP-FedAvg style), with the ``update_norm`` criterion leading the
  priority order.

Reports accuracy against the *virtual clock* (``RoundMetrics.sim_time``):
sync pays the straggler barrier ``max_k dt_k`` every round, async pays
the aggregate-arrival-rate wave time.  On ``flaky-network`` (uniform
compute) the barrier is mild, so buffering mostly demonstrates dropout
tolerance; run ``--preset tiered-fleet`` (2-4x compute stragglers) to see
the async win — e.g. at defaults async reaches 0.60 global accuracy in
~84 simulated-time units vs ~153 for sync (sync's 120 rounds cost 459
time units; async's cost 146).

``--policy`` swaps the client-selection policy under every strategy
(``repro.federated.selection``): ``uniform`` (the paper's draw),
``bias`` (availability-weighted), ``deadline`` (Gumbel top-k over
predicted completion time + staleness — shrinks the sync barrier on
tiered fleets), ``oracle`` (true sampled completion times, the
barrier's lower bound).

    PYTHONPATH=src python examples/async_fleet.py --rounds 120
    PYTHONPATH=src python examples/async_fleet.py --preset tiered-fleet
    PYTHONPATH=src python examples/async_fleet.py --preset tiered-fleet \\
        --policy deadline
    PYTHONPATH=src python examples/async_fleet.py --mesh   # shard the
        # client axis over the local devices (flat server path)
    PYTHONPATH=src python examples/async_fleet.py --compress int8
        # quantized client uploads + error feedback (flat server path)
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax

from repro.core import AggregationConfig
from repro.data.synthetic import make_synth_femnist
from repro.federated import (
    STRATEGIES,
    ScenarioConfig,
    make_policy,
    make_strategy,
)
from repro.federated.selection import POLICIES
from repro.federated.simulation import FederatedSimulation, FedSimConfig
from repro.models.mlp import init_mlp_params, mlp_accuracy, mlp_loss
from repro.utils.compile_cache import use_compile_cache


def _config(name: str, args) -> FedSimConfig:
    """Per-strategy specialization over the ``STRATEGIES`` registry.

    Every registered aggregation strategy gets a run; the branches below
    pick each one's natural criteria/priority setup (and constructor
    kwargs), with a generic fallback so a strategy added to the registry
    is swept here automatically instead of silently skipped.
    """
    scenario = ScenarioConfig(preset=args.preset, seed=args.fleet_seed)
    common = dict(fraction=0.25, batch_size=10, local_epochs=1, lr=0.1,
                  max_rounds=args.rounds, eval_every=args.block,
                  scenario=scenario, selection=make_policy(args.policy))
    if getattr(args, "mesh_obj", None) is not None:
        # --mesh: every strategy in the sweep runs the same round block
        # shard_map'd over the client axis (flat path required)
        common.update(mesh=args.mesh_obj, flat_params=True,
                      fraction=args.mesh_fraction)
    if args.compress != "none":
        # --compress: clients upload blockwise-absmax int8/int4 updates
        # with per-client error feedback (flat path required)
        common.update(compress=args.compress, flat_params=True)
    if name == "sync":
        return FedSimConfig(
            aggregation=AggregationConfig(priority=(2, 0, 1)), **common)
    if name == "buffered-async":
        # staleness leads the priority order: late arrivals from slow
        # tiers are attenuated before Ds/Ld/Md get a say
        return FedSimConfig(
            aggregation=AggregationConfig(
                criteria=("staleness", "Ds", "Ld", "Md"),
                priority=(0, 1, 2, 3)),
            strategy=make_strategy(name, buffer_size=args.buffer),
            **common)
    if name == "fedavg":
        return FedSimConfig(
            aggregation=AggregationConfig(priority=(0, 1, 2)),
            strategy=make_strategy(name), **common)
    if name == "trimmed-mean":
        # quarter-cohort trim, clamped so 2*trim < cohort always holds
        cohort = max(1, round(0.25 * args.clients))
        return FedSimConfig(
            aggregation=AggregationConfig(priority=(2, 0, 1)),
            strategy=make_strategy(
                name, trim=min(cohort // 4, (cohort - 1) // 2)),
            **common)
    if name in ("krum", "multi-krum"):
        # distance scoring needs a cohort of >= 3 (self + 2 others after
        # excluding f); bump tiny smoke cohorts up, keeping any mesh
        # shard-multiple rounding intact
        cohort = max(3, round(common["fraction"] * args.clients))
        if getattr(args, "mesh_obj", None) is not None:
            cohort += (-cohort) % args.mesh_shards
        cohort = min(cohort, args.clients)
        common["fraction"] = cohort / args.clients
        return FedSimConfig(
            aggregation=AggregationConfig(priority=(2, 0, 1)),
            strategy=make_strategy(name), **common)
    if name == "clipped-dp":
        return FedSimConfig(
            aggregation=AggregationConfig(
                criteria=("Ds", "Ld", "Md", "update_norm"),
                priority=(3, 2, 0, 1)),
            strategy=make_strategy(name, clip_norm=1.0,
                                   noise_multiplier=0.05),
            **common)
    # a strategy registered after this example was written: run it with
    # its constructor defaults and the standard criteria setup
    return FedSimConfig(
        aggregation=AggregationConfig(priority=(2, 0, 1)),
        strategy=make_strategy(name), **common)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=48)
    ap.add_argument("--rounds", type=int, default=120)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--block", type=int, default=10,
                    help="rounds per lax.scan block (eval cadence)")
    ap.add_argument("--buffer", type=int, default=18,
                    help="async buffer size (arrivals per commit)")
    ap.add_argument("--preset", default="flaky-network")
    ap.add_argument("--policy", default="uniform", choices=sorted(POLICIES),
                    help="client-selection policy (see "
                         "repro.federated.selection)")
    ap.add_argument("--mesh", action="store_true",
                    help="run the flat server path mesh-parallel over the "
                         "client axis (launch.mesh.make_host_mesh over the "
                         "local devices; see docs/ARCHITECTURE.md)")
    ap.add_argument("--compress", default="none",
                    choices=("none", "int8", "int4"),
                    help="quantize client uploads (blockwise absmax, "
                         "per-client error feedback; implies the flat "
                         "server path)")
    ap.add_argument("--fleet-seed", type=int, default=0)
    ap.add_argument("--target", type=float, default=0.6)
    ap.add_argument("--out", default="checkpoints/async_fleet.json")
    args = ap.parse_args()

    args.mesh_obj = None
    if args.mesh:
        from repro.launch.mesh import client_sharding, make_host_mesh

        mesh = make_host_mesh()
        n_sh = client_sharding(mesh).num_shards
        if args.clients % n_sh:
            ap.error(f"--mesh: --clients {args.clients} must be divisible "
                     f"by the {n_sh} client shard(s) of the local mesh")
        cohort = max(1, round(0.25 * args.clients))
        cohort += (-cohort) % n_sh   # round size up to a shard multiple
        args.mesh_obj = mesh
        args.mesh_shards = n_sh
        args.mesh_fraction = cohort / args.clients
        print(f"[driver] mesh: {n_sh} client shard(s), "
              f"cohort {cohort}/{args.clients}")

    data = make_synth_femnist(num_clients=args.clients, mean_samples=40,
                              seed=0)
    params = init_mlp_params(jax.random.key(0), hidden=args.hidden)

    if args.compress != "none":
        from repro.kernels.quantize import wire_bytes

        n = sum(leaf.size for leaf in jax.tree.leaves(params))
        wb = wire_bytes(n, args.compress)
        print(f"[driver] compress={args.compress}: {wb} wire bytes per "
              f"upload vs {4 * n} uncompressed ({4 * n / wb:.2f}x)")

    report = {}
    for name in sorted(STRATEGIES):
        sim = FederatedSimulation(data, params, mlp_loss, mlp_accuracy,
                                  _config(name, args))
        res = sim.run(targets=(args.target,), device_fracs=(0.99,),
                      verbose=False)
        accs = [m.global_acc for m in res.metrics]
        hit = next(((m.round, m.sim_time) for m in res.metrics
                    if m.global_acc >= args.target), None)
        report[name] = {
            "final_acc": accs[-1],
            "best_acc": max(accs),
            "commits": res.metrics[-1].commits,
            "sim_time_total": res.metrics[-1].sim_time,
            "rounds_to_target": hit[0] if hit else None,
            "sim_time_to_target": hit[1] if hit else None,
            "curve": [(m.round, round(m.global_acc, 4), round(m.sim_time, 2))
                      for m in res.metrics],
        }
        t_hit = f"{hit[1]:8.1f}" if hit else "   never"
        print(f"[{name:14s}] best={max(accs):.3f} "
              f"commits={res.metrics[-1].commits:4d} "
              f"sim_time_to_{args.target:.2f}={t_hit} "
              f"(total simulated {res.metrics[-1].sim_time:.1f})")

    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(report, indent=2))
    print(f"[driver] report in {out}")


if __name__ == "__main__":
    use_compile_cache()
    main()
