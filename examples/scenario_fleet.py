"""Device-heterogeneity scenarios: the same federated workload across
every fleet preset registered in ``repro.federated.scenarios.PRESETS``
(benign: uniform / mobile-heavy / flaky-network / tiered-fleet; hostile:
churn / diurnal / byzantine) — a preset added to the registry is swept
here automatically.

Runs the on-device round loop once per preset at a fixed seed — identical
sampling/batching streams, only the fleet differs — and reports final
accuracy, mean participants per round, and rounds/sec, showing how
dropouts, duty cycles, stragglers, and adversaries reshape device-aware
aggregation.  The hostile preset gets a second *counterpoint* row under
a defending server: by default ``byzantine`` is rerun under the
coordinate-wise trimmed mean (``byzantine+trimmed-mean``).  ``--attack
colluding`` swaps the counterpoint to the adaptive ``colluding-flip``
cohort on ``byzantine-colluding``, and ``--strategy`` picks the defense
(``trimmed-mean`` / ``krum`` / ``multi-krum`` / ``clipped-dp``); the
``clipped-dp`` row meters its Rényi privacy budget and reports the
``(epsilon, delta)`` spent.

The ``outage`` preset (mid-round faults: transient crashes, permanent
departures, correlated regional outage waves) rides the registry sweep
like any other; ``--faults`` adds its fault-tolerant counterpoint row
``outage+deadline`` — deadline rounds with over-provisioning, quorum and
retry backoff (``--deadline`` sets the per-round budget) — reporting
arrivals / timeouts / retries per round next to the accuracy numbers.

    PYTHONPATH=src python examples/scenario_fleet.py --rounds 60
    PYTHONPATH=src python examples/scenario_fleet.py \\
        --attack colluding --strategy multi-krum
    PYTHONPATH=src python examples/scenario_fleet.py \\
        --faults --deadline 2.0
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.core import AggregationConfig
from repro.data.synthetic import make_synth_femnist
from repro.federated import make_strategy
from repro.federated.scenarios import PRESETS, ScenarioConfig
from repro.federated.simulation import FederatedSimulation, FedSimConfig
from repro.models.cnn import cnn_accuracy, cnn_loss, init_cnn_params
# Default model is the small MLP (repro.models.mlp): the scenario engine is
# model-agnostic and XLA CPU's vmapped conv gradient makes the paper CNN
# orders of magnitude slower per round; pass --cnn for the paper path.
from repro.models.mlp import init_mlp_params, mlp_accuracy, mlp_loss
from repro.utils.compile_cache import use_compile_cache

def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=48)
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--block", type=int, default=10,
                    help="rounds per lax.scan block (eval cadence)")
    ap.add_argument("--adjust", action="store_true",
                    help="enable Algorithm-1 online priority adjustment")
    ap.add_argument("--cnn", action="store_true",
                    help="use the paper CNN (slow on CPU) instead of the MLP")
    ap.add_argument("--bias-sampling", action="store_true",
                    help="weight client selection by expected availability")
    ap.add_argument("--attack", default="static",
                    choices=("static", "colluding"),
                    help="payload for the hostile counterpoint row: the "
                         "byzantine preset's static sign-flip, or the "
                         "adaptive colluding-flip cohort on "
                         "byzantine-colluding")
    ap.add_argument("--strategy", default="trimmed-mean",
                    choices=("trimmed-mean", "krum", "multi-krum",
                             "clipped-dp"),
                    help="defense for the hostile counterpoint row")
    ap.add_argument("--faults", action="store_true",
                    help="add the fault-tolerant counterpoint row: the "
                         "outage preset under deadline rounds with over-"
                         "provisioning, quorum and retry backoff")
    ap.add_argument("--deadline", type=float, default=2.0,
                    help="per-round completion-time budget for the "
                         "--faults row (simulated-time units)")
    ap.add_argument("--out", default="checkpoints/scenarios.json")
    args = ap.parse_args()

    data = make_synth_femnist(num_clients=args.clients, mean_samples=40,
                              seed=0)
    if args.cnn:
        params = init_cnn_params(jax.random.key(0), hidden=args.hidden)
        loss_fn, acc_fn = cnn_loss, cnn_accuracy
    else:
        params = init_mlp_params(jax.random.key(0), hidden=args.hidden)
        loss_fn, acc_fn = mlp_loss, mlp_accuracy

    # the registry sweep, plus the robust-aggregation counterpoint for
    # the hostile preset picked by --attack (same fleet, a defending
    # server picked by --strategy)
    runs = [dict(label=preset, preset=preset) for preset in sorted(PRESETS)]
    hostile = ("byzantine" if args.attack == "static"
               else "byzantine-colluding")
    if hostile in PRESETS:
        row = dict(label=f"{hostile}+{args.strategy}", preset=hostile,
                   dp=args.strategy == "clipped-dp")
        if args.attack == "colluding":
            # override the preset's default colluding-alie payload with
            # the inner-product flip that actually separates defenses
            row["scenario_kw"] = dict(attack="colluding-flip",
                                      attack_scale=4.0)
        cohort = max(1, round(0.2 * args.clients))
        if args.strategy == "trimmed-mean":
            # quarter-cohort trim, clamped so 2*trim < cohort always
            # holds (tiny --clients smoke runs degrade to a plain mean)
            row["strategy"] = make_strategy(
                "trimmed-mean", trim=min(cohort // 4, (cohort - 1) // 2))
        elif args.strategy in ("krum", "multi-krum"):
            # distance scoring needs a cohort of >= 3; bump tiny smoke
            # cohorts up (f/m resolve per-cohort at trace time)
            row["strategy"] = make_strategy(args.strategy)
            row["fraction"] = min(args.clients, max(3, cohort)) / args.clients
        else:  # clipped-dp: clip + noise, the Rényi accountant metering
            # (accounting requires the DP-safe uniform mean + uniform
            # selection; criteria still feed the update_norm telemetry)
            row["strategy"] = make_strategy("clipped-dp", clip_norm=1.0,
                                            noise_multiplier=0.5,
                                            uniform_weights=True)
            row["aggregation"] = AggregationConfig(
                criteria=("Ds", "Ld", "Md", "update_norm"),
                priority=(3, 2, 0, 1))
            row["cfg_kw"] = dict(dp_delta=1e-3)
        runs.append(row)
    if args.faults:
        # fault-tolerance counterpoint: same hostile outage fleet, but
        # the server runs deadline rounds — over-provisioned cohort,
        # quorum-gated commits, exponential retry backoff
        runs.append(dict(
            label="outage+deadline", preset="outage", faults=True,
            cfg_kw=dict(deadline=args.deadline, overprovision=0.5,
                        quorum=0.25)))

    report = {}
    for run in runs:
        label = run["label"]
        cfg = FedSimConfig(
            fraction=run.get("fraction", 0.2), batch_size=10,
            local_epochs=1, lr=0.05,
            max_rounds=args.rounds, eval_every=args.block,
            online_adjust=args.adjust,
            aggregation=run.get("aggregation",
                                AggregationConfig(priority=(2, 0, 1))),
            strategy=run.get("strategy"),
            scenario=ScenarioConfig(preset=run["preset"],
                                    bias_sampling=args.bias_sampling,
                                    **run.get("scenario_kw", {})),
            **run.get("cfg_kw", {}),
        )
        sim = FederatedSimulation(data, params, loss_fn, acc_fn, cfg)
        t0 = time.time()
        res = sim.run(targets=(0.5,), device_fracs=(0.5,), verbose=False)
        dt = time.time() - t0
        accs = [m.global_acc for m in res.metrics] or [float("nan")]
        parts = [m.participants for m in res.metrics] or [0]
        report[label] = {
            "final_acc": accs[-1],
            "best_acc": max(accs),
            "mean_participants": float(np.mean(parts)),
            "rounds_per_sec": args.rounds / dt,
        }
        print(f"[{label:22s}] final={accs[-1]:.3f} best={max(accs):.3f} "
              f"mean_participants={np.mean(parts):.1f} "
              f"({args.rounds / dt:.1f} rounds/s)")
        if run.get("dp"):
            eps = res.metrics[-1].epsilon_spent if res.metrics else None
            report[label]["epsilon_spent"] = eps
            eps_txt = f"{eps:.2f}" if eps is not None else "n/a"
            print(f"[{label:22s}] privacy budget spent: "
                  f"eps={eps_txt} at delta=1e-3")
        if run.get("faults"):
            n_rounds = res.metrics[-1].round if res.metrics else args.rounds
            arr = sum(m.arrivals for m in res.metrics)
            tos = sum(m.timeouts for m in res.metrics)
            ret = sum(m.retries for m in res.metrics)
            sim_t = res.metrics[-1].sim_time if res.metrics else 0.0
            report[label].update(
                arrivals_per_round=arr / max(1, n_rounds),
                timeouts_per_round=tos / max(1, n_rounds),
                retries=ret, sim_time=sim_t)
            print(f"[{label:22s}] arrivals/round="
                  f"{arr / max(1, n_rounds):.2f} timeouts/round="
                  f"{tos / max(1, n_rounds):.2f} retries={ret} "
                  f"sim_time={sim_t:.1f}")

    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(report, indent=2))
    print(f"[driver] report in {out}")


if __name__ == "__main__":
    use_compile_cache()
    main()
