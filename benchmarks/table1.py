"""Paper Table 1 reproduction: studies A / B / C on SynthFEMNIST.

* Study A — individual criteria (Ds baseline vs Md vs Ld)
* Study B — fixed priority permutations of the prioritized MCA operator
* Study C — online adjustment (Algorithm 1) from each initialization

Metric (paper §3): rounds of communication until X% of participating
devices reach a target local-test accuracy.  Absolute numbers are NOT
comparable to the paper's Table 1 (SynthFEMNIST stands in for FEMNIST —
DESIGN.md §2); the *relative* orderings are the reproduction target.

Scale knobs default to CPU-tractable values; pass ``--full`` for a run
closer to the paper's (371 clients, CNN-2048, 1000 rounds).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.core import AggregationConfig
from repro.core.operators import all_permutations
from repro.data.synthetic import make_synth_femnist
from repro.federated.simulation import FederatedSimulation, FedSimConfig
from repro.models.cnn import cnn_accuracy, cnn_loss, init_cnn_params
from repro.utils.compile_cache import use_compile_cache

RESULTS = Path(__file__).resolve().parent / "results"

PERM_NAMES = {
    (0, 1, 2): "Ds>Ld>Md", (0, 2, 1): "Ds>Md>Ld",
    (1, 0, 2): "Ld>Ds>Md", (2, 0, 1): "Md>Ds>Ld",
    (1, 2, 0): "Ld>Md>Ds", (2, 1, 0): "Md>Ld>Ds",
}
# criteria tuple is (Ds, Ld, Md): index 0=Ds, 1=Ld, 2=Md


def run_setting(data, hidden, rounds, name, agg_cfg, online, targets,
                fracs, seed=0, lr=0.05, epochs=1, batch=10, fraction=0.15,
                verbose=False):
    params = init_cnn_params(jax.random.key(seed), hidden=hidden)
    cfg = FedSimConfig(
        fraction=fraction, batch_size=batch, local_epochs=epochs, lr=lr,
        max_rounds=rounds, aggregation=agg_cfg, online_adjust=online,
        seed=seed,
    )
    sim = FederatedSimulation(data, params, cnn_loss, cnn_accuracy, cfg)
    t0 = time.time()
    res = sim.run(targets=targets, device_fracs=fracs, verbose=verbose)
    out = {
        "name": name,
        "rounds_to_target": {f"{t}/{f}": res.rounds_to_target[(t, f)]
                             for t in targets for f in fracs},
        "final_acc": res.metrics[-1].global_acc if res.metrics else None,
        "elapsed_s": round(time.time() - t0, 1),
        "acc_curve": [round(m.global_acc, 4) for m in res.metrics],
        "priority_trace": [PERM_NAMES.get(tuple(m.priority), str(m.priority))
                           for m in res.metrics][:50],
    }
    print(f"  {name:12s} rounds_to={out['rounds_to_target']} "
          f"final={out['final_acc']:.3f} ({out['elapsed_s']}s)", flush=True)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--study", choices=["A", "B", "C", "D", "all"],
                    default="all")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--clients", type=int, default=None)
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--out", default=None, help="output JSON filename")
    args = ap.parse_args()

    if args.full:
        n_clients, mean_samples, hidden, rounds = 371, 60, 2048, 1000
        targets, fracs = (0.75, 0.80), (0.2, 0.3, 0.4, 0.5)
    else:
        n_clients, mean_samples, hidden, rounds = 32, 36, 96, 40
        targets, fracs = (0.30, 0.40), (0.2, 0.4)
    if args.clients:
        n_clients = args.clients
    if args.rounds:
        rounds = args.rounds

    data = make_synth_femnist(num_clients=n_clients, mean_samples=mean_samples,
                              seed=0)
    print(f"[table1] SynthFEMNIST: {n_clients} clients, hidden={hidden}, "
          f"rounds<={rounds}, targets={targets}", flush=True)

    results = {"config": {"clients": n_clients, "hidden": hidden,
                          "rounds": rounds, "targets": targets,
                          "fracs": fracs}}

    if args.study in ("A", "all"):
        print("[table1] Study A — individual criteria")
        results["A"] = [
            run_setting(data, hidden, rounds, "Ds(base)",
                        AggregationConfig(criteria=("Ds",), priority=(0,)),
                        False, targets, fracs),
            run_setting(data, hidden, rounds, "Ld",
                        AggregationConfig(criteria=("Ld",), priority=(0,)),
                        False, targets, fracs),
            run_setting(data, hidden, rounds, "Md",
                        AggregationConfig(criteria=("Md",), priority=(0,)),
                        False, targets, fracs),
        ]

    if args.study in ("B", "all"):
        print("[table1] Study B — MCA priority permutations")
        results["B"] = [
            run_setting(data, hidden, rounds, PERM_NAMES[perm],
                        AggregationConfig(priority=perm), False, targets, fracs)
            for perm in all_permutations(3)
        ]

    if args.study in ("C", "all"):
        print("[table1] Study C — online adjustment (Algorithm 1)")
        results["C"] = [
            run_setting(data, hidden, rounds, f"adj:{PERM_NAMES[perm]}",
                        AggregationConfig(priority=perm), True, targets, fracs)
            for perm in all_permutations(3)
        ]

    if args.study in ("D", "all"):
        # Beyond Table 1: the paper states it selected the prioritized
        # operator over weighted-average / OWA / Choquet "because of its
        # better performance" (§2.2) but shows no numbers — Study D is that
        # comparison on SynthFEMNIST.
        print("[table1] Study D — aggregation-operator comparison")
        results["D"] = [
            run_setting(data, hidden, rounds, "prioritized",
                        AggregationConfig(operator="prioritized",
                                          priority=(2, 0, 1)),
                        False, targets, fracs),
            run_setting(data, hidden, rounds, "weighted_avg",
                        AggregationConfig(operator="weighted_average"),
                        False, targets, fracs),
            run_setting(data, hidden, rounds, "owa(a=2)",
                        AggregationConfig(operator="owa", owa_alpha=2.0),
                        False, targets, fracs),
            run_setting(data, hidden, rounds, "choquet",
                        AggregationConfig(operator="choquet",
                                          choquet_lambda=-0.5),
                        False, targets, fracs),
        ]

    RESULTS.mkdir(parents=True, exist_ok=True)
    out = RESULTS / (args.out or ("table1_full.json" if args.full else "table1.json"))
    out.write_text(json.dumps(results, indent=2))
    print(f"[table1] saved {out}")


if __name__ == "__main__":
    use_compile_cache()
    main()
