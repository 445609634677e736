"""End-to-end driver: the paper's experiment, start to finish.

Runs the full device-aware federated pipeline on SynthFEMNIST:
  data generation → client sampling → local SGD (vmapped) → criteria
  measurement → prioritized aggregation (+ Algorithm-1 online adjustment)
  → LEAF-style per-device evaluation → rounds-to-target report →
  checkpointing.

Default scale is CPU-tractable; ``--paper-scale`` uses the paper's exact
hyperparameters (371 clients, CNN-2048 with 6,603,710 params, B=10, E=5,
lr=0.01, 10% fraction, ≤1000 rounds).

    PYTHONPATH=src python examples/femnist_federated.py --rounds 200
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax

from repro.checkpoint.io import save_pytree
from repro.core import AggregationConfig
from repro.data.synthetic import make_synth_femnist
from repro.federated.simulation import FederatedSimulation, FedSimConfig
from repro.models.cnn import cnn_accuracy, cnn_loss, init_cnn_params
from repro.utils.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper-scale", action="store_true",
                    help="371 clients, CNN-2048, B=10 E=5 lr=0.01 (slow on CPU)")
    ap.add_argument("--clients", type=int, default=48)
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--priority", default="Md,Ds,Ld",
                    help="comma-separated priority order over {Ds,Ld,Md}")
    ap.add_argument("--no-adjust", action="store_true",
                    help="disable Algorithm-1 online adjustment")
    ap.add_argument("--operator", default="prioritized",
                    choices=["prioritized", "weighted_average", "owa", "choquet"])
    ap.add_argument("--out", default="checkpoints/femnist")
    args = ap.parse_args()

    if args.paper_scale:
        clients, hidden = 371, 2048
        lr, epochs, batch, fraction = 0.01, 5, 10, 0.1
        targets, fracs = (0.75, 0.80), (0.2, 0.3, 0.4, 0.5, 0.7, 0.75)
    else:
        clients, hidden = args.clients, args.hidden
        lr, epochs, batch, fraction = 0.05, 2, 10, 0.2
        targets, fracs = (0.35, 0.45), (0.2, 0.4, 0.6)

    name_to_idx = {"Ds": 0, "Ld": 1, "Md": 2}
    priority = tuple(name_to_idx[p.strip()] for p in args.priority.split(","))

    print(f"[driver] SynthFEMNIST {clients} clients; CNN hidden={hidden}; "
          f"priority={args.priority} adjust={not args.no_adjust}")
    data = make_synth_femnist(num_clients=clients, mean_samples=60, seed=0)
    params = init_cnn_params(jax.random.key(0), hidden=hidden)

    cfg = FedSimConfig(
        fraction=fraction, batch_size=batch, local_epochs=epochs, lr=lr,
        max_rounds=args.rounds, online_adjust=not args.no_adjust,
        aggregation=AggregationConfig(operator=args.operator,
                                      priority=priority),
    )
    sim = FederatedSimulation(data, params, cnn_loss, cnn_accuracy, cfg)
    result = sim.run(targets=targets, device_fracs=fracs, log_every=10)

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    save_pytree(str(out_dir / "global_model.msgpack"), result.final_params,
                metadata={"rounds": len(result.metrics)})
    report = {
        "rounds_to_target": {f"{t}/{f}": result.rounds_to_target[(t, f)]
                             for t in targets for f in fracs},
        "final_acc": result.metrics[-1].global_acc if result.metrics else None,
        "backtrack_rounds": [m.round for m in result.metrics if m.backtracked],
    }
    (out_dir / "report.json").write_text(json.dumps(report, indent=2))
    print(f"[driver] final acc {report['final_acc']:.4f}; "
          f"rounds-to-target {report['rounds_to_target']}")
    print(f"[driver] checkpoint + report in {out_dir}/")


if __name__ == "__main__":
    use_compile_cache()
    main()
