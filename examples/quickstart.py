"""Quickstart: device-aware federated learning in ~40 lines.

Trains the paper's CNN on SynthFEMNIST with the prioritized multi-criteria
aggregation operator (Md > Ds > Ld, the paper's best Study-C init) and
online priority adjustment, then prints the accuracy trajectory.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.core import AggregationConfig
from repro.data.synthetic import make_synth_femnist
from repro.federated.simulation import FederatedSimulation, FedSimConfig
from repro.models.cnn import cnn_accuracy, cnn_loss, init_cnn_params
from repro.utils.compile_cache import use_compile_cache


def main() -> None:
    # 24 writers, non-IID by construction (CPU-friendly scale)
    data = make_synth_femnist(num_clients=24, mean_samples=30, seed=0)

    params = init_cnn_params(jax.random.key(0), hidden=128)

    cfg = FedSimConfig(
        fraction=0.25,          # 25% of clients per round
        batch_size=10,          # paper's B
        local_epochs=2,
        lr=0.05,
        max_rounds=10,
        aggregation=AggregationConfig(
            criteria=("Ds", "Ld", "Md"),
            operator="prioritized",
            priority=(2, 0, 1),           # Md > Ds > Ld
        ),
        online_adjust=True,     # Algorithm 1
    )

    sim = FederatedSimulation(data, params, cnn_loss, cnn_accuracy, cfg)
    result = sim.run(targets=(0.30,), device_fracs=(0.4,), log_every=5)

    print("\nround | global acc | priority (Ds,Ld,Md idx) | backtracked")
    for m in result.metrics:
        print(f"{m.round:5d} | {m.global_acc:10.4f} | {str(m.priority):23s} "
              f"| {m.backtracked}")
    hit = result.rounds_to_target[(0.30, 0.4)]
    print(f"\n40% of devices reached 30% accuracy after: {hit} rounds")


if __name__ == "__main__":
    use_compile_cache()
    main()
