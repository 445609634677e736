"""Device-aware federated training of a transformer LM.

A reduced qwen2-style LM trains through the same round engine as the
paper's CNN (``FederatedSimulation`` on its flat server path): each client
owns a topic slice of a synthetic corpus and trains next-token prediction
locally, and the server weights the updates by the paper's prioritized
criteria (Md > Ds > Ld).  ``--adjust`` adds Algorithm-1 online priority
adjustment; ``--fedavg`` runs the dataset-size-only FedAvg baseline.
Accuracy is next-token accuracy on each client's held-out documents.

The default model finishes in minutes on a CPU; ``--layers/--d-model``
scale it up (``--d-model 768 --layers 12`` is about 100M parameters).

    PYTHONPATH=src python examples/federated_llm.py --steps 30
    PYTHONPATH=src python examples/federated_llm.py --adjust --steps 30
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCHS
from repro.core import AggregationConfig
from repro.data.synthetic import FederatedDataset, make_lm_federated
from repro.federated import FedAvgStrategy, FederatedSimulation, FedSimConfig
from repro.models.registry import bundle as make_bundle
from repro.models.transformer import lm_logits
from repro.utils.compile_cache import use_compile_cache
from repro.utils.pytree import tree_count_params

NUM_CLIENTS = 8
FRACTION = 0.5         # 4 clients train each round
DOCS_PER_CLIENT = 16
TEST_DOCS = 4          # the last documents of each client are its test set


def lm_dataset(tokens: np.ndarray) -> FederatedDataset:
    """Next-token rows from ``[K, docs, T + 1]`` token documents: rows are
    ``tokens[..., :-1]`` and labels ``tokens[..., 1:]``; each client's
    last ``TEST_DOCS`` documents are held out as its test set."""
    k, docs, _ = tokens.shape
    train, test = tokens[:, :docs - TEST_DOCS], tokens[:, docs - TEST_DOCS:]
    return FederatedDataset(
        images=train[..., :-1], labels=train[..., 1:],
        counts=np.full(k, train.shape[1], np.int32),
        test_images=test[..., :-1], test_labels=test[..., 1:],
        test_counts=np.full(k, TEST_DOCS, np.int32),
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30, help="rounds")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch-per-client", type=int, default=2)
    ap.add_argument("--lr", type=float, default=0.02)
    ap.add_argument("--adjust", action="store_true",
                    help="Algorithm-1 online priority adjustment")
    ap.add_argument("--fedavg", action="store_true",
                    help="FedAvg baseline instead of prioritized MCA")
    args = ap.parse_args()

    cfg = ARCHS["qwen2-0.5b"].reduced().with_overrides(
        num_layers=args.layers,
        d_model=args.d_model,
        d_ff=4 * args.d_model,
        vocab_size=2048,
        head_dim=max(32, args.d_model // 4),
        num_heads=4, num_kv_heads=2,
    )
    mdl = make_bundle(cfg)
    params = mdl.init(jax.random.key(0))
    print(f"[fed-llm] {NUM_CLIENTS} clients, "
          f"params: {tree_count_params(params) / 1e6:.1f}M")

    # non-IID client corpora: each client owns a topic slice of the vocab
    toks, _ = make_lm_federated(NUM_CLIENTS, cfg.vocab_size, args.seq + 1,
                                docs_per_client=DOCS_PER_CLIENT, seed=1)
    data = lm_dataset(toks)

    def loss_fn(p, x, y):
        return mdl.loss(p, {"tokens": x, "labels": y})[0]

    def acc_fn(p, x, y, mask):
        # a masked mean of per-row next-token hit rates
        hits = jnp.argmax(lm_logits(p, cfg, x), axis=-1) == y
        w = mask.astype(jnp.float32)
        return (jnp.sum(jnp.mean(hits, axis=-1) * w)
                / jnp.maximum(jnp.sum(w), 1.0))

    sim_cfg = FedSimConfig(
        fraction=FRACTION, batch_size=args.batch_per_client,
        local_epochs=1, lr=args.lr, max_rounds=args.steps,
        aggregation=AggregationConfig(priority=(2, 0, 1)),  # Md > Ds > Ld
        online_adjust=args.adjust,
        strategy=FedAvgStrategy() if args.fedavg else None,
        flat_params=True,
    )
    sim = FederatedSimulation(data, params, loss_fn, acc_fn, sim_cfg)
    t0 = time.time()
    result = sim.run(targets=(1.0,), device_fracs=(1.0,), verbose=False)
    for m in result.metrics:
        if m.round % 5 == 1 or m.round == args.steps:
            print(f"[fed-llm] round {m.round:4d} "
                  f"next-token acc={m.global_acc:.4f} "
                  f"priority={m.priority} bt={m.backtracked}")
    print(f"[fed-llm] done in {time.time() - t0:.0f}s — next-token accuracy "
          f"{result.metrics[0].global_acc:.4f} -> "
          f"{result.metrics[-1].global_acc:.4f}")


if __name__ == "__main__":
    use_compile_cache()
    main()
