"""Device-aware federated training of a transformer LM (Mode B).

Demonstrates the *scaling layer*: the paper's criteria-weighted aggregation
driving a modern LM on a device mesh, exactly the computation the dry-run
lowers for the production pod — here on the host's devices.

The default model is a reduced qwen2-style LM; ``--layers/--d-model`` scale
it up (``--d-model 768 --layers 12`` ≈ 100M params — a few hundred steps of
that is a real overnight CPU run; the default finishes in minutes).

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
from repro.data.synthetic import make_lm_federated
from repro.federated.distributed import (
    make_federated_adjust_step,
    make_federated_train_step,
)
from repro.launch.mesh import make_host_mesh, num_clients
from repro.launch.sharding_rules import param_shardings
from repro.models import sharding as msharding
from repro.models.registry import bundle as make_bundle
from repro.utils.pytree import tree_count_params
from repro.utils.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
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

    mesh = make_host_mesh(model=1)
    K = num_clients(mesh)
    print(f"[fed-llm] mesh {dict(mesh.shape)} -> {K} federated clients")

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
    print(f"[fed-llm] params: {tree_count_params(params) / 1e6:.1f}M")
    params = jax.device_put(params, param_shardings(params, mesh))

    # non-IID client corpora: each client owns a topic slice of the vocab
    toks, _ = make_lm_federated(K, cfg.vocab_size, args.seq + 1,
                                docs_per_client=64, seed=1)
    rng = np.random.default_rng(2)

    def sample_batch(step):
        docs = rng.integers(0, toks.shape[1], size=(K, args.batch_per_client))
        seqs = np.stack([toks[k, docs[k]] for k in range(K)])  # [K, b, S+1]
        seqs = seqs.reshape(K * args.batch_per_client, args.seq + 1)
        return {
            "tokens": jnp.asarray(seqs[:, :-1]),
            "labels": jnp.asarray(seqs[:, 1:]),
        }

    msharding.configure(True, mesh_axes=mesh.axis_names, manual_axes=("data",))
    with jax.set_mesh(mesh):
        if args.adjust:
            step_fn = jax.jit(make_federated_adjust_step(mdl, mesh, lr=args.lr))
        else:
            step_fn = jax.jit(make_federated_train_step(
                mdl, mesh, lr=args.lr, priority=(2, 0, 1),
                fedavg_baseline=args.fedavg,
            ))

        prev_q = jnp.asarray(-1e9, jnp.float32)
        prio = jnp.asarray(0, jnp.int32)
        t0 = time.time()
        for step in range(args.steps):
            batch = sample_batch(step)
            if args.adjust:
                val = {k: v[: 2] for k, v in batch.items()}
                params, stats = step_fn(params, batch, val, prev_q, prio)
                prev_q, prio = stats["quality"], stats["priority_idx"]
                extra = (f" perm={int(prio)} "
                         f"bt={bool(stats['backtracked'])}")
            else:
                params, stats = step_fn(params, batch)
                w = np.asarray(stats["weight"])
                extra = f" weights=[{w.min():.3f}..{w.max():.3f}]"
            if step % 5 == 0 or step == args.steps - 1:
                print(f"[fed-llm] step {step:4d} loss={float(stats['loss']):.4f}"
                      f"{extra} ({time.time() - t0:.0f}s)", flush=True)
    msharding.configure(False)
    print("[fed-llm] done — loss should be falling from ~ln(2048)=7.6")


if __name__ == "__main__":
    use_compile_cache()
    main()
