#!/usr/bin/env python3
"""Smoke run of the paper's federated round on one TPU chip.

    python3 chip_smoke.py                  # one chip: every phase below
    python3 chip_smoke.py --four-chips     # the mesh-sharded flat path only
    python3 chip_smoke.py --cpu-rehearsal  # the same phases at toy sizes

All phases run in this one process (the process that touches JAX holds
the chip).  Any failure exits non-zero before the last line is printed.

1. **device** — what JAX sees; anything but a TPU exits non-zero.
2. **main path** — ``FederatedSimulation.run`` with the paper's setup:
   FEMNIST CNN at full width (6,603,710 params), 371 SynthFEMNIST
   clients, fraction 0.1 (S=37), B=10, E=5, lr=0.01, prioritized
   aggregation (Md > Ds > Ld) with Algorithm-1 online adjustment, two
   scan blocks of two rounds.  Once on the default pytree path, once
   with ``flat_params=True``, whose compiled round block must contain
   the Mosaic kernels (``tpu_custom_call``).  The two accuracy
   trajectories must agree within ``ACC_TOL``.
3. **kernels** — the five flat-path Mosaic kernels compiled at
   ``[37, 6,603,710]`` and checked against their jnp twins on seeded
   data.

``--four-chips`` runs only the mesh phase: the flat sync path with
Algorithm-1 at K=360, S=36 sharded over the host's four chips
(``mesh=make_host_mesh()``), then unsharded on one chip, and compares
the two trajectories.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Compile and
wall seconds printed on the way are observations of this run, not
benchmark numbers.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: |accuracy difference| allowed between two paths at every eval point.
#: Local training is the same program on both sides; what differs is how
#: the global model is formed.  On the TPU a f32 matmul runs at the
#: default (one bf16 pass) precision, and the flat path builds the
#: Algorithm-1 candidates as one [6, S] @ [S, N] matmul while the pytree
#: path sums them on the vector unit in f32, so the committed weights
#: differ in about the 9th bit.  That moves only near-tie predictions:
#: 0.02 is ~110 of the ~5,400 test images of the 371-client fleet.
ACC_TOL = 0.02

PAPER = dict(clients=371, hidden=2048, mean_samples=60)
TOY = dict(clients=20, hidden=16, mean_samples=12)
MESH_CLIENTS = {"paper": 360, "toy": 40}


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"[fail] {msg}")


def device_phase(rehearsal: bool, want: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"[device] {devs}")
    log(f"[device] platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if rehearsal:
        check(d.platform != "tpu", "--cpu-rehearsal is for hosts without "
              "a TPU; run without it on the chip")
    else:
        check(d.platform == "tpu",
              f"no TPU found (platform {d.platform!r}); on a host without "
              "one, use --cpu-rehearsal")
    check(len(devs) >= want, f"need {want} devices, found {len(devs)}")
    return d, len(devs)


def make_sim(data, hidden: int, flat: bool, mesh=None):
    import jax

    from repro.core import AggregationConfig
    from repro.federated import FedSimConfig, FederatedSimulation
    from repro.models.cnn import cnn_accuracy, cnn_loss, init_cnn_params

    params = init_cnn_params(jax.random.key(0), hidden=hidden)
    cfg = FedSimConfig(
        fraction=0.1, batch_size=10, local_epochs=5, lr=0.01,
        max_rounds=4, eval_every=2, online_adjust=True,
        aggregation=AggregationConfig(priority=(2, 0, 1)),   # Md > Ds > Ld
        flat_params=flat, mesh=mesh,
    )
    return FederatedSimulation(data, params, cnn_loss, cnn_accuracy, cfg)


def run_path(name: str, sim, want_kernels: bool):
    """Compile one round block, then ``run`` two of them.  Returns the
    run's result and the compiled block's HLO text."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ids = jnp.arange(1, sim.cfg.eval_every + 1, dtype=jnp.int32)
    t0 = time.perf_counter()
    compiled = sim._run_block.lower(sim.init_state(), ids).compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    has_kernels = "tpu_custom_call" in hlo

    t0 = time.perf_counter()
    res = sim.run(targets=(0.99,), device_fracs=(0.99,), verbose=False)
    jax.block_until_ready(res.final_state)
    wall_s = time.perf_counter() - t0

    accs = [m.global_acc for m in res.metrics]
    log(f"[{name}] params={sim._fspec.num_params:,} K={sim.data.num_clients}"
        f" S={sim._num_sel} rounds={[m.round for m in res.metrics]}")
    log(f"[{name}] acc per block={accs} priority="
        f"{[m.priority for m in res.metrics]} commits={res.metrics[-1].commits}"
        f" tpu_custom_call={has_kernels}")
    log(f"[{name}] observed: compile {compile_s:.1f}s, run of 2 blocks "
        f"{wall_s:.1f}s")
    check(len(res.metrics) == 2 and res.metrics[-1].round == 4,
          f"{name}: expected 2 blocks ending at round 4")
    check(all(np.isfinite(a) and 0.0 <= a <= 1.0 for a in accs),
          f"{name}: accuracy out of range {accs}")
    check(res.metrics[-1].commits == 4, f"{name}: expected 4 commits")
    leaves = jax.tree.leaves(res.final_params)
    check(all(bool(jnp.all(jnp.isfinite(x))) for x in leaves),
          f"{name}: non-finite final params")
    if want_kernels:
        check(has_kernels, f"{name}: no tpu_custom_call in the compiled "
              "round block — the Mosaic kernels did not run")
    return res, hlo


def compare(a, b, what: str) -> None:
    for ma, mb in zip(a, b):
        diff = abs(ma.global_acc - mb.global_acc)
        log(f"[compare] {what} round {ma.round}: acc {ma.global_acc:.4f} vs "
            f"{mb.global_acc:.4f} (|diff| {diff:.4f}, tol {ACC_TOL}); "
            f"priority {ma.priority} vs {mb.priority}")
        check(diff <= ACC_TOL, f"{what}: accuracy differs by {diff:.4f} "
              f"> {ACC_TOL} at round {ma.round}")
        check(ma.commits == mb.commits and ma.sim_time == mb.sim_time,
              f"{what}: commit count or virtual clock differ")


def main_path_phase(size: dict, rehearsal: bool) -> None:
    from repro.data.synthetic import make_synth_femnist

    t0 = time.perf_counter()
    data = make_synth_femnist(num_clients=size["clients"],
                              mean_samples=size["mean_samples"], seed=0)
    log(f"[data] SynthFEMNIST K={data.num_clients} max_local="
        f"{data.images.shape[1]} ({time.perf_counter() - t0:.1f}s)")
    pytree, _ = run_path("pytree", make_sim(data, size["hidden"], False),
                         want_kernels=False)
    flat, _ = run_path("flat", make_sim(data, size["hidden"], True),
                       want_kernels=not rehearsal)
    compare(pytree.metrics, flat.metrics, "pytree vs flat")


def kernel_phase(S: int, N: int, rehearsal: bool) -> None:
    """Each flat kernel, compiled (``interpret=False``) on the chip, in
    interpret mode for the rehearsal, against its jnp twin."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import krum, ref
    from repro.kernels.divergence import divergence_sq
    from repro.kernels.quantize import qagg, qagg_ref, quantize_blockwise
    from repro.kernels.trimmed import trimmed_agg
    from repro.kernels.weighted_agg import weighted_agg

    interp = rehearsal
    keys = jax.random.split(jax.random.key(7), 3)
    x = jax.random.normal(keys[0], (S, N), jnp.float32)
    g = jax.random.normal(keys[1], (N,), jnp.float32)
    w = jax.random.uniform(keys[2], (S,), jnp.float32, 0.1, 1.0)
    w = w / jnp.sum(w)
    trim = max(1, S // 10)
    q, scales = jax.jit(quantize_blockwise, static_argnums=1)(x, "int8")

    # (name, kernel, its jnp twin, arguments, rtol, atol)
    cases = [
        ("weighted_agg", functools.partial(weighted_agg, interpret=interp),
         ref.weighted_agg_ref, (x, w), 1e-5, 1e-5),
        ("divergence_sq", functools.partial(divergence_sq, interpret=interp),
         ref.divergence_ref, (x, g), 1e-5, 0.0),
        ("qagg", functools.partial(qagg, interpret=interp),
         qagg_ref, (q, scales, w), 1e-5, 1e-5),
        ("trimmed_agg",
         functools.partial(trimmed_agg, trim=trim, interpret=interp),
         functools.partial(ref.trimmed_agg_ref, trim=trim), (x, w),
         1e-5, 1e-5),
        ("pairwise_sq_dists",
         functools.partial(krum.pairwise_sq_dists, interpret=interp),
         ref.pairwise_sq_dists_ref, (x,), 1e-4, 0.0),
    ]
    for name, kernel, twin, args, rtol, atol in cases:
        t0 = time.perf_counter()
        out = jax.block_until_ready(kernel(*args))
        k_s = time.perf_counter() - t0
        # the twins are the f32 reference: no reduced-precision matmul pass
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(jax.jit(twin)(*args))
        out, want = np.asarray(out), np.asarray(want)
        tol = np.maximum(atol + rtol * np.abs(want), np.finfo(np.float32).tiny)
        err = float(np.max(np.abs(out - want) / tol))
        log(f"[kernel] {name} [{S}, {N:,}] -> {out.shape}: max err/tol "
            f"{err:.3f} (rtol {rtol}, atol {atol}); observed: compile+run "
            f"{k_s:.1f}s")
        check(out.shape == want.shape and np.all(np.isfinite(out)),
              f"{name}: bad output")
        check(err <= 1.0, f"{name}: differs from its jnp twin")


def mesh_phase(clients: int, hidden: int, mean_samples: int,
               rehearsal: bool) -> None:
    import jax
    import jax.numpy as jnp

    from repro.data.synthetic import make_synth_femnist
    from repro.launch.mesh import make_host_mesh

    data = make_synth_femnist(num_clients=clients, mean_samples=mean_samples,
                              seed=0)
    mesh = make_host_mesh()
    log(f"[mesh] {dict(mesh.shape)} over {mesh.devices.ravel().tolist()}")
    sharded_sim = make_sim(data, hidden, True, mesh=mesh)
    s_loc = sharded_sim._num_sel // sharded_sim._shard.num_shards
    sharded, hlo = run_path("sharded", sharded_sim,
                            want_kernels=not rehearsal)
    carry = sharded.final_state
    log(f"[mesh] carry placement: last_sync[K] {carry.last_sync.sharding}; "
        f"params[N] {carry.params.sharding}")
    wave = f"f32[{s_loc},{sharded_sim._fspec.num_params}]"
    log(f"[mesh] per-device wave block {wave} in the compiled round block: "
        f"{wave in hlo}")
    check(wave in hlo, "the compiled block holds no per-device wave block")
    check(len(carry.last_sync.sharding.device_set) == len(jax.devices()),
          "the [K] carry is not spread over the mesh")
    del sharded_sim, carry, hlo
    with jax.default_device(jax.devices()[0]):
        single, _ = run_path("single", make_sim(data, hidden, True),
                             want_kernels=not rehearsal)
    compare(sharded.metrics, single.metrics, "sharded vs single chip")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the mesh-sharded flat path on 4 chips vs 1")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="toy sizes on the CPU (kernels in interpret mode)")
    args = ap.parse_args()

    want = 4 if args.four_chips else 1
    dev, count = device_phase(args.cpu_rehearsal, want)

    from repro.federated.sampler import num_selected
    from repro.utils.compile_cache import use_compile_cache

    log(f"[cache] {use_compile_cache()}")
    size = TOY if args.cpu_rehearsal else PAPER
    t0 = time.perf_counter()
    if args.four_chips:
        mesh_phase(MESH_CLIENTS["toy" if args.cpu_rehearsal else "paper"],
                   size["hidden"], size["mean_samples"], args.cpu_rehearsal)
    else:
        main_path_phase(size, args.cpu_rehearsal)
        gc.collect()
        # the paper's cohort S at the paper CNN's width
        S = num_selected(PAPER["clients"], 0.1)
        kernel_phase(S, 5_000 if args.cpu_rehearsal else 6_603_710,
                     args.cpu_rehearsal)
    log(f"[done] observed: all phases {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
