"""One run of one benchmark cell.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a model
configuration and a traffic mix; each is a data file found by its name:

* ``chipbench/configs/<config>.json``: the model and the client data;
* ``chipbench/traffic/<traffic>.json``: the round recipe (cohort
  fraction, batch, local epochs, learning rate, criteria, Algorithm-1);
* ``chipbench/limits/<cell>.json``: the limit of each number compared;
* ``chipbench/models/<model.kind>.py``: the model's initial weights,
  plain forward pass and operation counts (the contract below);
* ``chipbench/metrics/<metric>.py``: one reader per per-layer metric.

A model file defines ``init_params(m, key)``, the flat dict of the
leaves clients train, made on the device in one jitted call from the
run's seed key; ``forward(params, x, precision)`` and ``loss(params, x,
y, precision)``, the plain model the reference trains and evaluates;
``forward_flops(m)``, the operations of one row's forward pass;
``num_params(m)``; and ``program_fns()``, the system under test's
``(loss_fn, acc_fn)``.  Optional hooks, each defaulting to what a model
without it gets:

* ``init_shared(m, key) -> dict``: weights every client holds and no
  client trains, such as a frozen base.  Default: none.  Its key is the
  seed key folded with :data:`SHARED_FOLD`.  Where the file defines it,
  the program (through ``make_sim``) and the reference both get the
  weights, and ``forward``, ``loss`` and ``row_scores`` take them as a
  ``shared=`` keyword.  What is compared (``observe``, the change of the
  weights, the aggregation) reads only ``init_params``' leaves.
* ``make_sim(fds, params0, shared, sim_cfg)``: builds the
  ``FederatedSimulation``, so that shared weights can reach the program
  as an argument rather than as constants compiled into it.  Default:
  ``FederatedSimulation(fds, params0, *program_fns(), sim_cfg)``.
* ``row_scores(params, x, y, precision[, shared]) -> [B]``: each test
  row's score in [0, 1], the per-row score the program's ``acc_fn``
  averages.  Default: ``argmax(forward(...)) == y``.
* ``train_flops(m)``: the operations of one training row's forward and
  backward passes.  Default: ``3 * forward_flops(m)``.

A run builds one ``FederatedSimulation`` (the flat path), drives its
``run`` through the first ``checked_rounds`` rounds from the seeded
model (compiling, or loading from the cache, and warming up), and
records what they produced.  The same object then runs the measured
window: one ``run`` of as many rounds as fill ``--seconds`` at the
warm-up's pace.  A traced run then reads the op-to-layer table of the
window's own round block (``chipbench/layers.py``).  After the window
the program is freed and the plain reference
(``chipbench/reference.py``) follows the checked rounds, with shared
weights made anew from the seed.
"""
from __future__ import annotations

import gc
import importlib.util
import itertools
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
#: what never being met keeps ``run`` from stopping early
NEVER = dict(targets=(2.0,), device_fracs=(1.0,))
#: folded into the seed key for a model's shared weights
SHARED_FOLD = 0x5EED


class Refused(SystemExit):
    """The run cannot be made here; nothing is printed on stdout."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: Path, name: str):
    if not path.is_file():
        raise KeyError(f"no {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_module(config: dict):
    kind = config["model"]["kind"]
    return _module(HERE / "models" / f"{kind}.py", f"chipbench_model_{kind}")


def metric_reader(name: str):
    return _module(HERE / "metrics" / f"{name}.py",
                   "chipbench_metric_" + name.replace(".", "_"))


def find_cell(workload: str, bench: Optional[dict] = None) -> dict:
    """The workload entry with its configuration, traffic and limits."""
    bench = bench if bench is not None else load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    return {
        "workload": w,
        "config": load_json(HERE / "configs" / f"{w['config']}.json"),
        "traffic": load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        "limits": load_json(HERE / "limits" / f"{workload}.json"),
        "per_layer": [m for m in bench["per_layer"]
                      if workload in m.get("workloads", [workload])],
        "end_to_end": [m for m in bench["end_to_end"]
                       if workload in m.get("workloads", [workload])],
    }


def peaks_for(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in chipbench/peaks.json "
                      f"(have {sorted(table)})")
    return table[kind]


def accelerator(chips: int):
    """The chip this cell runs on, or :class:`Refused`."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no accelerator: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    peaks_for(devs[0].device_kind)
    return devs


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    import jax

    seed %= 2**64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def init_shared(model, m: dict, seed: int):
    """The model's shared weights for ``seed``, or ``None`` where its file
    defines no ``init_shared``."""
    import jax

    make = getattr(model, "init_shared", None)
    if make is None:
        return None
    return make(m, jax.random.fold_in(seed_key(seed), SHARED_FOLD))


def train_flops(model, m: dict) -> int:
    """Operations of one training row's forward and backward passes."""
    count = getattr(model, "train_flops", None)
    return count(m) if count is not None else 3 * model.forward_flops(m)


def recipe(config: dict, traffic: dict, counts: np.ndarray) -> dict:
    """The round recipe the reference and the program both follow."""
    k = len(counts)
    s = max(1, min(k, int(round(traffic["fraction"] * k))))
    b = traffic["batch_size"]
    steps = max(1, int(counts.max()) // b) * traffic["local_epochs"]
    return {
        "S": s, "batch_size": int(b), "steps": steps,
        "local_epochs": traffic["local_epochs"], "lr": traffic["lr"],
        "criteria": list(traffic["criteria"]),
        "priority": list(traffic["priority"]),
        "online_adjust": bool(traffic["online_adjust"]),
        "sim_seed": config["sim_seed"],
        "checked_rounds": traffic["checked_rounds"],
    }


class CompileCounter:
    """Counts executables built or loaded from the cache, and cache misses."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax

        self.builds = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == self.BUILD:
            self.builds += 1

    def _event(self, event, **kw):
        if event == self.MISS:
            self.misses += 1


def configure_jax() -> None:
    """JAX's persistent compilation cache, in the checkout."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(HERE / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # a round program carries the client data as constants: no size cap
    jax.config.update("jax_compilation_cache_max_size", -1)


def run_program(sim, config: dict):
    """One ``run`` of the program, at the configuration's precision, to
    its last round's state."""
    import jax

    from chipbench import precision

    with precision.program(config):
        res = sim.run(verbose=False, **NEVER)
    jax.block_until_ready(res.final_state)
    return res


def build_sim(cell: dict, data, params0: dict, rec: dict, shared=None):
    """The cell's ``FederatedSimulation``, built by the model file's
    ``make_sim`` where it has one."""
    from repro.core import AggregationConfig
    from repro.data.synthetic import FederatedDataset
    from repro.federated import FedSimConfig, FederatedSimulation

    model = model_module(cell["config"])
    fds = FederatedDataset(
        images=data.images, labels=data.labels, counts=data.counts,
        test_images=data.test_images, test_labels=data.test_labels,
        test_counts=data.test_counts)
    cfg = FedSimConfig(
        fraction=cell["traffic"]["fraction"], batch_size=rec["batch_size"],
        local_epochs=rec["local_epochs"], lr=rec["lr"],
        max_rounds=rec["checked_rounds"], eval_every=1,
        aggregation=AggregationConfig(criteria=tuple(rec["criteria"]),
                                      priority=tuple(rec["priority"])),
        online_adjust=rec["online_adjust"], seed=rec["sim_seed"],
        flat_params=True)
    if hasattr(model, "make_sim"):
        return model.make_sim(fds, params0, shared, cfg)
    return FederatedSimulation(fds, params0, *model.program_fns(), cfg)


def observe(res) -> dict:
    """What the checked rounds produced, on the host; a priority order as
    its index among the permutations, in ``itertools`` order."""
    perms = list(itertools.permutations(range(len(res.metrics[0].priority))))
    return {
        "acc": [m.global_acc for m in res.metrics],
        "priority": [perms.index(tuple(m.priority)) for m in res.metrics],
        "entropy": [m.weights_entropy for m in res.metrics],
        "commits": res.metrics[-1].commits if res.metrics else 0,
        "params": {k: np.asarray(v, np.float32)
                   for k, v in res.final_params.items()},
    }


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def execute(cell: dict, seed: int, seconds: float, trace: bool,
            t_start: float, devices=None, data=None) -> dict:
    """One run of ``cell``; returns the result object.  ``devices=None``
    skips the look for a chip (the tests' CPU runs)."""
    import jax

    from chipbench import data as datasets
    from chipbench import layers, reference
    from chipbench.trace import WINDOW, Trace

    counter = CompileCounter()
    dev = (devices or jax.devices())[0]
    config, model = cell["config"], model_module(cell["config"])
    if data is None:
        data = datasets.load(config["dataset"])
    rec = recipe(config, cell["traffic"], data.counts)
    params0 = model.init_params(config["model"], seed_key(seed))
    w0 = {k: np.asarray(v, np.float32) for k, v in params0.items()}
    sim = build_sim(cell, data, params0, rec,
                    init_shared(model, config["model"], seed))

    # the checked rounds: the window's own call, timed block by block
    stamps = []
    run_block = sim._run_block

    def timed(*a, **kw):
        stamps.append(time.perf_counter())
        return run_block(*a, **kw)

    sim._run_block = timed
    res = run_program(sim, config)
    stamps.append(time.perf_counter())
    sim._run_block = run_block
    observed = observe(res)
    del res
    blocks = np.diff(stamps)          # the first compiles or loads
    block_s = float(np.min(blocks[1:] if len(blocks) > 1 else blocks))
    rounds = max(1, int(round(seconds / block_s)))
    sim.cfg.max_rounds = rounds
    builds_setup, misses_setup = counter.builds, counter.misses
    trace_dir = HERE / ".traces" / cell["workload"]["name"]
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    setup_s = time.perf_counter() - t_start

    with jax.profiler.TraceAnnotation(WINDOW):
        t0 = time.perf_counter()
        res = run_program(sim, config)
        window_s = time.perf_counter() - t0
    window_builds = counter.builds - builds_setup
    counter.close()
    if trace:
        jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    # the TPU runtime holds a program's temporaries as reserved memory,
    # apart from the buffers it counts as in use
    peak = int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))
    committed = int(res.final_state.commits)
    updates = sum(m.participants for m in res.metrics[:committed])
    attempted = rounds * rec["S"]
    del res
    op_table = layers.build_table(sim, config) if trace else None
    del sim
    jax.clear_caches()
    gc.collect()
    log(f"[setup] {setup_s:.3f} s; executables built or loaded "
        f"{builds_setup}, cache misses {misses_setup}; checked blocks "
        f"{[round(b, 4) for b in blocks]} s -> {rounds} rounds in the "
        f"window; memory {stats}")
    log(f"[window] {window_s:.4f} s, {committed} commits, {updates} "
        f"updates, compilations inside {window_builds}")

    t0 = time.perf_counter()
    nums = reference.Reference(data, model, rec).check(
        observed, w0, init_shared(model, config["model"], seed))
    log(f"[reference] {time.perf_counter() - t0:.1f} s over "
        f"{rec['checked_rounds']} rounds; program's accuracy "
        f"{observed['acc']}")
    for k in sorted(set(nums) - set(cell["limits"])):
        log(f"[reading] {k} {nums[k]!r} (not compared)")
    nums["window_compiles"] = float(window_builds)
    nums["checked_commits_missing"] = float(rec["checked_rounds"]
                                            - observed["commits"])
    limits = dict(cell["limits"], window_compiles=0.0,
                  checked_commits_missing=0.0)
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    correct = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
                  for v in checks.values())

    result = {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": attempted - updates,
        "metrics": {},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices or jax.devices()),
                   "memory_peak_bytes": peak},
    }
    e2e = {"updates_per_s": (updates / window_s, "updates/s"),
           "peak_hbm_gib": (peak / 2**30, "GiB"),
           "setup_s": (setup_s, "s")}
    if not trace:
        for m in cell["end_to_end"]:
            value, unit = e2e[m["name"]]
            result["metrics"][m["name"]] = {"value": value, "unit": unit}
    else:
        tr = Trace.from_dir(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if op_table is not None:
            layers.log_coverage(tr, *op_table)
        ctx = {"trace": tr, "config": config, "recipe": rec,
               "rounds": rounds, "model": model, "op_layers": op_table,
               "test_rows": int(data.test_counts.sum()),
               "peaks": peaks_for(dev.device_kind)
               if devices is not None else None}
        for m in cell["per_layer"]:
            value = metric_reader(m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = tr.busy_s()
        result["device"]["window_s"] = tr.window_s
        result["breakdown"] = tr.breakdown()
    result["checks"] = checks
    for k, v in checks.items():
        log(f"[check] {k} {v['value']!r} limit {v['limit']!r}")
    return result


def main(argv, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = find_cell(args.workload)
        devices = accelerator(cell["workload"]["chips"])
    except (Refused, KeyError, FileNotFoundError) as e:
        log(f"[refused] {e}")
        return 2
    configure_jax()
    result = execute(cell, args.seed, args.seconds, bool(args.trace),
                     t_start, devices=devices)
    print(json.dumps(result))
    return 0
