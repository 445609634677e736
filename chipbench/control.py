#!/usr/bin/env python3
"""Readings the limits of ``correct`` are set from, on the chip.

    python3 chipbench/control.py --workload <cell> --seeds 12 --control-seeds 3

In one process, at the cell's own sizes:

* the program: one ``FederatedSimulation`` built as a run builds it
  (one per seed where the model has shared weights), driven through
  the checked rounds from each of ``--seeds`` seeded models (the same
  compiled program and call as a run's, at the configuration's
  precision), each judged by the float32 reference: these give each
  number's *lower* reading;
* the control: the reference itself one precision below the
  configuration's (three bfloat16 passes for float32 at ``highest``,
  bfloat16 for float32 at the default), in the program's place, judged
  the same way;
* faults, each in the reference put in the program's place: half of
  the cohort left out of the aggregation (the weights renormalised over
  the rest); the reported accuracy altered where it is made (one test
  row in ten miscounted: 0.1 off); and, where Algorithm-1 is on, its
  rule picking the worst candidate.  A model left unchanged reads 1 on
  ``change_gap`` by construction and needs no run.

Every reading is printed on standard output as one JSON line.  The
benchmark's runs never run this.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_011)
    args = ap.parse_args()

    import numpy as np

    from chipbench import data as datasets
    from chipbench import harness, precision, reference

    harness.configure_jax()
    cell = harness.find_cell(args.workload)
    harness.accelerator(cell["workload"]["chips"])
    config = cell["config"]
    model = harness.model_module(config)
    data = datasets.load(config["dataset"])
    rec = harness.recipe(config, cell["traffic"], data.counts)
    rounds = rec["checked_rounds"]
    judge = reference.Reference(data, model, rec)

    def emit(kind, seed, nums, extra=None):
        print(json.dumps(dict(kind=kind, seed=seed, **nums, **(extra or {}))),
              flush=True)

    def seeded(seed):
        p = model.init_params(config["model"], harness.seed_key(seed))
        return (p, {k: np.asarray(v, np.float32) for k, v in p.items()},
                harness.init_shared(model, config["model"], seed))

    sim = None
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    for seed in seeds:
        params0, w0, shared = seeded(seed)
        if sim is None or shared is not None:   # shared weights are built in
            sim = harness.build_sim(cell, data, params0, rec, shared)
        sim.params = params0
        t0 = time.perf_counter()
        res = harness.run_program(sim, config)
        observed = harness.observe(res)
        t1 = time.perf_counter()
        nums = judge.check(observed, w0, shared)
        emit("program", seed, nums, {
            "program_s": t1 - t0, "reference_s": time.perf_counter() - t1,
            "acc": observed["acc"], "priority": observed["priority"]})
        del res
    del sim

    def altered(acc):
        """An accuracy one test row in ten off."""
        return acc - 0.1 if acc >= 0.1 else acc + 0.1

    class HalfCohort(reference.Reference):
        @staticmethod
        def prioritized_weights(c, perm):
            keep = np.arange(c.shape[0]) < c.shape[0] // 2
            w = reference.Reference.prioritized_weights(c, perm) * keep
            return w / w.sum()

    class WorstPick(reference.Reference):
        rule = staticmethod(lambda q, prev_q, cur: int(np.argmin(q)))

    dtype, prec = precision.control_of(config)
    systems = [("control", reference.Reference(data, model, rec, dtype=dtype,
                                               precision=prec)),
               ("fault_half", HalfCohort(data, model, rec))]
    if rec["online_adjust"]:
        systems.append(("fault_worst_pick", WorstPick(data, model, rec)))
    for seed in seeds[:args.control_seeds]:
        _, w0, shared = seeded(seed)
        for kind, system in systems:
            t0 = time.perf_counter()
            emit(kind, seed, judge.check(system.run(w0, rounds, shared=shared),
                                         w0, shared),
                 {"s": time.perf_counter() - t0})
        # the float32 reference's own trajectory, its accuracies altered
        sound = judge.run(w0, rounds, shared=shared)
        emit("fault_answer", seed, judge.check(
            dict(sound, acc=[altered(a) for a in sound["acc"]]), w0, shared))


if __name__ == "__main__":
    main()
