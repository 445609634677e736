#!/usr/bin/env python3
"""Readings of answer faults at the scale of a cell's own scores.

    python3 chipbench/scaled_answer.py --workload <cell> --seeds 3

``control.py`` alters the reported accuracy by 0.1, which says nothing
of a limit on a cell whose scores are far smaller: a language-model
row's mean probability of its targets is ~1/V from a random base.  Here
the float32 reference's own trajectory from each seeded model is
reported with every accuracy 1% off, and as 0, and judged as a run is
judged (``Reference.check`` against the cell's limits).  Each reading
is printed on standard output as one JSON line, with the sound
trajectory's accuracies and whether the faulted one would be
``correct``.  The benchmark's runs never run this.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

FAULTS = {"one_percent_off": lambda a: 0.99 * a, "zero_scores": lambda a: 0.0}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_011)
    args = ap.parse_args()

    import numpy as np

    from chipbench import data as datasets
    from chipbench import harness, reference

    harness.configure_jax()
    cell = harness.find_cell(args.workload)
    harness.accelerator(cell["workload"]["chips"])
    config = cell["config"]
    model = harness.model_module(config)
    data = datasets.load(config["dataset"])
    rec = harness.recipe(config, cell["traffic"], data.counts)
    judge = reference.Reference(data, model, rec)
    limits = cell["limits"]

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        p = model.init_params(config["model"], harness.seed_key(seed))
        w0 = {k: np.asarray(v, np.float32) for k, v in p.items()}
        shared = harness.init_shared(model, config["model"], seed)
        sound = judge.run(w0, rec["checked_rounds"], shared=shared)
        for kind, alter in FAULTS.items():
            t0 = time.perf_counter()
            nums = judge.check(dict(sound, acc=[alter(a) for a in
                                                sound["acc"]]), w0, shared)
            failed = sorted(k for k, v in nums.items()
                            if k in limits and not v <= limits[k])
            print(json.dumps(dict(kind=kind, seed=seed, **nums,
                                  acc=sound["acc"], failed=failed,
                                  correct=not failed,
                                  s=time.perf_counter() - t0)), flush=True)


if __name__ == "__main__":
    main()
