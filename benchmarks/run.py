"""Benchmark harness entry point (``python -m benchmarks.run``).

One section per paper table/figure:
  * Table 1 (studies A/B/C) — reduced-scale reproduction on SynthFEMNIST
    (``benchmarks/table1.py`` runs the full sweep; here we run a compact
    A + C slice so the harness finishes in CPU-budget time).
  * Figure 1 behaviour — the online-adjustment trace (backtracking events)
    is exercised inside study C and reported as a derived column.

Dry-run/roofline numbers are produced by ``python -m repro.launch.dryrun``
(they need the 512-device XLA override and are therefore not run from
here); see EXPERIMENTS.md §Dry-run / §Roofline.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale CI slice: every section still runs "
                         "and every BENCH_roundloop.json key is emitted, "
                         "but at toy sizes (and table1 is skipped)")
    ap.add_argument("--out", default=str(ROOT / "BENCH_roundloop.json"),
                    help="where to write the roundloop results JSON")
    args = ap.parse_args(argv)

    print("# === round loop: dispatch modes x aggregation strategies ===",
          flush=True)
    from benchmarks import roundloop

    roundloop_results = roundloop.main(smoke=args.smoke)
    bench_out = Path(args.out)
    bench_out.write_text(json.dumps(roundloop_results, indent=2) + "\n")
    print(f"# roundloop results -> {bench_out}", flush=True)

    if args.smoke:
        return

    print("# === paper Table 1 (reduced scale; see benchmarks/table1.py "
          "--full for the complete sweep) ===", flush=True)
    t0 = time.time()
    env_argv = sys.argv
    sys.argv = ["table1", "--study", "A", "--clients", "24", "--rounds", "16",
                "--out", "table1_slice.json"]
    try:
        from benchmarks import table1

        table1.main()
    finally:
        sys.argv = env_argv
    print(f"# table1 slice done in {time.time() - t0:.0f}s", flush=True)


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
