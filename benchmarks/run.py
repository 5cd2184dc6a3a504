"""Benchmark harness: one module per paper table/figure + kernel microbenches.

Prints ``name,us_per_call,derived`` CSV.  With ``--json-dir`` every bench
whose ``main`` returns a record additionally lands a machine-readable
``BENCH_<name>.json`` (step-time p50/p95, structural pass counts, ...) so
future PRs can diff perf instead of re-parsing logs.  Roofline terms come
from the dry-run artifacts (launch/dryrun.py --out) — see
benchmarks/roofline_table.py for the aggregation used in EXPERIMENTS.
"""

from __future__ import annotations

import argparse
import sys
import time

from .common import write_bench_json


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--json-dir", default=None,
                    help="write BENCH_<name>.json artifacts here")
    args = ap.parse_args(argv)

    from . import (bench_fp4, bench_kernels, bench_lm_quant, bench_opt_step,
                   bench_penalty_placement, bench_quadratic,
                   bench_train_robustness, bench_twolayer)

    benches = {
        "kernels": bench_kernels.main,
        "quadratic": bench_quadratic.main,
        "twolayer": bench_twolayer.main,
        "lm_quant": (lambda: bench_lm_quant.main(fast=args.fast)),
        "fp4": bench_fp4.main,
        "penalty_placement": (
            lambda: bench_penalty_placement.main(fast=args.fast)),
        "opt_step": (lambda: bench_opt_step.main(fast=args.fast)),
        # registered as "train" so the JSON artifact lands as
        # BENCH_train.json — the name check_regression.py gates
        "train": (lambda: bench_train_robustness.main(fast=args.fast)),
    }
    only = set(args.only.split(",")) if args.only else None
    failed = []
    print("name,us_per_call,derived")
    for name, fn in benches.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            rec = fn()
            if args.json_dir is not None and isinstance(rec, dict):
                print(f"wrote {write_bench_json(name, rec, args.json_dir)}")
        except Exception as e:  # run the rest, then exit nonzero
            failed.append(name)
            print(f"{name}_FAILED,0,{type(e).__name__}:{e}", file=sys.stderr)
            print(f"{name}_failed,0,error={type(e).__name__}")
        print(f"bench_{name}_total,{(time.time()-t0)*1e6:.0f},wall")
    if failed:
        print(f"benches failed: {','.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
