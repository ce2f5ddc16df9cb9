"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Fast mode keeps CPU wall time sane;
pass --full for the paper-scale grids, --smoke for the CI completeness check
(tiny shapes, one trial -- benchmark code must at least *run* on every PR so
it cannot rot uncollected).  ``--json PATH`` additionally writes the rows as
structured records (suite, name, us_per_call, mode, derived) -- the CI
tier-1 job uploads that file as a ``BENCH_*.json`` artifact on every commit
so the perf trajectory is machine-readable, and ``benchmarks/compare.py``
gates PRs on its coverage against ``benchmarks/baseline_smoke.json``.

  PYTHONPATH=src python -m benchmarks.run [--full|--smoke] [--only NAME]
                                          [--json PATH]
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback

BENCH_SCHEMA_VERSION = 1


def parse_row(suite: str, mode: str, row: str) -> dict:
    """One ``name,us_per_call,derived`` CSV line -> a structured record."""
    name, us, derived = row.split(",", 2)
    return {"suite": suite, "name": name, "us_per_call": float(us),
            "mode": mode, "derived": derived}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / single trial; used by the CI tier-1 "
                         "job to keep benchmark code importable and runnable")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write structured results (suite, name, "
                         "us_per_call, mode, derived) to PATH")
    args = ap.parse_args()
    if args.full and args.smoke:
        ap.error("--full and --smoke are mutually exclusive")

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (burgers_e2e, fwd_bwd, memory_scaling, operators_bench,
                   partition_growth, ratio_grid, serving_bench)

    mode = "smoke" if args.smoke else ("full" if args.full else "fast")
    # one entry per suite: (runner, {mode: kwargs}) -- a new suite added here
    # is automatically part of the CI --smoke completeness check
    registry = {
        "partition_growth": (partition_growth.run, {
            "smoke": dict(max_order=8), "fast": dict(max_order=16),
            "full": dict(max_order=16)}),
        "fwd_bwd": (fwd_bwd.run, {
            "smoke": dict(max_order=3, trials=1),
            "fast": dict(max_order=5, trials=3),
            "full": dict(max_order=8, trials=5)}),
        "ratio_grid": (ratio_grid.run, {
            "smoke": dict(trials=1), "fast": dict(trials=2),
            "full": dict(trials=3)}),
        "memory_scaling": (memory_scaling.run, {
            "smoke": dict(max_order=4), "fast": dict(max_order=6),
            "full": dict(max_order=6)}),
        "operators": (operators_bench.run, {
            # smoke carries the network axis (residual + transformer on the
            # representative op) so every registered trunk stays coverage-
            # gated per commit, like every operator x engine pair
            "smoke": dict(n_pts=16, width=8, depth=2, trials=1,
                          include_pallas=True,
                          network_axis=operators_bench.NETWORK_AXIS),
            "fast": dict(n_pts=256, trials=2, include_pallas=False),
            "full": dict(n_pts=1024, trials=5, include_pallas=True,
                         network_axis=operators_bench.NETWORK_AXIS)}),
        "serving": (serving_bench.run, {
            # rate axis (RATES) is mode-independent so row names -- and the
            # compare.py coverage gate derived from them -- stay stable
            mode_key: dict(kw) for mode_key, kw
            in serving_bench.MODE_KWARGS.items()}),
        "burgers_e2e": (burgers_e2e.run, {
            "smoke": dict(adam_steps=4, lbfgs_steps=2),
            "fast": dict(adam_steps=40, lbfgs_steps=8),
            "full": dict(adam_steps=200, lbfgs_steps=40)}),
    }
    if args.only and args.only not in registry:
        ap.error(f"unknown suite {args.only!r}; known: "
                 f"{', '.join(sorted(registry))}")
    suites = {name: (lambda fn=fn, kw=kws[mode]: fn(**kw))
              for name, (fn, kws) in registry.items()}
    print("name,us_per_call,derived")
    records = []
    failed_suites = []
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        try:
            for row in fn():
                print(row)
                sys.stdout.flush()
                records.append(parse_row(name, mode, row))
        except Exception:
            traceback.print_exc()
            failed_suites.append(name)

    if args.json:
        payload = {
            "schema_version": BENCH_SCHEMA_VERSION,
            "mode": mode,
            "only": args.only,
            "failed_suites": failed_suites,
            "results": records,
        }
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(f"wrote {len(records)} records to {args.json}", file=sys.stderr)

    sys.exit(1 if failed_suites else 0)


if __name__ == "__main__":
    main()
