"""Readings that set ``pfns.train``'s limits, and the control that must fail
them: ``bench/control.py`` for the PINNsFormer cell, whose weights and
reference are not the MLP's.

    python3 bench/pfns_control.py --seeds 1,2,3 [--points N]

For every seed, in one process, the numbers a run compares and the verdict
of the run's own comparison against ``bench/limits/pfns.train.json``:

* ``program``: the program's first three steps against the reference (the
  limit's lower reading), on every seed;
* ``control``: the reference computed at ``"high"`` precision (XLA's
  ``Precision.HIGH`` on a TPU, the step below the configuration's
  ``highest``) in the program's place, on the first three seeds;
* ``half_batch``: the reference with half of each batch left out, on the
  first three seeds.

Each line printed is one JSON object; the last holds the largest program
reading, the smallest control and fault readings of each number, and how
many seeds of each came out not correct.  ``--points`` overrides the
cell's collocation batch (a rehearsal at a small size).  The benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from bench import harness, traffic  # noqa: E402
from bench.control import CONTROL_SEEDS  # noqa: E402

CELL = "pfns.train"


def readings(cell, seeds, program=True):
    """(seed, who, [(name, value)], extra) for every run, as
    ``control.READINGS`` gives them."""
    mode = harness.mode_module(cell)
    cfg, tr = cell.config, cell.traffic
    bc = mode.train.boundary_grid(mode.ref.DOMAIN, tr["boundary_per_face"])
    if program:
        _, _, step, _, _ = mode.build(cell, seeds[0])
    for seed in seeds:
        w, params = mode.weights(cfg, seed)
        sets = traffic.point_sets(seed, 1, mode.ref.DOMAIN, tr["points"],
                                  mode.FIRST_STEPS, cfg["dtype"])
        xs = [np.asarray(x) for x in sets]
        args = (cfg, w, xs, bc, tr["loss_weights"], tr["lr"])
        want = mode.reference_steps(*args)
        runs = []
        if seed in seeds[:CONTROL_SEEDS]:
            runs = [("control", lambda: mode.reference_steps(
                        *args, precision="high")),
                    ("half_batch", lambda: mode.reference_steps(
                        *args, batch_share=0.5))]
        if program:
            runs.insert(0, ("program",
                            lambda: mode.first_steps(step, params, sets)[0]))
        for who, get in runs:
            have = get()
            steps, flips = mode.train.loss_gaps(have, want)
            yield seed, who, mode.train.compare(have, want), {
                "loss_gap_steps": steps, "sign_flips": flips}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--points", type=int, default=0)
    args = ap.parse_args(argv)
    cell = harness.find_cell(CELL)
    if args.points:
        cell.traffic["points"] = args.points
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    harness.check_device(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",")]
    worst: dict = {}
    failed: dict = {}
    t0 = time.perf_counter()
    for seed, who, nums, extra in readings(cell, seeds):
        correct = harness.correct(harness.checks(cell, nums))
        failed[who] = failed.get(who, 0) + (not correct)
        print(json.dumps({"seed": seed, "who": who, "correct": correct,
                          **dict(nums), **extra}), flush=True)
        for name, v in nums:
            key = f"{who}.{name}"
            pick = max if who == "program" else min
            worst[key] = pick(worst.get(key, v), v)
    print(json.dumps({"workload": cell.name, "seconds": time.perf_counter()
                      - t0, "seeds": len(seeds), "not_correct": failed,
                      "worst": worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
