"""Readings that set each cell's limits, and the control that must fail them.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

For every seed, in one process, the numbers a run compares and the verdict
of the run's own comparison (``harness.checks`` against the cell's limits):

* ``program``: read from the program's timed path against the reference
  (the limit's lower reading);
* ``control``: the reference itself, computed at ``"high"`` precision
  (bfloat16_3x: XLA's ``Precision.HIGH`` on a TPU, the step below the
  configuration's ``highest``), put in the program's place (the limit's
  upper reading); it has to come out not correct on every seed it reads,
  the first three;
* training cells also read, on those seeds, a planted fault: half of each batch left out
  (the loss taken over the rest).  A step that returns its state unchanged
  reads 1 on ``update_gap`` by construction.  Each training line also
  gives every first step's loss gap and the number of gradient elements
  whose sign differs from the reference's.

No window is needed.  Each line printed is one JSON object; the last holds
the largest program reading, the smallest control and fault readings of
each number, and how many seeds of each came out not correct.  The
benchmark's own runs never run this.
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

from bench import harness, system, traffic  # noqa: E402

# the control and the faults read on the first seeds only; the program on
# every seed (its largest reading over a dozen or more sets the limits)
CONTROL_SEEDS = 3


def train_readings(cell, seeds, program=True):
    mode = harness.mode_module(cell)
    tr = cell.traffic
    op = harness.reference_operator(cell)
    bc = mode.boundary_grid(op.DOMAIN, tr["boundary_per_face"])
    if program:
        _, _, _, step, _, _ = mode.build(cell, seeds[0])
    for seed in seeds:
        layers, params = system.weights(cell.config, seed)
        sets = traffic.point_sets(seed, 1, op.DOMAIN, tr["points"],
                                  mode.FIRST_STEPS, cell.config["dtype"])
        xs = [np.asarray(x) for x in sets]
        args = (layers, xs, bc, op, tr["loss_weights"], tr["lr"])
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
            steps, flips = mode.loss_gaps(have, want)
            yield seed, who, mode.compare(have, want), {
                "loss_gap_steps": steps, "sign_flips": flips}


def table_readings(cell, seeds, program=True):
    mode = harness.mode_module(cell)
    tr = cell.traffic
    for seed in seeds:
        layers, params, grid, sets = mode.build(cell, seed)
        x = np.asarray(sets[0])
        want = mode.reference_table(layers, x, tr["order"])
        runs = []
        if seed in seeds[:CONTROL_SEEDS]:
            runs = [("control", lambda: mode.reference_table(
                layers, x, tr["order"], "high"))]
        if program:
            runs.insert(0, ("program", lambda: np.asarray(grid(params,
                                                                sets[0]))))
        for who, get in runs:
            have = get()
            yield seed, who, [("table_gap", mode.table_gap(have, want)),
                              ("table_rms_gap",
                               mode.table_rms_gap(have, want))], {}


READINGS = {"train": train_readings, "table": table_readings}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    harness.check_device(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",")]
    worst: dict = {}
    failed: dict = {}
    t0 = time.perf_counter()
    for seed, who, nums, extra in READINGS[cell.traffic["mode"]](cell, seeds):
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
