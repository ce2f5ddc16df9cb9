"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; everything it
needs is found by name under ``bench/`` (see ``bench/harness.py``).  Set-up
makes the weights and inputs from ``--seed`` on the device, warms every
shape the window uses (JAX's persistent compile cache lives in the
checkout, so only a checkout's first run compiles), then measures for
``--seconds``.  With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
result carries the per-layer metrics, ``busy_s``/``window_s`` and a
breakdown.  After the window the timed path's output is compared with a
plain reference; each compared number is printed beside its limit, last on
standard error and last in the result line.  The last line of standard
output is one JSON object.  A run that finds no TPU exits non-zero and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import harness  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def breakdown(trace) -> dict:
    return {"device_ops": [[n, s] for n, s in trace.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in trace.top_gaps(10)]}


def result_line(cell, out, device, trace: bool) -> dict:
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": out.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    else:
        ctx = dict(out.layer, trace=out.trace, device=device,
                   peaks=harness.peaks(device["kind"], cell.root))
        for m in cell.per_layer:
            value = harness.metric_reader(cell, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": cell.chips, "memory_peak_bytes": out.memory_peak_bytes}
    line = {"correct": harness.correct(out.checks),
            "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = out.trace.busy_s
        dev["window_s"] = out.layer["window_s"]
        line["breakdown"] = breakdown(out.trace)
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


def main(argv=None) -> int:
    faulthandler.enable()        # a fatal signal prints the Python stack
    args = parse(argv)
    cell = harness.find_cell(args.workload)
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    device = harness.check_device(cell.chips)
    harness.peaks(device["kind"])                # unknown kind: fail now
    mode = harness.mode_module(cell)
    out = mode.run(cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), t_start=T_START)
    line = result_line(cell, out, device, bool(args.trace))
    for c in out.checks:
        harness.log(f"check {c.name}: {c.value!r} limit {c.limit!r} "
                    f"{'ok' if c.ok else 'FAILED'}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
