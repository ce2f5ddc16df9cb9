"""What every cell shares: finding its files by name, the device, the clock,
the traced window and the check of its numbers against their limits.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Its files are
found by name alone, so a later change adds a configuration, a traffic mix,
a mode or a per-layer metric as a new file, and edits none:

* ``bench/configs/<config>.json``          sizes, operator, precision
* ``bench/workloads/<traffic>.json``       the traffic mix; ``mode`` names
* ``bench/modes/<mode>.py``                the driver of that kind of traffic
* ``bench/limits/<cell>.json``             the limit of each compared number
* ``bench/layer_metrics/<metric>.py``      ``read(ctx)`` of one per-layer metric
* ``bench/reference/<operator>.py``        the operator's plain reference
* ``bench/operators/<operator>.py``        where present, registers with the
                                            program an operator it lacks
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path

    @property
    def bench(self) -> Path:
        return self.root / "bench"


@dataclass
class Check:
    """One compared number beside its limit; it passes when finite and at
    most the limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class RunOutput:
    """What a mode hands back to ``run.py``."""

    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    checks: List[Check]
    memory_peak_bytes: int
    layer: dict = field(default_factory=dict)    # inputs of the readers
    trace: Optional["object"] = None             # trace_reduce.Summary


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve a cell of ``BENCHMARK.json`` and the files it names."""
    spec = _json(root / "BENCHMARK.json")
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have "
                       f"{[w['name'] for w in spec['workloads']]})")
    bench = root / "bench"
    return Cell(
        name=name, chips=int(entry["chips"]),
        config=_json(bench / "configs" / f"{entry['config']}.json"),
        traffic=_json(bench / "workloads" / f"{entry['traffic']}.json"),
        limits=_json(bench / "limits" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        root=root)


def load_module(path: Path):
    """Import a file by path (names hold dots and dashes, so not by
    ``import``)."""
    name = "bench_" + "".join(c if c.isalnum() else "_"
                              for c in f"{path.parent.name}_{path.stem}")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def mode_module(cell: Cell):
    return load_module(cell.bench / "modes" / f"{cell.traffic['mode']}.py")


def reference_operator(cell: Cell):
    return load_module(cell.bench / "reference"
                       / f"{cell.config['operator']}.py")


def program_operator(cell: Cell) -> str:
    """The operator's name in the program's registry, after registering it
    from ``bench/operators/<operator>.py`` where the benchmark brings it."""
    name = cell.config["operator"]
    path = cell.bench / "operators" / f"{name}.py"
    if path.exists():
        from repro.pinn.operators import operator_names

        if name not in operator_names():
            load_module(path)
    return name


def metric_reader(cell: Cell, name: str) -> Callable[[dict], Optional[float]]:
    return load_module(cell.bench / "layer_metrics" / f"{name}.py").read


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    table = _json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]


class NoAccelerator(SystemExit):
    pass


def check_device(chips: int) -> dict:
    """The device JAX sees; no TPU, or fewer chips than the cell asks for,
    ends the run before anything is timed."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {dev['platform']!r} devices")
    if dev["count"] < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{dev['count']}")
    return dev


# a window asks for this many seconds of device work dispatched ahead of
# the host, so that the host standing still for less leaves the chip busy;
# the depth comes from a short burst of waited-for calls in set-up.  The
# TPU runtime itself holds about 32 executions in flight and makes the
# host wait beyond that, so at the cells' step times the runtime's limit
# is the one that binds (PERF.md)
AHEAD_S = 4.0
PACE_S = 0.25


def depth(calls: int, seconds: float) -> int:
    """Calls to keep in flight for ``AHEAD_S`` seconds of device work, from
    ``calls`` waited-for calls that took ``seconds``."""
    n = max(2, int(AHEAD_S * calls / seconds))
    log(f"in flight: {n} calls ({seconds / calls * 1e3:.3f} ms a call)")
    return n


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    import jax

    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in jax.local_devices()]
    return int(max(peaks_))


def seed_key(seed: int, stream: int):
    """A JAX key for one stream of a run's inputs, from a seed of any size
    (the low 32 bits seed the key, the rest and the stream fold in)."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


@contextlib.contextmanager
def traced(enabled: bool, out: dict):
    """Run the body under the profiler when ``enabled``; on exit put the
    reduced trace in ``out["trace"]`` and the traced window's host-clock
    length in ``out["window_s"]``.  The raw trace is deleted once read."""
    if not enabled:
        yield
        return
    import jax

    from bench import trace_reduce

    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            out["window_s"] = time.perf_counter() - t0
            jax.profiler.stop_trace()
        out["trace"] = trace_reduce.reduce_dir(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def checks(cell: Cell, numbers) -> List[Check]:
    """The numbers the cell's limits file names, each beside its limit.  A
    number the file does not name is not compared (``PERF.md`` says why);
    it is logged as a reading."""
    out = []
    for name, value in numbers:
        if name in cell.limits:
            out.append(Check(name, value, cell.limits[name]))
        else:
            log(f"reading {name}: {value!r} (not compared)")
    return out


def correct(checks_: List[Check]) -> bool:
    """A run is correct when every compared number is within its limit."""
    return all(c.ok for c in checks_)


def rel_gap(got: float, want: float) -> float:
    return abs(got - want) / abs(want)


def leaf_norm_gap(got: List, want: List, keep: Optional[List[bool]] = None
                  ) -> float:
    """Worst leaf's gap between two norms: | |got_l| - |want_l| | over the
    larger of |want_l| and the median leaf's |want|."""
    import numpy as np

    g = np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                  for x in got])
    w = np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                  for x in want])
    med = float(np.median(w))
    sel = np.ones(len(w), bool) if keep is None else np.asarray(keep)
    return float(np.max(np.abs(g - w)[sel] / np.maximum(w, med)[sel]))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def free_device_memory() -> None:
    """Drop compiled programs and collect garbage so that the reference
    that follows runs on a chip the program has left."""
    import gc

    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()
