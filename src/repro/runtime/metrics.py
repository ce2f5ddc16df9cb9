"""Latency/counter metrics shared by the runtime and serving layers, and
the program's layer scopes and compile counters.

A :class:`LatencyStats` is a thread-safe sliding-window reservoir of float
samples (seconds) with percentile snapshots -- the serving layer records
queue waits and end-to-end latencies into these, and the benchmark harness
reuses :func:`percentile` for its p50/p99 rows so both report the same
quantile definition (linear interpolation, numpy's default).

:func:`scope` names one layer of the derivative and training path
(``ntp.grid``, ``optim.adam``, ...): traced under ``jit`` its name becomes
the ``op_name`` prefix of every device operation inside it, in a profiler
trace it is a host event, and its host seconds and calls add up in an
in-process registry.  The same registry keeps, per jitted function, what
JAX's compile events report: jaxpr tracing, lowering to MLIR and the XLA
compile (or persistent-cache load), and plain counters (:func:`count`,
such as the jet rows a derivative table runs).  :func:`snapshot` reads it
all, :func:`reset` empties it.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Dict, Iterator, Sequence, Tuple

import jax
import numpy as np


def percentile(samples: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile of ``samples``; 0.0 when empty."""
    if not samples:
        return 0.0
    return float(np.percentile(np.asarray(samples, dtype=np.float64), q))


class LatencyStats:
    """Sliding-window latency reservoir (thread-safe).

    ``record`` keeps the last ``window`` samples for percentiles while the
    count/total accumulate over the full lifetime, so long-running servers
    report recent tail latency but exact request counts.
    """

    def __init__(self, window: int = 4096):
        self._samples: deque = deque(maxlen=window)
        self._lock = threading.Lock()
        self.count = 0
        self.total = 0.0

    def record(self, seconds: float) -> None:
        with self._lock:
            self._samples.append(float(seconds))
            self.count += 1
            self.total += float(seconds)

    def snapshot(self) -> Dict[str, float]:
        """{count, mean_us, p50_us, p99_us} over the window (us = 1e-6 s)."""
        with self._lock:
            samples = list(self._samples)
            count, total = self.count, self.total
        return {
            "count": count,
            "mean_us": (total / count * 1e6) if count else 0.0,
            "p50_us": percentile(samples, 50) * 1e6,
            "p99_us": percentile(samples, 99) * 1e6,
        }


# ---------------------------------------------------------------------------
# layer scopes and compile counters
# ---------------------------------------------------------------------------

# JAX's compile events -> the registry's kind of count
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}

_lock = threading.Lock()
_counts: Dict[str, Dict[str, Tuple[int, float]]] = {
    k: {} for k in ("span", *COMPILE_EVENTS.values(), "counter")}


def _add(kind: str, name: str, value: float) -> None:
    with _lock:
        n, s = _counts[kind].get(name, (0, 0.0))
        _counts[kind][name] = (n + 1, s + value)


def count(name: str, value: float) -> None:
    """Add ``value`` to the counter ``name``: its registry entry is
    (calls, total)."""
    _add("counter", name, value)


@contextlib.contextmanager
def scope(name: str) -> Iterator[None]:
    """Name one layer: ``jax.named_scope`` (the device ``op_name``) and a
    ``jax.profiler.TraceAnnotation`` (a host event), and add the host
    seconds spent inside to the registry under ``("span", name)``.  Under
    ``jit`` those seconds are the layer's tracing, not its device time."""
    t0 = time.perf_counter()
    try:
        with jax.named_scope(name), jax.profiler.TraceAnnotation(name):
            yield
    finally:
        _add("span", name, time.perf_counter() - t0)


def _on_compile_event(event: str, seconds: float, **kwargs) -> None:
    """Count a compile event under the function's own name: tracing
    reports ``name``, lowering and compiling ``jit(name)``.  Other
    lowerings (``pmap(name)``) are left out; a nested jit's events count
    under its own name, not its caller's."""
    kind = COMPILE_EVENTS.get(event)
    name = kwargs.get("fun_name")
    if kind is None or name is None:
        return
    if kind != "trace":
        if not (name.startswith("jit(") and name.endswith(")")):
            return
        name = name[4:-1]
    _add(kind, name, seconds)


def snapshot() -> Dict[str, Dict[str, Tuple[int, float]]]:
    """``{kind: {name: (count, seconds)}}`` for the kinds ``span`` (a
    :func:`scope`), ``trace``, ``lower`` and ``compile`` (per jitted
    function), and ``{name: (calls, total)}`` under ``counter`` (a
    :func:`count`), since start-up or the last :func:`reset`."""
    with _lock:
        return {k: dict(v) for k, v in _counts.items()}


def reset() -> None:
    with _lock:
        for v in _counts.values():
            v.clear()


# once per process, on import: before the program's first compile
jax.monitoring.register_event_duration_secs_listener(_on_compile_event)
