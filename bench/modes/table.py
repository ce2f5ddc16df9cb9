"""Derivative tables, closed loop: the program's jitted ``engine.grid`` over
a fixed number of points, one call after another (up to
``harness.AHEAD_S`` seconds of calls in flight, paced in set-up as in
``modes/train.py``), on
``point_sets`` sets made from the seed and taken in turn.

The check: the outputs of a seed-drawn sample of the window's calls (about
one in ``sample_every``, and the last) are kept, and once the window has
closed each is compared with the plain reference's table of its point set
(nested ``jvp``), order by order, as the largest gap over that order's
largest reference value.  A traced run sends calls for only
``trace_seconds`` and waits for them.
"""

from __future__ import annotations

import time
from collections import deque

import numpy as np

from bench import harness, system, traffic, work
from bench.reference import mlp

REF_BLOCK = 8192          # reference rows per call


_PURE_TABLE: dict = {}


def reference_table(layers, x, order, precision="highest"):
    """(d_in, order+1, N, d_out) pure derivatives by the reference, in
    blocks."""
    import jax
    import jax.numpy as jnp

    if (order, precision) not in _PURE_TABLE:
        _PURE_TABLE[order, precision] = jax.jit(
            lambda ls, xb: mlp.pure_table(ls, xb, order, precision))
    f = _PURE_TABLE[order, precision]
    ls = [(jnp.asarray(w), jnp.asarray(b)) for w, b in layers]
    x = np.asarray(x)
    return np.concatenate([np.asarray(f(ls, x[i:i + REF_BLOCK]))
                           for i in range(0, len(x), REF_BLOCK)], axis=2)


def _by_order(a):
    """(d_in, order+1, N, d_out) -> (order+1, everything else)."""
    a = np.asarray(a, np.float64)
    return np.moveaxis(a, 1, 0).reshape(a.shape[1], -1)


def table_gap(got, want) -> float:
    """Largest |got - want| of each order over that order's largest
    |want|, worst order."""
    got, want = _by_order(got), _by_order(want)
    return float(np.max(np.abs(got - want).max(1) / np.abs(want).max(1)))


def table_rms_gap(got, want) -> float:
    """|got - want| over |want| of each order (2-norms over every entry),
    worst order."""
    got, want = _by_order(got), _by_order(want)
    return float(np.max(np.linalg.norm(got - want, axis=1)
                        / np.linalg.norm(want, axis=1)))


def build(cell, seed):
    import jax

    from repro.core.engines import DerivativeEngine

    cfg, tr = cell.config, cell.traffic
    op = harness.reference_operator(cell)
    layers, params = system.weights(cfg, seed)
    net = system.network(cfg)
    engine = DerivativeEngine.from_spec(cfg["engine"])
    order = tr["order"]
    grid = jax.jit(lambda p, x: engine.grid(net, p, x, order))
    sets = traffic.point_sets(seed, 1, op.DOMAIN, tr["points"],
                              tr["point_sets"], cfg["dtype"])
    return layers, params, grid, sets


def run(cell, seed: int, seconds: float, trace: bool, t_start: float):
    cfg, tr = cell.config, cell.traffic
    layers, params, grid, sets = build(cell, seed)
    grid(params, sets[0]).block_until_ready()          # warm the one shape
    t, n = time.perf_counter(), 0
    while time.perf_counter() - t < harness.PACE_S:
        grid(params, sets[n % len(sets)]).block_until_ready()
        n += 1
    ahead = harness.depth(n, time.perf_counter() - t)
    rng = np.random.default_rng(seed)
    if trace:
        seconds = min(seconds, tr["trace_seconds"])
    clock: dict = {}
    kept = {}                                          # call index -> table
    with harness.traced(trace, clock):
        t0 = time.perf_counter()
        calls, inflight = 0, deque()
        while True:
            out = grid(params, sets[calls % len(sets)])
            if rng.random() * tr["sample_every"] < 1.0:
                kept[calls] = out
            calls += 1
            inflight.append(out)
            if len(inflight) > ahead:
                inflight.popleft().block_until_ready()
            if time.perf_counter() - t0 >= seconds:
                break
        out.block_until_ready()
        window = time.perf_counter() - t0
    kept[calls - 1] = out
    setup_s = t0 - t_start
    mem = harness.memory_peak_bytes()

    host_sets = [np.asarray(x) for x in sets]
    kept = {i: np.asarray(t) for i, t in kept.items()}
    del out, inflight, sets, grid, params
    harness.free_device_memory()

    t_ref = time.perf_counter()
    refs = {}
    gap = rms = 0.0
    for i, table in sorted(kept.items()):
        s = i % len(host_sets)
        if s not in refs:
            refs[s] = reference_table(layers, host_sets[s], tr["order"])
        gap = max(gap, table_gap(table, refs[s]))
        rms = max(rms, table_rms_gap(table, refs[s]))
    harness.log(f"reference: {time.perf_counter() - t_ref:.1f} s")
    checks = harness.checks(cell, [("table_gap", gap),
                                   ("table_rms_gap", rms)])
    calls_per_table = work.table_calls(cfg, tr["points"], tr["order"])
    return harness.RunOutput(
        attempted=calls, failed=0,
        end_to_end={"table_points_per_s": calls * tr["points"] / window,
                    "setup_s": setup_s},
        checks=checks, memory_peak_bytes=mem,
        layer={"calls": calls, "window_s": clock.get("window_s", window),
               "flops_per_call": sum(c.flops for c in calls_per_table),
               "kernel_calls_per_call": calls_per_table,
               "compared_calls": len(kept)},
        trace=clock.get("trace"))
