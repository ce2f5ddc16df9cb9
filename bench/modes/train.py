"""PINN training: the program's own jitted Adam step, driven for a window.

Set-up builds the step with ``train_operator`` (no Adam steps of its own),
makes the weights and ``points_sets`` collocation sets from the seed, and
drives the step through its first three steps on three different sets.
A short burst of steps, each waited for, gives the step time; the window
then calls the same step on the next sets in turn, up to
``harness.AHEAD_S`` seconds of steps in flight, and when its time is up
sends no more and ends with ``block_until_ready`` on the parameters: every
step sent counts, over the time to that wait's end.
A traced run sends steps for only ``trace_seconds`` and waits for them (a
trace of the whole window would run to hundreds of megabytes).

The check: a plain reference (``bench/reference``: nested-``jvp``
derivatives, the residual written out, plain Adam) follows the same three
steps from the same weights.  Read, each as a relative gap: the first
step's loss, the worst of the three steps' losses, the first gradient (from
the optimizer's first moment after step one) and the parameters' change
over the three steps, both leaf by leaf (see :func:`compare`).  The cell's
limits file names the ones compared.
"""

from __future__ import annotations

import time
from collections import deque

import numpy as np

from bench import harness, system, traffic, work
from bench.reference import mlp

FIRST_STEPS = 3
# a leaf whose reference gradient is below this share of the median leaf's
# moves under Adam by rounding alone; its change is not compared
GRAD_FLOOR = 1e-3


def boundary_grid(domain, n_per_face: int) -> np.ndarray:
    """Points on every face of a box, laid out as the program's trainer
    lays them out: on each face an evenly spaced tensor grid of
    round(n_per_face ** (1 / (d - 1))) points per other axis, faces in the
    order (axis 0 low, axis 0 high, axis 1 low, ...)."""
    d = len(domain)
    n_side = max(2, int(round(n_per_face ** (1.0 / (d - 1)))))
    faces = []
    for a in range(d):
        others = [i for i in range(d) if i != a]
        lines = [np.linspace(domain[i][0], domain[i][1], n_side,
                             dtype=np.float32) for i in others]
        rest = np.stack([m.ravel() for m in np.meshgrid(*lines,
                                                        indexing="ij")], -1)
        for side in domain[a]:
            pts = np.zeros((len(rest), d), np.float32)
            pts[:, others], pts[:, a] = rest, side
            faces.append(pts)
    return np.concatenate(faces)


_VALUE_AND_GRAD: dict = {}


def _value_and_grad(op, weights, precision):
    """The reference loss's jitted value and gradient, built once per
    operator, loss weights and precision."""
    import jax
    import jax.numpy as jnp

    key = (op.__name__, tuple(sorted(weights.items())), precision)
    if key not in _VALUE_AND_GRAD:
        def loss_fn(ls, x, bc):
            r = op.residual(ls, x, precision)
            ub = mlp.apply(ls, bc, precision)
            return (weights["residual"] * jnp.mean(r ** 2)
                    + weights["boundary"] * jnp.mean((ub - op.exact(bc)) ** 2))
        _VALUE_AND_GRAD[key] = jax.jit(jax.value_and_grad(loss_fn))
    return _VALUE_AND_GRAD[key]


def reference_steps(layers, xs, bc, op, weights, lr, precision="highest",
                    batch_share=1.0):
    """The reference's first steps: (losses, first gradient, change of the
    parameters), leaves as host arrays.  ``batch_share`` < 1 keeps only
    the leading share of each set's points (a fault, for the control)."""
    import jax.numpy as jnp

    vg = _value_and_grad(op, weights, precision)
    bc = jnp.asarray(bc)
    ls = [(jnp.asarray(w), jnp.asarray(b)) for w, b in layers]
    state, losses, first = mlp.adam_init(ls), [], None
    for x in xs:
        x = jnp.asarray(x)[: int(round(batch_share * x.shape[0]))]
        loss, g = vg(ls, x, bc)
        losses.append(float(loss))
        first = g if first is None else first
        ls, state = mlp.adam_step(ls, g, state, lr)
    delta = [np.asarray(a) - b for a, b in zip(system.leaves(ls),
                                               system.leaves(layers))]
    return losses, [np.asarray(a) for a in system.leaves(first)], delta


def compare(got, want) -> list:
    """The compared numbers of a program's (losses, gradient, change)
    against the reference's, without limits: the first step's loss, the
    worst of the three losses, the first gradient and the change, leaf by
    leaf."""
    g_loss, g_grad, g_delta = got
    w_loss, w_grad, w_delta = want
    norms = np.array([np.linalg.norm(g) for g in w_grad])
    keep = list(norms >= GRAD_FLOOR * np.median(norms))
    return [("loss1_gap", harness.rel_gap(g_loss[0], w_loss[0])),
            ("loss_gap", max(harness.rel_gap(a, b)
                             for a, b in zip(g_loss, w_loss))),
            ("grad_gap", harness.leaf_norm_gap(g_grad, w_grad)),
            ("update_gap", harness.leaf_norm_gap(g_delta, w_delta, keep))]


def loss_gaps(got, want) -> list:
    """Each first step's loss gap, and how many gradient elements have the
    other sign than the reference's (what moves the later losses)."""
    flips = sum(int(np.sum(np.sign(a) != np.sign(b)))
                for a, b in zip(got[1], want[1]))
    return [harness.rel_gap(a, b) for a, b in zip(got[0], want[0])], flips


def build(cell, seed):
    """The program's step, the weights, the point sets and the boundary:
    everything the window and the check share."""
    from repro.pinn import LossWeights, OperatorRunConfig, train_operator

    cfg, tr = cell.config, cell.traffic
    op = harness.reference_operator(cell)
    layers, params = system.weights(cfg, seed)
    res = train_operator(OperatorRunConfig(
        op=harness.program_operator(cell), network=cfg["network"], width=cfg["width"],
        depth=cfg["depth"], activation=cfg["activation"],
        n_domain=tr["points"], n_bc=tr["boundary_per_face"], adam_steps=0,
        adam_lr=tr["lr"], engine=cfg["engine"], seed=seed % 2 ** 31,
        eval_pts_per_axis=2,
        weights=LossWeights(residual=tr["loss_weights"]["residual"],
                            bc=tr["loss_weights"]["boundary"])))
    sets = traffic.point_sets(seed, 1, op.DOMAIN, tr["points"],
                              tr["point_sets"], cfg["dtype"])
    bc = boundary_grid(op.DOMAIN, tr["boundary_per_face"])
    return op, layers, params, res.train_step, sets, bc


def first_steps(step, params, sets):
    """Drive the step through its first steps: (losses, first gradient,
    change of the parameters) as host arrays, and the state it reached."""
    import jax

    from repro.optim import adam_init

    p, s, losses, grad = params, adam_init(params), [], None
    for i in range(FIRST_STEPS):
        p, s, loss = step(p, s, sets[i])
        losses.append(loss)
        if grad is None:
            grad = [np.asarray(m) / (1.0 - mlp.ADAM_B1)
                    for m in system.leaves(system.program_layers(s.m))]
    jax.block_until_ready(p)
    delta = [a - b for a, b in zip(system.leaves(system.program_layers(p)),
                                   system.leaves(system.program_layers(params)))]
    return ([float(x) for x in losses], grad, delta), p, s


def run(cell, seed: int, seconds: float, trace: bool, t_start: float):
    import jax

    cfg, tr = cell.config, cell.traffic
    op, layers, params, step, sets, bc = build(cell, seed)
    host_sets = [np.asarray(x) for x in sets[:FIRST_STEPS]]
    got, p, s = first_steps(step, params, sets)
    del params

    t, first = time.perf_counter(), FIRST_STEPS
    while time.perf_counter() - t < harness.PACE_S:
        p, s, loss = step(p, s, sets[first % len(sets)])
        loss.block_until_ready()
        first += 1
    ahead = harness.depth(first - FIRST_STEPS, time.perf_counter() - t)

    if trace:
        seconds = min(seconds, tr["trace_seconds"])
    clock: dict = {}
    with harness.traced(trace, clock):
        t0 = time.perf_counter()
        steps, inflight = 0, deque()
        while True:
            p, s, loss = step(p, s, sets[(first + steps) % len(sets)])
            steps += 1
            inflight.append(loss)
            if len(inflight) > ahead:
                inflight.popleft().block_until_ready()
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(p)
        window = time.perf_counter() - t0
    setup_s = t0 - t_start
    mem = harness.memory_peak_bytes()
    del p, s, loss, inflight, sets, step
    harness.free_device_memory()

    t_ref = time.perf_counter()
    want = reference_steps(layers, host_sets, bc, op, tr["loss_weights"],
                           tr["lr"])
    harness.log(f"reference: {time.perf_counter() - t_ref:.1f} s")
    checks = harness.checks(cell, compare(got, want))
    mixed = [tuple(m) for m in op.MIXED]
    calls = work.table_calls(cfg, tr["points"], op.ORDER, mixed)
    return harness.RunOutput(
        attempted=steps, failed=0,
        end_to_end={"train_step_ms": window / steps * 1e3, "setup_s": setup_s},
        checks=checks, memory_peak_bytes=mem,
        layer={"steps": steps, "window_s": clock.get("window_s", window),
               "flops_per_step": work.train_step_flops(
                   cfg, tr["points"], len(bc), op.ORDER, mixed),
               "kernel_calls_per_step": calls},
        trace=clock.get("trace"))
