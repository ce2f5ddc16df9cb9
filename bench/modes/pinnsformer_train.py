"""PINNsFormer training: the program's own jitted Adam step, driven for a
window, as ``modes/train.py`` drives the MLP cells.

Set-up makes the weights from the seed in the plain reference's layout
(``bench/reference/pinnsformer.py``) and hands the program the same numbers
in its own layout; builds the step with ``train_operator`` (network
``pinnsformer``, no Adam steps of its own); makes ``point_sets``
collocation sets from the seed; and drives the step through its first three
steps on three different sets.  A short burst of waited-for steps gives the
step time; the window then calls the step on the next sets in turn, up to
``harness.AHEAD_S`` seconds of steps in flight, and ends with
``block_until_ready`` on the parameters.  A traced run sends steps for only
``trace_seconds``.

The check: the reference follows the same three steps from the same
weights (nested-``jvp`` derivatives of every token's output with respect to
the point, the residual written out, plain Adam; the residual's squares
summed over blocks of points so that the towers fit), and the same numbers
as ``modes/train.py`` are read: the first loss, the worst loss, the first
gradient and the parameters' change, leaf by leaf in the program's
layout.

A program without the ``pinnsformer`` network fails at once, in
``train_operator``.
"""

from __future__ import annotations

import time
from collections import deque
from pathlib import Path

import numpy as np

from bench import harness, traffic
from bench.reference import mlp
from bench.reference import pinnsformer as ref
from bench.work import pinnsformer as work

train = harness.load_module(Path(__file__).with_name("train.py"))
FIRST_STEPS = train.FIRST_STEPS
REF_BLOCK = 125           # most reference points per block of its residual


def net_config(cfg: dict) -> dict:
    """The sizes the reference and the work count read."""
    keys = ("d_in", "d_out", "width", "depth", "n_heads", "ff", "head",
            "tokens", "step")
    return {k: cfg[k] for k in keys}


# ---------------------------------------------- weights, in both layouts

def to_program(w):
    """Reference layout -> the program's ``PINNsFormer`` parameters."""
    def attn(a):
        d = a["in_w"].shape[0]
        out = {"w" + n: a["in_w"][:, i * d:(i + 1) * d]
               for i, n in enumerate("qkv")}
        out.update({"b" + n: a["in_b"][i * d:(i + 1) * d]
                    for i, n in enumerate("qkv")})
        out.update(wo=a["out"]["w"], bo=a["out"]["b"])
        return out

    def mlp3(m):
        lin = lambda p: (p["w"], p["b"])
        return (lin(m["l0"]), m["act0"], lin(m["l1"]), m["act1"],
                lin(m["l2"]))

    def layer(p):
        return {"wave_attn": p["act1"], "attn": attn(p["attn"]),
                "wave_ff": p["act2"], "ff": mlp3(p["ff"])}

    return {"embed": (w["embed"]["w"], w["embed"]["b"]),
            "encoder": tuple(layer(p) for p in w["encoder"]),
            "encoder_wave": w["encoder_act"],
            "decoder": tuple(layer(p) for p in w["decoder"]),
            "decoder_wave": w["decoder_act"],
            "head": mlp3(w["head"])}


def leaves(params):
    """The program's parameters (or a pytree of their shape) as a list of
    host arrays.  The program's layout keeps the q, k and v projections
    apart, so the key bias, whose gradient is zero but for rounding
    (softmax ignores a shift shared by a row), is a leaf of its own and
    ``train.compare`` leaves its change out."""
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(params)]


def weights(cfg: dict, seed: int):
    """(reference weights on the host, program parameters on the device),
    the same numbers, made on the device in one jitted call from the
    seed."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(cfg["dtype"])
    make = jax.jit(lambda key: (lambda w: (w, to_program(w)))(
        ref.init(key, net_config(cfg), dtype)))
    w, params = make(harness.seed_key(seed, 0))
    return jax.tree_util.tree_map(np.asarray, w), params


# --------------------------------------------------------- the reference

_LOSS_AND_GRAD: dict = {}


def reference_steps(cfg, w, xs, bc, loss_weights, lr, precision="highest",
                    batch_share=1.0):
    """The reference's first steps: (losses, first gradient, change of the
    parameters), as host arrays in the program's layout (:func:`leaves`).
    ``batch_share`` < 1 keeps only the leading share of each set's points
    (a fault, for the control)."""
    import jax
    import jax.numpy as jnp

    net = net_config(cfg)
    w = jax.tree_util.tree_map(jnp.asarray, w)
    bc = jnp.asarray(bc)
    state, losses, first = mlp.adam_init(w), [], None
    params = w
    for x in xs:
        x = jnp.asarray(x)[: int(round(batch_share * x.shape[0]))]
        block = max(b for b in range(1, min(x.shape[0], REF_BLOCK) + 1)
                    if x.shape[0] % b == 0)
        key = (tuple(sorted(net.items())), tuple(sorted(loss_weights.items())),
               precision, block)
        if key not in _LOSS_AND_GRAD:
            _LOSS_AND_GRAD[key] = ref.loss_and_grad(net, loss_weights, block,
                                                    precision)
        loss, g = _LOSS_AND_GRAD[key](params, x, bc)
        losses.append(float(loss))
        first = g if first is None else first
        params, state = mlp.adam_step(params, g, state, lr)
    delta = [a - b for a, b in zip(leaves(to_program(params)),
                                   leaves(to_program(w)))]
    return losses, leaves(to_program(first)), delta


# ------------------------------------------------------------ the program

def build(cell, seed):
    """The program's step, the weights in both layouts, the point sets and
    the boundary: everything the window and the check share."""
    from repro.pinn import LossWeights, OperatorRunConfig, train_operator

    cfg, tr = cell.config, cell.traffic
    net = net_config(cfg)
    w, params = weights(cfg, seed)
    res = train_operator(OperatorRunConfig(
        op=harness.program_operator(cell), network=cfg["network"],
        width=cfg["width"], depth=cfg["depth"], activation=cfg["activation"],
        net_kwargs={k: net[k] for k in ("n_heads", "ff", "head", "tokens",
                                        "step")},
        n_domain=tr["points"], n_bc=tr["boundary_per_face"], adam_steps=0,
        adam_lr=tr["lr"], engine=cfg["engine"], seed=seed % 2 ** 31,
        eval_pts_per_axis=2,
        weights=LossWeights(residual=tr["loss_weights"]["residual"],
                            bc=tr["loss_weights"]["boundary"])))
    sets = traffic.point_sets(seed, 1, ref.DOMAIN, tr["points"],
                              tr["point_sets"], cfg["dtype"])
    bc = train.boundary_grid(ref.DOMAIN, tr["boundary_per_face"])
    return w, params, res.train_step, sets, bc


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
            grad = [m / (1.0 - mlp.ADAM_B1) for m in leaves(s.m)]
    jax.block_until_ready(p)
    delta = [a - b for a, b in zip(leaves(p), leaves(params))]
    return ([float(x) for x in losses], grad, delta), p, s


def run(cell, seed: int, seconds: float, trace: bool, t_start: float):
    import jax

    cfg, tr = cell.config, cell.traffic
    w, params, step, sets, bc = build(cell, seed)
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
    want = reference_steps(cfg, w, host_sets, bc, tr["loss_weights"],
                           tr["lr"])
    harness.log(f"reference: {time.perf_counter() - t_ref:.1f} s")
    checks = harness.checks(cell, train.compare(got, want))
    net, mixed = net_config(cfg), ref.MIXED
    return harness.RunOutput(
        attempted=steps, failed=0,
        end_to_end={"train_step_ms": window / steps * 1e3, "setup_s": setup_s},
        checks=checks, memory_peak_bytes=mem,
        layer={"steps": steps, "window_s": clock.get("window_s", window),
               "flops_per_step": work.train_step_flops(
                   net, tr["points"], len(bc), ref.ORDER, mixed),
               "kernel_calls_per_step": work.table_calls(
                   net, tr["points"], ref.ORDER, mixed),
               "flash_calls_per_step": work.flash_calls(
                   net, tr["points"], ref.ORDER, mixed)},
        trace=clock.get("trace"))
