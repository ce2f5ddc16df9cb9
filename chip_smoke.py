"""Smoke test of the main path on a TPU: n-TangentProp PINN training under the
fused Pallas kernels, derivative tables checked three ways, and derivative
serving.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # data-parallel path on four chips

The one-chip run has four phases, each reporting on a line of its own:

* training: Adam steps of ``train_operator`` under ``ntp/pallas`` on the
  pinn-pde model (dense 3x32 tanh) for ``navier-stokes`` (order 4, with the
  ``psi_xxyy`` polarization cross), and on its transformer trunk (2 heads,
  RMSNorm) for ``heat``, so flash-jet attention and ``jet_rms_norm`` run;
* kernels: every compiled train step holds ``tpu_custom_call``, i.e. the
  Pallas kernels ran compiled, not interpreted and not through the jnp
  reference;
* correctness: at the trained parameters the order-4 ``grid`` table and a
  ``cross`` agree across ``ntp/pallas``, ``ntp`` and ``autodiff``;
* serving: a ``DerivativeServer`` answers coalesced ``grid(order=4)``
  requests and a ``cross`` request, each equal to a direct call at the
  bucket shape.

``--chips 4`` instead trains data-parallel over four chips and checks
``ShardedEngine`` tables against the single-device ones bit for bit.

Weights and points come from fixed seeds; nothing is downloaded.  Any
failure exits non-zero before the last line, which on success is one JSON
object naming the device.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Per-order error bound between engines, relative to the order's largest
# magnitude.  f32 engines agree to ~1e-6 (CPU, f32 vs an f64 oracle); a
# matmul rounded to bf16 (eps 3.9e-3) or a wrong kernel lands far above.
ERROR_BOUND = 1e-4
GRID_ORDER = 4
NS_CROSS = (0, 0, 1, 1)         # psi_xxyy, the navier-stokes order-4 cross
HEAT_CROSS = (0, 1)             # u_tx
N_POINTS = 256                  # correctness-phase query points
SERVE_REQUESTS = (40, 56, 24, 64)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def device_check(chips: int):
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log("device", f"platform={dev['platform']} kind={dev['kind']} "
                  f"count={dev['count']}")
    if dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev['platform']!r} devices")
    if dev["count"] < chips:
        raise SystemExit(f"--chips {chips} needs {chips} devices, JAX found "
                         f"{dev['count']}")
    return dev


def pinn_pde_config(op: str, network: str, **overrides):
    """``OperatorRunConfig`` at the pinn-pde widths (configs/pinn_pde.py)."""
    from repro.configs.pinn_pde import CONFIG
    from repro.pinn import OperatorRunConfig

    net_kwargs = {}
    if network == "transformer":
        net_kwargs = {"n_heads": CONFIG.n_heads,
                      "mlp_ratio": CONFIG.d_ff // CONFIG.d_model}
    kw = dict(op=op, engine="ntp/pallas", network=network,
              net_kwargs=net_kwargs, width=CONFIG.d_model,
              depth=CONFIG.n_layers, activation="tanh", adam_steps=10,
              log_every=1, seed=0)
    kw.update(overrides)
    return OperatorRunConfig(**kw)


def train(cfg):
    """Train, then require a finite loss that dropped."""
    import numpy as np
    from repro.pinn import train_operator

    t0 = time.perf_counter()
    res = train_operator(cfg)
    wall = time.perf_counter() - t0
    losses = np.asarray(res.loss_history)
    tag = f"{cfg.op}/{cfg.network}"
    log("train", f"{tag}: {cfg.adam_steps} Adam steps on {cfg.n_domain} "
                 f"points, loss {losses[0]:.6e} -> {losses[-1]:.6e}, "
                 f"L2 vs exact {res.l2_error:.3e}, {wall:.1f} s chip wall "
                 f"clock with compilation")
    if not np.isfinite(losses).all():
        raise AssertionError(f"{tag}: non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{tag}: loss did not drop {losses}")
    return res


def kernel_count(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def check_train_step_kernels(cfg, res) -> None:
    """The step ``train_operator`` ran, compiled for the chip, must call the
    Pallas kernels (``tpu_custom_call``)."""
    import jax
    from repro.data.collocation import sample_box
    from repro.optim import adam_init
    from repro.pinn import get_operator

    pts = sample_box(jax.random.PRNGKey(0), get_operator(cfg.op).domain,
                     cfg.n_domain)
    compiled = res.train_step.lower(res.params, adam_init(res.params),
                                    pts).compile()
    n = kernel_count(compiled)
    log("kernels", f"{cfg.op}/{cfg.network} train step: {n} "
                   f"tpu_custom_call sites")
    if n == 0:
        raise AssertionError(f"{cfg.op}/{cfg.network}: the compiled train "
                             "step calls no Pallas kernel")


def rel_errors(got, want, scale_rows):
    """max |got - want| per order, over that order's max |want|."""
    import jax.numpy as jnp
    return [float(jnp.max(jnp.abs(got[:, k] - want[:, k]))
                  / jnp.max(jnp.abs(scale_rows[:, k])))
            for k in range(want.shape[1])]


def check_engines(tag: str, net, params, op, axes) -> None:
    """Grid through ``GRID_ORDER`` and one cross, three engines, on the chip.

    Grid errors are normalized by each order's max |autodiff|.  The cross is
    a polarization sum of 2^m directional order-m derivatives, so its f32
    rounding scales with those, not with the (possibly cancelled) result:
    it is normalized by the max |pure order-m derivative|."""
    import jax
    import jax.numpy as jnp
    from repro.core.engines import DerivativeEngine
    from repro.data.collocation import sample_box

    x = sample_box(jax.random.PRNGKey(1), op.domain, N_POINTS)
    m = len(axes)
    tables = {}
    for spec in ("ntp/pallas", "ntp", "autodiff"):
        eng = DerivativeEngine.from_spec(spec)
        grid = jax.jit(lambda p, xx, e=eng: e.grid(net, p, xx, GRID_ORDER))
        cross = jax.jit(lambda p, xx, e=eng: e.cross(net, p, xx, axes))
        tables[spec] = (grid(params, x), cross(params, x))
        if spec == "ntp/pallas":
            n = kernel_count(grid.lower(params, x).compile())
            if n == 0:
                raise AssertionError(f"{tag}: ntp/pallas grid calls no "
                                     "Pallas kernel")
    ref_grid, ref_cross = tables["autodiff"]
    if not (jnp.isfinite(ref_grid).all() and jnp.isfinite(ref_cross).all()):
        raise AssertionError(f"{tag}: non-finite autodiff table")
    worst = 0.0
    for spec, want_spec in (("ntp/pallas", "autodiff"), ("ntp", "autodiff"),
                            ("ntp/pallas", "ntp")):
        g, c = tables[spec]
        wg, wc = tables[want_spec]
        errs = rel_errors(g, wg, ref_grid)
        cerr = float(jnp.max(jnp.abs(c - wc))
                     / jnp.max(jnp.abs(ref_grid[:, m])))
        log("correct", f"{tag}: {spec} vs {want_spec} grid per-order rel "
                       f"err {[f'{e:.3e}' for e in errs]}, cross{axes} "
                       f"{cerr:.3e} (bound {ERROR_BOUND:g})")
        worst = max(worst, cerr, *errs)
    if worst > ERROR_BOUND:
        raise AssertionError(f"{tag}: engines disagree by {worst:.3e} > "
                             f"{ERROR_BOUND:g}")


def check_serving(net, params, op) -> None:
    """Coalesced grid(order=4) requests and one cross through the server;
    each answer must equal a direct jitted call at the launch's bucket
    shape, pad rows sliced off."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.engines import DerivativeEngine
    from repro.data.collocation import sample_box
    from repro.serving import DerivativeServer, pad_to

    spec = "ntp/pallas"
    engine = DerivativeEngine.from_spec(spec)
    keys = jax.random.split(jax.random.PRNGKey(2), len(SERVE_REQUESTS))
    xs = [sample_box(k, op.domain, n) for k, n in zip(keys, SERVE_REQUESTS)]
    server = DerivativeServer(net, params, spec, flush_window_s=0.005,
                              autostart=False)
    try:
        # every request is queued before the worker starts, so they are
        # concurrent by construction and coalesce, in order, into one launch
        futs = [server.submit(x, order=GRID_ORDER) for x in xs]
        server.start()
        results = [f.result(timeout=600) for f in futs]
        res_cross = server.submit(xs[0], axes=NS_CROSS).result(timeout=600)
        metrics = server.metrics()
    finally:
        server.close()

    bucket = results[0].bucket
    if any(r.bucket != bucket or r.batch_rows != sum(SERVE_REQUESTS)
           for r in results):
        raise AssertionError("grid requests did not coalesce into one launch")
    grid = jax.jit(lambda p, xx: engine.grid(net, p, xx, GRID_ORDER))
    direct = grid(params, pad_to(jnp.concatenate(xs), bucket))
    offset = 0
    for r, x in zip(results, xs):
        want = direct[:, :, offset:offset + x.shape[0]]
        offset += x.shape[0]
        if not np.array_equal(np.asarray(r.table), np.asarray(want)):
            raise AssertionError("served grid differs from the direct call")
    cross = jax.jit(lambda p, xx: engine.cross(net, p, xx, NS_CROSS))
    want = cross(params, pad_to(xs[0], res_cross.bucket))[:xs[0].shape[0]]
    if not np.array_equal(np.asarray(res_cross.table), np.asarray(want)):
        raise AssertionError("served cross differs from the direct call")
    lat = metrics["latency"]
    log("serve", f"{len(results)} grid(order={GRID_ORDER}) requests "
                 f"({sum(SERVE_REQUESTS)} rows) in one launch at bucket "
                 f"{bucket} + cross{NS_CROSS} at bucket {res_cross.bucket}: "
                 f"bit-equal to direct calls; {metrics['batches']} launches, "
                 f"latency p50 {lat['p50_us']:.0f} us p99 {lat['p99_us']:.0f}"
                 f" us (chip, first launches include compilation)")


def one_chip() -> None:
    from repro.pinn import get_operator

    ns_cfg = pinn_pde_config("navier-stokes", "dense")
    heat_cfg = pinn_pde_config("heat", "transformer")
    ns = train(ns_cfg)
    heat = train(heat_cfg)
    check_train_step_kernels(ns_cfg, ns)
    check_train_step_kernels(heat_cfg, heat)
    check_engines("navier-stokes/dense", ns.net, ns.params,
                  get_operator("navier-stokes"), NS_CROSS)
    check_engines("heat/transformer", heat.net, heat.params,
                  get_operator("heat"), HEAT_CROSS)
    check_serving(ns.net, ns.params, get_operator("navier-stokes"))


def four_chips() -> None:
    """Data-parallel training over four chips, then ``ShardedEngine`` grid
    and cross tables against the single-device launch: bit parity is the
    contract (README, Distributed)."""
    import jax
    import numpy as np
    from repro.core.engines import NTPEngine
    from repro.data.collocation import sample_box
    from repro.parallel.jet_shard import ShardedEngine, resolve_mesh
    from repro.pinn import get_operator

    cfg = pinn_pde_config("navier-stokes", "dense", data_parallel=4)
    res = train(cfg)
    op = get_operator(cfg.op)
    single = NTPEngine("pallas")
    sharded = ShardedEngine(single, resolve_mesh(data_parallel=4))
    # the trained parameters come back replicated over the mesh; the
    # single-device reference takes its own copy on one chip (a Pallas
    # kernel cannot be partitioned over a mesh outside shard_map)
    params_1 = jax.device_put(res.params, jax.devices()[0])
    mismatched = []
    for n in (N_POINTS, N_POINTS + 1):      # divisible, and padded by 3 rows
        x = sample_box(jax.random.PRNGKey(1), op.domain, n)
        for what, call in (
                ("grid", lambda e: jax.jit(
                    lambda p, xx: e.grid(res.net, p, xx, GRID_ORDER))),
                (f"cross{NS_CROSS}", lambda e: jax.jit(
                    lambda p, xx: e.cross(res.net, p, xx, NS_CROSS)))):
            want = np.asarray(call(single)(params_1, x))
            got = np.asarray(call(sharded)(res.params, x))
            diff = float(np.max(np.abs(got - want)))
            log("sharded", f"{what} on {n} points, 4 chips vs 1: max abs "
                           f"diff {diff!r}")
            if not np.array_equal(got, want):
                mismatched.append(f"{what} on {n} points by {diff!r}")
    if mismatched:
        raise AssertionError("sharded tables differ from the single-device "
                             "ones: " + "; ".join(mismatched))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the data-parallel "
                         "path and its single-device comparison")
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"no repro package under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.runtime.compile_cache import enable_compile_cache

    log("setup", f"compile cache at {enable_compile_cache()}")
    dev = device_check(args.chips)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    log("done", f"all phases passed in {time.perf_counter() - t0:.1f} s "
                f"(chip wall clock, compilation included)")
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
