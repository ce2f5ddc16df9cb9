"""End-to-end PINN training for the self-similar Burgers profiles.

Faithful to the paper's schedule: Adam warm phase, then L-BFGS with strong
Wolfe line search (the forward-pass-heavy phase where n-TangentProp shines).
``engine`` switches the derivative machinery between n-TangentProp and the
nested-autodiff baseline with everything else identical, which is exactly the
comparison in paper Fig. 6.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.core.engines import DerivativeEngine
from repro.core.jet import float_dtype
from repro.core.network import Network, make_network, token_points
from repro.core.ntp import MLPParams, num_params
from repro.data.collocation import (boundary_grid, eval_grid, resample,
                                    sample_box, uniform_grid)
from repro.optim import adam_init, adam_update, lbfgs
from repro.parallel.jet_shard import (ShardedEngine, build_sharded_train_step,
                                      resolve_mesh)
from repro.runtime.metrics import scope

from .burgers import lambda_window, profile_lambda, smoothness_order
from .losses import LossWeights, bc_targets, burgers_pinn_loss, pinn_loss
from .operators import exact_values, get_operator


@dataclass
class PINNRunConfig:
    k: int = 1                      # profile index (lam = 1/2k)
    width: int = 24                 # paper's standard PINN: 3 x 24 tanh
    depth: int = 3
    domain: float = 2.0
    n_domain: int = 512
    n_origin: int = 128
    origin_radius: float = 0.15
    adam_steps: int = 1500
    adam_lr: float = 2e-3
    lbfgs_steps: int = 300
    engine: str = "ntp"             # spec: "ntp" | "ntp/pallas" | "autodiff"
    activation: str = "tanh"
    weights: LossWeights = field(default_factory=LossWeights)
    seed: int = 0
    resample_every: int = 250
    log_every: int = 250


@dataclass
class PINNResult:
    params: MLPParams
    lam: float
    lam_history: List[float]
    loss_history: List[float]
    adam_time_s: float
    lbfgs_time_s: float
    n_params: int
    order: int
    target_lam: float               # lam of the profile the run was set for

    @property
    def lam_error(self) -> float:
        return abs(self.lam - self.target_lam)


def train(cfg: PINNRunConfig) -> PINNResult:
    dtype = float_dtype()
    key = jax.random.PRNGKey(cfg.seed)
    k_init, k_pts = jax.random.split(key)
    net = make_network("dense", d_in=1, d_out=1, width=cfg.width,
                       depth=cfg.depth, activation=cfg.activation)
    params = net.init(k_init, dtype=dtype)
    lam_raw = jnp.zeros((), dtype)
    order = smoothness_order(cfg.k)
    window = lambda_window(cfg.k)
    bc_vals = bc_targets(cfg.k, cfg.domain)

    def loss_fn(ps, pts, origin_pts):
        p, lr = ps
        return burgers_pinn_loss(p, lr, net=net, k=cfg.k, pts=pts,
                                 origin_pts=origin_pts, domain=cfg.domain,
                                 order=order, weights=cfg.weights,
                                 lam_window=window, engine=cfg.engine,
                                 bc_vals=bc_vals)

    # ---------------- Adam phase
    state = adam_init((params, lam_raw))
    pts, origin_pts = resample(k_pts, -cfg.domain, cfg.domain,
                               cfg.n_domain, cfg.n_origin, cfg.origin_radius, dtype)
    lam_hist: List[float] = []
    loss_hist: List[float] = []

    @jax.jit
    def adam_step(ps, state, pts, origin_pts):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            ps, pts, origin_pts)
        ps, state = adam_update(grads, state, ps, cfg.adam_lr)
        return ps, state, loss, aux

    ps = (params, lam_raw)
    t0 = time.perf_counter()
    for step in range(cfg.adam_steps):
        if step and step % cfg.resample_every == 0:
            k_pts, sub = jax.random.split(k_pts)
            pts, origin_pts = resample(sub, -cfg.domain, cfg.domain,
                                       cfg.n_domain, cfg.n_origin,
                                       cfg.origin_radius, dtype)
        ps, state, loss, aux = adam_step(ps, state, pts, origin_pts)
        if step % cfg.log_every == 0 or step == cfg.adam_steps - 1:
            lam_hist.append(float(aux["lambda"]))
            loss_hist.append(float(loss))
    jax.block_until_ready(ps)
    adam_time = time.perf_counter() - t0

    # ---------------- L-BFGS phase (fixed grid, full batch, as in the paper)
    grid = uniform_grid(-cfg.domain, cfg.domain, cfg.n_domain, dtype)
    ogrid = uniform_grid(-cfg.origin_radius, cfg.origin_radius, cfg.n_origin, dtype)
    vg = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))

    def vg_flat(ps):
        (loss, aux), grads = vg(ps, grid, ogrid)
        return loss, grads

    t0 = time.perf_counter()
    # the callback samples lambda only: res.loss_history already carries the
    # full per-iteration L-BFGS losses, so appending them here as well would
    # double-count the phase with interleaved every-10th duplicates
    res = lbfgs(vg_flat, ps, steps=cfg.lbfgs_steps,
                callback=lambda it, f, p: (
                    lam_hist.append(float(_lam_of(p[1], window)))
                    if it % 10 == 0 else None))
    lbfgs_time = time.perf_counter() - t0

    params, lam_raw = res.params
    lam = float(_lam_of(lam_raw, window))
    return PINNResult(params=params, lam=lam, lam_history=lam_hist,
                      loss_history=loss_hist + res.loss_history,
                      adam_time_s=adam_time, lbfgs_time_s=lbfgs_time,
                      n_params=num_params(params), order=order,
                      target_lam=profile_lambda(cfg.k))


def _lam_of(lam_raw, window):
    lo, hi = window
    return lo + (hi - lo) * jax.nn.sigmoid(lam_raw)


# ---------------------------------------------------------------------------
# generic operator training (method of manufactured solutions)
# ---------------------------------------------------------------------------

# the name of train_operator's jitted single-device step, as JAX's compile
# events report it (repro.runtime.metrics counts its tracing, lowering and
# compiling under this name); no other function of the program bears it
TRAIN_STEP_NAME = "pinn_train_step"
# the scope of train_operator's set-up: building the step, and the closing
# accuracy check with its own compile
SETUP_SCOPE = "pinn.setup"


@dataclass
class OperatorRunConfig:
    """Training config for any registered differential operator.

    ``engine`` accepts a spec string ("ntp", "ntp/pallas", "autodiff") or a
    :class:`DerivativeEngine` instance.  ``network`` names a registered
    architecture ("dense", "mlp", "residual", "fourier", "transformer",
    "pinnsformer" -- any composition over the jet-module layer, see
    ``repro.core.modules``); ``net_kwargs`` passes architecture extras (e.g.
    ``{"n_features": 32}`` for fourier, ``{"n_heads": 4, "mlp_ratio": 2}``
    for transformer, whose ``width`` must be divisible by ``n_heads``,
    ``{"ff": 256, "head": 512, "tokens": 5, "step": 1e-4}`` for
    pinnsformer, with ``activation="wave"``).  The network's output rank
    follows the operator (``op.d_out``), so multi-equation systems like
    "gray-scott" train with no extra plumbing.
    """

    op: str = "heat"
    width: int = 32
    depth: int = 3
    activation: str = "tanh"
    network: str = "dense"
    net_kwargs: Dict = field(default_factory=dict)
    n_domain: int = 1024
    n_bc: int = 64                  # boundary points per face
    adam_steps: int = 2000
    adam_lr: float = 2e-3
    lbfgs_steps: int = 0
    engine: str = "ntp"             # spec string or DerivativeEngine
    weights: LossWeights = field(default_factory=LossWeights)
    seed: int = 0
    resample_every: int = 500
    log_every: int = 500
    eval_pts_per_axis: int = 48
    # -- multi-device data parallelism (repro.parallel.jet_shard) ----------
    # data_parallel=N shards collocation batches over an (N,)-device "data"
    # mesh (0 = single-device, the default); mesh= passes an explicit mesh
    # carrying a "data" axis instead (e.g. a (4, 2) host mesh).  n_domain
    # must divide the data-axis size.  grad_compression routes the gradient
    # all-reduce through repro.parallel.compression: None (exact fp psum,
    # default), "int8", or "topk:<frac>" -- both with error feedback.
    data_parallel: int = 0
    mesh: Optional[object] = None   # jax.sharding.Mesh (kept untyped: configs
    grad_compression: Optional[str] = None  # import before jax init)


@dataclass
class OperatorResult:
    params: object                  # the network's parameter pytree
    op_name: str
    loss_history: List[float]
    l2_error: float                 # RMS vs the exact solution on a dense grid
    adam_time_s: float
    lbfgs_time_s: float
    n_params: int
    net: Optional[Network] = None
    # the jitted Adam step the run used, kept so callers can inspect its
    # compiled program: (params, opt_state, pts) -> (params, opt_state,
    # loss) single-device, ShardedTrainStep.step under a mesh
    train_step: Optional[Callable] = None


def train_operator(cfg: OperatorRunConfig) -> OperatorResult:
    """Adam (+ optional L-BFGS) on the generic operator objective; the
    operator's exact solution supplies boundary/initial data and the final
    accuracy oracle.  Building the step and the final accuracy check run
    under the :data:`SETUP_SCOPE` scope (``repro.runtime.metrics``)."""
    with scope(SETUP_SCOPE):
        op = get_operator(cfg.op)
        dtype = float_dtype()
        key = jax.random.PRNGKey(cfg.seed)
        k_init, k_pts = jax.random.split(key)
        net = make_network(cfg.network, d_in=op.d_in, d_out=op.d_out,
                           width=cfg.width, depth=cfg.depth,
                           activation=cfg.activation, **cfg.net_kwargs)
        engine = DerivativeEngine.from_spec(cfg.engine)
        params = net.init(k_init, dtype=dtype)

        bc_pts = boundary_grid(op.domain, cfg.n_bc, dtype)
        bc_vals = exact_values(op, token_points(net, bc_pts), dtype)

        def make_loss(eng):
            def loss_fn(p, pts):
                return pinn_loss(p, op=op, pts=pts, bc_pts=bc_pts,
                                 bc_vals=bc_vals, weights=cfg.weights,
                                 engine=eng, net=net)
            return loss_fn

        loss_fn = make_loss(engine)
        mesh = resolve_mesh(cfg.mesh, cfg.data_parallel)
        if mesh is None:
            def pinn_train_step(p, state, pts):
                (loss, aux), grads = jax.value_and_grad(loss_fn,
                                                        has_aux=True)(p, pts)
                with scope("optim.adam"):
                    p, state = adam_update(grads, state, p, cfg.adam_lr)
                return p, state, loss
            train_step = adam_step = jax.jit(pinn_train_step)
        else:
            # one shard_map program per step: local loss+grad on each
            # device's collocation shard, psum (optionally compressed) of
            # the grads, and a replicated Adam update -- see
            # repro.parallel.jet_shard
            if cfg.n_domain % mesh.shape["data"]:
                raise ValueError(
                    f"n_domain={cfg.n_domain} does not divide the "
                    f"{mesh.shape['data']}-way data axis of the mesh")
            built = build_sharded_train_step(
                loss_fn, mesh, adam_lr=cfg.adam_lr,
                compression=cfg.grad_compression)
            ef_err = built.init_err(params)
            train_step = built.step

            def adam_step(p, state, pts):
                nonlocal ef_err
                p, state, (loss, aux), ef_err = built.step(p, state, pts,
                                                           ef_err)
                return p, state, loss

    state = adam_init(params)
    pts = sample_box(k_pts, op.domain, cfg.n_domain, dtype)
    loss_hist: List[float] = []

    t0 = time.perf_counter()
    for step in range(cfg.adam_steps):
        if step and step % cfg.resample_every == 0:
            k_pts, sub = jax.random.split(k_pts)
            pts = sample_box(sub, op.domain, cfg.n_domain, dtype)
        params, state, loss = adam_step(params, state, pts)
        if step % cfg.log_every == 0 or step == cfg.adam_steps - 1:
            loss_hist.append(float(loss))
    jax.block_until_ready(params)
    adam_time = time.perf_counter() - t0

    lbfgs_time = 0.0
    if cfg.lbfgs_steps > 0:
        grid_pts = sample_box(jax.random.PRNGKey(cfg.seed + 1), op.domain,
                              cfg.n_domain, dtype)
        # under a mesh the full-batch L-BFGS objective shards its grid/cross
        # calls (grads flow through shard_map's transpose); compression is
        # an Adam-phase knob only
        lbfgs_loss = loss_fn if mesh is None \
            else make_loss(ShardedEngine(engine, mesh))
        vg = jax.jit(jax.value_and_grad(lbfgs_loss, has_aux=True))

        def vg_flat(p):
            (loss, aux), grads = vg(p, grid_pts)
            return loss, grads

        t0 = time.perf_counter()
        res = lbfgs(vg_flat, params, steps=cfg.lbfgs_steps)
        lbfgs_time = time.perf_counter() - t0
        params = res.params
        loss_hist.extend(res.loss_history)

    with scope(SETUP_SCOPE):
        xe = eval_grid(op.domain, cfg.eval_pts_per_axis, dtype)
        u_net = net.apply(params, xe).reshape(-1, net.d_out)   # (N, d_out)
        u_true = exact_values(op, token_points(net, xe), dtype)
        l2 = float(jnp.sqrt(jnp.mean((u_net - u_true) ** 2)))

    return OperatorResult(params=params, op_name=op.name,
                          loss_history=loss_hist, l2_error=l2,
                          adam_time_s=adam_time, lbfgs_time_s=lbfgs_time,
                          n_params=num_params(params), net=net,
                          train_step=train_step)
