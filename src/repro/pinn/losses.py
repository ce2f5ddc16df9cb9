"""PINN loss assembly, generic over a differential operator.

``pinn_loss`` is the operator-generic objective: residual MSE over interior
collocation points plus boundary/initial supervision against the operator's
exact solution, generic over the :class:`DerivativeEngine` (``NTPEngine``
quasilinear vs ``AutodiffEngine`` baseline, by object or spec string), the
:class:`Network` (``net=``, required -- the loss never guesses the
architecture from a parameter pytree), and the operator's output rank:
scalar PDEs and multi-equation systems (``op.d_out > 1``, e.g. Gray-Scott)
run through the same code path, with boundary supervision across every
component.  The self-similar Burgers workload keeps its specialized
objective (learnable lambda, Sobolev term, high-order origin smoothness --
paper eq. 1, 2 and appendix A) as ``burgers_pinn_loss``; its residual
algebra is also registered in the operator registry as ``"burgers"``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import jet as J
from repro.core.engines import AutodiffEngine, DerivativeEngine, NTPEngine
from repro.core.network import Network, token_points
from repro.runtime.metrics import scope

from .burgers import exact_profile, residual_derivs_autodiff, residual_jet
from .operators import Operator, build_table, get_operator


@dataclass(frozen=True)
class LossWeights:
    residual: float = 1.0
    sobolev1: float = 0.1     # Q_1 of the Sobolev loss (paper eq. 2, m=1)
    origin: float = 1.0e-3    # high-order smoothness at the origin (L*)
    bc: float = 10.0


# ---------------------------------------------------------------------------
# generic operator objective
# ---------------------------------------------------------------------------

def pinn_loss(params, *, op: Union[Operator, str], pts: jnp.ndarray,
              bc_pts: jnp.ndarray, bc_vals: jnp.ndarray, net: Network,
              weights: LossWeights = LossWeights(),
              engine: Union[str, DerivativeEngine] = "ntp",
              mesh=None) -> Tuple[jnp.ndarray, Dict]:
    """Operator-generic PINN objective: w_r ||R[u]||^2 + w_bc ||u - u*||^2_bd.

    ``bc_vals`` is the exact solution on ``bc_pts`` -- (N,) for scalar
    operators, (N, d_out) for systems; for a network whose output has a
    token axis (PINNsFormer), on ``token_points(net, bc_pts)``, as the
    residual is taken at ``token_points(net, pts)``.  Precompute it outside jit
    (``op.exact`` may be numpy-backed, e.g. the Burgers profile;
    :func:`repro.pinn.operators.exact_values` normalizes the shape).  For a
    multi-equation system the residual term averages the squares of every
    equation and the boundary term supervises every output component.  Only
    ``engine``/``net`` change the derivative machinery and architecture; the
    loss surface is identical across engines (the paper's "exact method"
    property).  ``mesh`` (a ``jax.sharding.Mesh`` with a ``"data"`` axis)
    shards the residual's grid/cross calls over the mesh's data axis via
    :class:`repro.parallel.jet_shard.ShardedEngine` -- same loss value (bit
    identical for the ntp engines), collocation batch split across devices.
    """
    if isinstance(op, str):
        op = get_operator(op)
    eng = DerivativeEngine.from_spec(engine)
    if mesh is not None:
        from repro.parallel.jet_shard import ShardedEngine
        eng = ShardedEngine(eng, mesh)
    table = build_table(net, params, eng, op, pts)
    with scope("pinn.residual"):
        r = op.residual(token_points(net, pts), table)
        l_res = jnp.mean(r ** 2)
    with scope("pinn.boundary"):
        # (Nb, d_out); a token axis folds into the point axis
        ub = net.apply(params, bc_pts).reshape(-1, net.d_out)
        bv = jnp.asarray(bc_vals)
        if bv.ndim == 1:
            bv = bv[:, None]
        if bv.shape != ub.shape:
            raise ValueError(
                f"bc_vals shape {bv.shape} does not match the network's "
                f"boundary output {ub.shape}; systems need one column per "
                f"component")
        l_bc = jnp.mean((ub - bv) ** 2)
    loss = weights.residual * l_res + weights.bc * l_bc
    return loss, {"residual": l_res, "bc": l_bc}


# ---------------------------------------------------------------------------
# the self-similar Burgers objective (paper section IV-C)
# ---------------------------------------------------------------------------

def bc_targets(k: int, domain: float) -> Tuple[float, float]:
    """U_true(+-L) with the C=1 normalization."""
    import numpy as np
    vals = exact_profile(np.array([-domain, domain]), k)
    return float(vals[0]), float(vals[1])


def burgers_pinn_loss(params, lam_raw: jnp.ndarray, *, net: Network, k: int,
                      pts: jnp.ndarray, origin_pts: jnp.ndarray, domain: float,
                      order: int, weights: LossWeights,
                      lam_window: Tuple[float, float],
                      engine: Union[str, DerivativeEngine] = "ntp",
                      bc_vals: Tuple[float, float] = None) -> Tuple[jnp.ndarray, Dict]:
    """Full self-similar Burgers objective on the scalar network ``net``.
    ``engine``: a spec string ("ntp", "ntp/pallas", "autodiff") or
    :class:`DerivativeEngine` instance; the ntp engines push one jet per
    point set through ``net.jet_apply``, the autodiff engine nests
    ``jax.grad`` over ``net.apply``.  Everything else is identical, so the
    benchmark isolates the derivative engine."""
    lo, hi = lam_window
    lam = lo + (hi - lo) * jax.nn.sigmoid(lam_raw)
    eng = DerivativeEngine.from_spec(engine)
    if isinstance(eng, NTPEngine):
        def r_derivs(x, n):
            return J.derivatives(residual_jet(net, params, lam, x, n,
                                              impl=eng.impl))
    elif isinstance(eng, AutodiffEngine):
        def r_derivs(x, n):
            return residual_derivs_autodiff(net, params, lam, x, n)
    else:
        raise ValueError(f"burgers objective supports the ntp and autodiff "
                         f"engines, not {eng.spec!r}")

    # one order-1 table on the full domain (residual + Sobolev-1) and one
    # high-order table on the origin cluster
    r_dom = r_derivs(pts, 1)
    r_org = r_derivs(origin_pts, order)

    l_res = jnp.mean(r_dom[0] ** 2)
    l_sob = jnp.mean(r_dom[1] ** 2)
    l_org = jnp.mean(r_org[order] ** 2)

    # boundary conditions: U(0)=0, U'(0)=-1, U(+-L) pinned to the C=1 profile
    x0 = jnp.zeros((1, 1), pts.dtype)
    u0j = eng.derivs(net, params, x0, 1)
    u0, du0 = u0j[0, 0, 0], u0j[1, 0, 0]
    xb = jnp.asarray([[-domain], [domain]], pts.dtype)
    ub = net.apply(params, xb)
    tb = jnp.asarray(bc_vals, pts.dtype)
    l_bc = u0 ** 2 + (du0 + 1.0) ** 2 + jnp.mean((ub[:, 0] - tb) ** 2)

    loss = (weights.residual * l_res + weights.sobolev1 * l_sob +
            weights.origin * l_org + weights.bc * l_bc)
    return loss, {"residual": l_res, "sobolev1": l_sob, "origin": l_org,
                  "bc": l_bc, "lambda": lam}
