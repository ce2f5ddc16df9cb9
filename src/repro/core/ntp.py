"""n-TangentProp: the paper's algorithm (Alg. 1) for dense feed-forward nets.

This is the faithful reproduction of the paper's contribution: compute
``f(x), f'(x), ..., f^(n)(x)`` w.r.t. the *network inputs* in a single
forward pass.  Linear layers act coefficient-wise on the jet; activations go
through the Faa di Bruno contraction.  Cost is ``O(n p(n) M)`` time and
``O(n M)`` memory -- quasilinear in the model size M, versus ``O(M^n)`` for
nested autodiff.

Two execution paths:
* ``impl='jnp'``    -- pure jax.numpy (reference; used by tests/oracles)
* ``impl='pallas'`` -- fused Pallas kernels (kernels/jet_dense.py): one VMEM
                       round-trip per layer tile, MXU for the stacked GEMM.

Gradients w.r.t. parameters flow through either path with ordinary
``jax.grad`` -- that single reverse sweep over the jet forward is exactly the
paper's "backward pass" and stays O(n p(n) M).
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from . import jet as J


class MLPParams(NamedTuple):
    """Stacked weights for a uniform-width MLP (paper's architecture)."""

    w_in: jnp.ndarray    # (d_in, width)
    b_in: jnp.ndarray    # (width,)
    w_hidden: jnp.ndarray  # (depth-1, width, width) -- scanned
    b_hidden: jnp.ndarray  # (depth-1, width)
    w_out: jnp.ndarray   # (width, d_out)
    b_out: jnp.ndarray   # (d_out,)


def xavier_uniform(key: jax.Array, fan_in: int, fan_out: int,
                   dtype=jnp.float32) -> jnp.ndarray:
    """Xavier-uniform weight init matching the paper's PyTorch defaults
    (shared by every architecture in core/network.py)."""
    lim = jnp.sqrt(6.0 / (fan_in + fan_out)).astype(dtype)
    return jax.random.uniform(key, (fan_in, fan_out), dtype, -lim, lim)


def init_mlp(key: jax.Array, d_in: int, width: int, depth: int, d_out: int,
             dtype=jnp.float32) -> MLPParams:
    ks = jax.random.split(key, depth + 1)

    def xavier(k, fan_in, fan_out):
        return xavier_uniform(k, fan_in, fan_out, dtype)

    w_in = xavier(ks[0], d_in, width)
    wh = jnp.stack([xavier(ks[i + 1], width, width) for i in range(depth - 1)]) \
        if depth > 1 else jnp.zeros((0, width, width), dtype)
    w_out = xavier(ks[depth], width, d_out)
    return MLPParams(
        w_in=w_in, b_in=jnp.zeros((width,), dtype),
        w_hidden=wh, b_hidden=jnp.zeros((max(depth - 1, 0), width), dtype),
        w_out=w_out, b_out=jnp.zeros((d_out,), dtype),
    )


def num_params(p: MLPParams) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(p))


def mlp_apply(params: MLPParams, x: jnp.ndarray, activation: str = "tanh",
              unroll: bool = False) -> jnp.ndarray:
    """Plain forward pass (no derivatives).  ``unroll=True`` avoids lax.scan
    (needed by jax.experimental.jet, which has no scan rule)."""
    from .activations import PRIMALS
    act = PRIMALS[activation]

    def mm(a, w):
        return jnp.matmul(a, w, precision=J.MATMUL_PRECISION)

    h = act(mm(x, params.w_in) + params.b_in)

    if unroll:
        for i in range(params.w_hidden.shape[0]):
            h = act(mm(h, params.w_hidden[i]) + params.b_hidden[i])
        return mm(h, params.w_out) + params.b_out

    def body(h, wb):
        w, b = wb
        return act(mm(h, w) + b), None

    if params.w_hidden.shape[0]:
        h, _ = jax.lax.scan(body, h, (params.w_hidden, params.b_hidden))
    return mm(h, params.w_out) + params.b_out


# ---------------------------------------------------------------------------
# the n-TangentProp forward pass
# ---------------------------------------------------------------------------

def ntp_jet(params: MLPParams, jet: J.Jet, activation: str = "tanh",
            impl: str = "jnp") -> J.Jet:
    """Push an input jet through the dense stack (the body of Algorithm 1).

    This is the ``Network.jet_apply`` of the paper's architecture; it is
    split out from :func:`ntp_forward` so :class:`repro.core.network.DenseMLP`
    can run arbitrary pre-seeded jets through the same code path.
    """
    if impl == "pallas":
        from repro.kernels import ops as kops
        coeffs = kops.jet_dense(jet.coeffs, params.w_in, params.b_in, activation)

        def body(coeffs, wb):
            w, b = wb
            return kops.jet_dense(coeffs, w, b, activation), None

        if params.w_hidden.shape[0]:
            coeffs, _ = jax.lax.scan(body, coeffs, (params.w_hidden, params.b_hidden))
        jet = J.Jet(coeffs)
        return J.linear(jet, params.w_out, params.b_out)

    # reference path: jet algebra, scanned over the hidden stack
    jet = J.compose(J.linear(jet, params.w_in, params.b_in), activation)

    def body(coeffs, wb):
        w, b = wb
        j = J.compose(J.linear(J.Jet(coeffs), w, b), activation)
        return j.coeffs, None

    if params.w_hidden.shape[0]:
        coeffs, _ = jax.lax.scan(body, jet.coeffs, (params.w_hidden, params.b_hidden))
        jet = J.Jet(coeffs)
    return J.linear(jet, params.w_out, params.b_out)


def ntp_forward(params: MLPParams, x: jnp.ndarray, order: int,
                tangent: jnp.ndarray | None = None, activation: str = "tanh",
                impl: str = "jnp") -> J.Jet:
    """Jet of the network output along the input curve ``x + t v``.

    ``x``: (batch, d_in).  ``tangent`` defaults to ones (the paper's 1-D PINN
    seeding ``y_1 = L_1(1) - b_1``).  Returns a Jet of (batch, d_out).
    """
    if order == 0:
        y = mlp_apply(params, x, activation)
        return J.Jet(y[None])
    return ntp_jet(params, J.seed(x, tangent, order), activation, impl)


def ntp_derivatives(params: MLPParams, x: jnp.ndarray, order: int,
                    tangent: jnp.ndarray | None = None, activation: str = "tanh",
                    impl: str = "jnp") -> jnp.ndarray:
    """Raw derivatives (order+1, batch, d_out): d^k/dt^k f(x + t v) at t=0."""
    return J.derivatives(ntp_forward(params, x, order, tangent, activation, impl))


# ---------------------------------------------------------------------------
# multi-directional jets: full nabla^k for small input dimension d
#
# The direction folding and polarization algebra are engine- and network-
# generic; they live in core/engines.py.  These wrappers keep the seed
# MLPParams surface (and its callers/tests) working verbatim.
# ---------------------------------------------------------------------------

def _dense_view(params: MLPParams, activation: str, impl: str):
    from .engines import NTPEngine
    from .network import DenseMLP
    return DenseMLP.from_params(params, activation), NTPEngine(impl)


def ntp_grid(params: MLPParams, x: jnp.ndarray, order: int, activation: str = "tanh",
             impl: str = "jnp") -> jnp.ndarray:
    """Pure n-th derivatives along each coordinate axis: (d_in, order+1, batch, d_out).

    PINN losses for 1-D/2-D problems only need pure (non-mixed) directional
    derivatives per axis; mixed partials are recovered by polarization of
    directional jets -- see :func:`cross`.
    """
    net, engine = _dense_view(params, activation, impl)
    return engine.grid(net, params, x, order)


def cross(params: MLPParams, x: jnp.ndarray, axes: Sequence[int],
          activation: str = "tanh", impl: str = "jnp") -> jnp.ndarray:
    """Mixed partial ``d^m f / dx_{axes[0]} ... dx_{axes[m-1]}`` at each point,
    shape (batch, d_out), via the polarization identity

        D_{v_1 ... v_m} f = 1/(2^m m!) sum_{eps in {+-1}^m}
                            (prod_k eps_k) D^m_{sum_k eps_k v_k} f

    with ``v_k = e_{axes[k]}``.  Repeated axes are allowed (``axes=(0, 0, 1)``
    gives u_xxy), so together with :func:`ntp_grid` this spans the full
    nabla^m tensor from at most 2^(m-1) distinct directional jets
    (:class:`repro.core.engines.PolarizationPlan`) -- still one
    n-TangentProp batch, never a nested-autodiff graph.
    """
    net, engine = _dense_view(params, activation, impl)
    return engine.cross(net, params, x, axes)
