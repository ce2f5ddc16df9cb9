"""PINN substrate: Burgers residual jets, exact profiles, mini end-to-end."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DenseMLP
from repro.core import jet as J
from repro.pinn import (PINNRunConfig, exact_profile, lambda_window,
                        profile_lambda, residual_derivs_autodiff, residual_jet,
                        smoothness_order, train)


def test_profile_constants():
    assert profile_lambda(1) == 0.5
    assert lambda_window(1) == (1 / 3, 1.0)
    assert smoothness_order(2) == 5


def test_exact_profile_roundtrip():
    xs = np.linspace(-2, 2, 41)
    for k in (1, 2, 3):
        u = exact_profile(xs, k)
        np.testing.assert_allclose(-u - u ** (2 * k + 1), xs, atol=1e-10)
        # odd function
        np.testing.assert_allclose(u, -u[::-1], atol=1e-10)


@pytest.mark.parametrize("order", [
    1, 3, 5,
    # the order-7 nested-autodiff oracle alone costs minutes on CPU (the
    # O(M^n) blowup the paper removes) -- keep it, but out of tier-1
    pytest.param(7, marks=pytest.mark.slow)])
def test_residual_jet_matches_autodiff(order):
    net = DenseMLP(1, 24, 3, 1)
    params = net.init(jax.random.PRNGKey(0), dtype=jnp.float64)
    x = jnp.linspace(-1, 1, 7, dtype=jnp.float64)[:, None]
    ours = J.derivatives(residual_jet(net, params, 0.5, x, order))
    ref = residual_derivs_autodiff(net, params, 0.5, x, order)
    np.testing.assert_allclose(ours, ref, rtol=1e-8, atol=1e-10)


def test_residual_zero_on_exact_solution():
    """R evaluated through the jets vanishes on the closed-form profile: wire
    the exact U into a 'network' by fitting... instead check directly with a
    polynomial-free approach: finite-difference the implicit solution."""
    xs = np.linspace(-1.5, 1.5, 201)
    u = exact_profile(xs, 1)  # lam = 1/2
    du = np.gradient(u, xs)
    r = -0.5 * u + (1.5 * xs + u) * du
    assert np.max(np.abs(r[5:-5])) < 5e-3  # FD error only


@pytest.mark.slow
def test_mini_burgers_training_converges_toward_lambda():
    cfg = PINNRunConfig(k=1, adam_steps=200, lbfgs_steps=40, n_domain=128,
                        n_origin=32, log_every=100)
    res = train(cfg)
    # full runs converge to 0.5; the mini run must at least enter the
    # neighborhood from the window midpoint (0.667 -> toward 0.5)
    assert abs(res.lam - 0.5) < 0.12
    assert res.loss_history[-1] < res.loss_history[0] * 1e-2


def test_engines_share_loss_surface():
    """ntp and autodiff engines compute the same loss (paper: exact method)."""
    from repro.pinn.losses import LossWeights, bc_targets, burgers_pinn_loss

    net = DenseMLP(1, 16, 2, 1)
    params = net.init(jax.random.PRNGKey(0), dtype=jnp.float64)
    pts = jnp.linspace(-1, 1, 16, dtype=jnp.float64)[:, None]
    opts = jnp.linspace(-0.1, 0.1, 8, dtype=jnp.float64)[:, None]
    kw = dict(net=net, k=1, pts=pts, origin_pts=opts, domain=1.0, order=3,
              weights=LossWeights(), lam_window=(1 / 3, 1.0),
              bc_vals=bc_targets(1, 1.0))
    l1, _ = burgers_pinn_loss(params, jnp.zeros(()), engine="ntp", **kw)
    l2, _ = burgers_pinn_loss(params, jnp.zeros(()), engine="autodiff", **kw)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-9)
    with pytest.raises(ValueError, match="ntp and autodiff"):
        burgers_pinn_loss(params, jnp.zeros(()), engine="jet", **kw)


def test_burgers_loss_respects_activation():
    """The boundary term used to silently fall back to tanh regardless of the
    configured activation; a sin-activated net must yield a different loss."""
    from repro.pinn.losses import LossWeights, bc_targets, burgers_pinn_loss

    tanh_net, sin_net = DenseMLP(1, 16, 2, 1), DenseMLP(1, 16, 2, 1, "sin")
    params = tanh_net.init(jax.random.PRNGKey(3), dtype=jnp.float64)
    pts = jnp.linspace(-1, 1, 16, dtype=jnp.float64)[:, None]
    opts = jnp.linspace(-0.1, 0.1, 8, dtype=jnp.float64)[:, None]
    kw = dict(k=1, pts=pts, origin_pts=opts, domain=1.0, order=3,
              weights=LossWeights(), lam_window=(1 / 3, 1.0),
              bc_vals=bc_targets(1, 1.0))
    l_tanh, _ = burgers_pinn_loss(params, jnp.zeros(()), net=tanh_net, **kw)
    l_sin, _ = burgers_pinn_loss(params, jnp.zeros(()), net=sin_net, **kw)
    assert not np.isclose(float(l_tanh), float(l_sin))
    # and the sin-activated loss agrees across engines (activation threaded
    # consistently through every term, boundary included)
    l_sin_ad, _ = burgers_pinn_loss(params, jnp.zeros(()), net=sin_net,
                                    engine="autodiff", **kw)
    np.testing.assert_allclose(float(l_sin), float(l_sin_ad), rtol=1e-9)
