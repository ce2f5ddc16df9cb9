"""n-TangentProp (the paper's algorithm) on the paper's DenseMLP vs three
oracles: nested reverse-mode and forward-mode autodiff (AutodiffEngine) and
jax.experimental.jet (JaxJetEngine)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AutodiffEngine, DenseMLP, JaxJetEngine, NTPEngine


@pytest.fixture(scope="module")
def net():
    mlp = DenseMLP(1, 24, 3, 1)  # paper's 3x24
    params = mlp.init(jax.random.PRNGKey(0), dtype=jnp.float64)
    x = jax.random.uniform(jax.random.PRNGKey(1), (9, 1), jnp.float64, -1, 1)
    return mlp, params, x


@pytest.mark.parametrize("order", [
    0, 1, 3, 5,
    # order-7 nested autodiff takes ~2 min on CPU; tier-1 keeps order <= 5
    pytest.param(7, marks=pytest.mark.slow)])
def test_matches_nested_autodiff(net, order):
    mlp, params, x = net
    ours = NTPEngine().derivs(mlp, params, x, order)
    ref = AutodiffEngine().derivs(mlp, params, x, order)
    np.testing.assert_allclose(ours, ref, rtol=1e-9, atol=1e-11)


@pytest.mark.parametrize("order", [1, 3, 6])
def test_matches_jax_experimental_jet(net, order):
    mlp, params, x = net
    ours = NTPEngine().derivs(mlp, params, x, order)
    ref = JaxJetEngine().derivs(mlp, params, x, order)
    np.testing.assert_allclose(ours, ref, rtol=1e-9, atol=1e-11)


@pytest.mark.parametrize("order", [1, 4])
def test_matches_nested_jacfwd(net, order):
    """A vector-output net: AutodiffEngine nests forward-mode jax.jacfwd."""
    _, _, x = net
    mlp = DenseMLP(1, 24, 3, 2)
    params = mlp.init(jax.random.PRNGKey(0), dtype=jnp.float64)
    ours = NTPEngine().derivs(mlp, params, x, order)
    ref = AutodiffEngine().derivs(mlp, params, x, order)
    assert ref.shape == (order + 1, 9, 2)
    np.testing.assert_allclose(ours, ref, rtol=1e-9, atol=1e-11)


@pytest.mark.parametrize("activation", ["tanh", "sigmoid", "sin", "softplus"])
def test_other_activations(net, activation):
    mlp, params, x = net
    mlp = dataclasses.replace(mlp, activation=activation)
    ours = NTPEngine().derivs(mlp, params, x, 4)
    ref = AutodiffEngine().derivs(mlp, params, x, 4)
    np.testing.assert_allclose(ours, ref, rtol=1e-8, atol=1e-10)


def test_multi_directional_grid(net):
    mlp = DenseMLP(3, 16, 2, 1)
    params = mlp.init(jax.random.PRNGKey(2), dtype=jnp.float64)
    x = jax.random.normal(jax.random.PRNGKey(3), (5, 3), jnp.float64)
    grid = NTPEngine().grid(mlp, params, x, 3)  # (d_in, order+1, batch, 1)
    assert grid.shape == (3, 4, 5, 1)
    # axis-0 pure derivative equals the directional derivative along e_0
    v = jnp.zeros_like(x).at[:, 0].set(1.0)
    ref = AutodiffEngine().derivs(mlp, params, x, 3, tangent=v)
    np.testing.assert_allclose(grid[0], ref, rtol=1e-9, atol=1e-11)


def test_pallas_impl_matches_jnp(net):
    mlp = DenseMLP(1, 24, 3, 1)
    params = mlp.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    x = jax.random.uniform(jax.random.PRNGKey(1), (16, 1), jnp.float32, -1, 1)
    a = NTPEngine("jnp").derivs(mlp, params, x, 5)
    b = NTPEngine("pallas").derivs(mlp, params, x, 5)
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_gradients_flow_through_both_impls():
    mlp = DenseMLP(1, 16, 2, 1)
    params = mlp.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    x = jax.random.uniform(jax.random.PRNGKey(1), (8, 1), jnp.float32, -1, 1)

    def loss(p, impl):
        return jnp.sum(NTPEngine(impl).derivs(mlp, p, x, 3)[3] ** 2)

    g1 = jax.grad(lambda p: loss(p, "jnp"))(params)
    g2 = jax.grad(lambda p: loss(p, "pallas"))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-5)


def test_order_zero_is_plain_forward(net):
    mlp, params, x = net
    plain = mlp.apply(params, x)
    out = NTPEngine().derivs(mlp, params, x, 0)
    np.testing.assert_allclose(out[0], plain, rtol=1e-12)
    # a jet's order-0 coefficient is the plain forward too, and the scanned
    # and the unrolled forward agree
    np.testing.assert_allclose(NTPEngine().derivs(mlp, params, x, 3)[0],
                               plain, rtol=1e-12)
    np.testing.assert_allclose(mlp.apply(params, x, unroll=True), plain,
                               rtol=1e-12)


def test_linear_memory_stack_shape(net):
    """The jet stack is (order+1, batch, d_out): O(n M) memory, no M^n graph."""
    mlp, params, x = net
    for n in (1, 4, 8):
        assert NTPEngine().derivs(mlp, params, x, n).shape == (n + 1, 9, 1)
