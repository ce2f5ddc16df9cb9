"""The wavelet-jet kernels (``repro.kernels.wave_jet``, dispatched by
``ops.wave_jet``) against the jet algebra (``repro.core.jet.wave``) and the
straight-line oracle (``ref.wave_jet_ref``): the forward at orders 0-4,
and the VJP with respect to the stack and both weights against ``jax.vjp``
through the algebra.  Interpret mode, f64 (``conftest.py``); the weights
sit away from their initial (1, 1), so that a swapped or dropped weight
shows."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import jet as J
from repro.kernels import ops, ref

WJ = importlib.import_module("repro.kernels.wave_jet")

ORDERS = (0, 1, 2, 3, 4)
WEIGHTS = jnp.array([0.7, -1.3])
# (leading batch axes, width): PINNsFormer's d_model 32 behind a token axis
# of 5, FF width 256, and a width that fills no lane tile
CASES = {"w20": ((7,), 20), "tokens_w32": ((3, 5), 32), "w256": ((9,), 256)}
TIGHT = dict(rtol=1e-10, atol=1e-10)


def _algebra(c, w):
    return J.wave(J.Jet(c), w[0], w[1]).coeffs


def _stack(order, batch, width, seed=0):
    return 0.8 * jax.random.normal(jax.random.PRNGKey(seed),
                                   (order + 1,) + batch + (width,),
                                   jnp.float64)


def _vjps(c, w, g):
    """(kernel's VJP, autodiff through the algebra's), each (dc, dw)."""
    _, kernel = jax.vjp(ops.wave_jet, c, w)
    _, algebra = jax.vjp(_algebra, c, w)
    return kernel(g), algebra(g)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("order", ORDERS)
def test_forward_matches_algebra_and_oracle(order, case):
    batch, width = CASES[case]
    c = _stack(order, batch, width)
    got = ops.wave_jet(c, WEIGHTS)
    assert got.shape == c.shape
    np.testing.assert_allclose(got, _algebra(c, WEIGHTS), **TIGHT)
    np.testing.assert_allclose(got, ref.wave_jet_ref(c, WEIGHTS), **TIGHT)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("order", ORDERS)
def test_vjp_matches_autodiff_through_the_algebra(order, case):
    batch, width = CASES[case]
    c = _stack(order, batch, width)
    g = _stack(order, batch, width, seed=1)
    (dc, dw), (dc_want, dw_want) = _vjps(c, WEIGHTS, g)
    np.testing.assert_allclose(dc, dc_want, **TIGHT)
    np.testing.assert_allclose(dw, dw_want, **TIGHT)
    assert dw.shape == (2,) and dw.dtype == WEIGHTS.dtype


@pytest.mark.parametrize("order", (1, 3, 4))
def test_ragged_last_block_stays_out_of_the_weight_gradient(order):
    """Rows over three blocks, the last one ragged: the interpreter fills
    the rows past the end with NaN, so a weight gradient that summed them
    would not be finite."""
    width = 32
    bb = WJ.block_rows(1 << 30, width, order + 1)
    rows = 2 * bb + 13
    assert WJ.block_rows(rows, width, order + 1) == bb
    c = _stack(order, (rows,), width)
    g = _stack(order, (rows,), width, seed=1)
    (dc, dw), (dc_want, dw_want) = _vjps(c, WEIGHTS, g)
    assert bool(jnp.all(jnp.isfinite(dw)))
    np.testing.assert_allclose(dw, dw_want, **TIGHT)
    np.testing.assert_allclose(dc, dc_want, **TIGHT)
    np.testing.assert_allclose(ops.wave_jet(c, WEIGHTS),
                               _algebra(c, WEIGHTS), **TIGHT)


def test_wave_is_a_fused_op_not_a_dense_epilogue():
    assert ops.epilogues()["wave"] is ops.EpilogueKind.FUSED_OP
