"""The polarization plan: one jet forward per derivative table.

``PolarizationPlan`` reduces the 2^m sign patterns of each mixed partial to
distinct primitive directions; ``build_table`` runs those and the coordinate
axes as one batch.  Checked here: the direction and row counts, every mixed
partial of a table against a straight-line 2^m polarization written below
and against nested autodiff, the exactness of the sign flip the plan relies
on, that a table without mixed partials is the plain grid, and the row
counters.
"""

import importlib.util
import itertools
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engines import (AutodiffEngine, NTPEngine, PolarizationPlan,
                                axis_directions)
from repro.core.network import DenseMLP
from repro.data.collocation import sample_box
from repro.pinn.operators import (Operator, autodiff_mixed_partial_fn,
                                  build_table, get_operator)
from repro.runtime import metrics

ROOT = Path(__file__).resolve().parents[1]

# Raissi et al.'s Navier-Stokes in (psi, p) form on (x, y, t): order 3
NS_MIXED = ((0, 1), (0, 2), (1, 2), (0, 0, 1), (0, 1, 1))


def _ns_residual(x, d):
    u_x, u_t, v_t = d.mixed(0, 1), d.mixed(1, 2), -d.mixed(0, 2)
    u_xx, v_yy = d.mixed(0, 0, 1), -d.mixed(0, 1, 1)
    f = u_t + d(1, 1) * u_x + d(0, 1, comp=1) - 0.01 * (u_xx + d(1, 3))
    g = v_t - d(0, 1) * u_x + d(1, 1, comp=1) - 0.01 * (v_yy - d(0, 3))
    return jnp.stack([f, g])


NS_OP = Operator(name="ns-psi-p", d_in=3, d_out=2, order=3,
                 residual=_ns_residual, exact=lambda x: jnp.zeros((len(x), 2)),
                 domain=((1.0, 8.0), (-2.0, 2.0), (0.0, 20.0)),
                 mixed=NS_MIXED)
OPS_WITH_MIXED = ("advection-diffusion", "navier-stokes", NS_OP.name)


def _op(name):
    return NS_OP if name == NS_OP.name else get_operator(name)


def _net_and_pts(op, dtype, width=10, depth=3, n=7):
    net = DenseMLP(op.d_in, width, depth, op.d_out)
    params = net.init(jax.random.PRNGKey(0), dtype=dtype)
    x = sample_box(jax.random.PRNGKey(1), op.domain, n, dtype)
    return net, params, x


def straight_polarization(engine, net, params, x, axes):
    """All 2^m sign patterns, one directional jet each, summed in turn."""
    m, top = len(axes), 0.0
    for eps in itertools.product((1.0, -1.0), repeat=m):
        v = np.zeros(x.shape[-1])
        for e, a in zip(eps, axes):
            v[a] += e
        tangent = jnp.broadcast_to(jnp.asarray(v, x.dtype), x.shape)
        top = top + math.prod(eps) * engine.derivs(net, params, x, m,
                                                   tangent)[m]
    return top / (2.0 ** m * math.factorial(m))


# ------------------------------------------------------------ the plan


@pytest.mark.parametrize("d_in,mixed,axes,n_dirs", [
    (3, NS_MIXED, True, 13),                             # raissi-ns table
    (2, get_operator("navier-stokes").mixed, True, 8),   # steady, order 4
    (3, get_operator("advection-diffusion").mixed, True, 5),
    (2, ((0, 1),), False, 2),                            # a lone cross
    (2, ((0, 1),), True, 4),
    (2, ((1, 1),), False, 1),              # (+, -) cancels to zero: dropped
    (2, ((0, 0, 1, 1),), False, 4),        # four patterns cancel to zero
    (3, (), True, 3),
])
def test_plan_direction_count(d_in, mixed, axes, n_dirs):
    plan = PolarizationPlan.build(d_in, mixed, axes=axes)
    assert len(plan.directions) == n_dirs
    assert len(set(plan.directions)) == n_dirs
    assert all(any(p) for p in plan.directions)
    assert all(next(a for a in p if a) > 0 for p in plan.directions)
    if axes:
        assert plan.directions[:d_in] == axis_directions(d_in)


def test_raissi_ns_plan_runs_52_rows_where_crosses_ran_112():
    plan = PolarizationPlan.build(3, NS_MIXED, axes=True)
    assert set(plan.directions[3:]) == {
        (1, 1, 0), (1, -1, 0), (1, 0, 1), (1, 0, -1), (0, 1, 1), (0, 1, -1),
        (2, 1, 0), (2, -1, 0), (1, 2, 0), (1, -2, 0)}
    assert len(plan.directions) * (plan.order(3) + 1) == 52
    assert 3 * 4 + sum(2 ** len(a) * (len(a) + 1) for a in NS_MIXED) == 112


@pytest.mark.parametrize("axes", [(0, 1), (0, 0, 1), (0, 1, 1), (1, 2),
                                  (0, 0, 1, 1), (2, 2), (0, 0, 0)])
def test_plan_weights_are_the_polarization_identity(axes):
    """Spread back over all 2^m patterns, each direction's weight is the
    signed count of patterns on it, times c^m for a scaled copy."""
    d_in, m = 3, len(axes)
    plan = PolarizationPlan.build(d_in, (axes,))
    want = {}
    for eps in itertools.product((1, -1), repeat=m):
        v = [0] * d_in
        for e, a in zip(eps, axes):
            v[a] += e
        if any(v):
            c = math.gcd(*v) * (1 if next(a for a in v if a) > 0 else -1)
            p = tuple(a // c for a in v)
            want[p] = want.get(p, 0) + math.prod(eps) * c ** m
    got = {plan.directions[i]: w for i, w in plan.terms[0]}
    assert got == want


# ------------------------------------------------------- table values


@pytest.mark.parametrize("dtype,rtol", [(jnp.float64, 1e-12),
                                        (jnp.float32, 1e-6)])
@pytest.mark.parametrize("name", OPS_WITH_MIXED)
def test_table_matches_straight_polarization(name, dtype, rtol):
    op = _op(name)
    net, params, x = _net_and_pts(op, dtype, n=256)
    eng = NTPEngine("jnp")
    table = jax.jit(lambda p, xx: build_table(net, p, eng, op, xx)
                    ._mixed)(params, x)
    for axes in op.mixed:
        want = straight_polarization(eng, net, params, x, axes)
        got = table[tuple(sorted(axes))]
        scale = float(jnp.max(jnp.abs(want)))
        assert float(jnp.max(jnp.abs(got - want))) <= rtol * scale, axes


@pytest.mark.parametrize("name", OPS_WITH_MIXED)
def test_table_matches_autodiff(name):
    op = _op(name)
    net, params, x = _net_and_pts(op, jnp.float64)
    table = build_table(net, params, NTPEngine("jnp"), op, x)
    auto = build_table(net, params, AutodiffEngine(), op, x)
    for axes in op.mixed:
        key = tuple(sorted(axes))
        for c in range(op.d_out):
            nested = autodiff_mixed_partial_fn(
                lambda xi: net.apply(params, xi[None, :], unroll=True)[0, c],
                x, axes)
            np.testing.assert_allclose(table.mixed(*axes, comp=c), nested,
                                       rtol=1e-8, atol=1e-10)
        np.testing.assert_allclose(table._mixed[key], auto._mixed[key],
                                   rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(table._pure, auto._pure, rtol=1e-8,
                               atol=1e-10)


@pytest.mark.parametrize("direction", [(1.0, 1.0, 0.0), (2.0, -1.0, 0.0),
                                       (0.0, 1.0, -1.0)])
def test_sign_flip_is_bitwise_exact(direction):
    """Along -v every order-k derivative is exactly (-1)^k times the one
    along v: negation and round-to-nearest are symmetric, the primal does
    not depend on v.  The plan's sign pairs rest on it."""
    net, params, x = _net_and_pts(NS_OP, jnp.float32, width=20, depth=8,
                                  n=64)
    v = jnp.broadcast_to(jnp.asarray(direction, jnp.float32), x.shape)
    fwd = np.asarray(NTPEngine("jnp").derivs(net, params, x, 3, v))
    back = np.asarray(NTPEngine("jnp").derivs(net, params, x, 3, -v))
    for k in range(4):
        np.testing.assert_array_equal(back[k], (-1.0) ** k * fwd[k])


def test_table_without_mixed_partials_is_the_grid():
    op = get_operator("allen-cahn")
    net, params, x = _net_and_pts(op, jnp.float32)
    eng = NTPEngine("jnp")
    table = jax.make_jaxpr(
        lambda p, xx: build_table(net, p, eng, op, xx)._pure)(params, x)
    grid = jax.make_jaxpr(
        lambda p, xx: eng.grid(net, p, xx, op.order))(params, x)
    assert str(table) == str(grid)


def test_table_asks_the_pass_for_nothing_it_lacks():
    """The pure block and every mixed partial come out of the one pass: a
    table whose engine refuses a second forward still builds."""
    op = NS_OP
    net, params, x = _net_and_pts(op, jnp.float64)
    calls = []

    class Counting(NTPEngine):
        def _batched_directional(self, net, params, x, dirs, order):
            calls.append((dirs.shape[0], order))
            return super()._batched_directional(net, params, x, dirs, order)

    build_table(net, params, Counting("jnp"), op, x)
    assert calls == [(13, 3)]


# ---------------------------------------------------------- counters


def _counter(name):
    return metrics.snapshot()["counter"].get(name, (0, 0.0))


@pytest.mark.parametrize("name,rows,polarized", [
    (NS_OP.name, 52, 112),
    ("allen-cahn", 6, 6),
    ("navier-stokes", 8 * 5, 2 * 5 + 8 * 4 + 8 * 4 + 16 * 5),
])
def test_table_counts_its_rows(name, rows, polarized):
    op = _op(name)
    net, params, x = _net_and_pts(op, jnp.float32)
    before = (_counter("ntp.rows"), _counter("ntp.rows_polarized"))
    jax.jit(lambda p, xx: build_table(net, p, NTPEngine("jnp"), op, xx)
            ._pure).trace(params, x)
    after = (_counter("ntp.rows"), _counter("ntp.rows_polarized"))
    assert after[0][0] - before[0][0] == after[1][0] - before[1][0] == 1
    assert after[0][1] - before[0][1] == rows
    assert after[1][1] - before[1][1] == polarized


def test_rows_share_reader():
    """The benchmark's ``jet_rows_share.train`` reads the counters."""
    spec = importlib.util.spec_from_file_location(
        "jet_rows_share", ROOT / "bench" / "layer_metrics"
        / "jet_rows_share.train.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    metrics.reset()
    assert reader.read({}) is None
    net, params, x = _net_and_pts(NS_OP, jnp.float32)
    for _ in range(2):
        build_table(net, params, NTPEngine("jnp"), NS_OP, x)
    assert reader.read({}) == pytest.approx(100.0 * 52 / 112)
