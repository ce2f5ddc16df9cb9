"""PINNsFormer against its plain reference, at a small size on the CPU.

The network (``make_network("pinnsformer", ...)``) at d_model 8, 2 heads,
FF 16, head 16 and 3 tokens, on seeded random weights made in the plain
reference's layout (``bench/reference/pinnsformer.py``, which imports
nothing of the program) and handed to the program by the benchmark's own
conversion (``bench/modes/pinnsformer_train.py``), all in float64
(``conftest.py``).  Checked: the forward pass, the wavelet jet, the
cross-attention jet, the pseudo-sequence jet, the derivative table of the
benchmark's ``raissi-ns`` operator and the PINN loss with its gradient.
"""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench.reference import pinnsformer as ref  # noqa: E402
from repro.core import jet as J  # noqa: E402
from repro.core.modules import Attention, PseudoSequence, Wave  # noqa: E402
from repro.core.network import make_network, token_points  # noqa: E402
from repro.pinn.losses import LossWeights, pinn_loss  # noqa: E402
from repro.pinn.operators import (build_table, exact_values,  # noqa: E402
                                  get_operator)

SMALL = {"d_in": 3, "d_out": 2, "width": 8, "depth": 1, "n_heads": 2,
         "ff": 16, "head": 16, "tokens": 3, "step": 0.05}
# f64 end to end, the Pallas kernels (interpret mode) included: they
# accumulate in the promotion of their input and float32, float64 here.  The
# jet algebra and nested jvp then agree to rounding, and a gap of 1e-10 is
# a million ulps of the O(1)-O(100) values compared: a fault, never noise
TIGHT = dict(rtol=1e-10, atol=1e-10)


def _mode():
    return harness.load_module(ROOT / "bench" / "modes" / "pinnsformer_train.py")


@pytest.fixture(scope="module")
def model():
    """(program network, program params, reference weights)."""
    net = make_network("pinnsformer", d_in=SMALL["d_in"], d_out=SMALL["d_out"],
                       width=SMALL["width"], depth=SMALL["depth"],
                       activation="wave", n_heads=SMALL["n_heads"],
                       ff=SMALL["ff"], head=SMALL["head"],
                       tokens=SMALL["tokens"], step=SMALL["step"])
    w = ref.init(jax.random.PRNGKey(7), SMALL, jnp.float64)
    # wavelet pairs away from (1, 1), so that each one's gradient is tested
    w = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(a.size),
                                              a.shape, a.dtype)
        if a.shape == (2,) else a, w)
    return net, _mode().to_program(w), w


def _points(n, seed=3):
    lo = np.array([b[0] for b in ref.DOMAIN])
    hi = np.array([b[1] for b in ref.DOMAIN])
    u = jax.random.uniform(jax.random.PRNGKey(seed), (n, 3), jnp.float64)
    return jnp.asarray(lo + (hi - lo) * u)


def _tower(f, x, v, order):
    """[f(x), D_v f(x), ..., D_v^order f(x)] by nested ``jax.jvp``."""
    out, g = [f(x)], f
    for _ in range(order):
        g = (lambda g: lambda xx: jax.jvp(g, (xx,), (v,))[1])(g)
        out.append(g(x))
    return jnp.stack(out)


def test_apply_matches_the_reference(model):
    net, params, w = model
    x = _points(6)
    got = net.apply(params, x)
    assert got.shape == (6, SMALL["tokens"], SMALL["d_out"])
    np.testing.assert_allclose(got, ref.apply(SMALL, w, x), **TIGHT)
    np.testing.assert_allclose(token_points(net, x).reshape(6, -1, 3),
                               ref.tokens(SMALL, x), **TIGHT)


@pytest.mark.parametrize("order", [1, 2, 3, 4])
def test_wave_jet_matches_jvp_towers(order):
    """The wavelet jet's raw derivatives along a curve equal nested jvp of
    w1 sin + w2 cos, and so do their gradients in (w1, w2)."""
    c = 0.7 * jax.random.normal(jax.random.PRNGKey(order), (order + 1, 5),
                                jnp.float64)
    wts = jnp.array([0.8, -1.3])
    curve = lambda t, c: sum(c[k] * t ** k for k in range(order + 1))

    def mine(wts):
        return J.derivatives(Wave().jet_apply(wts, J.Jet(c)))

    def oracle(wts):
        f = lambda t: Wave().apply(wts, curve(t, c))
        return _tower(f, jnp.zeros(()), jnp.ones(()), order)

    np.testing.assert_allclose(mine(wts), oracle(wts), **TIGHT)
    probe = jax.random.normal(jax.random.PRNGKey(9), (order + 1, 5),
                              jnp.float64)
    grad = lambda f: jax.grad(lambda w: jnp.sum(probe * f(w)))(wts)
    np.testing.assert_allclose(grad(mine), grad(oracle), **TIGHT)


def test_pseudo_sequence_jet_is_exact():
    """Coefficient 0 is every token's point, the tangent is seeded on every
    token unchanged, and the higher coefficients are those of the point's
    curve: the map is affine."""
    seq = PseudoSequence(tokens=4, step=0.25)
    c = jax.random.normal(jax.random.PRNGKey(1), (4, 6, 3), jnp.float64)
    got = seq.jet_apply((), J.Jet(c)).coeffs
    assert got.shape == (4, 6, 4, 3)
    np.testing.assert_array_equal(got[0], seq.apply((), c[0]))
    for k in range(1, 4):
        np.testing.assert_array_equal(got[k], jnp.broadcast_to(
            c[k][:, None, :], got[k].shape))
    # token 3 lies 3 steps on in time; (x + 0.75) - x rounds by an ulp
    np.testing.assert_allclose(got[0, :, 3] - got[0, :, 0],
                               jnp.broadcast_to(jnp.array([0, 0, 0.75]),
                                                (6, 3)), rtol=0, atol=1e-15)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("order", [0, 1, 3])
def test_cross_attention_jet(impl, order):
    """Queries from one stream and keys/values from another, with biases:
    the jet under ``ntp`` (jnp) and ``ntp/pallas`` (the flash-jet kernel,
    interpret mode) against nested jvp of the primal block."""
    attn = Attention(8, n_heads=2, bias=True)
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1, attn.init(jax.random.PRNGKey(2), jnp.float64))
    cq = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (order + 1, 4, 3, 8),
                                 jnp.float64)
    ckv = 0.5 * jax.random.normal(jax.random.PRNGKey(4),
                                  (order + 1, 4, 3, 8), jnp.float64)
    got = J.derivatives(attn.jet_apply(params, J.Jet(cq), kv=J.Jet(ckv),
                                       impl=impl))
    curve = lambda t, c: sum(c[k] * t ** k for k in range(order + 1))
    f = lambda t: attn.apply(params, curve(t, cq), kv=curve(t, ckv))
    want = _tower(f, jnp.zeros(()), jnp.ones(()), order)
    np.testing.assert_allclose(got, want, **TIGHT)


@pytest.fixture(scope="module")
def raissi_ns():
    return get_operator(harness.program_operator(
        harness.find_cell("ns8x20.train")))


@pytest.mark.parametrize("engine", ["ntp", "ntp/pallas"])
def test_table_matches_nested_jvp_of_the_reference(model, raissi_ns, engine):
    """Every pure derivative through order 3 and every mixed partial of
    ``raissi-ns``, of every token, from the one jet forward of the table,
    against nested jvp of the reference."""
    from repro.core.engines import DerivativeEngine

    net, params, w = model
    x = _points(5)
    table = build_table(net, params, DerivativeEngine.from_spec(engine),
                        raissi_ns, x)
    for axis in range(3):
        tw = ref.tower(SMALL, w, x, (axis,) * 3)
        for k in range(4):
            for comp in range(2):
                np.testing.assert_allclose(table(axis, k, comp), tw[k][:, comp],
                                           **TIGHT)
    for axes in ref.MIXED:
        want = ref.tower(SMALL, w, x, axes)[-1]
        for comp in range(2):
            np.testing.assert_allclose(table.mixed(*axes, comp=comp),
                                       want[:, comp], **TIGHT)


LOSS_X, LOSS_BC = 4, 3          # points and face points of the loss test


@pytest.fixture(scope="module")
def reference_loss(model):
    """The reference's loss and gradient, once for both engines."""
    _, _, w = model
    return ref.loss_and_grad(SMALL, {"residual": 1.0, "boundary": 10.0}, 2)(
        w, _points(LOSS_X), _points(LOSS_BC, seed=5))


@pytest.mark.parametrize("engine", ["ntp", "ntp/pallas"])
def test_loss_and_gradient_match_the_reference(model, raissi_ns,
                                               reference_loss, engine):
    """``pinn_loss`` on the residual at every token's point and the face
    points' tokens, and its gradient leaf by leaf, against the reference's
    loss (blocks of 2 points) and ``jax.grad`` through its nested towers."""
    net, params, _ = model
    x, bc = _points(LOSS_X), _points(LOSS_BC, seed=5)
    bv = exact_values(raissi_ns, token_points(net, bc))

    @jax.jit
    def loss(p):
        return pinn_loss(p, op=raissi_ns, pts=x, bc_pts=bc, bc_vals=bv,
                         net=net, engine=engine,
                         weights=LossWeights(residual=1.0, bc=10.0))[0]

    got, g = jax.value_and_grad(loss)(params)
    want, g_ref = reference_loss
    mode = _mode()
    np.testing.assert_allclose(got, want, **TIGHT)
    for a, b in zip(mode.leaves(g), mode.leaves(mode.to_program(g_ref))):
        # a leaf's gradient is judged against its own largest entry, or a
        # thousandth of the loss where that is larger (the key bias's
        # gradient is zero but for rounding: softmax ignores a row shift)
        scale = max(float(np.abs(b).max()), 1e-3 * abs(float(want)))
        np.testing.assert_allclose(a / scale, b / scale, **TIGHT)
