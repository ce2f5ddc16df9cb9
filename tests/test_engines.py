"""The derivative-engine redesign: engines x networks agreement, spec
parsing and the deprecation shim, property tests of the jet algebra against
``jax.experimental.jet`` pushforwards (the :class:`JaxJetEngine` oracle), and
the new architectures training end-to-end."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import jet as jjet

from _compat import int_grid
from repro.core import jet as J
from repro.core import (AutodiffEngine, DenseMLP, DerivativeEngine,
                        FourierFeatureMLP, JaxJetEngine, MLP, MLPParams,
                        NTPEngine, ResidualMLP, Transformer, init_mlp,
                        make_network, network_names)
from repro.pinn import (OperatorRunConfig, get_operator, pinn_loss,
                        residual_values)
from repro.data.collocation import boundary_grid, sample_box

NETWORKS = {
    "dense": DenseMLP(2, 10, 3, 1),
    "mlp": MLP((2, 8, 12, 1)),
    "residual": ResidualMLP(2, 10, 2, 1),
    "fourier": FourierFeatureMLP(2, 10, 2, 1, n_features=6),
    # depth 1 / width 4 keeps the engine-agreement sweeps cheap (the
    # nested-autodiff oracle scales hard with both); the depth-2 width-8
    # trunk is oracle-checked through order 4 by the dedicated tests below
    "transformer": Transformer(2, 4, 1, 1, n_heads=2),
}


def _pts(n=5, d=2, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, d), jnp.float64)


# ---------------------------------------------------------------------------
# engines agree on every network
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(NETWORKS))
def test_all_engines_agree_on_derivs(name):
    net = NETWORKS[name]
    params = net.init(jax.random.PRNGKey(3), dtype=jnp.float64)
    x = _pts()
    a = NTPEngine("jnp").derivs(net, params, x, 3)
    b = AutodiffEngine().derivs(net, params, x, 3)
    c = JaxJetEngine().derivs(net, params, x, 3)
    assert a.shape == (4, x.shape[0], net.d_out)
    np.testing.assert_allclose(a, b, rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(a, c, rtol=1e-8, atol=1e-10)


@pytest.mark.parametrize("name", sorted(NETWORKS))
def test_grid_and_cross_agree(name):
    net = NETWORKS[name]
    params = net.init(jax.random.PRNGKey(4), dtype=jnp.float64)
    x = _pts(4)
    np.testing.assert_allclose(NTPEngine("jnp").grid(net, params, x, 2),
                               AutodiffEngine().grid(net, params, x, 2),
                               rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(NTPEngine("jnp").cross(net, params, x, (0, 1)),
                               AutodiffEngine().cross(net, params, x, (0, 1)),
                               rtol=1e-7, atol=1e-9)


@pytest.mark.parametrize("name", sorted(NETWORKS))
def test_pallas_impl_matches_jnp_on_networks(name):
    net = NETWORKS[name]
    params = net.init(jax.random.PRNGKey(5), dtype=jnp.float32)
    x = _pts(6).astype(jnp.float32)
    a = NTPEngine("jnp").derivs(net, params, x, 3)
    b = NTPEngine("pallas").derivs(net, params, x, 3)
    np.testing.assert_allclose(a, b, rtol=3e-3, atol=3e-4)


def test_vector_valued_network_derivs():
    net = MLP((2, 8, 3))
    params = net.init(jax.random.PRNGKey(9), dtype=jnp.float64)
    x = _pts()
    a = NTPEngine("jnp").derivs(net, params, x, 2)
    b = AutodiffEngine().derivs(net, params, x, 2)   # jacfwd tower path
    c = JaxJetEngine().derivs(net, params, x, 2)
    assert a.shape == (3, 5, 3)
    np.testing.assert_allclose(a, b, rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(a, c, rtol=1e-8, atol=1e-10)


def test_apply_matches_order_zero():
    for net in NETWORKS.values():
        params = net.init(jax.random.PRNGKey(6), dtype=jnp.float64)
        x = _pts(3)
        y = net.apply(params, x)
        np.testing.assert_allclose(y, net.apply(params, x, unroll=True),
                                   rtol=1e-12)
        np.testing.assert_allclose(
            y[None], NTPEngine("jnp").derivs(net, params, x, 0), rtol=1e-12)


# ---------------------------------------------------------------------------
# spec parsing (the engine=/impl= deprecation shim is gone: spec strings and
# engine instances are the only accepted forms)
# ---------------------------------------------------------------------------

def test_from_spec_round_trips():
    for spec, typ in (("ntp", NTPEngine), ("ntp/pallas", NTPEngine),
                      ("autodiff", AutodiffEngine), ("jet", JaxJetEngine)):
        eng = DerivativeEngine.from_spec(spec)
        assert isinstance(eng, typ)
        assert eng.spec == spec
        assert DerivativeEngine.from_spec(eng) is eng
    assert DerivativeEngine.from_spec("ntp/pallas").impl == "pallas"
    with pytest.raises(ValueError):
        DerivativeEngine.from_spec("hessian")
    with pytest.raises(ValueError):
        DerivativeEngine.from_spec("autodiff/pallas")
    with pytest.raises(ValueError):
        NTPEngine("cuda")


def test_engine_spec_parse_and_str_round_trip():
    """EngineSpec is the typed form of the spec string: parse accepts every
    user-facing spelling, str() renders the canonical short form, and the
    round trip is stable."""
    from repro.core import EngineSpec
    assert EngineSpec.parse("ntp") == EngineSpec("ntp", "jnp")
    assert EngineSpec.parse("ntp/jnp") == EngineSpec.parse("ntp")
    assert EngineSpec.parse("NTP/Pallas") == EngineSpec("ntp", "pallas")
    assert EngineSpec.parse("jax-jet") == EngineSpec("jet")
    assert str(EngineSpec.parse("ntp/jnp")) == "ntp"       # default impl short
    assert str(EngineSpec.parse("ntp/pallas")) == "ntp/pallas"
    assert str(EngineSpec.parse("autodiff")) == "autodiff"
    for spec in ("ntp", "ntp/pallas", "autodiff", "jet"):
        assert str(EngineSpec.parse(str(EngineSpec.parse(spec)))) == spec
    # parse also normalizes engine instances and passes specs through
    assert EngineSpec.parse(NTPEngine("pallas")) == EngineSpec("ntp", "pallas")
    assert EngineSpec.parse(EngineSpec("jet")) == EngineSpec("jet")
    for bad in ("hessian", "autodiff/pallas", "ntp/cuda", "jet/jnp", ""):
        with pytest.raises(ValueError, match="engine spec"):
            EngineSpec.parse(bad)


def test_engine_spec_build_matches_from_spec():
    from repro.core import EngineSpec
    eng = EngineSpec.parse("ntp/pallas").build()
    assert isinstance(eng, NTPEngine) and eng.impl == "pallas"
    assert isinstance(EngineSpec.parse("jaxjet").build(), JaxJetEngine)
    # aliases flow through from_spec too
    assert isinstance(DerivativeEngine.from_spec("jax-jet"), JaxJetEngine)


# every canonical rendering an EngineSpec can produce; the fuzz test pins
# that NO input string parses to anything outside this closed set
_CANONICAL_SPECS = {"ntp", "ntp/pallas", "autodiff", "jet"}

_FUZZ_NAMES = ("ntp", "autodiff", "jet", "jax-jet", "jaxjet", "JET",
               "", "pallas", "ntp2", "n t p", "autodif", "hessian",
               "ntp/jnp", "jet/")
_FUZZ_IMPLS = ("", "jnp", "pallas", "JNP", "Pallas", "cuda", "tpu", "x",
               "jnp/pallas")


@int_grid(("seed", 0, 100_000), max_examples=20)
def test_engine_spec_fuzz_roundtrip_or_typed_error(seed):
    """Random spec-ish strings (valid names, aliases, junk, case noise,
    stray whitespace, bogus or doubled impl suffixes) either parse to one
    of the four canonical specs -- with a stable parse/str round trip and
    a buildable engine whose own .spec re-parses to the same value -- or
    raise a ValueError carrying the offending input.  Nothing else: no
    silent fallbacks, no crashes of any other type."""
    import random

    from repro.core import EngineSpec
    rng = random.Random(seed)
    for _ in range(25):
        s = rng.choice(_FUZZ_NAMES)
        case = rng.choice((str.upper, str.lower, str.title, lambda t: t))
        s = case(s)
        if rng.random() < 0.6:
            s = f"{s}/{rng.choice(_FUZZ_IMPLS)}"
        if rng.random() < 0.3:
            s = f"  {s} "
        try:
            spec = EngineSpec.parse(s)
        except ValueError as e:
            # the typed error names the offending input verbatim
            assert "bad engine spec" in str(e) and repr(s) in str(e), (s, e)
            continue
        canonical = str(spec)
        assert canonical in _CANONICAL_SPECS, (s, canonical)
        assert EngineSpec.parse(canonical) == spec            # round trip
        assert str(EngineSpec.parse(canonical)) == canonical  # idempotent
        built = spec.build()
        assert EngineSpec.parse(built.spec) == spec           # engine agrees


def test_engine_spec_direct_constructor_validates():
    from repro.core import EngineSpec
    with pytest.raises(ValueError, match="unknown engine"):
        EngineSpec("hessian")
    with pytest.raises(ValueError, match="takes no /impl"):
        EngineSpec("autodiff", "pallas")
    with pytest.raises(ValueError, match="unknown impl"):
        EngineSpec("ntp", "cuda")
    # the default impl is filled in, making equality canonical
    assert EngineSpec("ntp") == EngineSpec("ntp", "jnp")


def test_legacy_shim_is_gone():
    """ROADMAP scheduled the PR-2 deprecation shim for removal: the
    engine=/impl= keyword pair and the bare-MLPParams reconstruction no
    longer exist anywhere on the public surface.  Nor does the seed's
    second dense-MLP path: its jet forward, plain forward and wrappers
    (``DenseMLP`` and the engines are the one path), its oracle module
    (``AutodiffEngine`` / ``JaxJetEngine`` are the oracles), and the Burgers
    objective's string-pair shim."""
    import importlib.util

    import repro.core as core
    import repro.core.ntp as ntp
    import repro.pinn as pinn
    import repro.pinn.losses as losses
    import repro.pinn.operators as operators
    import repro.pinn.trainer as trainer
    assert not hasattr(core, "resolve_engine")
    assert not hasattr(pinn, "resolve_net_engine")
    for name in ("ntp_jet", "ntp_forward", "ntp_derivatives", "ntp_grid",
                 "cross", "mlp_apply", "_dense_view"):
        assert not hasattr(core, name) and not hasattr(ntp, name), name
    assert importlib.util.find_spec("repro.core.baselines") is None
    assert not hasattr(DenseMLP, "from_params")
    assert not hasattr(pinn, "ntp_pure_derivs")
    assert not hasattr(operators, "ntp_pure_derivs")
    assert not hasattr(losses, "_burgers_engine")
    assert not hasattr(trainer, "profile_lambda_from_history")
    assert "target_lam" in trainer.PINNResult.__dataclass_fields__
    op = get_operator("heat")
    params = init_mlp(jax.random.PRNGKey(0), 2, 10, 2, 1, dtype=jnp.float64)
    x = sample_box(jax.random.PRNGKey(1), op.domain, 8, jnp.float64)
    with pytest.raises(TypeError):
        residual_values(params, op, x, engine="ntp", impl="jnp")
    with pytest.raises(TypeError):            # net= is now required
        residual_values(params, op, x)
    residual_values(params, op, x, net=DenseMLP(2, 10, 2, 1))  # new form ok


def test_net_must_match_operator_rank():
    """d_out/d_in mismatches raise up front instead of mis-slicing; matched
    vector networks flow through (the old d_out > 1 ValueError is gone)."""
    op = get_operator("heat")
    net = MLP((2, 8, 2))
    params = net.init(jax.random.PRNGKey(0), dtype=jnp.float64)
    x = sample_box(jax.random.PRNGKey(1), op.domain, 4, jnp.float64)
    bc = boundary_grid(op.domain, 4, jnp.float64)
    with pytest.raises(ValueError, match="d_out=2"):
        pinn_loss(params, op=op, pts=x, bc_pts=bc,
                  bc_vals=jnp.zeros(bc.shape[0]), net=net)
    with pytest.raises(ValueError, match="d_in"):
        residual_values(params, op, sample_box(jax.random.PRNGKey(1),
                                               ((0, 1),) * 3, 4, jnp.float64),
                        net=MLP((3, 8, 1)),
                        engine="ntp")


def test_network_registry():
    assert {"dense", "mlp", "residual", "fourier",
            "transformer", "pinnsformer"} <= set(network_names())
    net = make_network("fourier", d_in=3, d_out=1, width=8, depth=2,
                       n_features=4)
    assert net.d_in == 3 and net.d_out == 1
    with pytest.raises(KeyError):
        make_network("perceiver", d_in=2, d_out=1, width=8, depth=2)
    dense = make_network("dense", d_in=2, d_out=1, width=8, depth=2)
    assert isinstance(dense.init(jax.random.PRNGKey(0)), MLPParams)
    tr = make_network("transformer", d_in=2, d_out=1, width=8, depth=2,
                      n_heads=4)
    assert tr.n_heads == 4 and tr.d_out == 1
    with pytest.raises(ValueError):     # width must split across heads
        make_network("transformer", d_in=2, d_out=1, width=9, depth=1,
                     n_heads=2)


# ---------------------------------------------------------------------------
# jet-algebra property tests against jax.experimental.jet pushforwards
# ---------------------------------------------------------------------------

def _rand_jet(seed: int, order: int, shape=(3,), positive=False) -> J.Jet:
    c = 0.5 * jax.random.normal(jax.random.PRNGKey(seed),
                                (order + 1,) + shape, jnp.float64)
    if positive:
        c = c.at[0].set(jnp.abs(c[0]) + 1.0)
    return J.Jet(c)


def _jjet_raw(fn, *jets: J.Jet) -> jnp.ndarray:
    """Raw derivatives of fn(*jets) per jax.experimental.jet (the oracle)."""
    raws = [J.derivatives(j) for j in jets]
    y0, ys = jjet.jet(fn, tuple(r[0] for r in raws),
                      tuple(list(r[1:]) for r in raws))
    return jnp.stack([y0] + list(ys))


def _check(mine: J.Jet, fn, *jets: J.Jet):
    np.testing.assert_allclose(J.derivatives(mine), _jjet_raw(fn, *jets),
                               rtol=1e-8, atol=1e-9)


@int_grid(("order", 1, 6), ("seed", 0, 10_000), max_examples=10)
def test_exp_matches_jax_jet(order, seed):
    a = _rand_jet(seed, order)
    _check(J.exp(a), jnp.exp, a)


@int_grid(("order", 1, 6), ("seed", 0, 10_000), max_examples=10)
def test_log_matches_jax_jet(order, seed):
    a = _rand_jet(seed, order, positive=True)
    _check(J.log(a), jnp.log, a)


@int_grid(("order", 1, 6), ("seed", 0, 10_000), max_examples=10)
def test_div_matches_jax_jet(order, seed):
    a = _rand_jet(seed, order)
    b = _rand_jet(seed + 1, order, positive=True)
    _check(J.div(a, b), jnp.divide, a, b)


@int_grid(("order", 1, 6), ("seed", 0, 10_000), max_examples=10)
def test_powr_matches_jax_jet(order, seed):
    a = _rand_jet(seed, order, positive=True)
    _check(J.powr(a, 1.7), lambda x: jnp.power(x, 1.7), a)
    _check(J.sqrt(a), jnp.sqrt, a)
    _check(J.rsqrt(a), jax.lax.rsqrt, a)


@int_grid(("order", 1, 6), ("seed", 0, 10_000), max_examples=10)
def test_softmax_matches_jax_jet(order, seed):
    a = _rand_jet(seed, order, shape=(2, 4))
    _check(J.softmax(a), jax.nn.softmax, a)


@int_grid(("order", 1, 6), ("seed", 0, 10_000), max_examples=10)
def test_einsum_matches_jax_jet(order, seed):
    """Attention leans on jet x jet einsum: the batched score contraction
    (Cauchy convolution over the coefficient axis) and the degenerate
    jet x constant case must both match JAX's Taylor mode."""
    a = _rand_jet(seed, order, shape=(2, 3, 4))
    b = _rand_jet(seed + 1, order, shape=(2, 3, 4))
    eq = "bqd,bkd->bqk"
    _check(J.einsum(eq, a, b), lambda x, y: jnp.einsum(eq, x, y), a, b)
    # ellipsis batch form (what SelfAttention emits, with a head axis)
    ah = _rand_jet(seed + 2, order, shape=(2, 3, 2, 2))
    bh = _rand_jet(seed + 3, order, shape=(2, 3, 2, 2))
    eqh = "...qhd,...khd->...hqk"
    _check(J.einsum(eqh, ah, bh), lambda x, y: jnp.einsum(eqh, x, y), ah, bh)
    # t-constant operand degenerates to a per-coefficient contraction
    const = jnp.asarray(jax.random.normal(jax.random.PRNGKey(seed + 4),
                                          (2, 3, 4), jnp.float64))
    _check(J.einsum(eq, a, const), lambda x: jnp.einsum(eq, x, const), a)


@int_grid(("order", 1, 6), ("seed", 0, 10_000), max_examples=10)
def test_where_matches_jax_jet(order, seed):
    """Masked selection with a t-constant predicate (attention masking, relu):
    exact per-branch coefficients, including mask broadcast and the
    jet-vs-scalar promoted form."""
    a = _rand_jet(seed, order, shape=(3, 4))
    b = _rand_jet(seed + 1, order, shape=(3, 4))
    mask = jax.random.bernoulli(jax.random.PRNGKey(seed), 0.5, (3, 4))
    _check(J.where(mask, a, b), lambda x, y: jnp.where(mask, x, y), a, b)
    # mask broadcasts across leading axes
    row = jax.random.bernoulli(jax.random.PRNGKey(seed + 2), 0.5, (4,))
    _check(J.where(row, a, b), lambda x, y: jnp.where(row, x, y), a, b)
    # scalar branch promotes to a constant jet (the attention -inf fill)
    _check(J.where(mask, a, -30.0), lambda x: jnp.where(mask, x, -30.0), a)


@int_grid(("order", 1, 6), ("seed", 0, 10_000), max_examples=10)
def test_rms_norm_matches_jax_jet(order, seed):
    a = _rand_jet(seed, order, shape=(2, 4))
    gamma = jnp.linspace(0.5, 1.5, 4, dtype=jnp.float64)

    def ref(x):
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + 1e-6) * gamma

    _check(J.rms_norm(a, gamma), ref, a)


# ---------------------------------------------------------------------------
# high orders (5-6) at degenerate attention shapes: single token, d_head=1,
# n_heads=1 -- the edges a fused kernel is most likely to get wrong
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("order", (5, 6))
@pytest.mark.parametrize("shape", ((1, 1), (1, 3), (4, 1)))
def test_softmax_high_order_degenerate_shapes(order, shape):
    """Orders 5-6 on (rows, keys) score slabs including a single key (the
    softmax collapses to the constant 1: every higher coefficient must
    vanish exactly) and a single row."""
    a = _rand_jet(order * 7 + shape[0], order, shape=shape)
    _check(J.softmax(a), jax.nn.softmax, a)
    if shape[-1] == 1:
        p = J.softmax(a)
        np.testing.assert_allclose(p.coeffs[0], 1.0, rtol=1e-12)
        np.testing.assert_allclose(p.coeffs[1:], 0.0, atol=1e-12)


@pytest.mark.parametrize("order", (5, 6))
@pytest.mark.parametrize("width", (1, 2, 5))
def test_rms_norm_high_order_degenerate_shapes(order, width):
    """Orders 5-6 down to a single feature (rsqrt recurrence on a scalar
    mean square), primal shifted away from the ms ~ 0 singular point."""
    a = _rand_jet(order * 11 + width, order, shape=(3, width))
    a = J.Jet(a.coeffs.at[0].add(jnp.where(a.coeffs[0] >= 0, 1.0, -1.0)))
    gamma = jnp.linspace(0.7, 1.3, width, dtype=jnp.float64)

    def ref(x):
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + 1e-6) * gamma

    _check(J.rms_norm(a, gamma), ref, a)


@pytest.mark.parametrize("order", (5, 6))
@pytest.mark.parametrize("tok_d", ((1, 1), (1, 4), (3, 1)))
def test_attention_score_product_high_order_degenerate_shapes(order, tok_d):
    """The full attention-score chain (jet x jet Cauchy einsum -> scale ->
    softmax) at orders 5-6 for single-token and d_head=1 shapes, against
    jax.experimental.jet -- on BOTH the reference algebra and the fused
    kernel dispatch (ops.jet_attention_scores)."""
    from repro.kernels import ops as kops
    t, d = tok_d
    q = _rand_jet(order * 13 + t, order, shape=(2, t, d))
    k = _rand_jet(order * 13 + t + 1, order, shape=(2, t, d))
    scale = 1.0 / math.sqrt(d)

    def fn(qq, kk):
        return jax.nn.softmax(scale * jnp.einsum("bqd,bkd->bqk", qq, kk),
                              axis=-1)

    algebra = J.softmax(J.scale(J.einsum("bqd,bkd->bqk", q, k), scale))
    _check(algebra, fn, q, k)
    fused = J.Jet(kops.jet_attention_scores(q.coeffs, k.coeffs, scale))
    np.testing.assert_allclose(J.derivatives(fused), _jjet_raw(fn, q, k),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("dim,heads,tokens", [(2, 2, 3), (2, 1, 3), (4, 2, 1)])
def test_self_attention_degenerate_configs_match_jax_jet(dim, heads, tokens):
    """The SelfAttention leaf at order 5 for d_head=1, n_heads=1, and a
    single token, jnp and pallas paths both against jax.experimental.jet."""
    from jax.experimental import jet as jjet
    from repro.core.modules import SelfAttention
    attn = SelfAttention(dim, n_heads=heads)
    params = attn.init(jax.random.PRNGKey(dim * 10 + heads), jnp.float64)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(tokens),
                                (2, tokens, dim), jnp.float64)
    order = 5
    jin = _rand_jet(order + dim, order, shape=x.shape)
    raws = J.derivatives(jin)
    y0, ys = jjet.jet(lambda xx: attn.apply(params, xx),
                      (raws[0],), ([*raws[1:]],))
    want = jnp.stack([y0] + list(ys))
    for impl in ("jnp", "pallas"):
        got = J.derivatives(attn.jet_apply(params, jin, impl=impl))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# new architectures train end-to-end through the n-TangentProp engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("network,net_kwargs", [
    ("residual", {}),
    ("fourier", {"n_features": 8, "feature_scale": 0.5}),
])
def test_new_networks_train_on_registered_pde(network, net_kwargs,
                                              trained_operator):
    cfg = OperatorRunConfig(op="heat", network=network, net_kwargs=net_kwargs,
                            width=8, depth=2, adam_steps=30, adam_lr=3e-3,
                            n_domain=64, n_bc=8, log_every=10,
                            eval_pts_per_axis=8, engine="ntp")
    res = trained_operator(cfg)
    assert np.isfinite(res.l2_error)
    assert res.loss_history[-1] < res.loss_history[0]
    assert type(res.net).__name__ in ("ResidualMLP", "FourierFeatureMLP")


# ---------------------------------------------------------------------------
# the transformer trunk: oracle agreement through order 4 + e2e training
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def transformer_order4_oracles():
    """The depth-2 attention trunk's order-4 oracle stacks, computed ONCE
    for this module: the nested-autodiff tower here is by far the most
    expensive single computation in tier-1, and both the jnp and the fused
    pallas acceptance tests compare against the same reference."""
    net = Transformer(2, 8, 2, 1, n_heads=2)
    params = net.init(jax.random.PRNGKey(11), dtype=jnp.float64)
    x = _pts(4, seed=12)
    ad = AutodiffEngine().derivs(net, params, x, 4)
    jj = JaxJetEngine().derivs(net, params, x, 4)
    return net, params, x, ad, jj


def test_transformer_matches_autodiff_oracle_to_order_4(
        transformer_order4_oracles):
    """Acceptance: derivs and grid of the attention trunk match the nested
    autodiff oracle to <= 1e-4 through order 4 (they actually agree to
    float64 roundoff -- the jet algebra is exact, not approximate)."""
    net, params, x, ad, jj = transformer_order4_oracles
    a = NTPEngine("jnp").derivs(net, params, x, 4)
    assert a.shape == (5, 4, 1)
    np.testing.assert_allclose(a, ad, rtol=1e-6, atol=1e-4)
    np.testing.assert_allclose(a, jj, rtol=1e-6, atol=1e-4)
    np.testing.assert_allclose(NTPEngine("jnp").grid(net, params, x, 4),
                               AutodiffEngine().grid(net, params, x, 4),
                               rtol=1e-6, atol=1e-4)


def test_transformer_pallas_fused_matches_oracles_to_order_4(
        transformer_order4_oracles):
    """Acceptance: with the FUSED flash-attention and rms_norm kernels
    active (ntp/pallas routes SelfAttention through the single-launch
    kernels.ops.jet_flash_attention and RMSNorm through jet_rms_norm),
    the trunk still matches the nested-autodiff AND jax.experimental.jet
    oracles through order 4 within 1e-4."""
    from repro.kernels import ops as kops
    assert kops.epilogues()["flash_attention"] is kops.EpilogueKind.FUSED_OP
    assert kops.epilogues()["rms_norm"] is kops.EpilogueKind.FUSED_OP
    net, params, x, ad, jj = transformer_order4_oracles
    got = NTPEngine("pallas").derivs(net, params, x, 4)
    assert got.shape == (5, 4, 1)
    np.testing.assert_allclose(got, ad, rtol=1e-6, atol=1e-4)
    np.testing.assert_allclose(got, jj, rtol=1e-6, atol=1e-4)


@pytest.mark.parametrize("mask", (None, "causal", ("local", 2)),
                         ids=("none", "causal", "local2"))
def test_masked_transformer_flash_matches_jax_jet_to_order_4(mask):
    """Acceptance: every mask variant of the flash-jet attention trunk
    matches the independent jax.experimental.jet oracle to <= 1e-5 through
    order 4, under both impls (the oracle traces the PRIMAL apply, so the
    masked-softmax jet recurrences are checked against plain masking)."""
    net = Transformer(2, 8, 2, 1, n_heads=2, mask=mask)
    params = net.init(jax.random.PRNGKey(21), dtype=jnp.float64)
    x = _pts(4, seed=22)
    jj = JaxJetEngine().derivs(net, params, x, 4)
    for impl in ("jnp", "pallas"):
        got = NTPEngine(impl).derivs(net, params, x, 4)
        assert got.shape == (5, 4, 1)
        np.testing.assert_allclose(got, jj, rtol=1e-6, atol=1e-5)


def test_transformer_vector_output_and_cross():
    """d_out > 1 attention trunk: the component axis rides through derivs
    and the polarization cross, like every MLP-family network."""
    net = Transformer(2, 4, 1, 2, n_heads=2)
    params = net.init(jax.random.PRNGKey(13), dtype=jnp.float64)
    x = _pts(4, seed=14)
    a = NTPEngine("jnp").derivs(net, params, x, 2)
    b = AutodiffEngine().derivs(net, params, x, 2)   # jacfwd tower path
    assert a.shape == (3, 4, 2)
    np.testing.assert_allclose(a, b, rtol=1e-7, atol=1e-8)
    np.testing.assert_allclose(NTPEngine("jnp").cross(net, params, x, (0, 1)),
                               AutodiffEngine().cross(net, params, x, (0, 1)),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("engine", ("ntp", "ntp/pallas"))
def test_transformer_trains_on_registered_pde(engine, trained_operator):
    """Acceptance: make_network("transformer", ...) trains end to end on a
    registered operator under ntp AND ntp/pallas (the latter exercising the
    fused attention-score + rms_norm kernels inside the training loop)."""
    cfg = OperatorRunConfig(op="heat", network="transformer",
                            net_kwargs={"n_heads": 2}, width=8, depth=1,
                            adam_steps=30, adam_lr=1e-3, n_domain=48, n_bc=8,
                            log_every=10, eval_pts_per_axis=6, engine=engine)
    res = trained_operator(cfg)
    assert type(res.net).__name__ == "Transformer"
    assert np.isfinite(res.l2_error)
    assert res.loss_history[-1] < res.loss_history[0]
