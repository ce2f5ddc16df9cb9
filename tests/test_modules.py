"""The compositional jet-module layer (repro.core.modules): leaves and
combinators against the jet/autodiff oracles, the Pallas dispatch over
batched (token) axes, the leaf registry, and the refactor guard pinning the
four pre-existing networks' parameter pytrees to their pre-module formulas
bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import jet as J
from repro.core import (DenseMLP, FourierFeatureMLP, MLP, ResidualMLP,
                        Transformer)
from repro.core.modules import (Activation, CoordinateEmbedding, Dense,
                                FourierFeatures, MLPBlock, RMSNorm, Residual,
                                SelfAttention, Sequential, TokenPool,
                                make_module, module_names, register_module)
from repro.core.ntp import init_mlp, xavier_uniform
from repro.kernels import ops as kops


def _jet_of(x, order=3):
    return J.seed(x, jnp.ones_like(x), order)


def _autodiff_derivs(fn, x, v, order):
    """Directional-derivative stack of fn along v via nested jacfwd."""
    def along(xi, vi):
        g = lambda t: fn(xi + t * vi)
        outs, h = [], g
        for _ in range(order + 1):
            outs.append(h)
            h = jax.jacfwd(h)
        t0 = jnp.asarray(0.0, x.dtype)
        return jnp.stack([o(t0) for o in outs])
    return jax.vmap(along)(x, v)


def _check_module(mod, params, x, order=3, tol=1e-8):
    """jet_apply's raw derivatives match a nested-autodiff tower over apply."""
    jet = mod.jet_apply(params, _jet_of(x, order))
    got = J.derivatives(jet)
    ref = _autodiff_derivs(lambda xi: mod.apply(params, xi), x,
                           jnp.ones_like(x), order)
    np.testing.assert_allclose(got, np.moveaxis(np.asarray(ref), 0, 1),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# leaves against the autodiff oracle
# ---------------------------------------------------------------------------

def test_dense_and_activation_leaves():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 3), jnp.float64)
    mod = Dense(3, 5, "tanh")
    params = mod.init(jax.random.PRNGKey(1), dtype=jnp.float64)
    _check_module(mod, params, x)
    act = Activation("sin")
    _check_module(act, act.init(jax.random.PRNGKey(2)), x)
    # standalone Activation dispatches to the fused kernel under pallas
    xf = x.astype(jnp.float32)
    a = act.jet_apply((), _jet_of(xf, 3), impl="jnp")
    b = act.jet_apply((), _jet_of(xf, 3), impl="pallas")
    np.testing.assert_allclose(a.coeffs, b.coeffs, rtol=3e-3, atol=3e-4)


def test_rms_norm_and_mlp_block_leaves():
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 6), jnp.float64)
    norm = RMSNorm(6)
    _check_module(norm, norm.init(jax.random.PRNGKey(4), dtype=jnp.float64), x)
    blk = MLPBlock(6, 12, "tanh")
    _check_module(blk, blk.init(jax.random.PRNGKey(5), dtype=jnp.float64), x)


def test_self_attention_leaf():
    """Attention on tokens (N, T, D): jet einsum/softmax against autodiff.
    Shapes stay small -- the nested-jacfwd oracle is cubic-ish in the
    flattened token block; higher orders and degenerate head/token shapes
    are covered by the (quasilinear) jax.experimental.jet checks in
    tests/test_engines.py and the registry parity sweep."""
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 3, 4), jnp.float64)
    attn = SelfAttention(4, n_heads=2)
    params = attn.init(jax.random.PRNGKey(7), dtype=jnp.float64)
    # flatten the token axes into the vmapped point for the autodiff oracle
    def fn(flat):
        return attn.apply(params, flat.reshape(3, 4)).reshape(-1)
    jet = attn.jet_apply(params, _jet_of(x, 3))
    got = J.derivatives(jet).reshape(4, 2, -1)
    ref = _autodiff_derivs(fn, x.reshape(2, -1), jnp.ones((2, 12), x.dtype), 3)
    np.testing.assert_allclose(got, np.moveaxis(np.asarray(ref), 0, 1),
                               rtol=1e-8, atol=1e-8)
    with pytest.raises(ValueError, match="divisible"):
        SelfAttention(6, n_heads=4)


def test_coordinate_embedding_and_pool():
    x = jax.random.normal(jax.random.PRNGKey(8), (5, 2), jnp.float64)
    emb = CoordinateEmbedding(2, 4)
    params = emb.init(jax.random.PRNGKey(9), dtype=jnp.float64)
    toks = emb.apply(params, x)
    assert toks.shape == (5, 2, 4)
    jet = emb.jet_apply(params, _jet_of(x, 2))
    assert jet.shape == (5, 2, 4)
    np.testing.assert_allclose(jet.primal, toks, rtol=1e-12)
    pooled = TokenPool().apply((), toks)
    np.testing.assert_allclose(pooled, toks.mean(axis=-2), rtol=1e-12)


def test_fourier_features_leaf():
    x = jax.random.normal(jax.random.PRNGKey(10), (4, 2), jnp.float64)
    ff = FourierFeatures(2, 5, scale=0.7)
    B = ff.init(jax.random.PRNGKey(11), dtype=jnp.float64)
    assert B.shape == (2, 5)
    _check_module(ff, B, x)


# ---------------------------------------------------------------------------
# combinators
# ---------------------------------------------------------------------------

def test_sequential_and_residual_compose():
    x = jax.random.normal(jax.random.PRNGKey(12), (4, 3), jnp.float64)
    seq = Sequential((Dense(3, 8, "tanh"), Residual(Dense(8, 8, "tanh")),
                      Dense(8, 2, None)))
    params = seq.init(jax.random.PRNGKey(13), dtype=jnp.float64)
    assert len(params) == 3
    _check_module(seq, params, x)
    # residual params ARE the inner module's (no extra nesting)
    w, b = params[1]
    assert w.shape == (8, 8) and b.shape == (8,)


def test_sequential_key_split_is_stable():
    """One key per child, in order: inserting a stateless module must not
    reshuffle the parameterized siblings' initializations (the property the
    bit-identical network rewrites rely on)."""
    key = jax.random.PRNGKey(14)
    plain = Sequential((Dense(3, 4, "tanh"), Dense(4, 2, None)))
    ks = jax.random.split(key, 2)
    p = plain.init(key, dtype=jnp.float64)
    np.testing.assert_array_equal(p[0][0],
                                  xavier_uniform(ks[0], 3, 4, jnp.float64))
    np.testing.assert_array_equal(p[1][0],
                                  xavier_uniform(ks[1], 4, 2, jnp.float64))


# ---------------------------------------------------------------------------
# pallas dispatch: batched (token) axes + epilogue fallback
# ---------------------------------------------------------------------------

def test_jet_dense_folds_token_axes():
    """ops.jet_dense accepts (n+1, N, T, D) and matches the per-token
    reference -- the dispatch path every transformer Dense rides."""
    c = jax.random.normal(jax.random.PRNGKey(15), (4, 3, 2, 8), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(16), (8, 5), jnp.float32) * 0.3
    b = jnp.linspace(-0.2, 0.2, 5, dtype=jnp.float32)
    out = kops.jet_dense(c, w, b, "tanh")
    assert out.shape == (4, 3, 2, 5)
    for t in range(2):
        np.testing.assert_allclose(out[:, :, t],
                                   kops.jet_dense(c[:, :, t], w, b, "tanh"),
                                   rtol=2e-5, atol=2e-6)


def test_dense_pallas_epilogue_fallback():
    """An activation without a kernel table (softplus) still runs under
    impl='pallas': the kernel does the linear part, the jet algebra the
    activation.  Fused epilogues must be flagged correctly."""
    assert kops.epilogues().get("tanh") is kops.EpilogueKind.ACTIVATION
    assert "softplus" not in kops.epilogues()
    x = jax.random.normal(jax.random.PRNGKey(17), (4, 3), jnp.float32)
    mod = Dense(3, 6, "softplus")
    params = mod.init(jax.random.PRNGKey(18), dtype=jnp.float32)
    a = mod.jet_apply(params, _jet_of(x, 3), impl="jnp")
    b = mod.jet_apply(params, _jet_of(x, 3), impl="pallas")
    np.testing.assert_allclose(a.coeffs, b.coeffs, rtol=3e-3, atol=3e-4)
    with pytest.raises(ValueError, match="impl"):
        mod.jet_apply(params, _jet_of(x, 3), impl="cuda")


# ---------------------------------------------------------------------------
# leaf registry
# ---------------------------------------------------------------------------

def test_module_registry():
    assert {"dense", "activation", "fourier_features", "rms_norm",
            "self_attention", "mlp_block", "coordinate_embedding",
            "token_pool", "sequential", "residual", "wave",
            "pseudo_sequence"} <= set(module_names())
    mod = make_module("dense", d_in=3, d_out=4, activation="tanh")
    assert isinstance(mod, Dense)
    with pytest.raises(KeyError):
        make_module("flash_attention")
    with pytest.raises(ValueError):
        register_module("dense", Dense)  # duplicate


# ---------------------------------------------------------------------------
# refactor guard: the four pre-module networks keep their exact param
# pytrees (structure AND values) and their module graphs consume them
# ---------------------------------------------------------------------------

def test_dense_mlp_params_unchanged_by_module_refactor():
    net = DenseMLP(2, 10, 3, 1)
    key = jax.random.PRNGKey(19)
    p = net.init(key, dtype=jnp.float64)
    ref = init_mlp(key, 2, 10, 3, 1, dtype=jnp.float64)
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(a, b)


def test_mlp_params_unchanged_by_module_refactor():
    """The module-native Sequential init reproduces the pre-refactor MLP
    formula (split once per layer, xavier + zero bias) bit for bit."""
    key = jax.random.PRNGKey(20)
    widths = (2, 8, 12, 3)
    p = MLP(widths).init(key, dtype=jnp.float64)
    ks = jax.random.split(key, len(widths) - 1)
    ref = tuple(
        (xavier_uniform(k, fi, fo, jnp.float64), jnp.zeros((fo,), jnp.float64))
        for k, fi, fo in zip(ks, widths[:-1], widths[1:]))
    assert jax.tree_util.tree_structure(p) == jax.tree_util.tree_structure(ref)
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(a, b)


def test_residual_mlp_params_unchanged_by_module_refactor():
    key = jax.random.PRNGKey(21)
    p = ResidualMLP(2, 6, 2, 1).init(key, dtype=jnp.float64)
    ks = jax.random.split(key, 4)
    np.testing.assert_array_equal(p["w_in"],
                                  xavier_uniform(ks[0], 2, 6, jnp.float64))
    np.testing.assert_array_equal(p["blocks"][1][0],
                                  xavier_uniform(ks[2], 6, 6, jnp.float64))
    np.testing.assert_array_equal(p["w_out"],
                                  xavier_uniform(ks[-1], 6, 1, jnp.float64))
    assert set(p) == {"w_in", "b_in", "blocks", "w_out", "b_out"}


def test_fourier_mlp_params_unchanged_by_module_refactor():
    key = jax.random.PRNGKey(22)
    net = FourierFeatureMLP(2, 8, 2, 1, n_features=5, feature_scale=1.5)
    p = net.init(key, dtype=jnp.float64)
    kb, km = jax.random.split(key)
    np.testing.assert_array_equal(
        p["B"], 1.5 * jax.random.normal(kb, (2, 5), jnp.float64))
    ref_mlp = MLP((10, 8, 8, 1)).init(km, dtype=jnp.float64)
    for a, b in zip(jax.tree_util.tree_leaves(p["mlp"]),
                    jax.tree_util.tree_leaves(ref_mlp)):
        np.testing.assert_array_equal(a, b)
    assert set(p) == {"B", "mlp"}


def test_transformer_graph_shapes():
    """Structure sanity of the first module-native network: block count,
    token flow, head split."""
    net = Transformer(3, 8, 2, 2, n_heads=2, mlp_ratio=2)
    graph = net._graph()
    # embed + 2*(attn, mlp) + norm + pool + head
    assert len(graph.modules) == 1 + 2 * 2 + 3
    params = net.init(jax.random.PRNGKey(23), dtype=jnp.float64)
    x = jax.random.normal(jax.random.PRNGKey(24), (5, 3), jnp.float64)
    y = net.apply(params, x)
    assert y.shape == (5, 2)
    jet = net.jet_apply(params, _jet_of(x, 2))
    np.testing.assert_allclose(jet.primal, y, rtol=1e-12)
