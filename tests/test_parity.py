"""Registry-driven jnp <-> pallas parity harness.

The safety net every fused Pallas kernel lands behind: for EVERY registered
leaf module/combinator (``repro.core.modules``) and EVERY registered
``Network`` (``repro.core.network``), ``jet_apply`` under ``impl="pallas"``
must match ``impl="jnp"`` at orders 0..4.

Coverage is asserted *from the registries*: the parametrize lists come from
``module_names()`` / ``network_names()``, so registering a new module or
network without adding a parity case here fails this file (first the
explicit coverage tests, then the KeyError in the sweep) -- a fused fast
path can never ship unchecked.

Inputs are float64 so the jnp side is a tight reference; the only pallas-
side deviation is the kernels' float32 MXU accumulation, well inside the
1e-5 gate at these shapes.  Params and coefficient stacks are built once
per case in session-scoped caches, so the full sweep stays cheap.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import jet as J
from repro.core.modules import (Activation, CoordinateEmbedding, Dense,
                                FourierFeatures, MLPBlock, PseudoSequence,
                                RMSNorm, Residual, SelfAttention, Sequential,
                                TokenPool, Wave, module_names,
                                normalize_attention_mask)
from repro.core.network import make_network, network_names

ORDERS = (0, 1, 2, 3, 4)
MAX_ORDER = max(ORDERS)
TOL = dict(rtol=1e-5, atol=1e-5)

# every attention-mask variant the API accepts, in user-facing spelling;
# the coverage test below proves this tuple spans every canonical kind, so
# a new mask variant cannot ship without joining the parity sweep
MASK_VARIANTS = (None, "causal", ("local", 2))

# one case per registered module: () -> (module, input shape).  Shapes keep
# a leading batch axis; token-axis modules carry (batch, tokens, features)
# so the pallas batch folding is exercised too.
MODULE_CASES = {
    "dense": lambda: (Dense(5, 4, "tanh"), (3, 5)),
    "activation": lambda: (Activation("sin"), (3, 5)),
    "fourier_features": lambda: (FourierFeatures(2, 4, scale=0.7), (3, 2)),
    "rms_norm": lambda: (RMSNorm(6), (3, 2, 6)),
    "self_attention": lambda: (SelfAttention(6, n_heads=2), (3, 4, 6)),
    "mlp_block": lambda: (MLPBlock(6, 12, "tanh"), (3, 6)),
    "coordinate_embedding": lambda: (CoordinateEmbedding(2, 4), (3, 2)),
    "token_pool": lambda: (TokenPool(), (3, 4, 6)),
    "sequential": lambda: (Sequential((Dense(4, 8, "sigmoid"),
                                       Dense(8, 2, None))), (3, 4)),
    "residual": lambda: (Residual(Dense(6, 6, "tanh")), (3, 6)),
    "wave": lambda: (Wave(), (3, 2, 5)),
    "pseudo_sequence": lambda: (PseudoSequence(3, 0.1), (3, 2)),
}

# one case per registered network: extra make_network kwargs
NETWORK_KWARGS = {
    "dense": {},
    "mlp": {},
    "residual": {},
    "fourier": {"n_features": 4},
    "transformer": {"n_heads": 2},
    "pinnsformer": {"activation": "wave", "n_heads": 2, "ff": 16, "head": 16,
                    "tokens": 3},
}


# ---------------------------------------------------------------------------
# coverage: the case tables above must track the registries exactly
# ---------------------------------------------------------------------------

def test_every_registered_module_has_a_parity_case():
    assert set(MODULE_CASES) == set(module_names()), (
        "parity sweep out of sync with the module registry; add a case to "
        "MODULE_CASES for every registered module")


def test_every_registered_network_has_a_parity_case():
    assert set(NETWORK_KWARGS) == set(network_names()), (
        "parity sweep out of sync with the network registry; add kwargs to "
        "NETWORK_KWARGS for every registered network")


def test_every_mask_kind_has_a_parity_variant():
    from repro.core.modules import ATTENTION_MASK_KINDS
    swept = {normalize_attention_mask(m)[0] for m in MASK_VARIANTS}
    assert swept == set(ATTENTION_MASK_KINDS), (
        "masked-attention parity sweep out of sync with the mask kinds "
        "normalize_attention_mask accepts; extend MASK_VARIANTS")


# ---------------------------------------------------------------------------
# session-scoped case caches: params + a max-order coefficient stack built
# once per case; lower orders slice the same stack
# ---------------------------------------------------------------------------

@pytest.fixture(scope="session")
def module_cases():
    cache = {}

    def get(name):
        if name not in cache:
            mod, shape = MODULE_CASES[name]()
            seed = sum(map(ord, name))
            params = mod.init(jax.random.PRNGKey(seed), dtype=jnp.float64)
            coeffs = 0.5 * jax.random.normal(
                jax.random.PRNGKey(seed + 1),
                (MAX_ORDER + 1,) + shape, jnp.float64)
            cache[name] = (mod, params, coeffs)
        return cache[name]

    return get


@pytest.fixture(scope="session")
def network_cases():
    cache = {}

    def get(name):
        if name not in cache:
            net = make_network(name, d_in=2, d_out=1, width=8, depth=2,
                              **NETWORK_KWARGS[name])
            seed = sum(map(ord, name))
            params = net.init(jax.random.PRNGKey(seed), dtype=jnp.float64)
            coeffs = 0.5 * jax.random.normal(
                jax.random.PRNGKey(seed + 1),
                (MAX_ORDER + 1, 4, net.d_in), jnp.float64)
            cache[name] = (net, params, coeffs)
        return cache[name]

    return get


# ---------------------------------------------------------------------------
# the sweep: pallas == jnp at every order for every registry entry
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("name", sorted(module_names()))
def test_module_pallas_matches_jnp(name, order, module_cases):
    mod, params, coeffs = module_cases(name)
    jet = J.Jet(coeffs[:order + 1])
    a = mod.jet_apply(params, jet, impl="jnp")
    b = mod.jet_apply(params, jet, impl="pallas")
    assert a.coeffs.shape == b.coeffs.shape
    np.testing.assert_allclose(np.asarray(a.coeffs), np.asarray(b.coeffs),
                               **TOL)


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("name", sorted(network_names()))
def test_network_pallas_matches_jnp(name, order, network_cases):
    net, params, coeffs = network_cases(name)
    jet = J.Jet(coeffs[:order + 1])
    a = net.jet_apply(params, jet, impl="jnp")
    b = net.jet_apply(params, jet, impl="pallas")
    assert a.coeffs.shape == b.coeffs.shape
    np.testing.assert_allclose(np.asarray(a.coeffs), np.asarray(b.coeffs),
                               **TOL)


# ---------------------------------------------------------------------------
# masked attention: every mask variant through the same jnp <-> pallas gate,
# at the leaf and through the full transformer trunk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("mask", MASK_VARIANTS,
                         ids=[str(normalize_attention_mask(m))
                              for m in MASK_VARIANTS])
def test_masked_attention_pallas_matches_jnp(mask, order, module_cases):
    _, params, coeffs = module_cases("self_attention")
    mod = SelfAttention(6, n_heads=2, mask=mask)
    jet = J.Jet(coeffs[:order + 1])
    a = mod.jet_apply(params, jet, impl="jnp")
    b = mod.jet_apply(params, jet, impl="pallas")
    assert a.coeffs.shape == b.coeffs.shape
    np.testing.assert_allclose(np.asarray(a.coeffs), np.asarray(b.coeffs),
                               **TOL)


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("mask", MASK_VARIANTS,
                         ids=[str(normalize_attention_mask(m))
                              for m in MASK_VARIANTS])
def test_masked_transformer_pallas_matches_jnp(mask, order, network_cases):
    _, params, coeffs = network_cases("transformer")
    net = make_network("transformer", d_in=2, d_out=1, width=8, depth=2,
                       n_heads=2, mask=mask)
    jet = J.Jet(coeffs[:order + 1])
    a = net.jet_apply(params, jet, impl="jnp")
    b = net.jet_apply(params, jet, impl="pallas")
    assert a.coeffs.shape == b.coeffs.shape
    np.testing.assert_allclose(np.asarray(a.coeffs), np.asarray(b.coeffs),
                               **TOL)


# ---------------------------------------------------------------------------
# dispatch guard: parity alone cannot distinguish "fused kernel ran" from
# "silently fell back to the (identical-output) reference algebra", so the
# fused ops are counted through the module path explicitly
# ---------------------------------------------------------------------------

COUNTED_OPS = ("jet_dense", "jet_flash_attention", "jet_attention_scores",
               "jet_rms_norm")


def _count_kernel_calls(monkeypatch):
    from repro.kernels import ops as kops

    calls = {fn_name: 0 for fn_name in COUNTED_OPS}
    for fn_name in calls:
        real = getattr(kops, fn_name)

        def counted(*args, _real=real, _key=fn_name, **kwargs):
            calls[_key] += 1
            return _real(*args, **kwargs)

        monkeypatch.setattr(kops, fn_name, counted)
    return calls


@pytest.mark.parametrize("mask", MASK_VARIANTS,
                         ids=[str(normalize_attention_mask(m))
                              for m in MASK_VARIANTS])
def test_pallas_impl_actually_dispatches_fused_kernels(monkeypatch, mask):
    """impl='pallas' on the transformer trunk must INVOKE ops.jet_dense,
    ops.jet_flash_attention, and ops.jet_rms_norm (not just match their
    output) for EVERY mask variant; impl='jnp' must invoke none of them; and
    the PR-5 materializing score kernel (ops.jet_attention_scores) must
    never run -- attention goes through the tiled flash path, no silent
    fallback."""
    from repro.core.engines import NTPEngine

    calls = _count_kernel_calls(monkeypatch)
    net = make_network("transformer", d_in=2, d_out=1, width=4, depth=1,
                       n_heads=2, mask=mask)
    params = net.init(jax.random.PRNGKey(0), dtype=jnp.float64)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (3, 2), jnp.float64)

    NTPEngine("jnp").derivs(net, params, x, 2)
    assert calls == {fn_name: 0 for fn_name in COUNTED_OPS}, \
        "jnp impl must not touch the kernels"

    NTPEngine("pallas").derivs(net, params, x, 2)
    assert calls["jet_flash_attention"] == 1      # ONE tiled launch per layer
    assert calls["jet_attention_scores"] == 0     # materializing kernel: dead
    assert calls["jet_rms_norm"] == 3             # 2 pre-norms + final norm
    assert calls["jet_dense"] > 0                 # q/k/v projections + MLP
