"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracles."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import jet as J
from repro.kernels import ops, ref
from repro.kernels.bell_tables import fdb_terms, tanh_poly_rows
from repro.kernels.jet_attention import (FLASH_BLOCK_K, flash_jet_bwd_pallas,
                                         jet_attention_scores_pallas,
                                         jet_flash_attention_pallas,
                                         jet_rms_norm_pallas)
from repro.kernels.jet_dense import jet_dense_pallas
from repro.kernels.tanh_jet import act_jet_pallas
from repro.runtime import metrics

SHAPES = [(4, 24), (32, 130), (17, 257)]
ORDERS = [1, 3, 6]
DTYPES = [jnp.float32]  # bf16 covered once below (CPU wall-time budget)


def _tol(dtype, order):
    if dtype == jnp.bfloat16:
        return dict(rtol=5e-2, atol=5e-2)
    return dict(rtol=5e-4, atol=10 ** -(6 - order // 3))


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_act_jet_sweep(order, shape, dtype):
    b, w = shape
    c = (jax.random.normal(jax.random.PRNGKey(order), (order + 1, b, w))
         * 0.7).astype(dtype)
    got = act_jet_pallas(c, "tanh", interpret=True)
    want = ref.act_jet_ref(c.astype(jnp.float32), "tanh").astype(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype, order))


@pytest.mark.parametrize("order", [1, 5])
@pytest.mark.parametrize("dims", [(8, 24, 24), (3, 260, 129)])
@pytest.mark.parametrize("activation", ["tanh", None])
def test_jet_dense_sweep(order, dims, activation):
    b, din, dout = dims
    key = jax.random.PRNGKey(1)
    c = jax.random.normal(key, (order + 1, b, din), jnp.float32) * 0.5
    w = jax.random.normal(jax.random.fold_in(key, 1), (din, dout), jnp.float32) * 0.1
    bias = jax.random.normal(jax.random.fold_in(key, 2), (dout,), jnp.float32)
    got = jet_dense_pallas(c, w, bias, activation, interpret=True)
    want = ref.jet_dense_ref(c, w, bias, activation)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_bfloat16_path():
    c = (jax.random.normal(jax.random.PRNGKey(9), (4, 16, 64)) * 0.7
         ).astype(jnp.bfloat16)
    got = act_jet_pallas(c, "tanh", interpret=True)
    want = ref.act_jet_ref(c.astype(jnp.float32), "tanh")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=5e-2, atol=5e-2)


def test_block_shapes_cover_non_divisible():
    c = jax.random.normal(jax.random.PRNGKey(0), (3, 37, 291), jnp.float32)
    got = act_jet_pallas(c, "tanh", block_b=16, block_w=128, interpret=True)
    want = ref.act_jet_ref(c, "tanh")
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-5)


def test_ref_matches_core_jet_algebra():
    """ref.py itself is validated against the independent core jet algebra."""
    c = jax.random.normal(jax.random.PRNGKey(3), (6, 5, 11), jnp.float64)
    want = J.compose(J.Jet(c), "tanh").coeffs
    got = ref.act_jet_ref(c, "tanh")
    np.testing.assert_allclose(got, want, rtol=1e-10, atol=1e-12)


def test_sigmoid_kernel_path():
    c = jax.random.normal(jax.random.PRNGKey(4), (4, 9, 33), jnp.float32)
    got = ops.act_jet(c, "sigmoid")
    want = ref.act_jet_ref(c, "sigmoid")
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=1e-5)


@pytest.mark.parametrize("activation", ["softplus", "gelu"])
def test_kernel_dispatch_refuses_activation_without_table(activation):
    """An activation without a Taylor table is an error at the kernel
    dispatch, not a silent detour through the reference; modules compose
    it through the jet algebra after the kernel's linear part instead."""
    c = jnp.ones((3, 4, 8), jnp.float32)
    w, b = jnp.ones((8, 8), jnp.float32), jnp.zeros((8,), jnp.float32)
    with pytest.raises(ValueError, match="no Pallas Taylor table"):
        ops.act_jet(c, activation)
    with pytest.raises(ValueError, match="no Pallas Taylor table"):
        ops.jet_dense(c, w, b, activation)


def test_sin_kernel_path():
    """The SIREN / Fourier-trunk activation runs in-kernel (cyclic
    sigma^(m)(a) = sin(a + m pi/2) stack), not via the reference fallback."""
    c = jax.random.normal(jax.random.PRNGKey(5), (5, 9, 33), jnp.float32)
    got = act_jet_pallas(c, "sin", interpret=True)
    want = ref.act_jet_ref(c, "sin")
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=1e-5)
    w = jax.random.normal(jax.random.PRNGKey(6), (33, 17), jnp.float32) * 0.1
    b = jnp.zeros((17,), jnp.float32)
    got = jet_dense_pallas(c, w, b, "sin", interpret=True)
    want = ref.jet_dense_ref(c, w, b, "sin")
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# fused attention-score + rms_norm kernels (kernels/jet_attention.py)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("order", [1, 6])
@pytest.mark.parametrize("dims", [(5, 3, 4), (19, 2, 8), (3, 1, 1)])
def test_jet_attention_scores_sweep(order, dims):
    """Pallas (interpret) vs the straight-line ref, across batch sizes that
    do and do not divide the block, plus the degenerate single-token /
    d_head=1 shape."""
    b, t, d = dims
    key = jax.random.PRNGKey(order)
    q = jax.random.normal(key, (order + 1, b, t, d), jnp.float32) * 0.6
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (order + 1, b, t, d), jnp.float32) * 0.6
    scale = 1.0 / math.sqrt(d)
    got = jet_attention_scores_pallas(q, k, scale, block_b=8, interpret=True)
    want = ref.jet_attention_scores_ref(q, k, scale)
    np.testing.assert_allclose(got, want, rtol=5e-4,
                               atol=10 ** -(6 - order // 3))
    # probability rows sum to one at order 0, to zero at every higher order
    row_sums = jnp.sum(got, axis=-1)
    np.testing.assert_allclose(row_sums[0], 1.0, rtol=1e-5)
    if order:
        np.testing.assert_allclose(row_sums[1:], 0.0, atol=1e-5)


@pytest.mark.parametrize("order", [1, 6])
@pytest.mark.parametrize("dims", [(6, 8), (21, 16), (4, 1)])
def test_jet_rms_norm_sweep(order, dims):
    b, w = dims
    key = jax.random.PRNGKey(10 + order)
    c = jax.random.normal(key, (order + 1, b, w), jnp.float32) * 0.8
    # keep the mean square away from zero: near ms ~ eps the rsqrt jet is
    # genuinely ill-conditioned (esp. w=1) and f32 kernel-vs-ref parity
    # would measure cancellation noise, not kernel arithmetic
    c = c.at[0].set(c[0] + jnp.where(c[0] >= 0, 1.0, -1.0))
    gamma = jnp.linspace(0.5, 1.5, w, dtype=jnp.float32)
    got = jet_rms_norm_pallas(c, gamma, eps=1e-6, block_b=8, interpret=True)
    want = ref.jet_rms_norm_ref(c, gamma, 1e-6)
    np.testing.assert_allclose(got, want, rtol=5e-4,
                               atol=10 ** -(6 - order // 3))


def test_attention_ref_matches_core_jet_algebra():
    """The new refs are themselves validated against the independent core
    jet algebra (einsum Cauchy conv + softmax/rms_norm recurrences)."""
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (5, 4, 3, 6), jnp.float64) * 0.5
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (5, 4, 3, 6), jnp.float64) * 0.5
    scale = 1.0 / math.sqrt(6.0)
    s = J.scale(J.einsum("...qd,...kd->...qk", J.Jet(q), J.Jet(k)), scale)
    want = J.softmax(s, axis=-1).coeffs
    got = ref.jet_attention_scores_ref(q, k, scale)
    np.testing.assert_allclose(got, want, rtol=1e-10, atol=1e-12)

    c = jax.random.normal(jax.random.fold_in(key, 2), (5, 4, 6), jnp.float64)
    gamma = jnp.linspace(0.5, 1.5, 6, dtype=jnp.float64)
    want = J.rms_norm(J.Jet(c), gamma, eps=1e-6).coeffs
    got = ref.jet_rms_norm_ref(c, gamma, 1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-10, atol=1e-12)


def test_attention_scores_dispatch_folds_batch_axes():
    """ops.jet_attention_scores folds (batch, head) axes into the kernel
    grid and unfolds on the way out -- the layout SelfAttention emits."""
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (4, 3, 2, 3, 4), jnp.float32) * 0.5
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (4, 3, 2, 3, 4), jnp.float32) * 0.5
    out = ops.jet_attention_scores(q, k, 0.5)
    assert out.shape == (4, 3, 2, 3, 3)
    for h in range(2):
        np.testing.assert_allclose(
            out[:, :, h], ops.jet_attention_scores(q[:, :, h], k[:, :, h], 0.5),
            rtol=2e-5, atol=2e-6)


def test_fused_kernels_grads_flow_through_reference_recompute():
    """The custom_vjp backward of both new ops recomputes through the ref
    path and matches autodiff of the ref directly (same contract as
    jet_dense)."""
    key = jax.random.PRNGKey(4)
    q = jax.random.normal(key, (3, 5, 2, 4), jnp.float64) * 0.5
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (3, 5, 2, 4), jnp.float64) * 0.5
    loss = lambda f: lambda a, b: jnp.sum(f(a, b) ** 2)
    g_ker = jax.grad(loss(lambda a, b: ops.jet_attention_scores(a, b, 0.5)),
                     argnums=(0, 1))(q, k)
    g_ref = jax.grad(loss(lambda a, b: ref.jet_attention_scores_ref(a, b, 0.5)),
                     argnums=(0, 1))(q, k)
    for a, b in zip(g_ker, g_ref):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8)

    c = jax.random.normal(jax.random.fold_in(key, 2), (3, 5, 6), jnp.float64)
    gamma = jnp.linspace(0.5, 1.5, 6, dtype=jnp.float64)
    g_ker = jax.grad(lambda x, g: jnp.sum(ops.jet_rms_norm(x, g) ** 2),
                     argnums=(0, 1))(c, gamma)
    g_ref = jax.grad(lambda x, g: jnp.sum(ref.jet_rms_norm_ref(x, g) ** 2),
                     argnums=(0, 1))(c, gamma)
    for a, b in zip(g_ker, g_ref):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8)


# ---------------------------------------------------------------------------
# single-launch flash-jet attention (kernels/jet_attention.py, PR-7)
# ---------------------------------------------------------------------------

def _flash_case(order, bsz, heads, t, dh, dm, seed=0):
    key = jax.random.PRNGKey(seed)
    kq, kk, kv, kw = jax.random.split(key, 4)
    shape = (order + 1, bsz, heads, t, dh)
    q = jax.random.normal(kq, shape, jnp.float32) * 0.6
    k = jax.random.normal(kk, shape, jnp.float32) * 0.6
    v = jax.random.normal(kv, shape, jnp.float32) * 0.6
    wo = jax.random.normal(kw, (heads, dh, dm), jnp.float32) * 0.3
    return q, k, v, wo, 1.0 / math.sqrt(dh)


def _dense_keep(mask, window, t):
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    if mask == "causal":
        return j <= i
    if mask == "local":
        return (j <= i) & (i - j < window)
    return None


@pytest.mark.parametrize("order", [1, 4])
@pytest.mark.parametrize("mask,window", [("none", 0), ("causal", 0),
                                         ("local", 3)])
@pytest.mark.parametrize("dims", [(2, 2, 7, 4, 6), (3, 1, 33, 8, 5)])
def test_jet_flash_attention_sweep(order, mask, window, dims):
    """Tiled online-softmax launch vs the straight-line ref, across every
    mask variant and shapes that do NOT divide the (block_q, block_k,
    block_b) tiling -- the masked tail blocks and the running-max rescale
    both get exercised."""
    b, h, t, dh, dm = dims
    q, k, v, wo, scale = _flash_case(order, b, h, t, dh, dm, seed=order)
    got = jet_flash_attention_pallas(q, k, v, wo, scale, mask=mask,
                                     window=window, block_q=8, block_k=8,
                                     block_b=2, interpret=True)
    want = ref.jet_flash_attention_ref(q, k, v, wo, scale,
                                       mask=_dense_keep(mask, window, t))
    np.testing.assert_allclose(got, want, rtol=5e-4,
                               atol=10 ** -(6 - order // 3))


def test_flash_attention_ref_matches_core_jet_algebra():
    """ref.jet_flash_attention_ref is itself validated against the
    independent core jet algebra: scores -> J.softmax(mask=...) -> Cauchy
    value contraction -> output projection."""
    q, k, v, wo, scale = _flash_case(3, 2, 2, 6, 4, 5, seed=7)
    q, k, v, wo = (x.astype(jnp.float64) for x in (q, k, v, wo))
    keep = _dense_keep("local", 2, 6)
    s = J.scale(J.einsum("...qd,...kd->...qk", J.Jet(q), J.Jet(k)), scale)
    p = J.softmax(s, axis=-1, mask=keep)
    o = J.einsum("...qk,...kd->...qd", p, J.Jet(v))
    want = jnp.einsum("nbhqd,hdo->nbqo", o.coeffs, wo)
    got = ref.jet_flash_attention_ref(q, k, v, wo, scale, mask=keep)
    np.testing.assert_allclose(got, want, rtol=1e-10, atol=1e-12)


def test_flash_attention_grads_flow_through_reference_recompute():
    """The custom_vjp backward of ops.jet_flash_attention (the one-tile
    ``flash_jet_bwd`` kernel at T = 5) matches autodiff of the ref."""
    q, k, v, wo, scale = _flash_case(2, 1, 2, 5, 4, 3, seed=11)
    q, k, v, wo = (x.astype(jnp.float64) for x in (q, k, v, wo))

    def loss(f):
        return lambda a, b, c, w: jnp.sum(f(a, b, c, w) ** 2)

    g_ker = jax.grad(loss(lambda a, b, c, w: ops.jet_flash_attention(
        a, b, c, w, scale, mask="causal")), argnums=(0, 1, 2, 3))(q, k, v, wo)
    keep = _dense_keep("causal", 0, 5)
    g_ref = jax.grad(loss(lambda a, b, c, w: ref.jet_flash_attention_ref(
        a, b, c, w, scale, mask=keep)), argnums=(0, 1, 2, 3))(q, k, v, wo)
    for a, b in zip(g_ker, g_ref):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8)


def _flash_bwd_case(order, bsz, heads, t, dtype, seed):
    q, k, v, wo, scale = _flash_case(order, bsz, heads, t, 4, 3, seed=seed)
    g = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (order + 1, bsz, t, 3), jnp.float32)
    return tuple(x.astype(dtype) for x in (q, k, v, wo, g)), scale


def _reference_vjp(q, k, v, wo, g, scale, mask, window):
    keep = _dense_keep(mask, window, q.shape[-2])
    _, vjp = jax.vjp(lambda a, b, c, w: ref.jet_flash_attention_ref(
        a, b, c, w, scale, mask=keep), q, k, v, wo)
    return vjp(g)


# a batch of 7 in blocks of 4: the ragged last block's rows past the batch
# must stay out of dwo
@pytest.mark.parametrize("order,t,heads", [(0, 5, 2), (1, 8, 1), (2, 1, 2),
                                           (3, 5, 2), (4, 8, 2)])
@pytest.mark.parametrize("mask,window", [("none", 0), ("causal", 0),
                                         ("local", 2)])
def test_flash_jet_bwd_matches_reference_vjp(order, t, heads, mask, window):
    """The one-tile backward kernel's (dq, dk, dv, dwo) against jax.vjp
    through the straight-line ref, in f64."""
    (q, k, v, wo, g), scale = _flash_bwd_case(order, 7, heads, t,
                                              jnp.float64, seed=3 + order)
    got = flash_jet_bwd_pallas(q, k, v, wo, g, scale, mask=mask,
                               window=window, block_b=4, interpret=True)
    want = _reference_vjp(q, k, v, wo, g, scale, mask, window)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=1e-10, atol=1e-10)


@pytest.mark.parametrize("mask,window", [("none", 0), ("causal", 0),
                                         ("local", 2)])
def test_flash_jet_bwd_f32(mask, window):
    """In f32 the kernel stays within 1e-5 of the ref's f64 VJP."""
    (q, k, v, wo, g), scale = _flash_bwd_case(3, 7, 2, 5, jnp.float32,
                                              seed=21)
    got = flash_jet_bwd_pallas(q, k, v, wo, g, scale, mask=mask,
                               window=window, block_b=4, interpret=True)
    want = _reference_vjp(*(x.astype(jnp.float64) for x in (q, k, v, wo, g)),
                          scale, mask, window)
    for a, b in zip(got, want):
        assert a.dtype == jnp.float32
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t,path", [(5, "kernel.flash_jet_bwd"),
                                    (FLASH_BLOCK_K + 1,
                                     "kernel.flash_jet_bwd.fallback")])
def test_flash_attention_backward_dispatch_by_key_tile(t, path):
    """A sequence within one key tile takes the backward kernel, a longer
    one the reference recompute; each dispatch counts once under its own
    counter, and both paths match autodiff of the ref."""
    q, k, v, wo, scale = _flash_case(1, 1, 1, t, 4, 3, seed=5)
    q, k, v, wo = (x.astype(jnp.float64) for x in (q, k, v, wo))
    names = ("kernel.flash_jet_bwd", "kernel.flash_jet_bwd.fallback")

    def counted():
        c = metrics.snapshot()["counter"]
        return [c.get(n, (0, 0.0))[0] for n in names]

    def loss(f):
        return lambda a, b, c, w: jnp.sum(f(a, b, c, w) ** 2)

    before = counted()
    g_ker = jax.grad(loss(lambda a, b, c, w: ops.jet_flash_attention(
        a, b, c, w, scale, mask="causal")), argnums=(0, 1, 2, 3))(q, k, v, wo)
    after = counted()
    assert [a - b for a, b in zip(after, before)] == \
        [int(n == path) for n in names]
    keep = _dense_keep("causal", 0, t)
    g_ref = jax.grad(loss(lambda a, b, c, w: ref.jet_flash_attention_ref(
        a, b, c, w, scale, mask=keep)), argnums=(0, 1, 2, 3))(q, k, v, wo)
    for a, b in zip(g_ker, g_ref):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8)


def test_epilogue_registry_is_typed_and_complete():
    """ops.epilogues() names both the dense-kernel activations (ACTIVATION:
    evaluable by jet_dense's Taylor tables) and the dedicated fused kernels
    (FUSED_OP: rms_norm / attention_scores / flash_attention); unknown names
    are absent; the mapping is read-only."""
    reg = ops.epilogues()
    for name in ("tanh", "sigmoid", "sin"):
        assert reg[name] is ops.EpilogueKind.ACTIVATION
    for name in ("rms_norm", "attention_scores", "flash_attention"):
        assert reg[name] is ops.EpilogueKind.FUSED_OP
    for name in ("softplus", "layer_norm"):
        assert name not in reg
    with pytest.raises(TypeError):
        reg["softplus"] = ops.EpilogueKind.ACTIVATION


def test_deprecated_epilogue_shims_are_gone():
    """The PR-7 supports_epilogue / supports_activation_epilogue shims had
    a one-PR lifetime; the typed registry is the only surface now."""
    assert not hasattr(ops, "supports_epilogue")
    assert not hasattr(ops, "supports_activation_epilogue")


def test_tables_are_static_and_exact():
    rows = tanh_poly_rows(6)
    assert rows[1][:3] == (1.0, 0.0, -1.0)  # tanh' = 1 - u^2
    for k, terms in enumerate(fdb_terms(6), start=1):
        assert all(isinstance(cf, float) for cf, _, _ in terms)
        assert sum(cf for cf, _, _ in terms) == 2.0 ** (k - 1)
