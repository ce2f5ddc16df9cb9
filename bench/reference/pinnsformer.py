"""PINNsFormer, written out plainly, with Raissi et al.'s Navier-Stokes
residual over it.

Zhao, Ding & Prakash, PINNsFormer: A Transformer-Based Framework for
Physics-Informed Neural Networks, ICLR 2024 (arXiv 2307.11833), and its
released code (github.com/AdityaLab/pinnsformer):

* a point p = (x, y, t) becomes ``k`` tokens p + (0, 0, i dt), i < k;
* embedding ``Linear(d_in, d)``;
* encoder layer: ``x + MHA(W(x), W(x), W(x))``, then ``x + FF(W(x))``;
  after the layers a final ``W``: the encoder output e;
* decoder layer, on the embedding: ``x + MHA(W(x), e, e)``, then
  ``x + FF(W(x))``; after the layers a final ``W``;
* head ``Linear(d, h) W Linear(h, h) W Linear(h, d_out)`` per token;
* MHA as ``torch.nn.MultiheadAttention``: one stacked (d, 3d) in-projection
  with bias, heads split from the feature axis, softmax(q k^T / sqrt(d_h)),
  an out-projection with bias;
* FF ``Linear(d, f) W Linear(f, f) W Linear(f, d)``;
* W(a) = w1 sin a + w2 cos a, each occurrence with its own learned pair.

Departures from the released code, all stated in the configuration's
``assumed``: derivatives are taken of each token's output with respect to
the point (every token moves with it; the released code's
``autograd.grad(pred, t, ones)`` differentiates the sum over tokens with
respect to each token's coordinates, which differs through attention's
cross-token terms); the operator is the forward Navier-Stokes problem on
the Taylor-Green vortex; Adam replaces L-BFGS.

Nothing here imports the program.  Derivatives come from nested forward-
mode ``jax.jvp`` towers along one-hot directions, with the same
polarization-free definitions as ``raissi-ns.py`` (copied here: that file
is written over the MLP).  Every contraction runs at ``precision``:
``"highest"`` or ``"high"`` (three bfloat16 passes, the control).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import mlp

NU = 0.01
DOMAIN = ((1.0, 8.0), (-2.0, 2.0), (0.0, 20.0))
ORDER = 3
MIXED = ((0, 1), (0, 2), (1, 2), (0, 0, 1), (0, 1, 1))


# ------------------------------------------------------------- the network

def _linear_init(key, fan_in, fan_out, dtype, bias=0.01):
    """Xavier-uniform weight (fan_in, fan_out) and a constant bias, as the
    released code's ``init_weights`` sets every ``nn.Linear``."""
    lim = math.sqrt(6.0 / (fan_in + fan_out))
    return {"w": jax.random.uniform(key, (fan_in, fan_out), dtype, -lim, lim),
            "b": jnp.full((fan_out,), bias, dtype)}


def _mlp3_init(key, d_in, hidden, d_out, dtype):
    k0, k1, k2 = jax.random.split(key, 3)
    ones = jnp.ones((2,), dtype)
    return {"l0": _linear_init(k0, d_in, hidden, dtype), "act0": ones,
            "l1": _linear_init(k1, hidden, hidden, dtype), "act1": ones,
            "l2": _linear_init(k2, hidden, d_out, dtype)}


def _layer_init(key, cfg, dtype):
    d = cfg["width"]
    ka, ko, kf = jax.random.split(key, 3)
    # nn.MultiheadAttention: Xavier-uniform over the stacked (3d, d)
    # in-projection, zero in-projection bias; its out-projection is an
    # nn.Linear, which init_weights reaches
    lim = math.sqrt(6.0 / (d + 3 * d))
    ones = jnp.ones((2,), dtype)
    return {"act1": ones,
            "attn": {"in_w": jax.random.uniform(ka, (d, 3 * d), dtype,
                                                -lim, lim),
                     "in_b": jnp.zeros((3 * d,), dtype),
                     "out": _linear_init(ko, d, d, dtype)},
            "act2": ones,
            "ff": _mlp3_init(kf, d, cfg["ff"], d, dtype)}


def init(key, cfg, dtype=jnp.float32):
    """Weights in the reference's layout, a nested dict; every wavelet pair
    starts at (1, 1)."""
    n = cfg["depth"]
    ke, kh, *kl = jax.random.split(key, 2 + 2 * n)
    ones = jnp.ones((2,), dtype)
    return {"embed": _linear_init(ke, cfg["d_in"], cfg["width"], dtype),
            "encoder": [_layer_init(k, cfg, dtype) for k in kl[:n]],
            "encoder_act": ones,
            "decoder": [_layer_init(k, cfg, dtype) for k in kl[n:]],
            "decoder_act": ones,
            "head": _mlp3_init(kh, cfg["width"], cfg["head"], cfg["d_out"],
                               dtype)}


def einsum(eq, a, b, precision="highest"):
    """A contraction at ``precision``, as :func:`mlp.matmul`: off the TPU,
    ``"high"`` spells out its three bfloat16 passes."""
    if precision == "highest":
        return jnp.einsum(eq, a, b, precision=mlp.HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    if jax.default_backend() == "tpu":
        return jnp.einsum(eq, a, b, precision=mlp.HIGH)
    ah, al = mlp._split_bf16(a)
    bh, bl = mlp._split_bf16(b)
    f = lambda u, v: jnp.einsum(eq, u, v, precision=mlp.HIGHEST)
    return f(ah, bh) + f(ah, bl) + f(al, bh)


def _wave(w, a):
    return w[0] * jnp.sin(a) + w[1] * jnp.cos(a)


def _linear(p, a, precision):
    return einsum("...i,ij->...j", a, p["w"], precision) + p["b"]


def _mlp3(p, a, precision):
    a = _wave(p["act0"], _linear(p["l0"], a, precision))
    a = _wave(p["act1"], _linear(p["l1"], a, precision))
    return _linear(p["l2"], a, precision)


def _mha(p, q_src, kv_src, n_heads, precision):
    d = q_src.shape[-1]
    dh = d // n_heads
    w, b = p["in_w"], p["in_b"]

    def heads(a, i):            # (N, T, d) -> (N, H, T, dh)
        y = einsum("ntd,de->nte", a, w[:, i * d:(i + 1) * d], precision) \
            + b[i * d:(i + 1) * d]
        return jnp.transpose(y.reshape(y.shape[:2] + (n_heads, dh)),
                             (0, 2, 1, 3))

    q, k, v = heads(q_src, 0), heads(kv_src, 1), heads(kv_src, 2)
    s = einsum("nhqd,nhkd->nhqk", q, k, precision) / math.sqrt(dh)
    o = einsum("nhqk,nhkd->nhqd", jax.nn.softmax(s, axis=-1), v, precision)
    o = jnp.transpose(o, (0, 2, 1, 3)).reshape(q_src.shape)
    return _linear(p["out"], o, precision)


def _layer(p, x, e, n_heads, precision):
    a = _wave(p["act1"], x)
    x = x + _mha(p["attn"], a, a if e is None else e, n_heads, precision)
    return x + _mlp3(p["ff"], _wave(p["act2"], x), precision)


def tokens(cfg, x):
    """(N, d_in) -> (N, k, d_in): token i at the point shifted by i dt in
    time (the last coordinate)."""
    shift = jnp.zeros((cfg["tokens"], x.shape[-1]), x.dtype)
    shift = shift.at[:, -1].set(cfg["step"] * jnp.arange(cfg["tokens"],
                                                         dtype=x.dtype))
    return x[:, None, :] + shift


def apply(cfg, params, x, precision="highest"):
    """(N, d_in) -> (N, k, d_out)."""
    src = _linear(params["embed"], tokens(cfg, x), precision)
    e = src
    for p in params["encoder"]:
        e = _layer(p, e, None, cfg["n_heads"], precision)
    e = _wave(params["encoder_act"], e)
    d = src
    for p in params["decoder"]:
        d = _layer(p, d, e, cfg["n_heads"], precision)
    d = _wave(params["decoder_act"], d)
    return _mlp3(params["head"], d, precision)


# ------------------------------------------------------ derivatives, PDE

def tower(cfg, params, x, axes, precision="highest"):
    """[f, D_{a1} f, D_{a1} D_{a2} f, ...], each (N k, d_out) with the
    token axis folded into the point axis, token-minor, by nested
    ``jax.jvp`` along one-hot directions (behind an optimization barrier,
    as ``mlp.tower`` and for the same reason)."""
    def g(xx):
        return (apply(cfg, params, xx, precision),)
    for a in axes:
        v = jax.lax.optimization_barrier(jnp.zeros_like(x).at[:, a].set(1.0))
        g = (lambda g, v: lambda xx: (lambda pt: pt[0] + (pt[1][-1],))(
            jax.jvp(g, (xx,), (v,))))(g, v)
    return [t.reshape(-1, t.shape[-1]) for t in g(x)]


def exact(x):
    """(M, 3) -> (M, 2): the decaying Taylor-Green vortex, (psi, p)."""
    f = jnp.exp(-2.0 * NU * x[:, 2])
    psi = -jnp.cos(x[:, 0]) * jnp.cos(x[:, 1]) * f
    p = -0.25 * (jnp.cos(2.0 * x[:, 0]) + jnp.cos(2.0 * x[:, 1])) * f ** 2
    return jnp.stack([psi, p], axis=1)


def residual(cfg, params, x, precision="highest"):
    """(2, N k): the two momentum residuals at every token's point."""
    def psi(*axes):
        return tower(cfg, params, x, axes, precision)[-1][:, 0]

    yyy = tower(cfg, params, x, (1, 1, 1), precision)
    xxx = tower(cfg, params, x, (0, 0, 0), precision)
    p_x = tower(cfg, params, x, (0,), precision)[-1][:, 1]
    p_y = tower(cfg, params, x, (1,), precision)[-1][:, 1]
    u, u_y, u_yy = yyy[1][:, 0], yyy[2][:, 0], yyy[3][:, 0]
    v, v_x, v_xx = -xxx[1][:, 0], -xxx[2][:, 0], -xxx[3][:, 0]
    u_x = psi(1, 0)
    v_y = -u_x
    u_t, v_t = psi(1, 2), -psi(0, 2)
    u_xx, v_yy = psi(1, 0, 0), -psi(0, 1, 1)
    f = u_t + (u * u_x + v * u_y) + p_x - NU * (u_xx + u_yy)
    g = v_t + (u * v_x + v * v_y) + p_y - NU * (v_xx + v_yy)
    return jnp.stack([f, g])


def loss_and_grad(cfg, weights, block, precision="highest"):
    """A jitted ``(params, x, bc) -> (loss, grad)`` of the PINN loss
    ``w_r mean(R^2) + w_b mean((u - u*)^2)`` over every token's point,
    the residual's squares summed over blocks of ``block`` points in a
    scan (so that the nested towers of a large batch fit)."""
    def res_sq(params, xb):
        return jnp.sum(residual(cfg, params, xb, precision) ** 2)

    def loss_fn(params, x, bc):
        xb = x.reshape((-1, block) + x.shape[1:])

        def body(acc, xi):
            val, g = jax.value_and_grad(res_sq)(params, xi)
            return jax.tree_util.tree_map(jnp.add, acc, (val, g)), None

        zero = (jnp.zeros((), x.dtype),
                jax.tree_util.tree_map(jnp.zeros_like, params))
        (sq, g_res), _ = jax.lax.scan(body, zero, xb)
        n_res = 2 * x.shape[0] * cfg["tokens"]

        def bc_loss(params):
            ub = apply(cfg, params, bc, precision).reshape(-1, cfg["d_out"])
            want = exact(tokens(cfg, bc).reshape(-1, bc.shape[-1]))
            return jnp.mean((ub - want) ** 2)

        l_bc, g_bc = jax.value_and_grad(bc_loss)(params)
        wr, wb = weights["residual"], weights["boundary"]
        loss = wr * sq / n_res + wb * l_bc
        grad = jax.tree_util.tree_map(lambda a, b: wr * a / n_res + wb * b,
                                      g_res, g_bc)
        return loss, grad

    return jax.jit(loss_fn)
