"""Plain tanh MLP, its input derivatives by nested ``jax.jvp``, and Adam."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
HIGH = jax.lax.Precision.HIGH


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def matmul(x, w, precision: str = "highest"):
    """``x @ w`` in float32.  ``"high"`` is bfloat16_3x, three bfloat16
    passes: on a TPU, XLA's own ``Precision.HIGH``; elsewhere XLA ignores
    the precision of a float32 dot, so the passes are spelled out (both
    operands split into a bfloat16 head and tail, tail x tail dropped)."""
    if precision == "highest":
        return jnp.dot(x, w, precision=HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    if jax.default_backend() == "tpu":
        return jnp.dot(x, w, precision=HIGH)
    xh, xl = _split_bf16(x)
    wh, wl = _split_bf16(w)
    return (jnp.dot(xh, wh, precision=HIGHEST) + jnp.dot(xh, wl, precision=HIGHEST)
            + jnp.dot(xl, wh, precision=HIGHEST))


def init(key, sizes, dtype=jnp.float32):
    """Xavier-normal weights and zero biases (Raissi et al.'s initializer),
    as a list of ``(w, b)``; one jitted call makes every layer."""
    keys = jax.random.split(key, len(sizes) - 1)
    layers = []
    for k, fan_in, fan_out in zip(keys, sizes[:-1], sizes[1:]):
        std = (2.0 / (fan_in + fan_out)) ** 0.5
        layers.append((std * jax.random.normal(k, (fan_in, fan_out), dtype),
                       jnp.zeros((fan_out,), dtype)))
    return layers


def apply(layers, x, precision: str = "highest"):
    """(N, d_in) -> (N, d_out): tanh on every hidden layer, linear
    read-out."""
    h = x
    for w, b in layers[:-1]:
        h = jnp.tanh(matmul(h, w, precision) + b)
    w, b = layers[-1]
    return matmul(h, w, precision) + b


def tower(layers, x, axes, precision: str = "highest"):
    """[f, D_{a1} f, D_{a1} D_{a2} f, ...]: the field and its partial
    derivatives along each prefix of ``axes`` (one input axis per
    differentiation, repeats allowed), each (N, d_out), by nested
    ``jax.jvp``."""
    def g(xx):
        return (apply(layers, xx, precision),)
    for a in axes:
        # the barrier keeps the compiler from folding the constant
        # direction into the tower: the TPU compiler's fusion pass crashes
        # (SIGILL) on some folded towers (PERF.md)
        v = jax.lax.optimization_barrier(jnp.zeros_like(x).at[:, a].set(1.0))
        g = (lambda g, v: lambda xx: (lambda pt: pt[0] + (pt[1][-1],))(
            jax.jvp(g, (xx,), (v,))))(g, v)
    return list(g(x))


def partial(layers, x, axes, precision: str = "highest"):
    """(N, d_out) partial derivative of the field along ``axes``."""
    return tower(layers, x, axes, precision)[-1]


def pure_table(layers, x, order, precision: str = "highest"):
    """(d_in, order+1, N, d_out): every pure derivative through
    ``order``."""
    return jnp.stack([jnp.stack(tower(layers, x, (a,) * order, precision))
                      for a in range(x.shape[1])])


ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def adam_init(layers):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, layers)
    return (0, zeros, zeros)


def adam_step(layers, grads, state, lr):
    """One Adam update with bias correction, as Kingma & Ba (2015)."""
    t, m, v = state
    t += 1
    m = jax.tree_util.tree_map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g,
                               m, grads)
    v = jax.tree_util.tree_map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g,
                               v, grads)
    c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
    layers = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS),
        layers, m, v)
    return layers, (t, m, v)
