"""Allen-Cahn in continuous time, written out plainly.

    u_t - eps u_xx + u^3 - u = f

on t in (0, 1), x in (-pi, pi) with eps = 0.4, manufactured
u = exp(-t) sin x, so f = (eps - 2) s + s^3 with s = exp(-t) sin x.
"""

import jax.numpy as jnp

from bench.reference import mlp

EPS = 0.4
DOMAIN = ((0.0, 1.0), (-float(jnp.pi), float(jnp.pi)))
ORDER = 2
MIXED = ()


def exact(x):
    return (jnp.exp(-x[:, 0]) * jnp.sin(x[:, 1]))[:, None]


def forcing(x):
    s = exact(x)[:, 0]
    return (EPS - 2.0) * s + s ** 3


def residual(layers, x, precision="highest"):
    u = mlp.apply(layers, x, precision)[:, 0]
    u_t = mlp.partial(layers, x, (0,), precision)[:, 0]
    u_xx = mlp.partial(layers, x, (1, 1), precision)[:, 0]
    return u_t - EPS * u_xx + u ** 3 - u - forcing(x)
