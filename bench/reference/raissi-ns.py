"""Raissi et al.'s Navier-Stokes example, written out plainly.

The network maps (x, y, t) to the streamfunction psi and the pressure p;
u = psi_y, v = -psi_x, and the two residuals are

    f = u_t + (u u_x + v u_y) + p_x - nu (u_xx + u_yy)
    g = v_t + (u v_x + v v_y) + p_y - nu (v_xx + v_yy)

with nu = 0.01 (the paper's lambda_1 = 1, lambda_2 = 0.01), on the paper's
box x in (1, 8), y in (-2, 2), t in (0, 20).  The manufactured solution is
the decaying Taylor-Green vortex, which solves the equations with no
forcing: psi = -cos x cos y F, p = -(cos 2x + cos 2y) F^2 / 4,
F = exp(-2 nu t).
"""

import jax.numpy as jnp

from bench.reference import mlp

NU = 0.01
DOMAIN = ((1.0, 8.0), (-2.0, 2.0), (0.0, 20.0))
ORDER = 3
MIXED = ((0, 1), (0, 2), (1, 2), (0, 0, 1), (0, 1, 1))


def exact(x):
    f = jnp.exp(-2.0 * NU * x[:, 2])
    psi = -jnp.cos(x[:, 0]) * jnp.cos(x[:, 1]) * f
    p = -0.25 * (jnp.cos(2.0 * x[:, 0]) + jnp.cos(2.0 * x[:, 1])) * f ** 2
    return jnp.stack([psi, p], axis=1)


def residual(layers, x, precision="highest"):
    def psi(*axes):
        return mlp.partial(layers, x, axes, precision)[:, 0]

    yyy = mlp.tower(layers, x, (1, 1, 1), precision)
    xxx = mlp.tower(layers, x, (0, 0, 0), precision)
    p_x = mlp.partial(layers, x, (0,), precision)[:, 1]
    p_y = mlp.partial(layers, x, (1,), precision)[:, 1]
    u, u_y, u_yy = yyy[1][:, 0], yyy[2][:, 0], yyy[3][:, 0]
    v, v_x, v_xx = -xxx[1][:, 0], -xxx[2][:, 0], -xxx[3][:, 0]
    u_x = psi(1, 0)
    v_y = -u_x
    u_t, v_t = psi(1, 2), -psi(0, 2)
    u_xx, v_yy = psi(1, 0, 0), -psi(0, 1, 1)
    f = u_t + (u * u_x + v * u_y) + p_x - NU * (u_xx + u_yy)
    g = v_t + (u * v_x + v * v_y) + p_y - NU * (v_xx + v_yy)
    return jnp.stack([f, g])
