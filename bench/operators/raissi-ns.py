"""Registers Raissi et al.'s Navier-Stokes example with the program's
operator registry, through its public ``register``; the program has no
operator of this form.  ``bench/reference/raissi-ns.py`` states the
equations; this is the same residual read from the program's derivative
table (axes x, y, t = 0, 1, 2; components psi, p = 0, 1)."""

import jax.numpy as jnp

from repro.pinn.operators import Operator, register

NU = 0.01


def _exact(x):
    f = jnp.exp(-2.0 * NU * x[:, 2])
    psi = -jnp.cos(x[:, 0]) * jnp.cos(x[:, 1]) * f
    p = -0.25 * (jnp.cos(2.0 * x[:, 0]) + jnp.cos(2.0 * x[:, 1])) * f ** 2
    return jnp.stack([psi, p], axis=1)


def _residual(x, d):
    u, u_y, u_yy = d(1, 1), d(1, 2), d(1, 3)
    v, v_x, v_xx = -d(0, 1), -d(0, 2), -d(0, 3)
    u_x = d.mixed(0, 1)
    v_y = -u_x
    u_t, v_t = d.mixed(1, 2), -d.mixed(0, 2)
    u_xx, v_yy = d.mixed(0, 0, 1), -d.mixed(0, 1, 1)
    p_x, p_y = d(0, 1, comp=1), d(1, 1, comp=1)
    f = u_t + (u * u_x + v * u_y) + p_x - NU * (u_xx + u_yy)
    g = v_t + (u * v_x + v * v_y) + p_y - NU * (v_xx + v_yy)
    return jnp.stack([f, g])


register(Operator(
    name="raissi-ns", d_in=3, d_out=2, order=3,
    residual=_residual, exact=_exact,
    domain=((1.0, 8.0), (-2.0, 2.0), (0.0, 20.0)),
    mixed=((0, 1), (0, 2), (1, 2), (0, 0, 1), (0, 1, 1)),
    description="Navier-Stokes in (psi, p) form on (x, y, t), Raissi et al. "
                "(2019); manufactured: the decaying Taylor-Green vortex",
))
