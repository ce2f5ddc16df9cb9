"""Pallas TPU kernel: fused Faa di Bruno activation jet (pointwise, VPU).

Input is the scaled-Taylor coefficient stack of the pre-activations,
``(n+1, B, W)``.  One VMEM round-trip computes the full activation jet:

  1. ``u = tanh(c_0)``                       (one transcendental per element)
  2. ``F_m = P_m(u)``                        (static Horner chains, m = 0..n)
  3. ``out_k = sum_{p in P(k)} C_p F_|p| prod_j c_j^{p_j}``
                                             (static partition contraction)

All tables are Python immediates (kernels/bell_tables.py) so the body is pure
FMA/VPU work; there is no gather, no control flow, and the (n+1) coefficient
axis lives entirely in VMEM for the tile.  Tiling: ``(n+1, block_b, block_w)``
blocks over a ``(B/block_b, W/block_w)`` grid -- the coefficient axis is never
split because order k mixes all lower orders.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

import math

from .bell_tables import fdb_terms, sigmoid_poly_rows, tanh_poly_rows

_POLY_ROWS = {"tanh": tanh_poly_rows, "sigmoid": sigmoid_poly_rows}
KERNEL_ACTS = ("tanh", "sigmoid", "sin")


def _horner(row, u):
    acc = jnp.full_like(u, row[-1])
    for c in row[-2::-1]:
        acc = acc * u + c
    return acc


def _taylor_stack(z0: jnp.ndarray, n: int, activation: str) -> list:
    """F_m = sigma^(m)(z0)/m! for m = 0..n, as pure VPU work.

    tanh/sigmoid evaluate one transcendental then static Horner chains in it;
    sin cycles sigma^(m)(a) = sin(a + m pi/2) through two transcendentals and
    sign flips (the SIREN / Fourier-feature trunk activation)."""
    if activation == "sin":
        s, c = jnp.sin(z0), jnp.cos(z0)
        cycle = (s, c, -s, -c)
        return [cycle[m % 4] * (1.0 / math.factorial(m)) for m in range(n + 1)]
    if activation == "tanh":
        u = jnp.tanh(z0)
    elif activation == "sigmoid":
        u = 0.5 * (jnp.tanh(0.5 * z0) + 1.0)
    else:
        raise ValueError(activation)
    rows_tab = _POLY_ROWS[activation](n)
    return [_horner(rows_tab[m], u) for m in range(n + 1)]


def act_jet_body(z: jnp.ndarray, activation: str) -> jnp.ndarray:
    """The jet epilogue on an in-register/in-VMEM stack ``z`` of shape (n+1, ...).

    Shared by this kernel and jet_dense's epilogue so both are tested by the
    same sweeps."""
    n = z.shape[0] - 1
    f = _taylor_stack(z[0], n, activation)
    out = [f[0]]
    for k, terms in enumerate(fdb_terms(n), start=1):
        acc = None
        for coef, m, powers in terms:
            prod = f[m] * coef
            for j, e in powers:
                zj = z[j]
                for _ in range(e):
                    prod = prod * zj
            acc = prod if acc is None else acc + prod
        out.append(acc)
    return jnp.stack(out)


def _kernel(y_ref, o_ref, *, activation: str):
    o_ref[...] = act_jet_body(y_ref[...], activation)


@functools.partial(jax.jit, static_argnames=("activation", "block_b", "block_w", "interpret"))
def act_jet_pallas(coeffs: jnp.ndarray, activation: str = "tanh",
                   block_b: int = 256, block_w: int = 256,
                   interpret: bool = True) -> jnp.ndarray:
    """coeffs: (n+1, B, W) -> activation jet of the same shape."""
    n1, b, w = coeffs.shape
    bb, bw = min(block_b, b), min(block_w, w)
    pb, pw = (-b) % bb, (-w) % bw
    padded = jnp.pad(coeffs, ((0, 0), (0, pb), (0, pw)))
    grid = (padded.shape[1] // bb, padded.shape[2] // bw)
    out = pl.pallas_call(
        functools.partial(_kernel, activation=activation),
        grid=grid,
        in_specs=[pl.BlockSpec((n1, bb, bw), lambda i, j: (0, i, j))],
        out_specs=pl.BlockSpec((n1, bb, bw), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct(padded.shape, coeffs.dtype),
        interpret=interpret,
        name="act_jet",
    )(padded)
    return out[:, :b, :w]
