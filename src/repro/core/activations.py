"""Closed-form higher derivatives of smooth activation functions.

The Faa di Bruno contraction (core/jet.py) needs all outer coefficients
``F_m = sigma^(m)(a)/m!`` for ``m = 0..n`` at the primal activations ``a``.
Computing these with nested autodiff would re-introduce the exponential blow-up
the paper removes, so every supported activation provides them in closed form:

* ``tanh``:    sigma' = 1 - u^2 with u = tanh(a).  Every derivative is a
               polynomial in u via the recurrence P_{m+1}(u) = P_m'(u)(1-u^2).
               One transcendental + Horner chains -- VPU friendly on TPU.
* ``sigmoid``: same trick with s' = s(1-s).
* ``softplus``:softplus' = sigmoid, so order-m derivatives reuse the sigmoid
               polynomials shifted by one.
* ``sin``:     sigma^(m)(a) = sin(a + m*pi/2).
* ``wave``:    PINNsFormer's w1 sin a + w2 cos a with learned w1, w2: its
               stack (``wave_taylor_stack``) takes the weights, so it is not
               a registry entry; ``repro.core.jet.wave`` composes it.
* ``exp``:     sigma^(m) = exp.
* ``identity``/``silu``/``gelu``: silu and (tanh-)gelu are *compositions* of
               the atoms above with products; they go through the jet algebra
               (mul + tanh/sigmoid jets) rather than a direct table.

Polynomial coefficient tables are exact integers computed once (lru_cache);
evaluation is Horner in the activation value.  The same tables are shared by
the Pallas kernels (kernels/bell_tables.py re-exports them).
"""

from __future__ import annotations

import math
from functools import lru_cache
from typing import Callable, Dict, Tuple

import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Exact integer polynomial tables
# ---------------------------------------------------------------------------

def _poly_mul(a: Tuple[int, ...], b: Tuple[int, ...]) -> Tuple[int, ...]:
    out = [0] * (len(a) + len(b) - 1)
    for i, ai in enumerate(a):
        for j, bj in enumerate(b):
            out[i + j] += ai * bj
    return tuple(out)


def _poly_diff(a: Tuple[int, ...]) -> Tuple[int, ...]:
    return tuple(i * ai for i, ai in enumerate(a))[1:] or (0,)


@lru_cache(maxsize=None)
def tanh_derivative_polys(n: int) -> Tuple[Tuple[int, ...], ...]:
    """P_m with tanh^(m)(a) = P_m(tanh(a)), for m = 0..n.  P_0 = u."""
    polys = [(0, 1)]  # P_0(u) = u
    dchain = (1, 0, -1)  # u' = 1 - u^2
    for _ in range(n):
        polys.append(_poly_mul(_poly_diff(polys[-1]), dchain))
    return tuple(polys)


@lru_cache(maxsize=None)
def sigmoid_derivative_polys(n: int) -> Tuple[Tuple[int, ...], ...]:
    """Q_m with sigmoid^(m)(a) = Q_m(sigmoid(a)), for m = 0..n.  Q_0 = s."""
    polys = [(0, 1)]  # Q_0(s) = s
    dchain = (0, 1, -1)  # s' = s - s^2
    for _ in range(n):
        polys.append(_poly_mul(_poly_diff(polys[-1]), dchain))
    return tuple(polys)


def poly_table_f32(polys: Tuple[Tuple[int, ...], ...]) -> np.ndarray:
    """Pack ragged integer polys into a dense (m+1, deg+1) float array (low->high)."""
    deg = max(len(p) for p in polys)
    out = np.zeros((len(polys), deg), dtype=np.float64)
    for i, p in enumerate(polys):
        out[i, : len(p)] = p
    return out


def _horner(table_row: np.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """Evaluate sum_i c_i u^i with Horner; table_row is low->high order."""
    acc = jnp.full_like(u, float(table_row[-1]))
    for c in table_row[-2::-1]:
        acc = acc * u + float(c)
    return acc


# ---------------------------------------------------------------------------
# Taylor-coefficient stacks F_m = sigma^(m)(a)/m!
# ---------------------------------------------------------------------------

def tanh_taylor_stack(a: jnp.ndarray, n: int) -> jnp.ndarray:
    """(n+1, *a.shape) stack of tanh^(m)(a)/m!."""
    u = jnp.tanh(a)
    table = poly_table_f32(tanh_derivative_polys(n))
    rows = [u]
    for m in range(1, n + 1):
        rows.append(_horner(table[m], u) * (1.0 / math.factorial(m)))
    return jnp.stack(rows)


def sigmoid_taylor_stack(a: jnp.ndarray, n: int) -> jnp.ndarray:
    s = jax_sigmoid(a)
    table = poly_table_f32(sigmoid_derivative_polys(n))
    rows = [s]
    for m in range(1, n + 1):
        rows.append(_horner(table[m], s) * (1.0 / math.factorial(m)))
    return jnp.stack(rows)


def softplus_taylor_stack(a: jnp.ndarray, n: int) -> jnp.ndarray:
    """softplus^(0) = log1p(exp a); higher orders are sigmoid derivatives shifted by one."""
    rows = [jnp.logaddexp(a, 0.0)]
    if n >= 1:
        s = jax_sigmoid(a)
        table = poly_table_f32(sigmoid_derivative_polys(max(n - 1, 0)))
        for m in range(1, n + 1):
            rows.append(_horner(table[m - 1], s) * (1.0 / math.factorial(m)))
    return jnp.stack(rows)


def sin_taylor_stack(a: jnp.ndarray, n: int) -> jnp.ndarray:
    rows = []
    for m in range(n + 1):
        phase = m % 4
        val = [jnp.sin, jnp.cos, lambda x: -jnp.sin(x), lambda x: -jnp.cos(x)][phase](a)
        rows.append(val * (1.0 / math.factorial(m)))
    return jnp.stack(rows)


def wave_taylor_stack(a: jnp.ndarray, n: int, w1, w2) -> jnp.ndarray:
    """(n+1, *a.shape) stack of W^(m)(a)/m! for the wavelet activation
    W(a) = w1 sin a + w2 cos a (PINNsFormer), whose m-th derivative is
    w1 sin(a + m pi/2) + w2 cos(a + m pi/2): one sin and one cos of ``a``
    serve every order."""
    s, c = jnp.sin(a), jnp.cos(a)
    cycle = ((s, c), (c, -s), (-s, -c), (-c, s))   # (sin, cos)(a + m pi/2)
    return jnp.stack([(w1 * cycle[m % 4][0] + w2 * cycle[m % 4][1])
                      * (1.0 / math.factorial(m)) for m in range(n + 1)])


def exp_taylor_stack(a: jnp.ndarray, n: int) -> jnp.ndarray:
    e = jnp.exp(a)
    return jnp.stack([e * (1.0 / math.factorial(m)) for m in range(n + 1)])


def jax_sigmoid(a: jnp.ndarray) -> jnp.ndarray:
    return 0.5 * (jnp.tanh(0.5 * a) + 1.0)


# registry: name -> callable(a, n) -> (n+1, *shape) Taylor stack
TAYLOR_STACKS: Dict[str, Callable[[jnp.ndarray, int], jnp.ndarray]] = {
    "tanh": tanh_taylor_stack,
    "sigmoid": sigmoid_taylor_stack,
    "softplus": softplus_taylor_stack,
    "sin": sin_taylor_stack,
    "exp": exp_taylor_stack,
}

# tanh-approximation GELU constants, shared with the jet-side composition
# (repro.core.jet.gelu) so primal and jet can never drift apart
GELU_TANH_C = math.sqrt(2.0 / math.pi)
GELU_TANH_CUBIC = 0.044715

# plain primal evaluation (for order-0 fast paths).  The composite names
# (silu / gelu / relu / identity) have no Taylor table -- their jets go
# through repro.core.jet.activation's algebraic definitions instead.
PRIMALS: Dict[str, Callable[[jnp.ndarray], jnp.ndarray]] = {
    "tanh": jnp.tanh,
    "sigmoid": jax_sigmoid,
    "softplus": lambda a: jnp.logaddexp(a, 0.0),
    "sin": jnp.sin,
    "exp": jnp.exp,
    "silu": lambda a: a * jax_sigmoid(a),
    "gelu": lambda a: 0.5 * a * (1.0 + jnp.tanh(
        GELU_TANH_C * (a + GELU_TANH_CUBIC * a ** 3))),
    "relu": lambda a: jnp.maximum(a, 0.0),
    "identity": lambda a: a,
}
