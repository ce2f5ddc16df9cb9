"""Pure-jnp oracles for the Pallas kernels.

These are *independent* straight-line implementations (no Pallas, no
core.jet reuse beyond the static tables) so kernel bugs cannot hide behind a
shared code path.  Tests sweep shapes/dtypes and assert allclose against
these.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.core.activations import sin_taylor_stack
from repro.core.jet import MATMUL_PRECISION

from .bell_tables import fdb_terms, sigmoid_poly_rows, tanh_poly_rows

_POLY_ROWS = {"tanh": tanh_poly_rows, "sigmoid": sigmoid_poly_rows}
_einsum = functools.partial(jnp.einsum, precision=MATMUL_PRECISION)
_PRIMAL = {"tanh": jnp.tanh, "sigmoid": lambda a: 0.5 * (jnp.tanh(0.5 * a) + 1.0)}


def _taylor_stack(a: jnp.ndarray, n: int, activation: str) -> list[jnp.ndarray]:
    """[sigma^(m)(a)/m! for m in 0..n] via Horner on the closed-form polys
    (tanh/sigmoid) or core.activations' sin phase cycle (same closed form the
    in-kernel stack hardcodes; only the polynomial tables stay independent)."""
    if activation == "sin":
        return list(sin_taylor_stack(a, n))
    u = _PRIMAL[activation](a)
    rows = _POLY_ROWS[activation](n)
    out = []
    for m in range(n + 1):
        row = rows[m]
        acc = jnp.full_like(u, row[-1])
        for c in row[-2::-1]:
            acc = acc * u + c
        out.append(acc)
    return out


def act_jet_ref(coeffs: jnp.ndarray, activation: str = "tanh") -> jnp.ndarray:
    """Faa di Bruno activation jet.  coeffs: (n+1, ...) scaled Taylor coeffs of
    the pre-activation; returns the same-shaped stack for sigma(pre-act)."""
    n = coeffs.shape[0] - 1
    f = _taylor_stack(coeffs[0], n, activation)
    rows = [f[0]]
    for k, terms in enumerate(fdb_terms(n), start=1):
        acc = jnp.zeros_like(coeffs[0])
        for coef, m, powers in terms:
            prod = f[m] * coef
            for j, e in powers:
                for _ in range(e):
                    prod = prod * coeffs[j]
            acc = acc + prod
        rows.append(acc)
    return jnp.stack(rows)


def wave_jet_ref(coeffs: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """PINNsFormer's wavelet jet ``w1 sin + w2 cos`` of a (n+1, ...) stack:
    the sin jet of the stack and of the stack shifted by pi/2 (cos a =
    sin(a + pi/2)), weighted."""
    shifted = coeffs.at[0].add(jnp.pi / 2)
    return (w[0] * act_jet_ref(coeffs, "sin")
            + w[1] * act_jet_ref(shifted, "sin"))


def jet_dense_ref(coeffs: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                  activation: str | None = "tanh") -> jnp.ndarray:
    """Fused layer oracle: (n+1, B, Din) @ (Din, Dout) + bias-on-c0, then
    the activation jet (or identity for the output layer)."""
    z = _einsum("nbi,io->nbo", coeffs, w)
    z = z.at[0].add(b)
    if activation is None:
        return z
    return act_jet_ref(z, activation)


def jet_attention_scores_ref(q: jnp.ndarray, k: jnp.ndarray,
                             scale: float) -> jnp.ndarray:
    """Fused attention-score oracle: (n+1, B, T, D) Q/K coefficient stacks
    -> the softmaxed score jet (n+1, B, Tq, Tk).

    Straight-line: the Cauchy convolution of the score contraction, then the
    softmax exp / sum / div power-series recurrences written out directly
    (no core.jet, no shared kernel body)."""
    n1 = q.shape[0]
    s = [scale * sum(_einsum("bqd,bkd->bqk", q[i], k[m - i])
                     for i in range(m + 1)) for m in range(n1)]
    shift = jnp.max(s[0], axis=-1, keepdims=True)
    e = [jnp.exp(s[0] - shift)]
    for m in range(1, n1):
        e.append(sum(j * s[j] * e[m - j] for j in range(1, m + 1)) / m)
    tot = [jnp.sum(em, axis=-1, keepdims=True) for em in e]
    p = [e[0] / tot[0]]
    for m in range(1, n1):
        p.append((e[m] - sum(tot[j] * p[m - j] for j in range(1, m + 1)))
                 / tot[0])
    return jnp.stack(p)


def jet_flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            wo: jnp.ndarray, scale: float,
                            mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Full fused-attention oracle: Q/K/V stacks (n+1, B, H, T, Dh) and the
    output projection ``wo`` (H, Dh, Dm) -> the attention-block output jet
    (n+1, B, T, Dm).

    Straight-line scores -> masked softmax -> value contraction -> output
    projection, all as explicit Cauchy convolutions / power-series
    recurrences (no core.jet, no shared kernel body, no online rescaling --
    the O(T^2)-memory computation the tiled kernel must reproduce).

    ``mask`` is a dense boolean (Tq, Tk) keep-matrix (True = attend); every
    query row must keep at least one key.  Masking replaces ``s_0`` with a
    large negative constant *before* the exp recurrence, so masked
    positions' whole e-jets vanish (exp underflows to exactly 0 and every
    higher coefficient carries an e-factor that is already 0) -- no
    inf/NaN enters even under differentiation.
    """
    n1 = q.shape[0]
    s = [scale * sum(_einsum("bhqd,bhkd->bhqk", q[i], k[m - i])
                     for i in range(m + 1)) for m in range(n1)]
    if mask is not None:
        s[0] = jnp.where(mask, s[0], jnp.asarray(-1e30, s[0].dtype))
    shift = jnp.max(s[0], axis=-1, keepdims=True)
    e = [jnp.exp(s[0] - shift)]
    for m in range(1, n1):
        e.append(sum(j * s[j] * e[m - j] for j in range(1, m + 1)) / m)
    tot = [jnp.sum(em, axis=-1, keepdims=True) for em in e]
    p = [e[0] / tot[0]]
    for m in range(1, n1):
        p.append((e[m] - sum(tot[j] * p[m - j] for j in range(1, m + 1)))
                 / tot[0])
    o = [sum(_einsum("bhqk,bhkd->bhqd", p[i], v[m - i])
             for i in range(m + 1)) for m in range(n1)]
    return jnp.stack([_einsum("bhqd,hdo->bqo", om, wo) for om in o])


def jet_rms_norm_ref(coeffs: jnp.ndarray, gamma: jnp.ndarray,
                     eps: float = 1e-6) -> jnp.ndarray:
    """Fused rms_norm oracle: (n+1, B, W) stack + (W,) gain -> rms_norm jet.

    Straight-line mean-square convolution, binomial-series rsqrt (Miller
    recurrence, r = -1/2), normalizing convolution, gain."""
    n1 = coeffs.shape[0]
    ms = [sum(jnp.mean(coeffs[i] * coeffs[m - i], axis=-1, keepdims=True)
              for i in range(m + 1)) for m in range(n1)]
    ms[0] = ms[0] + eps
    inv = [1.0 / jnp.sqrt(ms[0])]
    for m in range(1, n1):
        inv.append(sum((0.5 * j - m) * ms[j] * inv[m - j]
                       for j in range(1, m + 1)) / (m * ms[0]))
    out = [sum(coeffs[m - j] * inv[j] for j in range(m + 1)) * gamma
           for m in range(n1)]
    return jnp.stack(out)
