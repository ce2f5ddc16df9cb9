"""Pallas TPU kernels: PINNsFormer's wavelet activation jet, one launch
forward and one launch backward.

The wavelet ``W(a) = w1 sin a + w2 cos a`` has learned scalar weights, so
its jet is elementwise over the coefficient stack ``c`` of shape
``(n+1, R, W)``.  With ``A = W(c_0)`` and ``B = W'(c_0)`` the outer
coefficients are ``F_m = W^(m)(c_0)/m! = (A, B, -A, -B)[m % 4] / m!`` --
one sin and one cos of ``c_0`` serve every order -- and the output is the
Faa di Bruno contraction ``out_k = sum_t coef_t F_{m_t} prod_j c_j^{e_j}``
over the static terms of ``bell_tables.fdb_terms``.

The backward is the closed-form transpose of that contraction.  With the
cotangent ``g``, ``Q_m = sum_k g_k sum_{t of order k, m_t = m} coef_t
prod_j c_j^{e_j}`` (``Q_0 = g_0``) and its alternating even and odd sums
``Qe = Q_0 - Q_2/2! + Q_4/4! - ...``, ``Qo = Q_1 - Q_3/3! + ...``::

    dc_0 = sum_m (m+1) F_{m+1} Q_m = B Qe - A Qo
    dc_j = sum_k g_k sum_t coef_t F_{m_t} d(prod_j c_j^{e_j})/dc_j   (j >= 1)
    dw1  = sum g . S = sum (sin c_0 Qe + cos c_0 Qo)
    dw2  = sum g . C = sum (cos c_0 Qe - sin c_0 Qo)

where ``S`` and ``C`` are the sin- and cos-jets of ``c``.  Each grid step
writes its rows' sums of ``g . S`` and ``g . C`` as one ``(2, W)`` slab,
and XLA adds the slabs up; rows past the end of a ragged last block are
masked out of them.  The only residuals are ``(c, w)``.

Blocks are ``(n+1, block_rows, W)``: the coefficient axis is never split
and the feature axis stays whole, so the folded ``(n+1, *batch, W)`` stack
of a Dense -> Wave -> Dense chain reaches the kernel without a relayout.
The weights ride in SMEM.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bell_tables import fdb_terms

# elements of one block, lanes padded to 128: 512 KiB of f32 per operand
BLOCK_ELEMS = 128 * 1024
# rows of a block computed at a time, so that the temporaries stay small
SUB_ROWS = 8


def block_rows(rows: int, width: int, n1: int) -> int:
    """Rows of one block: a multiple of 8 near ``BLOCK_ELEMS`` elements, or
    every row when there are fewer."""
    lanes = -(-width // 128) * 128
    bb = max(8, BLOCK_ELEMS // (n1 * lanes) // 8 * 8)
    return rows if rows <= bb else bb


def _sign(m: int) -> float:
    """The sign and 1/m! of ``F_m`` against ``(A, B)[m % 2]``."""
    return (1.0, 1.0, -1.0, -1.0)[m % 4] / math.factorial(m)


def _monomial(c, powers, memo):
    """``prod_j c_j^{e_j}`` for ``powers = ((j, e), ...)``, with every
    product on the way kept in ``memo``; ``()`` is 1 (``None``)."""
    if not powers:
        return None
    if powers not in memo:
        (j, e), rest = powers[-1], powers[:-1]
        lower = _monomial(c, rest + (((j, e - 1),) if e > 1 else ()), memo)
        memo[powers] = c[j] if lower is None else lower * c[j]
    return memo[powers]


def _lowered(powers, j):
    """``powers`` with the exponent of ``c_j`` one less."""
    return tuple((i, e - (i == j)) for i, e in powers if e - (i == j) > 0)


def _add(acc, x):
    return x if acc is None else acc + x


def wave_jet_tile(c, w1, w2):
    """The wavelet jet of the rows ``c = [c_0, ..., c_n]`` (arrays of one
    shape): the list ``[out_0, ..., out_n]``."""
    n = len(c) - 1
    s, co = jnp.sin(c[0]), jnp.cos(c[0])
    a, b = w1 * s + w2 * co, w1 * co - w2 * s
    out, memo = [a], {}
    for terms in fdb_terms(n):
        even = odd = None                 # the terms on A, and those on B
        for coef, m, powers in terms:
            x = _monomial(c, powers, memo) * (coef * _sign(m))
            if m % 2:
                odd = _add(odd, x)
            else:
                even = _add(even, x)
        out.append(_add(None if even is None else a * even,
                        None if odd is None else b * odd))
    return out


def wave_jet_bwd_tile(c, g, w1, w2):
    """The transpose of :func:`wave_jet_tile` at ``c`` against the
    cotangent rows ``g``: ``([dc_0, ..., dc_n], g . S, g . C)``, the last
    two elementwise (summed over elements they are ``dw1`` and ``dw2``)."""
    n = len(c) - 1
    s, co = jnp.sin(c[0]), jnp.cos(c[0])
    a, b = w1 * s + w2 * co, w1 * co - w2 * s
    memo = {}
    qe, qo = g[0], None
    on_a = [None] * (n + 1)               # dc_j's part on A, and on B
    on_b = [None] * (n + 1)
    for k, terms in enumerate(fdb_terms(n), start=1):
        for coef, m, powers in terms:
            h = g[k] * (coef * _sign(m))
            if m % 2:
                qo = _add(qo, h * _monomial(c, powers, memo))
            else:
                qe = _add(qe, h * _monomial(c, powers, memo))
            part = on_b if m % 2 else on_a
            for j, e in powers:
                lower = _monomial(c, _lowered(powers, j), memo)
                d = h if lower is None else h * lower
                part[j] = _add(part[j], d * float(e) if e > 1 else d)
    dc = [b * qe if qo is None else b * qe - a * qo]
    for j in range(1, n + 1):
        dc.append(_add(None if on_a[j] is None else a * on_a[j],
                       None if on_b[j] is None else b * on_b[j]))
    if qo is None:
        return dc, s * qe, co * qe
    return dc, s * qe + co * qo, co * qe - s * qo


def over_chunks(bb: int, sub: int, body, init):
    """``body(rows, carry)`` over the block's rows ``sub`` at a time (one
    call over all of them when ``sub`` does not divide ``bb``)."""
    if sub >= bb or bb % sub:
        return body(pl.ds(0, bb), init)
    return jax.lax.fori_loop(
        0, bb // sub,
        lambda i, carry: body(pl.ds(pl.multiple_of(i * sub, sub), sub),
                              carry), init)


def _forward_kernel(w_ref, c_ref, o_ref, *, sub):
    n1, bb, _ = c_ref.shape
    w1, w2 = w_ref[0], w_ref[1]

    def chunk(rows, carry):
        out = wave_jet_tile([c_ref[k, rows, :] for k in range(n1)], w1, w2)
        for k in range(n1):
            o_ref[k, rows, :] = out[k].astype(o_ref.dtype)
        return carry

    over_chunks(bb, sub, chunk, 0)


def _backward_kernel(w_ref, c_ref, g_ref, dc_ref, dw_ref, *, rows, sub):
    n1, bb, width = c_ref.shape
    acc = dw_ref.dtype
    w1, w2 = w_ref[0].astype(acc), w_ref[1].astype(acc)
    first = pl.program_id(0) * bb

    def chunk(r, sums):
        dc, gs, gc = wave_jet_bwd_tile(
            [c_ref[k, r, :].astype(acc) for k in range(n1)],
            [g_ref[k, r, :].astype(acc) for k in range(n1)], w1, w2)
        for k in range(n1):
            dc_ref[k, r, :] = dc[k].astype(dc_ref.dtype)
        if rows % bb:
            # the ragged last block reads past the stack's end: keep those
            # rows out of the weight sums
            row = first + r.start + jax.lax.broadcasted_iota(
                jnp.int32, gs.shape, 0)
            gs = jnp.where(row < rows, gs, 0.0)
            gc = jnp.where(row < rows, gc, 0.0)
        return sums[0] + gs, sums[1] + gc

    n = bb if sub >= bb or bb % sub else sub
    zero = jnp.zeros((n, width), acc)
    gs, gc = over_chunks(bb, sub, chunk, (zero, zero))
    dw_ref[0, 0:1, :] = jnp.sum(gs, axis=0, keepdims=True)
    dw_ref[0, 1:2, :] = jnp.sum(gc, axis=0, keepdims=True)


def _smem():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _stack_spec(n1, bb, width):
    return pl.BlockSpec((n1, bb, width), lambda i: (0, i, 0))


_PARALLEL = pltpu.CompilerParams(dimension_semantics=("parallel",))


@functools.partial(jax.jit, static_argnames=("interpret",))
def wave_jet_pallas(coeffs: jnp.ndarray, w: jnp.ndarray,
                    interpret: bool = True) -> jnp.ndarray:
    """(n+1, R, W) coefficient stack and (2,) weights -> the wavelet jet
    ``w1 S + w2 C``, same shape."""
    n1, rows, width = coeffs.shape
    bb = block_rows(rows, width, n1)
    return pl.pallas_call(
        functools.partial(_forward_kernel, sub=SUB_ROWS),
        grid=(pl.cdiv(rows, bb),),
        in_specs=[_smem(), _stack_spec(n1, bb, width)],
        out_specs=_stack_spec(n1, bb, width),
        out_shape=jax.ShapeDtypeStruct(coeffs.shape, coeffs.dtype),
        compiler_params=_PARALLEL,
        interpret=interpret,
        name="wave_jet",
    )(w.astype(coeffs.dtype), coeffs)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wave_jet_bwd_pallas(coeffs: jnp.ndarray, w: jnp.ndarray, g: jnp.ndarray,
                        interpret: bool = True):
    """The VJP of :func:`wave_jet_pallas` at ``(coeffs, w)`` against ``g``:
    ``(dcoeffs, dw)``, ``dw`` summed from one partial per block."""
    n1, rows, width = coeffs.shape
    bb = block_rows(rows, width, n1)
    grid = pl.cdiv(rows, bb)
    acc = jnp.promote_types(coeffs.dtype, jnp.float32)
    dc, partials = pl.pallas_call(
        functools.partial(_backward_kernel, rows=rows, sub=SUB_ROWS),
        grid=(grid,),
        in_specs=[_smem(), _stack_spec(n1, bb, width),
                  _stack_spec(n1, bb, width)],
        out_specs=[_stack_spec(n1, bb, width),
                   pl.BlockSpec((1, 2, width), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(coeffs.shape, coeffs.dtype),
                   jax.ShapeDtypeStruct((grid, 2, width), acc)],
        compiler_params=_PARALLEL,
        interpret=interpret,
        name="wave_jet_bwd",
    )(w.astype(acc), coeffs, g.astype(coeffs.dtype))
    return dc, jnp.sum(partials, axis=(0, 2)).astype(w.dtype)
