"""Taylor-jet algebra: the n-TangentProp derivative stack and its arithmetic.

A ``Jet`` holds scaled Taylor coefficients ``c_k = (1/k!) d^k x(t)/dt^k`` of a
quantity along a 1-parameter input curve ``t -> f(x0 + t v)``, stacked on a
leading axis: ``coeffs[k]`` has the shape of the underlying tensor.  The
scaled normalization (vs raw derivatives) makes every rule below a clean
power-series identity with small integer constants (DESIGN.md section 2):

* linear maps apply coefficient-wise (bias touches only ``c_0``);
* products are Cauchy convolutions ``(AB)_k = sum_{i+j=k} A_i B_j`` --
  this covers matmul/einsum contractions between two jets (attention!);
* smooth scalar functions compose via the Taylor-normalized Faa di Bruno
  contraction (core/partitions.py) with closed-form outer coefficients
  (core/activations.py);
* ``exp/log/div/pow`` use the classical power-series recurrences, which are
  cheaper (O(n^2)) than the generic partition sum (O(n p(n))).

Everything is shape-polymorphic and jit/scan/pjit friendly: a Jet is a pytree
whose single leaf is the ``(order+1, *shape)`` stack, so it shards exactly
like a batch-expanded activation tensor.
"""

from __future__ import annotations

import math
from typing import Callable, Sequence, Union

import jax
import jax.numpy as jnp

from .activations import (GELU_TANH_C, GELU_TANH_CUBIC, TAYLOR_STACKS,
                          wave_taylor_stack)
from .partitions import faa_di_bruno_table

# Every contraction on the derivative path (this algebra, the Pallas kernels,
# their oracles, the primal forwards the autodiff engine differentiates)
# runs at full f32 precision.  A TPU's default f32 matmul may round its
# operands to bf16, an error the order-n coefficients amplify; the engines
# must agree to f32 rounding, not to bf16's.
MATMUL_PRECISION = jax.lax.Precision.HIGHEST


def float_dtype():
    """The widest float JAX has enabled: float64 under ``jax_enable_x64``
    (the CPU oracle tests), float32 otherwise (the chip, which has no f64)."""
    return jax.dtypes.canonicalize_dtype(jnp.float64)


@jax.tree_util.register_pytree_node_class
class Jet:
    """Stack of scaled Taylor coefficients c_0..c_n on a leading axis."""

    __slots__ = ("coeffs",)

    def __init__(self, coeffs: jnp.ndarray):
        self.coeffs = coeffs

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (self.coeffs,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])

    # -- basic accessors ----------------------------------------------------
    @property
    def order(self) -> int:
        return self.coeffs.shape[0] - 1

    @property
    def primal(self) -> jnp.ndarray:
        return self.coeffs[0]

    @property
    def shape(self):
        return self.coeffs.shape[1:]

    @property
    def dtype(self):
        return self.coeffs.dtype

    def __repr__(self):
        return f"Jet(order={self.order}, shape={self.shape}, dtype={self.dtype})"

    # -- operator sugar -------------------------------------------------------
    def __add__(self, other):
        return add(self, other)

    __radd__ = __add__

    def __sub__(self, other):
        return sub(self, other)

    def __rsub__(self, other):
        return sub(other, self)

    def __mul__(self, other):
        return mul(self, other)

    __rmul__ = __mul__

    def __truediv__(self, other):
        return div(self, other)

    def __neg__(self):
        return Jet(-self.coeffs)


JetLike = Union[Jet, jnp.ndarray, float, int]


# ---------------------------------------------------------------------------
# construction / extraction
# ---------------------------------------------------------------------------

def seed(x: jnp.ndarray, v: jnp.ndarray | None, order: int) -> Jet:
    """Jet of the curve t -> x + t v  (c_0 = x, c_1 = v, higher = 0)."""
    if v is None:
        v = jnp.ones_like(x)
    zeros = [jnp.zeros_like(x) for _ in range(order - 1)]
    return Jet(jnp.stack([x, v.astype(x.dtype)] + zeros))


def const(x: JetLike, order: int, like: Jet | None = None) -> Jet:
    """Constant-in-t jet (only c_0 populated)."""
    if isinstance(x, Jet):
        return x
    x = jnp.asarray(x, dtype=None if like is None else like.dtype)
    return Jet(jnp.concatenate([x[None], jnp.zeros((order,) + x.shape, x.dtype)]))


def derivatives(j: Jet) -> jnp.ndarray:
    """Raw derivatives d^k f/dt^k = k! * c_k, stacked (order+1, *shape)."""
    facts = jnp.asarray([math.factorial(k) for k in range(j.order + 1)], j.dtype)
    return j.coeffs * facts.reshape((-1,) + (1,) * len(j.shape))


def from_derivatives(d: jnp.ndarray) -> Jet:
    """Inverse of :func:`derivatives`."""
    n = d.shape[0] - 1
    inv = jnp.asarray([1.0 / math.factorial(k) for k in range(n + 1)], d.dtype)
    return Jet(d * inv.reshape((-1,) + (1,) * (d.ndim - 1)))


def _align(a: Jet, b: Jet) -> tuple[Jet, Jet]:
    """Insert singleton dims after the coefficient axis so the *underlying*
    shapes broadcast by trailing-dim rules (coeff axis stays leading)."""
    na, nb = len(a.shape), len(b.shape)
    if na < nb:
        a = Jet(a.coeffs.reshape(a.coeffs.shape[:1] + (1,) * (nb - na) + a.shape))
    elif nb < na:
        b = Jet(b.coeffs.reshape(b.coeffs.shape[:1] + (1,) * (na - nb) + b.shape))
    return a, b


def _promote(a: JetLike, b: JetLike) -> tuple[Jet, Jet]:
    if isinstance(a, Jet) and isinstance(b, Jet):
        if a.order != b.order:
            raise ValueError(f"jet order mismatch: {a.order} vs {b.order}")
        return _align(a, b)
    if isinstance(a, Jet):
        return _align(a, const(b, a.order, like=a))
    if isinstance(b, Jet):
        return _align(const(a, b.order, like=b), b)
    raise TypeError("at least one operand must be a Jet")


# ---------------------------------------------------------------------------
# linear operations (coefficient-wise)
# ---------------------------------------------------------------------------

def jmap(fn: Callable[..., jnp.ndarray], *jets: Jet) -> Jet:
    """Apply a *linear* array function to each coefficient (reshape, reduce-sum,
    transpose, pad, slice, concat of jets, multiplication by a constant...)."""
    n = jets[0].order
    rows = [fn(*(j.coeffs[k] for j in jets)) for k in range(n + 1)]
    return Jet(jnp.stack(rows))


def add(a: JetLike, b: JetLike) -> Jet:
    a, b = _promote(a, b)
    return Jet(a.coeffs + b.coeffs)


def sub(a: JetLike, b: JetLike) -> Jet:
    a, b = _promote(a, b)
    return Jet(a.coeffs - b.coeffs)


def scale(a: Jet, s) -> Jet:
    """Multiply by a t-constant scalar/array (broadcasts like arrays)."""
    return Jet(a.coeffs * s)


def linear(a: Jet, w: jnp.ndarray, b: jnp.ndarray | None = None,
           eq: str = "...i,ij->...j") -> Jet:
    """Dense layer on a jet: W acts on every coefficient, bias only on c_0.

    ``eq`` must open with an ellipsis on the jet operand: the coefficient
    axis (and any leading batch/token axes) folds into the ``...`` so the
    whole stack contracts in ONE einsum instead of per-coefficient calls."""
    if not eq.startswith("..."):
        raise ValueError(f"linear eq must start with '...' so the "
                         f"coefficient axis can ride it, got {eq!r}")
    out = jnp.einsum(eq, a.coeffs, w, precision=MATMUL_PRECISION)
    if b is not None:
        out = out.at[0].add(b)
    return Jet(out)


def reduce_sum(a: Jet, axis, keepdims: bool = False) -> Jet:
    return jmap(lambda c: jnp.sum(c, axis=axis, keepdims=keepdims), a)


def reduce_mean(a: Jet, axis, keepdims: bool = False) -> Jet:
    return jmap(lambda c: jnp.mean(c, axis=axis, keepdims=keepdims), a)


def where(mask: jnp.ndarray, a: JetLike, b: JetLike) -> Jet:
    """Select with a t-constant predicate (exact a.e.; mask must not depend on t)."""
    a, b = _promote(a, b)
    return jmap(lambda x, y: jnp.where(mask, x, y), a, b)


# ---------------------------------------------------------------------------
# bilinear operations (Cauchy convolution over the coefficient axis)
# ---------------------------------------------------------------------------

def _cauchy(a: Jet, b: Jet, combine: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]) -> Jet:
    n = a.order
    rows = []
    for k in range(n + 1):
        acc = combine(a.coeffs[0], b.coeffs[k])
        for i in range(1, k + 1):
            acc = acc + combine(a.coeffs[i], b.coeffs[k - i])
        rows.append(acc)
    return Jet(jnp.stack(rows))


def mul(a: JetLike, b: JetLike) -> Jet:
    a, b = _promote(a, b)
    return _cauchy(a, b, jnp.multiply)


def einsum(eq: str, a: JetLike, b: JetLike) -> Jet:
    """Jet-valued contraction: out_k = sum_{i+j=k} einsum(eq, a_i, b_j).

    If one operand is t-constant the convolution degenerates to a per-
    coefficient einsum (no extra FLOPs vs the primal op times (n+1)).
    NOTE: no broadcast alignment here -- einsum subscripts fix the ranks."""
    def contract(x, y):
        return jnp.einsum(eq, x, y, precision=MATMUL_PRECISION)

    if isinstance(a, Jet) and not isinstance(b, Jet):
        return jmap(lambda c: contract(c, b), a)
    if isinstance(b, Jet) and not isinstance(a, Jet):
        return jmap(lambda c: contract(a, c), b)
    if a.order != b.order:
        raise ValueError(f"jet order mismatch: {a.order} vs {b.order}")
    return _cauchy(a, b, contract)


# ---------------------------------------------------------------------------
# power-series recurrences
# ---------------------------------------------------------------------------

def exp(a: Jet) -> Jet:
    """e_0 = exp(a_0);  e_k = (1/k) sum_{j=1..k} j a_j e_{k-j}."""
    n = a.order
    rows = [jnp.exp(a.coeffs[0])]
    for k in range(1, n + 1):
        acc = a.coeffs[k] * rows[0] * k  # j = k term
        for j in range(1, k):
            acc = acc + j * a.coeffs[j] * rows[k - j]
        rows.append(acc / k)
    return Jet(jnp.stack(rows))


def log(a: Jet) -> Jet:
    """l_0 = log a_0;  l_k = (a_k - (1/k) sum_{j=1..k-1} j l_j a_{k-j}) / a_0."""
    n = a.order
    inv0 = 1.0 / a.coeffs[0]
    rows = [jnp.log(a.coeffs[0])]
    for k in range(1, n + 1):
        acc = a.coeffs[k]
        for j in range(1, k):
            acc = acc - (j / k) * rows[j] * a.coeffs[k - j]
        rows.append(acc * inv0)
    return Jet(jnp.stack(rows))


def div(a: JetLike, b: JetLike) -> Jet:
    """c_k = (a_k - sum_{j=1..k} b_j c_{k-j}) / b_0."""
    a, b = _promote(a, b)
    inv0 = 1.0 / b.coeffs[0]
    rows = [a.coeffs[0] * inv0]
    for k in range(1, a.order + 1):
        acc = a.coeffs[k]
        for j in range(1, k + 1):
            acc = acc - b.coeffs[j] * rows[k - j]
        rows.append(acc * inv0)
    return Jet(jnp.stack(rows))


def powr(a: Jet, r: float) -> Jet:
    """a^r (real r) via the J.C.P. Miller recurrence:
    c_k = (1/(k a_0)) sum_{j=1..k} ((r+1) j - k) a_j c_{k-j}."""
    n = a.order
    inv0 = 1.0 / a.coeffs[0]
    rows = [jnp.power(a.coeffs[0], r)]
    for k in range(1, n + 1):
        acc = ((r + 1) * 1 - k) * a.coeffs[1] * rows[k - 1]
        for j in range(2, k + 1):
            acc = acc + ((r + 1) * j - k) * a.coeffs[j] * rows[k - j]
        rows.append(acc * inv0 / k)
    return Jet(jnp.stack(rows))


def sqrt(a: Jet) -> Jet:
    return powr(a, 0.5)


def rsqrt(a: Jet) -> Jet:
    return powr(a, -0.5)


# ---------------------------------------------------------------------------
# smooth scalar composition (Faa di Bruno)
# ---------------------------------------------------------------------------

def compose(a: Jet, name: str) -> Jet:
    """sigma(a) for a registered smooth activation, via the Taylor-normalized
    Faa di Bruno contraction with closed-form outer coefficients."""
    return compose_stack(a, TAYLOR_STACKS[name](a.coeffs[0], a.order))


def compose_stack(a: Jet, fstack: jnp.ndarray) -> Jet:
    """sigma(a) from the outer coefficients ``fstack`` (n+1, *shape),
    ``F_m = sigma^(m)(a_0)/m!``, by the Faa di Bruno contraction."""
    n = a.order
    rows = [fstack[0]]
    for k in range(1, n + 1):
        acc = None
        for term in faa_di_bruno_table(k):
            prod = fstack[term.order] * float(term.coef)
            for j, e in term.powers:
                cj = a.coeffs[j]
                for _ in range(e):
                    prod = prod * cj
            acc = prod if acc is None else acc + prod
        rows.append(acc)
    return Jet(jnp.stack(rows))


def tanh(a: Jet) -> Jet:
    return compose(a, "tanh")


def sigmoid(a: Jet) -> Jet:
    return compose(a, "sigmoid")


def sin(a: Jet) -> Jet:
    return compose(a, "sin")


def wave(a: Jet, w1, w2) -> Jet:
    """PINNsFormer's wavelet activation ``w1 sin a + w2 cos a`` with learned
    scalars: one Faa di Bruno contraction over the outer coefficients of
    :func:`repro.core.activations.wave_taylor_stack`, differentiable in
    ``w1`` and ``w2``."""
    return compose_stack(a, wave_taylor_stack(a.coeffs[0], a.order, w1, w2))


def softplus(a: Jet) -> Jet:
    return compose(a, "softplus")


def silu(a: Jet) -> Jet:
    return mul(a, sigmoid(a))


def gelu(a: Jet) -> Jet:
    """tanh-approximation GELU as a pure jet composition (poly + tanh + mul);
    constants shared with PRIMALS['gelu'] via core.activations."""
    a3 = mul(mul(a, a), a)
    inner = scale(add(a, scale(a3, GELU_TANH_CUBIC)), GELU_TANH_C)
    return scale(mul(a, add(tanh(inner), 1.0)), 0.5)


def relu(a: Jet) -> Jet:
    """Piecewise-linear: exact wherever a_0 != 0 (jets vanish on the off side)."""
    return where(a.coeffs[0] > 0, a, scale(a, 0.0))


def identity(a: Jet) -> Jet:
    return a


_COMPOSITE_ACTS: dict[str, Callable[[Jet], Jet]] = {
    "silu": silu, "gelu": gelu, "relu": relu, "identity": identity,
}


def activation(a: Jet, name: str) -> Jet:
    """Named activation on a jet: table-backed names go through the Faa di
    Bruno contraction (:func:`compose`); composite ones (silu, gelu, relu,
    identity) through their jet-algebra definitions.  The single dispatch
    point for :class:`repro.core.modules.Dense`/``Activation`` leaves."""
    if name in TAYLOR_STACKS:
        return compose(a, name)
    if name in _COMPOSITE_ACTS:
        return _COMPOSITE_ACTS[name](a)
    raise KeyError(f"unknown activation {name!r}; known: "
                   f"{sorted(set(TAYLOR_STACKS) | set(_COMPOSITE_ACTS))}")


# ---------------------------------------------------------------------------
# softmax & norms (built from the primitives; used by attention jets)
# ---------------------------------------------------------------------------

# Finite stand-in for -inf at masked softmax positions: exp underflows to
# exactly 0 (killing the whole e-jet there by the exp recurrence), while
# arithmetic on it stays NaN-free -- a true -inf would produce inf - inf
# under the shift and 0 * inf in the recurrences.  Shared with the Pallas
# flash kernel (kernels/jet_attention.py).
MASK_NEG = -1e30


def softmax(a: Jet, axis: int = -1, mask: jnp.ndarray | None = None) -> Jet:
    """Softmax jet over ``axis``; ``mask`` is an optional t-constant boolean
    keep-matrix (True = attend, broadcastable against the coefficients).
    Masked positions are replaced by the constant jet ``MASK_NEG`` *before*
    the exp recurrence, so their probability jets vanish identically at
    every order and no inf/NaN enters even under differentiation.  A row
    that keeps NO position degrades gracefully instead of producing NaN:
    the whole row becomes the constant ``MASK_NEG`` jet, the shift cancels
    it exactly, and the result is the uniform distribution with zero
    higher-order coefficients (pinned by tests/test_jet.py)."""
    if mask is not None:
        a = where(mask, a, MASK_NEG)
    shift = jax.lax.stop_gradient(jnp.max(a.coeffs[0], axis=axis, keepdims=True))
    e = exp(sub(a, const(shift, a.order, like=a)))
    s = reduce_sum(e, axis=axis, keepdims=True)
    return div(e, s)


def rms_norm(x: Jet, gamma: jnp.ndarray, eps: float = 1e-6,
             axis: int = -1, offset: float = 0.0) -> Jet:
    ms = reduce_mean(mul(x, x), axis=axis, keepdims=True)
    inv = rsqrt(add(ms, eps))
    return scale(mul(x, inv), (offset + gamma))


def layer_norm(x: Jet, gamma: jnp.ndarray, beta: jnp.ndarray, eps: float = 1e-5,
               axis: int = -1) -> Jet:
    mu = reduce_mean(x, axis=axis, keepdims=True)
    xc = sub(x, mu)
    var = reduce_mean(mul(xc, xc), axis=axis, keepdims=True)
    y = mul(xc, rsqrt(add(var, eps)))
    y = scale(y, gamma)
    return add(y, const(beta, x.order, like=x))
