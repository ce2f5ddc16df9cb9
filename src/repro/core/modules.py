"""Compositional jet-modules: reusable blocks every Network is built from.

PR 2 made the *engines* network-agnostic; this layer makes the *networks*
module-agnostic.  A :class:`Module` is the smallest jet-traceable unit --
``init`` / ``apply`` / ``jet_apply`` with exactly the Network contract
(``repro.core.network``), so a Network is just a Module with ``d_in``/
``d_out``/``activation`` metadata and combinators compose freely:

* **leaves** own parameters and the jet rules for one operation --
  :class:`Dense` (with the Pallas ``jet_dense`` fast path and fused
  activation epilogue), :class:`Activation`, :class:`FourierFeatures`,
  :class:`RMSNorm`, :class:`Attention` (self- and cross-attention;
  ``SelfAttention`` is its self case), :class:`MLPBlock`,
  :class:`CoordinateEmbedding`, :class:`TokenPool`, and PINNsFormer's
  :class:`Wave` and :class:`PseudoSequence`;
* **combinators** own structure only -- :class:`Sequential` (params are a
  tuple, one entry per child, keys split once per child in order) and
  :class:`Residual` (``x + inner(x)``; jet addition is coefficient-wise and
  exact, so skips cost nothing in derivative accuracy).

``jet_apply`` composes because every leaf pushes the *same* scaled-Taylor
jet representation (``repro.core.jet``): the stack ``(order+1, *shape)``
rides through linear maps coefficient-wise, through contractions as Cauchy
convolutions (attention scores!), and through smooth scalars via Faa di
Bruno.  ``impl="pallas"`` routes every Dense contraction through the fused
kernel dispatch (``repro.kernels.ops.jet_dense``, which accepts arbitrary
leading batch axes -- token axes included -- and fuses the activation
epilogue when ``ops.epilogues()`` marks the name ``ACTIVATION``), the whole
attention layer through the single-launch ``ops.jet_flash_attention`` and
rms_norm through ``ops.jet_rms_norm`` (the ``"flash_attention"`` /
``"rms_norm"`` ``FUSED_OP`` entries of the same typed epilogue registry);
anything unfused runs the reference jet algebra, so a module mixes kernel
and reference paths freely.  ``Attention`` carries the attention-mask
surface (``mask=None | "causal" | ("local", window)``, canonicalized by
:func:`normalize_attention_mask`), honoured identically by the primal
``apply``, the jnp jet path (``J.softmax(mask=...)``), and the flash
kernel's per-block index test.

Leaves register themselves in a name -> factory registry
(:func:`register_module`) so configs and future conversion tools can build
graphs from data.  New blocks implement the three methods and slot into any
combinator; see ``repro.core.network.Transformer`` for the first non-MLP
consumer (pre-norm self-attention trunk over coordinate tokens) and
``repro.core.network.PINNsFormer`` for an encoder-decoder whose decoder
reads two streams.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.runtime.metrics import count, scope

from . import jet as J
from .activations import PRIMALS
from .ntp import xavier_uniform

Params = Any  # parameter pytree; structure owned by the module


def _matmul(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Primal ``x @ w`` at the derivative path's matmul precision."""
    return jnp.matmul(x, w, precision=J.MATMUL_PRECISION)


class Module:
    """Smallest jet-traceable unit: the Network contract without metadata.

    Stateless modules keep the default ``init`` (empty params) but still
    consume one RNG key inside :class:`Sequential` so adding parameters to a
    block never reshuffles its siblings' initializations.
    """

    def init(self, key: jax.Array, dtype=jnp.float32) -> Params:
        return ()

    def apply(self, params: Params, x: jnp.ndarray, *,
              unroll: bool = False) -> jnp.ndarray:
        raise NotImplementedError

    def jet_apply(self, params: Params, jet: J.Jet, *,
                  impl: str = "jnp") -> J.Jet:
        raise NotImplementedError


def _check_impl(impl: str) -> None:
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"unknown impl {impl!r} (want 'jnp' or 'pallas')")


def _has_epilogue(name: str) -> bool:
    """Lazy wrapper over the typed capability registry
    ``kernels.ops.epilogues()`` (kept lazy so the module layer imports
    without pulling the Pallas stack in)."""
    from repro.kernels import ops as kops
    return name in kops.epilogues()


def _is_activation_epilogue(name: str) -> bool:
    """Lazy: can the dense kernel run ``name`` in its Faa di Bruno epilogue
    (``epilogues()[name] is EpilogueKind.ACTIVATION``)?  The FUSED_OP
    entries ("rms_norm", "attention_scores", "flash_attention") are NOT
    dense epilogues and must take their own dispatch."""
    from repro.kernels import ops as kops
    return kops.epilogues().get(name) is kops.EpilogueKind.ACTIVATION


# every canonical attention-mask kind normalize_attention_mask can emit;
# the registry the parity sweep's mask coverage is asserted against
ATTENTION_MASK_KINDS = ("none", "causal", "local")


def normalize_attention_mask(mask) -> tuple:
    """Canonicalize an attention-mask spec to a hashable ``(kind, window)``
    pair: ``None``/"none" -> ("none", 0), "causal" -> ("causal", 0),
    ("local", w) -> ("local", int(w)) with w >= 1.  The single validation
    point shared by :class:`SelfAttention` and the flash-kernel dispatch in
    ``repro.kernels.ops``."""
    if mask is None or mask == "none" or mask == ("none", 0):
        return ("none", 0)
    if mask == "causal" or mask == ("causal", 0):
        return ("causal", 0)
    if (isinstance(mask, (tuple, list)) and len(mask) == 2
            and mask[0] == "local"):
        window = int(mask[1])
        if window < 1:
            raise ValueError(f"local attention window must be >= 1, "
                             f"got {mask[1]!r}")
        return ("local", window)
    raise ValueError(f"unknown attention mask {mask!r}; want None, "
                     "'causal', or ('local', window)")


def attention_mask(mask, t: int) -> jnp.ndarray | None:
    """Dense (T, T) boolean keep-matrix for a mask spec (None for "none"):
    what the jnp softmax path, the primal forward, and the flash-kernel
    backward recompute consume.  ``local(w)`` is a causal sliding window --
    query q attends keys j with ``q - w < j <= q`` -- so the diagonal is
    always kept and no query row is ever fully masked."""
    kind, window = normalize_attention_mask(mask)
    if kind == "none":
        return None
    qi = jnp.arange(t)[:, None]
    kj = jnp.arange(t)[None, :]
    keep = kj <= qi
    if kind == "local":
        keep = keep & ((qi - kj) < window)
    return keep


def dense_jet(jet: J.Jet, w: jnp.ndarray, b: jnp.ndarray | None,
              activation: str | None, impl: str) -> J.Jet:
    """One dense contraction (+ optional activation) on a jet, dispatched.

    The shared fast path for every module that multiplies a jet by a weight
    matrix: ``impl="pallas"`` runs the fused kernel (activation folded into
    the kernel epilogue when the table exists, else the kernel computes the
    linear part and the activation composes through the jet algebra);
    ``impl="jnp"`` is the reference algebra.  Arbitrary leading batch axes
    (collocation batch, token axis) are supported by both paths.
    """
    _check_impl(impl)
    if impl == "pallas":
        from repro.kernels import ops as kops
        if b is None:
            b = jnp.zeros((w.shape[1],), jet.dtype)
        # the narrow ACTIVATION-kind query, NOT bare membership: FUSED_OP
        # registry entries ("rms_norm", "attention_scores",
        # "flash_attention") are not dense epilogues and must take the
        # compose-after-kernel path
        if activation is None or _is_activation_epilogue(activation):
            return J.Jet(kops.jet_dense(jet.coeffs, w, b, activation))
        out = J.Jet(kops.jet_dense(jet.coeffs, w, b, None))
        return J.activation(out, activation)
    out = J.linear(jet, w, b)
    if activation is not None:
        out = J.activation(out, activation)
    return out


# ---------------------------------------------------------------------------
# leaf modules
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Dense(Module):
    """``act(x @ w + b)`` -- params ``(w, b)``; ``activation=None`` is the
    linear readout.  The jet path is the Pallas-fused layer of the paper's
    Algorithm 1."""

    d_in: int
    d_out: int
    activation: str | None = None

    def init(self, key: jax.Array, dtype=jnp.float32) -> Params:
        return (xavier_uniform(key, self.d_in, self.d_out, dtype),
                jnp.zeros((self.d_out,), dtype))

    def apply(self, params: Params, x: jnp.ndarray, *,
              unroll: bool = False) -> jnp.ndarray:
        w, b = params
        y = _matmul(x, w) + b
        return PRIMALS[self.activation](y) if self.activation else y

    def jet_apply(self, params: Params, jet: J.Jet, *,
                  impl: str = "jnp") -> J.Jet:
        w, b = params
        return dense_jet(jet, w, b, self.activation, impl)


@dataclass(frozen=True)
class Activation(Module):
    """Pointwise activation as its own (stateless) block.  Under
    ``impl="pallas"`` a table-backed activation runs the fused Faa di Bruno
    kernel (``ops.act_jet``); anything else composes through the algebra."""

    name: str

    def apply(self, params: Params, x: jnp.ndarray, *,
              unroll: bool = False) -> jnp.ndarray:
        return PRIMALS[self.name](x)

    def jet_apply(self, params: Params, jet: J.Jet, *,
                  impl: str = "jnp") -> J.Jet:
        _check_impl(impl)
        if impl == "pallas" and _is_activation_epilogue(self.name):
            from repro.kernels import ops as kops
            return J.Jet(kops.act_jet(jet.coeffs, self.name))
        return J.activation(jet, self.name)


@dataclass(frozen=True)
class FourierFeatures(Module):
    """``gamma(x) = [sin(2pi B x), cos(2pi B x)]`` with fixed Gaussian ``B``
    (Tancik et al. 2020).  Params are the bare ``B`` array, excluded from
    gradients via stop_gradient; the jet is exact (``sin`` through Faa di
    Bruno, ``cos z = sin(z + pi/2)`` reusing the same table)."""

    d_in: int
    n_features: int
    scale: float = 1.0

    @property
    def d_out(self) -> int:
        return 2 * self.n_features

    def init(self, key: jax.Array, dtype=jnp.float32) -> Params:
        return self.scale * jax.random.normal(
            key, (self.d_in, self.n_features), dtype)

    def _freqs(self, B: jnp.ndarray) -> jnp.ndarray:
        return 2.0 * math.pi * jax.lax.stop_gradient(B)

    def apply(self, params: Params, x: jnp.ndarray, *,
              unroll: bool = False) -> jnp.ndarray:
        z = _matmul(x, self._freqs(params))
        return jnp.concatenate([jnp.sin(z), jnp.cos(z)], axis=-1)

    def jet_apply(self, params: Params, jet: J.Jet, *,
                  impl: str = "jnp") -> J.Jet:
        _check_impl(impl)
        z = J.linear(jet, self._freqs(params))
        s = J.compose(z, "sin")
        c = J.compose(J.add(z, 0.5 * math.pi), "sin")  # cos z = sin(z + pi/2)
        return J.jmap(lambda a, b: jnp.concatenate([a, b], axis=-1), s, c)


@dataclass(frozen=True)
class RMSNorm(Module):
    """Pre-norm RMS normalization over the trailing feature axis; params are
    the gain ``gamma`` (ones-init).  Smooth everywhere (rsqrt of a positive
    mean square), so the jet is exact at every order.  Under
    ``impl="pallas"`` the whole chain (mean-square convolution, rsqrt
    recurrence, gain) runs as the fused ``ops.jet_rms_norm`` kernel -- the
    ``"rms_norm"`` entry of the epilogue registry."""

    dim: int
    eps: float = 1e-6

    def init(self, key: jax.Array, dtype=jnp.float32) -> Params:
        return jnp.ones((self.dim,), dtype)

    def apply(self, params: Params, x: jnp.ndarray, *,
              unroll: bool = False) -> jnp.ndarray:
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + self.eps) * params

    def jet_apply(self, params: Params, jet: J.Jet, *,
                  impl: str = "jnp") -> J.Jet:
        _check_impl(impl)
        if impl == "pallas" and _has_epilogue("rms_norm"):
            from repro.kernels import ops as kops
            return J.Jet(kops.jet_rms_norm(jet.coeffs, params, eps=self.eps))
        return J.rms_norm(jet, params, eps=self.eps)


@dataclass(frozen=True)
class Attention(Module):
    """Multi-head scaled-dot-product attention over the token axis
    (``x``: (..., T, dim)).  Queries come from ``x``; keys and values from
    ``kv`` (cross-attention, e.g. a decoder reading an encoder's output),
    or from ``x`` itself when ``kv`` is None: self-attention is the case in
    which the key/value source is the query source (``SelfAttention``).
    ``bias=True`` gives the q/k/v and output projections biases, as
    ``torch.nn.MultiheadAttention`` has them.  Scores are a jet x jet
    Cauchy-convolved einsum, softmax goes through the exp/div power-series
    recurrences, and the value contraction is a second jet x jet einsum --
    the whole block stays inside the quasilinear jet algebra (no nested
    autodiff anywhere).

    ``mask`` opens sequence-structured workloads: ``None`` (dense),
    ``"causal"``, or ``("local", window)`` -- a causal sliding window where
    query q attends keys j with ``q - window < j <= q``.  Both paths apply
    it as a t-constant ``where`` before the softmax recurrences, so masked
    probability jets vanish identically at every order.

    Under ``impl="pallas"`` the q/k/v projections ride the Pallas dense
    dispatch and everything downstream -- Cauchy QK^T, scale, masked
    softmax, value contraction, output projection -- runs as ONE tiled
    flash-jet launch (``ops.jet_flash_attention``, the ``"flash_attention"``
    registry entry): an online-softmax recurrence over KV blocks
    generalized to the coefficient axis, so the (Tq, Tk) score jet never
    materializes.  The launch takes the query and key/value stacks apart,
    so cross-attention is the same launch (at equal token counts); the
    output bias adds to coefficient 0 after it."""

    dim: int
    n_heads: int = 2
    mask: Any = None
    bias: bool = False

    def __post_init__(self):
        if self.dim % self.n_heads:
            raise ValueError(f"dim={self.dim} not divisible by "
                             f"n_heads={self.n_heads}")
        # canonicalize (and validate) so equal masks hash equal and the
        # spec stays hashable inside the frozen dataclass
        kind, window = normalize_attention_mask(self.mask)
        canon = None if kind == "none" else \
            ("causal" if kind == "causal" else (kind, window))
        object.__setattr__(self, "mask", canon)

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def init(self, key: jax.Array, dtype=jnp.float32) -> Params:
        kq, kk, kv, ko = jax.random.split(key, 4)
        mk = lambda k: xavier_uniform(k, self.dim, self.dim, dtype)
        params = {"wq": mk(kq), "wk": mk(kk), "wv": mk(kv), "wo": mk(ko)}
        if self.bias:
            params.update({b: jnp.zeros((self.dim,), dtype)
                           for b in ("bq", "bk", "bv", "bo")})
        return params

    def _split_heads(self, c: jnp.ndarray) -> jnp.ndarray:
        return c.reshape(c.shape[:-1] + (self.n_heads, self.head_dim))

    @staticmethod
    def _project(params: Params, z: jnp.ndarray, name: str) -> jnp.ndarray:
        """The primal projection ``name`` (q, k, v or o), with its bias if
        the block has biases."""
        y = _matmul(z, params["w" + name])
        return y + params["b" + name] if "b" + name in params else y

    def apply(self, params: Params, x: jnp.ndarray, *,
              kv: jnp.ndarray | None = None,
              unroll: bool = False) -> jnp.ndarray:
        kv = x if kv is None else kv
        q, k, v = (self._split_heads(self._project(params, z, n))
                   for z, n in ((x, "q"), (kv, "k"), (kv, "v")))
        s = jnp.einsum("...qhd,...khd->...hqk", q, k,
                       precision=J.MATMUL_PRECISION) / math.sqrt(self.head_dim)
        keep = attention_mask(self.mask, x.shape[-2])
        if keep is not None:
            s = jnp.where(keep, s, jnp.asarray(J.MASK_NEG, s.dtype))
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("...hqk,...khd->...qhd", p, v,
                       precision=J.MATMUL_PRECISION)
        return self._project(params, o.reshape(o.shape[:-2] + (self.dim,)),
                             "o")

    def jet_apply(self, params: Params, jet: J.Jet, *,
                  kv: J.Jet | None = None, impl: str = "jnp") -> J.Jet:
        kv = jet if kv is None else kv
        proj = lambda j, n: J.jmap(self._split_heads, dense_jet(
            j, params["w" + n], params.get("b" + n), None, impl))
        q, k, v = proj(jet, "q"), proj(kv, "k"), proj(kv, "v")
        scale = 1.0 / math.sqrt(self.head_dim)
        if impl == "pallas" and _has_epilogue("flash_attention"):
            # single tiled launch for the whole remaining block; the head
            # axis stays inside the kernel block so the output projection
            # (which mixes heads) can fold in as the epilogue
            from repro.kernels import ops as kops
            to_heads = lambda c: jnp.moveaxis(c, -2, -3)   # (..., H, T, D)
            out = kops.jet_flash_attention(
                to_heads(q.coeffs), to_heads(k.coeffs), to_heads(v.coeffs),
                params["wo"], scale, mask=self.mask)
            if "bo" in params:
                out = out.at[0].add(params["bo"])
            return J.Jet(out)
        s = J.scale(J.einsum("...qhd,...khd->...hqk", q, k), scale)
        p = J.softmax(s, axis=-1,
                      mask=attention_mask(self.mask, jet.shape[-2]))
        o = J.einsum("...hqk,...khd->...qhd", p, v)
        o = J.jmap(lambda c: c.reshape(c.shape[:-2] + (self.dim,)), o)
        return dense_jet(o, params["wo"], params.get("bo"), None, impl)


# self-attention is attention whose key/value source is its query source
SelfAttention = Attention


@dataclass(frozen=True)
class Wave(Module):
    """PINNsFormer's wavelet activation ``w1 sin x + w2 cos x`` (Zhao, Ding
    & Prakash, ICLR 2024), with its own learned pair ``(w1, w2)``, both
    starting at 1; params are the (2,) array.  Under ``impl="pallas"`` the
    jet runs the ``wave_jet`` kernels (``repro.kernels.ops.wave_jet``, the
    ``"wave"`` ``FUSED_OP`` registry entry: one launch forward, one
    backward), counted once per dispatch at trace time under
    ``net.wave_jets``; under ``"jnp"`` it composes through the jet algebra
    (``repro.core.jet.wave``), the kernels' oracle.  Runs under the
    ``net.wave`` scope."""

    def init(self, key: jax.Array, dtype=jnp.float32) -> Params:
        return jnp.ones((2,), dtype)

    def apply(self, params: Params, x: jnp.ndarray, *,
              unroll: bool = False) -> jnp.ndarray:
        with scope("net.wave"):
            return params[0] * jnp.sin(x) + params[1] * jnp.cos(x)

    def jet_apply(self, params: Params, jet: J.Jet, *,
                  impl: str = "jnp") -> J.Jet:
        _check_impl(impl)
        with scope("net.wave"):
            if impl == "pallas" and _has_epilogue("wave"):
                from repro.kernels import ops as kops
                count("net.wave_jets", 1)
                return J.Jet(kops.wave_jet(jet.coeffs, params))
            return J.wave(jet, params[0], params[1])


@dataclass(frozen=True)
class PseudoSequence(Module):
    """PINNsFormer's pseudo-sequence: a point x (..., d_in) becomes
    ``tokens`` tokens (..., tokens, d_in), token i at x + i * step along the
    last coordinate: time in PINNsFormer's (x, t) and in ``raissi-ns``'s
    (x, y, t) (an operator with time first would shift x).  The map is
    affine in the point, so its jet seeds the same tangent on every token
    and shifts coefficient 0 only.  Runs under the ``net.seq`` scope."""

    tokens: int
    step: float

    def offsets(self, d_in: int, dtype) -> jnp.ndarray:
        """(tokens, d_in): token i's shift from the point."""
        e = jnp.zeros((d_in,), dtype).at[-1].set(self.step)
        return jnp.arange(self.tokens, dtype=dtype)[:, None] * e

    def apply(self, params: Params, x: jnp.ndarray, *,
              unroll: bool = False) -> jnp.ndarray:
        with scope("net.seq"):
            return x[..., None, :] + self.offsets(x.shape[-1], x.dtype)

    def jet_apply(self, params: Params, jet: J.Jet, *,
                  impl: str = "jnp") -> J.Jet:
        _check_impl(impl)
        with scope("net.seq"):
            c = jet.coeffs[..., None, :]
            c = jnp.broadcast_to(c, c.shape[:-2] + (self.tokens,)
                                 + c.shape[-1:])
            return J.Jet(c.at[0].add(self.offsets(jet.shape[-1], jet.dtype)))


@dataclass(frozen=True)
class MLPBlock(Module):
    """Transformer feed-forward: ``Dense(dim, hidden, act) -> Dense(hidden,
    dim)``; params are the inner :class:`Sequential`'s tuple."""

    dim: int
    hidden: int
    activation: str = "tanh"

    def _seq(self) -> "Sequential":
        return Sequential((Dense(self.dim, self.hidden, self.activation),
                           Dense(self.hidden, self.dim, None)))

    def init(self, key: jax.Array, dtype=jnp.float32) -> Params:
        return self._seq().init(key, dtype)

    def apply(self, params: Params, x: jnp.ndarray, *,
              unroll: bool = False) -> jnp.ndarray:
        return self._seq().apply(params, x, unroll=unroll)

    def jet_apply(self, params: Params, jet: J.Jet, *,
                  impl: str = "jnp") -> J.Jet:
        return self._seq().jet_apply(params, jet, impl=impl)


@dataclass(frozen=True)
class CoordinateEmbedding(Module):
    """Tokens from coordinates: input point ``x`` (..., d_in) becomes d_in
    tokens, token t = ``x_t * w[t] + b[t]`` (..., d_in, dim).  Each
    coordinate gets its own embedding row, so ``w``/``b`` double as learned
    positional encodings; the map is linear, hence jet-exact."""

    d_in: int
    dim: int

    def init(self, key: jax.Array, dtype=jnp.float32) -> Params:
        return (xavier_uniform(key, self.d_in, self.dim, dtype),
                jnp.zeros((self.d_in, self.dim), dtype))

    def apply(self, params: Params, x: jnp.ndarray, *,
              unroll: bool = False) -> jnp.ndarray:
        w, b = params
        return x[..., :, None] * w + b

    def jet_apply(self, params: Params, jet: J.Jet, *,
                  impl: str = "jnp") -> J.Jet:
        _check_impl(impl)
        w, b = params
        coeffs = jet.coeffs[..., :, None] * w
        return J.Jet(coeffs.at[0].add(b))


@dataclass(frozen=True)
class TokenPool(Module):
    """Mean over the token axis (..., T, dim) -> (..., dim); linear, so the
    jet reduces coefficient-wise."""

    axis: int = -2

    def apply(self, params: Params, x: jnp.ndarray, *,
              unroll: bool = False) -> jnp.ndarray:
        return jnp.mean(x, axis=self.axis)

    def jet_apply(self, params: Params, jet: J.Jet, *,
                  impl: str = "jnp") -> J.Jet:
        _check_impl(impl)
        return J.reduce_mean(jet, axis=self.axis)


# ---------------------------------------------------------------------------
# combinators
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Sequential(Module):
    """Compose modules left to right.  Params are a tuple with one entry per
    child; ``init`` splits the key once per child *in order*, so a graph's
    initialization is a pure function of its structure (and a Sequential of
    Dense leaves reproduces the historical MLP init bit for bit)."""

    modules: Tuple[Module, ...]

    def init(self, key: jax.Array, dtype=jnp.float32) -> Params:
        ks = jax.random.split(key, len(self.modules))
        return tuple(m.init(k, dtype) for m, k in zip(self.modules, ks))

    def apply(self, params: Params, x: jnp.ndarray, *,
              unroll: bool = False) -> jnp.ndarray:
        for m, p in zip(self.modules, params):
            x = m.apply(p, x, unroll=unroll)
        return x

    def jet_apply(self, params: Params, jet: J.Jet, *,
                  impl: str = "jnp") -> J.Jet:
        for m, p in zip(self.modules, params):
            jet = m.jet_apply(p, jet, impl=impl)
        return jet


@dataclass(frozen=True)
class Residual(Module):
    """``x + inner(x)``: params are the inner module's.  Jet addition is
    coefficient-wise, so the skip is exact at every derivative order and
    costs nothing beyond the inner block."""

    inner: Module

    def init(self, key: jax.Array, dtype=jnp.float32) -> Params:
        return self.inner.init(key, dtype)

    def apply(self, params: Params, x: jnp.ndarray, *,
              unroll: bool = False) -> jnp.ndarray:
        return x + self.inner.apply(params, x, unroll=unroll)

    def jet_apply(self, params: Params, jet: J.Jet, *,
                  impl: str = "jnp") -> J.Jet:
        return J.add(jet, self.inner.jet_apply(params, jet, impl=impl))


# ---------------------------------------------------------------------------
# leaf registry: named factories for configs / conversion tools
# ---------------------------------------------------------------------------

ModuleFactory = Callable[..., Module]

_MODULES: Dict[str, ModuleFactory] = {}


def register_module(name: str, factory: ModuleFactory) -> None:
    if name in _MODULES:
        raise ValueError(f"module {name!r} already registered")
    _MODULES[name] = factory


def module_names() -> Tuple[str, ...]:
    return tuple(sorted(_MODULES))


def make_module(name: str, **kwargs) -> Module:
    if name not in _MODULES:
        raise KeyError(f"unknown module {name!r}; known: {module_names()}")
    return _MODULES[name](**kwargs)


for _name, _factory in (
    ("dense", Dense),
    ("activation", Activation),
    ("fourier_features", FourierFeatures),
    ("rms_norm", RMSNorm),
    ("self_attention", SelfAttention),
    ("wave", Wave),
    ("pseudo_sequence", PseudoSequence),
    ("mlp_block", MLPBlock),
    ("coordinate_embedding", CoordinateEmbedding),
    ("token_pool", TokenPool),
    ("sequential", Sequential),
    ("residual", Residual),
):
    register_module(_name, _factory)
