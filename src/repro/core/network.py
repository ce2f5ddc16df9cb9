"""Jet-traceable network architectures for the derivative engines.

The paper states n-TangentProp for uniform-width dense MLPs, but the jet
algebra (core/jet.py) is architecture-agnostic: anything built from linear
maps, Cauchy products, and registered smooth activations pushes a truncated
Taylor jet forward in the same O(n p(n) M).  This module makes that a
first-class abstraction: a :class:`Network` is an object with

* ``init(key, dtype)``            -- parameter pytree construction;
* ``apply(params, x, unroll=)``   -- plain forward (N, d_in) -> (N, d_out).
  ``unroll=True`` must avoid ``lax.scan`` so ``jax.experimental.jet`` (no
  scan rule) can trace it -- the :class:`~repro.core.engines.JaxJetEngine`
  oracle depends on this;
* ``jet_apply(params, jet, impl=)`` -- push a :class:`repro.core.jet.Jet`
  of the inputs through the network.  ``impl="jnp"`` runs the reference jet
  algebra; ``impl="pallas"`` routes every dense layer through the fused
  Pallas kernel dispatch (kernels/ops.jet_dense); an activation without a
  kernel table composes through the jet algebra after the kernel's linear
  part.

Every shipped network is a **thin composition over the jet-module layer**
(:mod:`repro.core.modules`): it declares a module graph (``Sequential`` /
``Residual`` over registered leaves) and adapts its public parameter pytree
onto that graph, so no architecture hand-writes jet plumbing -- the leaves
own the jet rules, the networks own only structure and the (stable) param
layout.

=================  ==========================================================
DenseMLP           the paper's uniform-width MLP, over the stacked
                   :class:`repro.core.ntp.MLPParams`
MLP                variable per-layer widths
ResidualMLP        pre-activation skip connections ``h <- h + act(W h + b)``
FourierFeatureMLP  random-feature embedding ``[sin 2pi Bx, cos 2pi Bx]`` in
                   front of an MLP trunk (the standard PINN spectral-bias
                   fix; B is fixed, not trained)
Transformer        pre-norm self-attention trunk over coordinate tokens
                   (the first non-MLP PINN architecture; softmax/einsum/
                   rms_norm all inside the quasilinear jet algebra)
PINNsFormer        encoder-decoder attention over a pseudo-sequence of
                   time-shifted points, wavelet activations; its output
                   keeps the token axis, (N, tokens, d_out)
=================  ==========================================================

New architectures compose modules the same way (or register a factory with
:func:`register_network`) and every :class:`DerivativeEngine`, the operator
subsystem, ``pinn_loss``, and ``train_operator`` consume them without
further plumbing.  ``d_out`` is unconstrained: a d_out > 1 network solves a
vector-valued PDE system (one shared trunk, one output column per unknown
field), and the engines carry the component axis through every derivative.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp

from . import jet as J
from repro.runtime.metrics import scope

from .activations import PRIMALS
from .modules import (Attention, CoordinateEmbedding, Dense, FourierFeatures,
                      MLPBlock, Module, PseudoSequence, Residual, RMSNorm,
                      SelfAttention, Sequential, TokenPool, Wave,
                      xavier_uniform)
from .ntp import MLPParams, init_mlp

Params = Any  # parameter pytree; its structure is owned by the network


@runtime_checkable
class Network(Protocol):
    """Anything the derivative engines can differentiate."""

    d_in: int
    d_out: int
    activation: str

    def init(self, key: jax.Array, dtype=jnp.float32) -> Params: ...

    def apply(self, params: Params, x: jnp.ndarray, *,
              unroll: bool = False) -> jnp.ndarray: ...

    def jet_apply(self, params: Params, jet: J.Jet, *,
                  impl: str = "jnp") -> J.Jet: ...


class _Composed:
    """Mixin: a network that IS a module graph.

    Subclasses provide ``_graph()`` (the module composition) and, when the
    public parameter pytree is not already the graph's tuple layout,
    ``_graph_params(params)`` to adapt it (a free re-view, never a copy).
    ``apply``/``jet_apply`` then delegate to the graph, so the network never
    hand-writes jet plumbing.
    """

    def _graph(self) -> Module:
        raise NotImplementedError

    def _graph_params(self, params: Params) -> Params:
        return params

    def apply(self, params: Params, x: jnp.ndarray, *,
              unroll: bool = False) -> jnp.ndarray:
        return self._graph().apply(self._graph_params(params), x,
                                   unroll=unroll)

    def jet_apply(self, params: Params, jet: J.Jet, *,
                  impl: str = "jnp") -> J.Jet:
        return self._graph().jet_apply(self._graph_params(params), jet,
                                       impl=impl)


# ---------------------------------------------------------------------------
# DenseMLP: the paper's architecture, over the stacked MLPParams pytree
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DenseMLP(_Composed):
    """Uniform-width MLP; params are the stacked :class:`MLPParams`
    NamedTuple, adapted onto a Sequential of Dense leaves at call time for
    the jet forward."""

    d_in: int
    width: int
    depth: int
    d_out: int
    activation: str = "tanh"

    def init(self, key: jax.Array, dtype=jnp.float32) -> MLPParams:
        return init_mlp(key, self.d_in, self.width, self.depth, self.d_out,
                        dtype=dtype)

    def _graph(self) -> Module:
        hidden = tuple(Dense(self.width, self.width, self.activation)
                       for _ in range(self.depth - 1))
        return Sequential((Dense(self.d_in, self.width, self.activation),
                           *hidden, Dense(self.width, self.d_out, None)))

    def _graph_params(self, p: MLPParams) -> Params:
        hidden = tuple((p.w_hidden[i], p.b_hidden[i])
                       for i in range(p.w_hidden.shape[0]))
        return ((p.w_in, p.b_in), *hidden, (p.w_out, p.b_out))

    def apply(self, params: MLPParams, x: jnp.ndarray, *,
              unroll: bool = False) -> jnp.ndarray:
        # the stacked pytree admits a lax.scan over hidden layers, keeping
        # the primal forward's compile time O(1) in depth; unroll=True (for
        # jax.experimental.jet, which has no scan rule) python-unrolls
        act = PRIMALS[self.activation]

        def mm(a, w):
            return jnp.matmul(a, w, precision=J.MATMUL_PRECISION)

        h = act(mm(x, params.w_in) + params.b_in)

        if unroll:
            for i in range(params.w_hidden.shape[0]):
                h = act(mm(h, params.w_hidden[i]) + params.b_hidden[i])
            return mm(h, params.w_out) + params.b_out

        def body(h, wb):
            w, b = wb
            return act(mm(h, w) + b), None

        if params.w_hidden.shape[0]:
            h, _ = jax.lax.scan(body, h, (params.w_hidden, params.b_hidden))
        return mm(h, params.w_out) + params.b_out


# ---------------------------------------------------------------------------
# MLP: variable per-layer widths
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MLP(_Composed):
    """Fully-connected net with arbitrary layer widths.

    ``widths = (d_in, h_1, ..., h_L, d_out)``; params ARE the module
    graph's: a tuple of (w, b) pairs, one per Dense leaf.  Hidden layers are
    activated, the last is linear.
    """

    widths: Tuple[int, ...]
    activation: str = "tanh"

    def __post_init__(self):
        if len(self.widths) < 2:
            raise ValueError("MLP needs at least (d_in, d_out) widths")

    @property
    def d_in(self) -> int:
        return self.widths[0]

    @property
    def d_out(self) -> int:
        return self.widths[-1]

    def _graph(self) -> Module:
        last = len(self.widths) - 2
        return Sequential(tuple(
            Dense(fi, fo, self.activation if i < last else None)
            for i, (fi, fo) in enumerate(zip(self.widths[:-1],
                                             self.widths[1:]))))

    def init(self, key: jax.Array, dtype=jnp.float32) -> Params:
        return self._graph().init(key, dtype)


# ---------------------------------------------------------------------------
# ResidualMLP: skip connections (jet addition is exact)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ResidualMLP(_Composed):
    """``h_0 = act(W_in x + b_in)``; ``h_j = h_{j-1} + act(W_j h_{j-1} + b_j)``
    for ``depth`` blocks; linear readout.  The graph is Dense ->
    Residual(Dense) x depth -> Dense; residual adds are coefficient-wise on
    the jet, so the derivative cost matches the plain MLP layer-for-layer.
    """

    d_in: int
    width: int
    depth: int
    d_out: int
    activation: str = "tanh"

    def init(self, key: jax.Array, dtype=jnp.float32) -> Params:
        ks = jax.random.split(key, self.depth + 2)
        return {
            "w_in": xavier_uniform(ks[0], self.d_in, self.width, dtype),
            "b_in": jnp.zeros((self.width,), dtype),
            "blocks": tuple(
                (xavier_uniform(ks[1 + j], self.width, self.width, dtype),
                 jnp.zeros((self.width,), dtype)) for j in range(self.depth)),
            "w_out": xavier_uniform(ks[-1], self.width, self.d_out, dtype),
            "b_out": jnp.zeros((self.d_out,), dtype),
        }

    def _graph(self) -> Module:
        blocks = tuple(
            Residual(Dense(self.width, self.width, self.activation))
            for _ in range(self.depth))
        return Sequential((Dense(self.d_in, self.width, self.activation),
                           *blocks, Dense(self.width, self.d_out, None)))

    def _graph_params(self, p: Params) -> Params:
        return ((p["w_in"], p["b_in"]), *p["blocks"],
                (p["w_out"], p["b_out"]))


# ---------------------------------------------------------------------------
# FourierFeatureMLP: random-feature embedding against spectral bias
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FourierFeatureMLP(_Composed):
    """``gamma(x) = [sin(2pi B x), cos(2pi B x)]`` with fixed Gaussian
    ``B ~ N(0, scale^2)`` of shape (d_in, n_features), then an MLP trunk on
    the 2*n_features embedding (Tancik et al. 2020; the standard PINN cure
    for spectral bias).  The graph is FourierFeatures -> Dense stack; B is
    excluded from gradients (stop_gradient) and the embedding jet is exact.
    """

    d_in: int
    width: int
    depth: int
    d_out: int
    n_features: int = 16
    feature_scale: float = 1.0
    activation: str = "tanh"

    def _trunk(self) -> MLP:
        widths = (2 * self.n_features,) + (self.width,) * self.depth \
            + (self.d_out,)
        return MLP(widths, self.activation)

    def init(self, key: jax.Array, dtype=jnp.float32) -> Params:
        kb, km = jax.random.split(key)
        B = FourierFeatures(self.d_in, self.n_features,
                            self.feature_scale).init(kb, dtype)
        return {"B": B, "mlp": self._trunk().init(km, dtype)}

    def _graph(self) -> Module:
        embed = FourierFeatures(self.d_in, self.n_features,
                                self.feature_scale)
        return Sequential((embed, *self._trunk()._graph().modules))

    def _graph_params(self, p: Params) -> Params:
        return (p["B"], *p["mlp"])


# ---------------------------------------------------------------------------
# Transformer: pre-norm self-attention trunk over coordinate tokens
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Transformer(_Composed):
    """Attention PINN trunk: each input coordinate becomes a token
    (:class:`CoordinateEmbedding`, whose per-coordinate rows double as
    learned positional encodings), ``depth`` pre-norm blocks of
    ``Residual(RMSNorm -> SelfAttention)`` then ``Residual(RMSNorm ->
    MLPBlock)`` mix the tokens, and a final RMSNorm -> mean token pool ->
    linear head reads out ``d_out`` components.

    Everything is smooth and jet-traceable: attention scores and value
    mixing are jet x jet Cauchy-convolved einsums, softmax runs on the
    exp/div power-series recurrences, RMSNorm on the rsqrt recurrence -- so
    the whole trunk keeps the paper's O(n p(n) M) derivative cost, versus
    O(M^n) for nested autodiff through attention.  Params are the module
    graph's native tuple (this is the first network with no legacy pytree
    to preserve).
    """

    d_in: int
    width: int               # token embedding dim (d_model)
    depth: int               # number of attention + MLP block pairs
    d_out: int
    n_heads: int = 2
    mlp_ratio: int = 2       # feed-forward hidden dim = mlp_ratio * width
    activation: str = "tanh"
    mask: Any = None         # None | "causal" | ("local", window)

    def __post_init__(self):
        if self.width % self.n_heads:
            raise ValueError(f"width={self.width} not divisible by "
                             f"n_heads={self.n_heads}")
        # validate + canonicalize once here (SelfAttention would anyway):
        # configs pass lists, the dataclass must stay hashable
        probe = SelfAttention(self.width, self.n_heads, self.mask)
        object.__setattr__(self, "mask", probe.mask)

    def _graph(self) -> Module:
        mods = [CoordinateEmbedding(self.d_in, self.width)]
        for _ in range(self.depth):
            mods.append(Residual(Sequential((
                RMSNorm(self.width),
                SelfAttention(self.width, self.n_heads, self.mask)))))
            mods.append(Residual(Sequential((
                RMSNorm(self.width),
                MLPBlock(self.width, self.mlp_ratio * self.width,
                         self.activation)))))
        mods += [RMSNorm(self.width), TokenPool(),
                 Dense(self.width, self.d_out, None)]
        return Sequential(tuple(mods))

    def init(self, key: jax.Array, dtype=jnp.float32) -> Params:
        return self._graph().init(key, dtype)


# ---------------------------------------------------------------------------
# PINNsFormer: encoder-decoder attention over a pseudo-sequence of points
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PINNsFormer:
    """PINNsFormer (Zhao, Ding & Prakash, ICLR 2024, arXiv 2307.11833): a
    point becomes a pseudo-sequence of ``tokens`` points, (x, t + i step)
    for i < tokens (:class:`PseudoSequence`), embedded by ``Dense(d_in,
    width)``.  ``depth`` encoder layers, each ``x + MHA(W(x), W(x), W(x))``
    then ``x + FF(W(x))``, and a final ``W`` make the encoder output e;
    ``depth`` decoder layers over the embedding, each ``x + MHA(W(x), e,
    e)`` then ``x + FF(W(x))``, and a final ``W``; then a per-token head
    ``Dense(width, head) W Dense(head, head) W Dense(head, d_out)``.  MHA
    has biases (:class:`Attention` with ``bias=True``), FF is ``Dense(width,
    ff) W Dense(ff, ff) W Dense(ff, width)``, and every ``W`` is a
    :class:`Wave` with its own learned pair.

    The output carries the token axis, (..., tokens, d_out): token i is the
    solution at the point's i-th pseudo-sequence point, and its jet is the
    derivative with respect to the point (all tokens move with it).  The
    engines fold the token axis into the point axis
    (:func:`token_points` gives the points the rows belong to).  The decoder
    reads two streams, so the graph is written here rather than as a
    ``Sequential``; attention runs under ``net.self_attn`` and
    ``net.cross_attn``."""

    d_in: int
    width: int               # d_model
    depth: int               # encoder layers, and as many decoder layers
    d_out: int
    n_heads: int = 2
    ff: int = 256
    head: int = 512
    tokens: int = 5
    step: float = 1e-4
    activation: str = "wave"

    def __post_init__(self):
        if self.activation != "wave":
            raise ValueError(f"pinnsformer's activation is the learned "
                             f"wavelet 'wave', not {self.activation!r}")
        if self.width % self.n_heads:
            raise ValueError(f"width={self.width} not divisible by "
                             f"n_heads={self.n_heads}")

    # -- the graph's parts --------------------------------------------------
    def _seq(self) -> PseudoSequence:
        return PseudoSequence(self.tokens, self.step)

    def _attn(self) -> Attention:
        return Attention(self.width, self.n_heads, bias=True)

    def _ff(self) -> Sequential:
        return Sequential((Dense(self.width, self.ff), Wave(),
                           Dense(self.ff, self.ff), Wave(),
                           Dense(self.ff, self.width)))

    def _head(self) -> Sequential:
        return Sequential((Dense(self.width, self.head), Wave(),
                           Dense(self.head, self.head), Wave(),
                           Dense(self.head, self.d_out)))

    def _layer_init(self, key: jax.Array, dtype) -> Params:
        ka, kf = jax.random.split(key)
        return {"wave_attn": Wave().init(key, dtype),
                "attn": self._attn().init(ka, dtype),
                "wave_ff": Wave().init(key, dtype),
                "ff": self._ff().init(kf, dtype)}

    def init(self, key: jax.Array, dtype=jnp.float32) -> Params:
        ke, kh, *kl = jax.random.split(key, 2 + 2 * self.depth)
        return {
            "embed": Dense(self.d_in, self.width).init(ke, dtype),
            "encoder": tuple(self._layer_init(k, dtype)
                             for k in kl[:self.depth]),
            "encoder_wave": Wave().init(ke, dtype),
            "decoder": tuple(self._layer_init(k, dtype)
                             for k in kl[self.depth:]),
            "decoder_wave": Wave().init(ke, dtype),
            "head": self._head().init(kh, dtype),
        }

    def _forward(self, params: Params, x, run: Callable, add: Callable):
        """The graph once for both passes: ``run(module, params, x, **kw)``
        is a module's ``apply`` or its ``jet_apply``, ``add`` the matching
        sum."""
        def layer(p, h, kv):
            name = "net.self_attn" if kv is None else "net.cross_attn"
            a = run(Wave(), p["wave_attn"], h)
            with scope(name):
                h = add(h, run(self._attn(), p["attn"], a,
                               kv=a if kv is None else kv))
            return add(h, run(self._ff(), p["ff"],
                              run(Wave(), p["wave_ff"], h)))

        src = run(Dense(self.d_in, self.width), params["embed"],
                  run(self._seq(), (), x))
        e = src
        for p in params["encoder"]:
            e = layer(p, e, None)
        e = run(Wave(), params["encoder_wave"], e)
        d = src
        for p in params["decoder"]:
            d = layer(p, d, e)
        d = run(Wave(), params["decoder_wave"], d)
        return run(self._head(), params["head"], d)

    def apply(self, params: Params, x: jnp.ndarray, *,
              unroll: bool = False) -> jnp.ndarray:
        """(N, d_in) -> (N, tokens, d_out)."""
        return self._forward(params, x,
                             lambda m, p, h, **kw: m.apply(p, h, **kw),
                             jnp.add)

    def jet_apply(self, params: Params, jet: J.Jet, *,
                  impl: str = "jnp") -> J.Jet:
        return self._forward(
            params, jet,
            lambda m, p, h, **kw: m.jet_apply(p, h, impl=impl, **kw), J.add)

    def token_points(self, x: jnp.ndarray) -> jnp.ndarray:
        """(N * tokens, d_in): the point of every output token, token-minor,
        in the order the folded output rows take."""
        return self._seq().apply((), x).reshape(-1, self.d_in)


def token_points(net: Network, x: jnp.ndarray) -> jnp.ndarray:
    """The points a network's outputs belong to, one row per output row
    once any token axis is folded into the point axis: ``x`` itself for a
    network with no token axis, else ``net.token_points(x)``."""
    points = getattr(net, "token_points", None)
    return x if points is None else points(x)


# ---------------------------------------------------------------------------
# registry: named factories for configs / CLIs
# ---------------------------------------------------------------------------

NetworkFactory = Callable[..., Network]

_NETWORKS: Dict[str, NetworkFactory] = {}


def register_network(name: str, factory: NetworkFactory) -> None:
    if name in _NETWORKS:
        raise ValueError(f"network {name!r} already registered")
    _NETWORKS[name] = factory


def network_names() -> Tuple[str, ...]:
    return tuple(sorted(_NETWORKS))


def make_network(kind: str, *, d_in: int, d_out: int, width: int, depth: int,
                 activation: str = "tanh", **kwargs) -> Network:
    """Build a registered network from the uniform (width, depth) vocabulary
    used by configs and CLIs; extra kwargs go to the factory."""
    if kind not in _NETWORKS:
        raise KeyError(f"unknown network {kind!r}; known: {network_names()}")
    return _NETWORKS[kind](d_in=d_in, d_out=d_out, width=width, depth=depth,
                           activation=activation, **kwargs)


register_network("dense", DenseMLP)
register_network("mlp", lambda *, d_in, d_out, width, depth, activation="tanh",
                 **kw: MLP((d_in,) + (width,) * depth + (d_out,), activation))
register_network("residual", ResidualMLP)
register_network("fourier", FourierFeatureMLP)
register_network("transformer", Transformer)
register_network("pinnsformer", PINNsFormer)
