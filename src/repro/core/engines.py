"""Derivative engines: one uniform surface over every way this repo computes
higher-order input derivatives of a network.

An engine answers three questions about any :class:`repro.core.network.Network`:

* ``derivs(net, params, x, order, tangent=None)`` -- raw directional
  derivatives ``d^k/dt^k f(x + t v)`` at t=0, stacked (order+1, N, d_out);
* ``grid(net, params, x, order)`` -- pure derivatives along every coordinate
  axis, (d_in, order+1, N, d_out), with the direction axis folded into the
  batch so the whole grid is ONE forward (a single Pallas launch per layer);
* ``cross(net, params, x, axes)`` -- the mixed partial
  ``d^m f / dx_{a_1}..dx_{a_m}``, (N, d_out), by polarization of
  directional derivatives (never a nested-autodiff graph).

``grid`` and ``cross`` are engine-generic: they are assembled from ``derivs``
here in the base class, so a new engine implements one method and inherits
the whole surface.  Both ask :meth:`DerivativeEngine.directional` for jets
along static integer directions.  A :class:`PolarizationPlan` says which:
the 2^m sign patterns of a mixed partial come in pairs that give the same
term, and patterns that give a zero, repeated or scaled direction collapse
onto one primitive direction.  :func:`table_engine` runs the directions of
a whole derivative table (pure and mixed) as ONE jet forward and answers
every ``grid`` and ``cross`` of that table from it.  Shipped engines:

=====================  =====================================================
``NTPEngine(impl)``    the paper's quasilinear jet forward (Algorithm 1);
                       ``impl="jnp"`` reference or ``impl="pallas"`` fused
                       kernels -- O(n p(n) M) time, O(n M) memory
``AutodiffEngine()``   nested autodiff towers, the O(M^n) baseline the paper
                       benchmarks against (reverse-mode for scalar outputs,
                       forward-over-forward for vector outputs)
``JaxJetEngine()``     ``jax.experimental.jet`` -- JAX's independent
                       Taylor-mode implementation, used as a correctness
                       oracle for ours
=====================  =====================================================

Configs address engines by spec string: ``Engine.from_spec("ntp/pallas")``,
``"ntp"``, ``"autodiff"``, ``"jet"``; instances pass through unchanged.
(The pre-redesign ``(engine="ntp", impl="pallas")`` keyword-pair shim was
removed after its scheduled one-release deprecation window.)

Spec strings have a typed, canonical identity: :class:`EngineSpec` parses
any accepted spelling (``"ntp"`` == ``"ntp/jnp"``, ``"jet"`` ==
``"jax-jet"`` == ``"jaxjet"``) to one frozen value whose ``str()`` is the
canonical form.  Everything keyed on an engine spec -- the serving layer's
``ExecutableKey.engine_spec``, benchmark row names -- goes through it, so
equivalent spellings share one compiled-executable cache entry and one
baseline row.

Every returned array carries a trailing component axis sized ``net.d_out``:
``derivs`` is (order+1, N, d_out), ``grid`` (d_in, order+1, N, d_out) and
``cross`` (N, d_out), for scalar fields and vector-valued PDE systems alike.
"""

from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.runtime.metrics import count, scope

from . import jet as J
from .network import Network

Direction = Tuple[int, ...]


def axis_directions(d_in: int) -> Tuple[Direction, ...]:
    """The coordinate axes e_0 .. e_{d_in-1}: the grid's directions."""
    return tuple(tuple(int(i == a) for i in range(d_in))
                 for a in range(d_in))


def _primitive(v: Sequence[int]) -> Tuple[Direction, int]:
    """``v == c * p`` with ``p`` integer, its entries coprime and its first
    nonzero entry positive: (p, c)."""
    c = math.gcd(*v)
    if next(a for a in v if a) < 0:
        c = -c
    return tuple(a // c for a in v), c


@dataclass(frozen=True)
class PolarizationPlan:
    """The directional jets a set of mixed partials needs, and the static
    weights that assemble each partial from them.

    The polarization identity

        D_{v_1..v_m} f = 1/(2^m m!) sum_{eps in {+-1}^m}
                         (prod_k eps_k) D^m_{sum_k eps_k v_k} f

    with ``v_k = e_{axes[k]}`` sums 2^m order-m directional derivatives.
    Patterns eps and -eps give the same term (along -v the order-m
    derivative is (-1)^m times the one along v, and so is the weight), so
    only eps_1 = +1 is run, at twice the weight.  A zero direction adds
    nothing; along c * p the order-m derivative is c^m times the one along
    p, so every direction is reduced to its primitive ``p`` and the terms
    on one ``p`` are merged into one integer weight.

    ``directions`` are the primitive directions, each once; ``terms[t]``
    lists ``(index into directions, integer weight)`` of the t-th partial,
    whose value is the weighted sum of the order-``orders[t]`` derivatives
    over ``2^m m!``.
    """

    directions: Tuple[Direction, ...]
    terms: Tuple[Tuple[Tuple[int, int], ...], ...]
    orders: Tuple[int, ...]

    @staticmethod
    def build(d_in: int, mixed: Sequence[Sequence[int]],
              axes: bool = False) -> "PolarizationPlan":
        """The plan of the partials ``mixed`` (axis tuples) over ``d_in``
        inputs; with ``axes`` the coordinate axes come first, in grid
        order, so the plan serves a whole derivative table.  The other
        directions follow in the order the terms first need them."""
        index: Dict[Direction, int] = {}
        if axes:
            index.update((e, a) for a, e in enumerate(axis_directions(d_in)))
        terms = []
        for term in mixed:
            m = len(term)
            weights: Dict[Direction, int] = {}
            for tail in itertools.product((1, -1), repeat=m - 1):
                eps = (1,) + tail
                v = [0] * d_in
                for e, a in zip(eps, term):
                    v[a] += e
                if not any(v):
                    continue
                p, c = _primitive(v)
                weights[p] = weights.get(p, 0) + 2 * math.prod(eps) * c ** m
            terms.append(tuple((index.setdefault(p, len(index)), w)
                               for p, w in weights.items()))
        return PolarizationPlan(tuple(index), tuple(terms),
                                tuple(len(t) for t in mixed))

    def order(self, pure_order: int) -> int:
        """The jet order that serves every term (and pure derivatives up
        to ``pure_order``)."""
        return max((pure_order, *self.orders))

    def assemble(self, jets: jnp.ndarray, t: int) -> jnp.ndarray:
        """The t-th partial, (N, d_out), from ``jets`` (n_dirs, >= m+1, N,
        d_out) along ``directions``.  The weights are static: the terms are
        added in a fixed order (not a matmul), so every launch -- one device
        or a mesh -- sums the same way."""
        m = self.orders[t]
        top = None
        for i, w in self.terms[t]:
            term = w * jets[i, m]
            top = term if top is None else top + term
        return top / (2.0 ** m * math.factorial(m))


# accepted alternate spellings -> canonical engine name
_SPEC_ALIASES = {"jax-jet": "jet", "jaxjet": "jet"}

# engine name -> implementation variants (None = no /impl suffix allowed)
_ENGINE_IMPLS = {"ntp": ("jnp", "pallas"), "autodiff": None, "jet": None}


@dataclass(frozen=True)
class EngineSpec:
    """Typed, canonical identity of an engine configuration.

    ``parse`` accepts every spelling ``from_spec`` does -- a spec string
    (``"ntp"``, ``"ntp/jnp"``, ``"ntp/pallas"``, ``"autodiff"``, ``"jet"``
    and its ``"jax-jet"``/``"jaxjet"`` aliases), an :class:`EngineSpec`, or
    a :class:`DerivativeEngine` instance -- and canonicalizes: ``"ntp"``
    and ``"ntp/jnp"`` are the SAME value (``impl`` is stored as ``"jnp"``,
    ``str()`` renders the short form).  ``str(EngineSpec.parse(s))`` is the
    canonical string every spec-keyed surface must use: the serving cache
    key (one compiled executable per distinct engine, not per spelling) and
    benchmark row names (one baseline row).  Round-trip law:
    ``EngineSpec.parse(str(spec)) == spec``.
    """

    name: str
    impl: str | None = None

    def __post_init__(self):
        impls = _ENGINE_IMPLS.get(self.name)
        if self.name not in _ENGINE_IMPLS:
            raise ValueError(f"unknown engine {self.name!r}; want one of "
                             f"{sorted(_ENGINE_IMPLS)}")
        if impls is None:
            if self.impl is not None:
                raise ValueError(f"engine {self.name!r} takes no /impl "
                                 f"suffix, got {self.impl!r}")
        else:
            impl = self.impl if self.impl is not None else impls[0]
            if impl not in impls:
                raise ValueError(f"unknown impl {impl!r} for engine "
                                 f"{self.name!r} (want one of {impls})")
            object.__setattr__(self, "impl", impl)

    @staticmethod
    def parse(spec: "str | EngineSpec | DerivativeEngine") -> "EngineSpec":
        if isinstance(spec, EngineSpec):
            return spec
        if isinstance(spec, DerivativeEngine):
            return EngineSpec.parse(spec.spec)
        name, _, impl = str(spec).strip().lower().partition("/")
        name = _SPEC_ALIASES.get(name, name)
        try:
            return EngineSpec(name, impl or None)
        except ValueError as e:
            raise ValueError(f"bad engine spec {spec!r}: {e}") from None

    def __str__(self) -> str:
        default = (_ENGINE_IMPLS.get(self.name) or (None,))[0]
        if self.impl is None or self.impl == default:
            return self.name
        return f"{self.name}/{self.impl}"

    def build(self) -> "DerivativeEngine":
        """Instantiate the engine this spec names."""
        if self.name == "ntp":
            return NTPEngine(self.impl)
        if self.name == "autodiff":
            return AutodiffEngine()
        return JaxJetEngine()


class DerivativeEngine:
    """Base class: implement ``derivs``, inherit ``grid``/``cross``."""

    def derivs(self, net: Network, params, x: jnp.ndarray, order: int,
               tangent: jnp.ndarray | None = None) -> jnp.ndarray:
        """Raw directional derivatives (order+1, N, d_out) along ``tangent``
        (defaults to ones, the seed convention for 1-D PINNs)."""
        raise NotImplementedError

    @property
    def spec(self) -> str:
        """The string this engine round-trips through :meth:`from_spec`."""
        raise NotImplementedError

    def _batched_directional(self, net: Network, params, x: jnp.ndarray,
                             dirs: jnp.ndarray, order: int) -> jnp.ndarray:
        """(n_dirs, order+1, N, d_out): derivatives along each row of ``dirs``,
        with the direction axis folded into the batch -- one large forward
        instead of a vmap over per-direction passes.  For a network whose
        output has a token axis, N counts the rows of
        :func:`repro.core.network.token_points`."""
        n_dirs, batch = dirs.shape[0], x.shape[0]
        with scope("ntp.fold"):
            xt = jnp.tile(x, (n_dirs, 1))
            vt = jnp.repeat(dirs, batch, axis=0)
        d = self.derivs(net, params, xt, order, vt)
        with scope("ntp.fold"):
            # a token axis ahead of d_out folds into the point axis
            return jnp.moveaxis(d.reshape((order + 1, n_dirs, -1,
                                           d.shape[-1])), 1, 0)

    def directional(self, net: Network, params, x: jnp.ndarray,
                    dirs: Sequence[Direction], order: int) -> jnp.ndarray:
        """(len(dirs), order+1, N, d_out): derivatives along each static
        integer direction in ``dirs``, in one folded forward."""
        return self._batched_directional(net, params, x,
                                         jnp.asarray(dirs, x.dtype), order)

    def grid(self, net: Network, params, x: jnp.ndarray,
             order: int) -> jnp.ndarray:
        """Pure derivatives along every coordinate axis:
        (d_in, order+1, N, d_out)."""
        with scope("ntp.grid"):
            return self.directional(net, params, x,
                                    axis_directions(x.shape[-1]), order)

    def cross(self, net: Network, params, x: jnp.ndarray,
              axes: Sequence[int]) -> jnp.ndarray:
        """Mixed partial ``d^m f / dx_{axes[0]} ... dx_{axes[m-1]}``, (N, d_out),
        via the polarization identity (:class:`PolarizationPlan`) over the
        distinct primitive directions of ``sum_k eps_k e_{axes[k]}``: at
        most 2^(m-1) of them.  Repeated axes are allowed (``axes=(0, 0,
        1)`` gives u_xxy)."""
        m, d = len(axes), x.shape[-1]
        if m == 0:
            raise ValueError("axes must name at least one differentiation axis")
        if any(a < 0 or a >= d for a in axes):
            raise ValueError(f"axes {tuple(axes)} out of range for d_in={d}")
        plan = PolarizationPlan.build(d, (tuple(axes),))
        with scope("ntp.cross"):
            jets = self.directional(net, params, x, plan.directions, m)
            with scope("ntp.polarize"):
                return plan.assemble(jets, 0)

    # -- spec parsing -------------------------------------------------------

    @staticmethod
    def from_spec(spec: "str | DerivativeEngine") -> "DerivativeEngine":
        """``"ntp"`` | ``"ntp/pallas"`` | ``"autodiff"`` | ``"jet"`` -> engine.
        Engine instances pass through unchanged; every string spelling goes
        through :meth:`EngineSpec.parse`, so aliases and the ``"ntp"`` ==
        ``"ntp/jnp"`` equivalence are handled in one place."""
        if isinstance(spec, DerivativeEngine):
            return spec
        return EngineSpec.parse(spec).build()

    def __repr__(self) -> str:
        return f"{type(self).__name__}({self.spec!r})"


class _TableJets(DerivativeEngine):
    """An engine whose every ``directional`` request is answered from jets
    already run: :func:`table_engine`'s view of a table's one forward.  A
    direction the pass lacks is an error, never a second forward."""

    def __init__(self, inner: DerivativeEngine,
                 directions: Sequence[Direction], jets: jnp.ndarray):
        self.inner = inner
        self.index = {p: i for i, p in enumerate(directions)}
        self.jets = jets                       # (n_dirs, order+1, N, d_out)

    @property
    def spec(self) -> str:
        return self.inner.spec

    def directional(self, net: Network, params, x: jnp.ndarray,
                    dirs: Sequence[Direction], order: int) -> jnp.ndarray:
        missing = [p for p in dirs if p not in self.index]
        if missing:
            raise KeyError(f"directions {missing} are not in this table's "
                           f"pass (have {tuple(self.index)})")
        return jnp.stack([self.jets[self.index[p], :order + 1]
                          for p in dirs])


def table_engine(engine: DerivativeEngine, net: Network, params,
                 x: jnp.ndarray, order: int,
                 mixed: Sequence[Sequence[int]]) -> DerivativeEngine:
    """The engine a derivative table asks: pure derivatives up to ``order``
    and the mixed partials ``mixed`` (axis tuples).  With mixed partials,
    the coordinate axes and every direction of their
    :class:`PolarizationPlan` run as ONE jet forward of ``engine`` under
    ``ntp.grid``, at the order that serves them all (lower orders of a jet
    do not depend on the higher ones), and the engine returned answers
    ``grid`` and ``cross`` from that forward's rows.  Without, it is
    ``engine`` itself.

    Counts, at trace time, the jet rows per point the table runs
    (``ntp.rows``) and the rows of a plain 2^m-direction polarization of
    each partial beside the grid (``ntp.rows_polarized``); for a network
    whose output has a token axis, also the token rows per point its
    forward carries (``net.token_rows``: rows x tokens)."""
    d_in = x.shape[-1]
    plan = PolarizationPlan.build(d_in, mixed, axes=True)
    rows = len(plan.directions) * (plan.order(order) + 1)
    count("ntp.rows", rows)
    if hasattr(net, "token_points"):
        points = jax.eval_shape(net.token_points, x).shape[0]
        count("net.token_rows", rows * points // x.shape[0])
    count("ntp.rows_polarized", d_in * (order + 1)
          + sum(2 ** len(a) * (len(a) + 1) for a in mixed))
    if not mixed:
        return engine
    with scope("ntp.grid"):
        jets = engine.directional(net, params, x, plan.directions,
                                  plan.order(order))
    return _TableJets(engine, plan.directions, jets)


# ---------------------------------------------------------------------------
# n-TangentProp: the paper's algorithm through Network.jet_apply
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class NTPEngine(DerivativeEngine):
    """Quasilinear Taylor-jet forward (paper Algorithm 1, generalized to any
    jet-traceable network)."""

    impl: str = "jnp"

    def __post_init__(self):
        if self.impl not in ("jnp", "pallas"):
            raise ValueError(f"unknown impl {self.impl!r} "
                             "(want 'jnp' or 'pallas')")

    @property
    def spec(self) -> str:
        return "ntp" if self.impl == "jnp" else f"ntp/{self.impl}"

    def derivs(self, net: Network, params, x: jnp.ndarray, order: int,
               tangent: jnp.ndarray | None = None) -> jnp.ndarray:
        if order == 0:
            return net.apply(params, x)[None]
        jet = net.jet_apply(params, J.seed(x, tangent, order), impl=self.impl)
        return J.derivatives(jet)


# ---------------------------------------------------------------------------
# nested autodiff: the O(M^n) baseline
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class AutodiffEngine(DerivativeEngine):
    """Nested autodiff towers over ``net.apply`` -- the standard-PINN-practice
    baseline whose graph grows O(M^order).  Scalar outputs nest reverse-mode
    ``jax.grad`` (what PINN codebases actually do); vector outputs fall back
    to forward-over-forward ``jax.jacfwd`` towers."""

    @property
    def spec(self) -> str:
        return "autodiff"

    def derivs(self, net: Network, params, x: jnp.ndarray, order: int,
               tangent: jnp.ndarray | None = None) -> jnp.ndarray:
        if tangent is None:
            tangent = jnp.ones_like(x)
        scalar = net.d_out == 1

        def along(xi, vi):
            if scalar:
                def g(t):
                    return net.apply(params, (xi + t * vi)[None, :],
                                     unroll=True)[0, 0]
                lift = jax.grad
            else:
                def g(t):
                    return net.apply(params, (xi + t * vi)[None, :],
                                     unroll=True)[0]
                lift = jax.jacfwd
            outs, h = [], g
            for _ in range(order + 1):
                outs.append(h)
                h = lift(h)
            t0 = jnp.asarray(0.0, x.dtype)
            return jnp.stack([jnp.atleast_1d(o(t0)) for o in outs])

        return jnp.moveaxis(jax.vmap(along)(x, tangent), 0, 1)


# ---------------------------------------------------------------------------
# jax.experimental.jet: the independent Taylor-mode oracle
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class JaxJetEngine(DerivativeEngine):
    """JAX's own Taylor mode.  Quasilinear like NTP but a fully independent
    implementation (primitive-level jet rules vs our layer-level algebra), so
    agreement between the two certifies both.  Requires ``net.apply`` to be
    scan-free (``unroll=True``): jax.experimental.jet has no scan rule."""

    @property
    def spec(self) -> str:
        return "jet"

    def derivs(self, net: Network, params, x: jnp.ndarray, order: int,
               tangent: jnp.ndarray | None = None) -> jnp.ndarray:
        from jax.experimental import jet as jjet

        if tangent is None:
            tangent = jnp.ones_like(x)
        if order == 0:
            return net.apply(params, x)[None]
        series = [tangent.astype(x.dtype)] + \
            [jnp.zeros_like(x) for _ in range(order - 1)]
        y0, ys = jjet.jet(lambda xx: net.apply(params, xx, unroll=True),
                          (x,), (series,))
        return jnp.stack([y0] + list(ys))
