"""Derivative engines: one uniform surface over every way this repo computes
higher-order input derivatives of a network.

An engine answers three questions about any :class:`repro.core.network.Network`:

* ``derivs(net, params, x, order, tangent=None)`` -- raw directional
  derivatives ``d^k/dt^k f(x + t v)`` at t=0, stacked (order+1, N, d_out);
* ``grid(net, params, x, order)`` -- pure derivatives along every coordinate
  axis, (d_in, order+1, N, d_out), with the direction axis folded into the
  batch so the whole grid is ONE forward (a single Pallas launch per layer);
* ``cross(net, params, x, axes)`` -- the mixed partial
  ``d^m f / dx_{a_1}..dx_{a_m}``, (N, d_out), by polarization of 2^m
  directional derivatives (never a nested-autodiff graph).

``grid`` and ``cross`` are engine-generic: they are assembled from ``derivs``
here in the base class, so a new engine implements one method and inherits
the whole surface.  Shipped engines:

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
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.runtime.metrics import scope

from . import jet as J
from .network import Network

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
        instead of a vmap over per-direction passes."""
        n_dirs, batch = dirs.shape[0], x.shape[0]
        with scope("ntp.fold"):
            xt = jnp.tile(x, (n_dirs, 1))
            vt = jnp.repeat(dirs, batch, axis=0)
        d = self.derivs(net, params, xt, order, vt)
        with scope("ntp.fold"):
            return jnp.moveaxis(d.reshape((order + 1, n_dirs, batch, -1)),
                                1, 0)

    def grid(self, net: Network, params, x: jnp.ndarray,
             order: int) -> jnp.ndarray:
        """Pure derivatives along every coordinate axis:
        (d_in, order+1, N, d_out)."""
        with scope("ntp.grid"):
            eye = jnp.eye(x.shape[-1], dtype=x.dtype)
            return self._batched_directional(net, params, x, eye, order)

    def cross(self, net: Network, params, x: jnp.ndarray,
              axes: Sequence[int]) -> jnp.ndarray:
        """Mixed partial ``d^m f / dx_{axes[0]} ... dx_{axes[m-1]}``, (N, d_out),
        via the polarization identity

            D_{v_1..v_m} f = 1/(2^m m!) sum_{eps in {+-1}^m}
                             (prod_k eps_k) D^m_{sum_k eps_k v_k} f

        with ``v_k = e_{axes[k]}``.  Repeated axes are allowed
        (``axes=(0, 0, 1)`` gives u_xxy)."""
        m, d = len(axes), x.shape[-1]
        if m == 0:
            raise ValueError("axes must name at least one differentiation axis")
        if any(a < 0 or a >= d for a in axes):
            raise ValueError(f"axes {tuple(axes)} out of range for d_in={d}")
        signs = list(itertools.product((1.0, -1.0), repeat=m))
        with scope("ntp.cross"):
            basis = jnp.eye(d, dtype=x.dtype)[jnp.asarray(axes)]   # (m, d)
            dirs = jnp.asarray(signs, x.dtype) @ basis              # (2^m, d)
            derivs = self._batched_directional(net, params, x, dirs, m)
            # the +-1 weights are static: add the signed terms in a fixed
            # order (not a matmul), so every launch -- one device or a mesh
            # -- sums the same way
            with scope("ntp.polarize"):
                top = None                                      # (N, d_out)
                for i, eps in enumerate(signs):
                    term = derivs[i, m] if math.prod(eps) > 0 \
                        else -derivs[i, m]
                    top = term if top is None else top + term
                return top / (2.0 ** m * math.factorial(m))

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
