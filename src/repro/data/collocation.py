"""Collocation-point samplers for PINN training."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.jet import float_dtype


def _dtype(dtype):
    """A sampler's ``dtype=None`` means :func:`float_dtype` at call time:
    float64 under ``jax_enable_x64``, float32 otherwise."""
    return float_dtype() if dtype is None else dtype


def uniform_grid(lo: float, hi: float, n: int, dtype=None) -> jnp.ndarray:
    dtype = _dtype(dtype)
    return jnp.linspace(lo, hi, n, dtype=dtype)[:, None]


def random_points(key: jax.Array, lo: float, hi: float, n: int,
                  dtype=None) -> jnp.ndarray:
    dtype = _dtype(dtype)
    return jax.random.uniform(key, (n, 1), dtype, lo, hi)


def origin_cluster(key: jax.Array, radius: float, n: int,
                   dtype=None) -> jnp.ndarray:
    """Points concentrated near x=0 where the high-order smoothness loss acts."""
    dtype = _dtype(dtype)
    return jax.random.uniform(key, (n, 1), dtype, -radius, radius)


def resample(key: jax.Array, lo: float, hi: float, n_domain: int,
             n_origin: int, origin_radius: float, dtype=None):
    k1, k2 = jax.random.split(key)
    return (random_points(k1, lo, hi, n_domain, dtype),
            origin_cluster(k2, origin_radius, n_origin, dtype))


# ---------------------------------------------------------------------------
# d-dimensional boxes (the operator subsystem's collocation surface)
# ---------------------------------------------------------------------------

Domain = tuple  # ((lo, hi), ...) -- one interval per input axis


def sample_box(key: jax.Array, domain: Domain, n: int,
               dtype=None) -> jnp.ndarray:
    """(n, d) uniform interior collocation points in a box domain."""
    dtype = _dtype(dtype)
    d = len(domain)
    lo = jnp.asarray([b[0] for b in domain], dtype)
    hi = jnp.asarray([b[1] for b in domain], dtype)
    return lo + (hi - lo) * jax.random.uniform(key, (n, d), dtype)


def boundary_grid(domain: Domain, n_per_face: int,
                  dtype=None) -> jnp.ndarray:
    """Deterministic points on every face of the box (both endpoints of each
    axis).  For time-dependent PDEs trained by manufactured solutions the
    t=0 face supplies the initial condition and the other faces Dirichlet
    data -- supervising on the t=T face too is harmless extra data."""
    dtype = _dtype(dtype)
    d = len(domain)
    if d == 1:
        return jnp.asarray([[domain[0][0]], [domain[0][1]]], dtype)
    n_side = max(2, int(round(n_per_face ** (1.0 / (d - 1)))))
    faces = []
    for a in range(d):
        others = [i for i in range(d) if i != a]
        axes = [jnp.linspace(domain[i][0], domain[i][1], n_side, dtype=dtype)
                for i in others]
        mesh = jnp.meshgrid(*axes, indexing="ij")
        rest = jnp.stack([m.ravel() for m in mesh], axis=-1)
        for side in domain[a]:
            pts = jnp.zeros((rest.shape[0], d), dtype)
            pts = pts.at[:, jnp.asarray(others)].set(rest).at[:, a].set(side)
            faces.append(pts)
    return jnp.concatenate(faces)


def eval_grid(domain: Domain, n_per_axis: int, dtype=None) -> jnp.ndarray:
    """Dense tensor-product grid over the box, for accuracy reporting."""
    dtype = _dtype(dtype)
    axes = [jnp.linspace(lo, hi, n_per_axis, dtype=dtype) for lo, hi in domain]
    mesh = jnp.meshgrid(*axes, indexing="ij")
    return jnp.stack([m.ravel() for m in mesh], axis=-1)
