"""The paper's parameter layout for a uniform-width dense MLP.

:class:`MLPParams` stacks the hidden layers so a forward can ``lax.scan``
over them; :class:`repro.core.network.DenseMLP` is the network over it
(plain forward and jet forward), and the engines of
:mod:`repro.core.engines` differentiate it.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class MLPParams(NamedTuple):
    """Stacked weights for a uniform-width MLP (paper's architecture)."""

    w_in: jnp.ndarray    # (d_in, width)
    b_in: jnp.ndarray    # (width,)
    w_hidden: jnp.ndarray  # (depth-1, width, width) -- scanned
    b_hidden: jnp.ndarray  # (depth-1, width)
    w_out: jnp.ndarray   # (width, d_out)
    b_out: jnp.ndarray   # (d_out,)


def init_mlp(key: jax.Array, d_in: int, width: int, depth: int, d_out: int,
             dtype=jnp.float32) -> MLPParams:
    ks = jax.random.split(key, depth + 1)

    def xavier(k, fan_in, fan_out):
        # core.modules.xavier_uniform, written out: this layout module
        # imports nothing of the network layers
        lim = jnp.sqrt(6.0 / (fan_in + fan_out)).astype(dtype)
        return jax.random.uniform(k, (fan_in, fan_out), dtype, -lim, lim)

    w_in = xavier(ks[0], d_in, width)
    wh = jnp.stack([xavier(ks[i + 1], width, width) for i in range(depth - 1)]) \
        if depth > 1 else jnp.zeros((0, width, width), dtype)
    w_out = xavier(ks[depth], width, d_out)
    return MLPParams(
        w_in=w_in, b_in=jnp.zeros((width,), dtype),
        w_hidden=wh, b_hidden=jnp.zeros((max(depth - 1, 0), width), dtype),
        w_out=w_out, b_out=jnp.zeros((d_out,), dtype),
    )


def num_params(p) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(p))
