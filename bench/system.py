"""The benchmark's side of the interface to the system under test: weights
made from the seed, in the program's parameter layout and in the
reference's, and the program's network."""

from __future__ import annotations

import numpy as np

from bench import harness
from bench.reference import mlp


def layer_sizes(cfg: dict):
    return [cfg["d_in"]] + [cfg["width"]] * cfg["depth"] + [cfg["d_out"]]


def weights(cfg: dict, seed: int):
    """(reference layers on the host, program ``MLPParams`` on the device),
    the same numbers, made on the device in one jitted call from the
    seed."""
    import jax
    import jax.numpy as jnp

    from repro.core.ntp import MLPParams

    sizes = layer_sizes(cfg)
    dtype = jnp.dtype(cfg["dtype"])

    @jax.jit
    def make(key):
        layers = mlp.init(key, sizes, dtype)
        hidden = layers[1:-1]
        return layers, MLPParams(
            w_in=layers[0][0], b_in=layers[0][1],
            w_hidden=jnp.stack([w for w, _ in hidden]),
            b_hidden=jnp.stack([b for _, b in hidden]),
            w_out=layers[-1][0], b_out=layers[-1][1])

    layers, params = make(harness.seed_key(seed, 0))
    return [(np.asarray(w), np.asarray(b)) for w, b in layers], params


def program_layers(params):
    """The program's ``MLPParams`` as host ``(w, b)`` per dense map."""
    wh, bh = np.asarray(params.w_hidden), np.asarray(params.b_hidden)
    hidden = [(wh[i], bh[i]) for i in range(wh.shape[0])]
    return ([(np.asarray(params.w_in), np.asarray(params.b_in))] + hidden
            + [(np.asarray(params.w_out), np.asarray(params.b_out))])


def network(cfg: dict):
    from repro.core.network import make_network

    return make_network(cfg["network"], d_in=cfg["d_in"], d_out=cfg["d_out"],
                        width=cfg["width"], depth=cfg["depth"],
                        activation=cfg["activation"])


def leaves(layers):
    return [a for wb in layers for a in wb]
