"""The one generator of every traffic mix: collocation and evaluation point
sets, made on the device from the run's seed."""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

from bench import harness


def point_sets(seed: int, stream: int, domain: Sequence[Sequence[float]],
               n: int, count: int, dtype="float32") -> List:
    """``count`` sets of ``n`` uniform points in the box ``domain``, made on
    the device in one jitted call and handed back as a list, so that a
    loop feeds existing arrays and compiles nothing."""
    import jax

    lo = np.asarray([b[0] for b in domain], dtype)
    hi = np.asarray([b[1] for b in domain], dtype)

    @jax.jit
    def make(key):
        u = jax.random.uniform(key, (count, n, len(domain)), dtype)
        return [lo + (hi - lo) * u[i] for i in range(count)]

    return make(harness.seed_key(seed, stream))
