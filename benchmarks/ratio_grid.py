"""Paper Fig. 4-5: autodiff/n-TangentProp runtime ratio across width, depth,
batch size, and derivative order (ratio > 1 means n-TangentProp is faster).
The nets are ``DenseMLP``s; the autodiff side is ``AutodiffEngine``, nested
``jax.grad`` for these scalar-output nets.  Host-clock CPU figures, not
chip evidence."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import AutodiffEngine, DenseMLP, NTPEngine

from .common import csv_row, time_fn

WIDTHS = (24, 64)
DEPTHS = (3, 5)
BATCHES = (64, 256)
ORDERS = (2, 4, 6)


def run(trials: int = 3):
    ntp, autodiff = NTPEngine(), AutodiffEngine()
    rows = []
    for w in WIDTHS:
        for d in DEPTHS:
            net = DenseMLP(1, w, d, 1)
            params = net.init(jax.random.PRNGKey(w * d), dtype=jnp.float32)
            for b in BATCHES:
                x = jax.random.uniform(jax.random.PRNGKey(b), (b, 1),
                                       jnp.float32, -1, 1)
                for n in ORDERS:
                    t_ntp = time_fn(jax.jit(
                        lambda p, x, n=n: ntp.derivs(net, p, x, n)),
                        params, x, trials=trials)
                    t_ad = time_fn(jax.jit(
                        lambda p, x, n=n: autodiff.derivs(net, p, x, n)),
                        params, x, trials=trials)
                    rows.append(csv_row(
                        f"ratio_w{w}_d{d}_b{b}_n{n}", t_ntp,
                        f"ratio={t_ad / t_ntp:.2f}"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
