"""Paper Fig. 1-3: forward / forward+backward times vs derivative order,
autodiff vs n-TangentProp, for the paper's 3x24 tanh PINN net
(``DenseMLP``).  The autodiff column is ``AutodiffEngine``: nested
``jax.grad`` for this scalar-output net, the paper's baseline.  Host-clock
CPU figures, not chip evidence.

Expectation being reproduced: autodiff wall time grows exponentially in n;
n-TangentProp grows quasilinearly (~ n * p(n)); the crossover sits at small n.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import AutodiffEngine, DenseMLP, NTPEngine
from repro.core.partitions import partition_count

from .common import csv_row, time_fn


def run(max_order: int = 6, batch: int = 256, width: int = 24, depth: int = 3,
        trials: int = 5, fwd_only: bool = False):
    net = DenseMLP(1, width, depth, 1)
    ntp, autodiff = NTPEngine(), AutodiffEngine()
    params = net.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    x = jax.random.uniform(jax.random.PRNGKey(1), (batch, 1), jnp.float32, -1, 1)
    rows = []
    for n in range(1, max_order + 1):
        ntp_f = jax.jit(lambda p, x, n=n: ntp.derivs(net, p, x, n))
        ad_f = jax.jit(lambda p, x, n=n: autodiff.derivs(net, p, x, n))
        t_ntp = time_fn(ntp_f, params, x, trials=trials)
        t_ad = time_fn(ad_f, params, x, trials=trials)
        rows.append(csv_row(f"fwd_ntp_n{n}", t_ntp, f"pn={partition_count(n)}"))
        rows.append(csv_row(f"fwd_autodiff_n{n}", t_ad,
                            f"ratio={t_ad / t_ntp:.2f}"))
        if not fwd_only:
            loss_ntp = jax.jit(jax.grad(
                lambda p, x, n=n: jnp.sum(ntp.derivs(net, p, x, n)[n] ** 2)))
            loss_ad = jax.jit(jax.grad(
                lambda p, x, n=n: jnp.sum(autodiff.derivs(net, p, x, n)[n] ** 2)))
            t_ntp_b = time_fn(loss_ntp, params, x, trials=trials)
            t_ad_b = time_fn(loss_ad, params, x, trials=trials)
            rows.append(csv_row(f"fwdbwd_ntp_n{n}", t_ntp_b, ""))
            rows.append(csv_row(f"fwdbwd_autodiff_n{n}", t_ad_b,
                                f"ratio={t_ad_b / t_ntp_b:.2f}"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
