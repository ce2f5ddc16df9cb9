"""Quickstart: n-TangentProp in 30 lines.

    PYTHONPATH=src python examples/quickstart.py

Computes f, f', ..., f^(8) of a tanh MLP in ONE forward pass, checks them
against nested autodiff, and shows the cost difference.
"""

import time

import jax
import jax.numpy as jnp

from repro.core import baselines, init_mlp, ntp_derivatives
from repro.core import jet as J
from repro.runtime.compile_cache import enable_compile_cache

enable_compile_cache()
dtype = J.float_dtype()     # float32; JAX_ENABLE_X64=1 gives float64 on CPU

# the paper's standard PINN network: 3 hidden layers x 24 neurons, tanh
params = init_mlp(jax.random.PRNGKey(0), d_in=1, width=24, depth=3, d_out=1,
                  dtype=dtype)
x = jnp.linspace(-1.0, 1.0, 256, dtype=dtype)[:, None]

N = 8
t0 = time.perf_counter()
derivs = ntp_derivatives(params, x, N)      # (N+1, batch, 1): f, f', ..., f^(8)
derivs.block_until_ready()
t_ntp = time.perf_counter() - t0
print(f"n-TangentProp: all {N + 1} derivatives in one pass "
      f"({t_ntp * 1e3:.1f} ms untraced)")

# independent oracle: nested reverse-mode autodiff (the O(M^n) way)
ref = baselines.nested_autodiff(params, x[:8], 6)
err = jnp.max(jnp.abs(derivs[:7, :8] - ref))
print(f"max |ntp - nested autodiff| over orders 0..6: {err:.2e}")

# jets through a full attention block work too (beyond the paper):
h = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 16), dtype)
v = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 16), dtype)
jet = J.softmax(J.seed(h, v, 4), axis=-1)
print("4th directional derivative of softmax:", jet.coeffs[4].shape)
