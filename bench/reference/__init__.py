"""Plain float32 ``jax.numpy`` references for the benchmark's check.

Nothing here imports the program under test.  Derivatives come from nested
forward-mode ``jax.jvp`` towers (no jets, no polarization, no kernels); the
PDE residuals, exact solutions and boundary points are written out from
their formulas.  Every contraction goes through :func:`matmul`, whose
``precision`` is either ``"highest"`` (the configuration's precision) or
``"high"``, three bfloat16 passes, the next precision below (the
control).
"""
