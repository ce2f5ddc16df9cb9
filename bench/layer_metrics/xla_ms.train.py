"""Device time per training step of every operation that is not a Pallas
kernel (the backward through the jnp oracle, polarization sums, loss,
Adam), from the trace."""


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or not trace.xla_s or not ctx.get("steps"):
        return None
    return 1e3 * trace.xla_s / ctx["steps"]
