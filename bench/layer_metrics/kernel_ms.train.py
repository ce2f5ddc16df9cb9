"""Device time of the Pallas kernels per training step, from the trace."""


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or not trace.pallas_s or not ctx.get("steps"):
        return None
    return 1e3 * trace.pallas_s / ctx["steps"]
