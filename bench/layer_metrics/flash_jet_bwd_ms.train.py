"""Device time of the flash-jet attention backward kernel per training step
(``flash_jet_bwd``), from the trace; nothing on a program whose attention
backward runs as XLA work."""


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or not ctx.get("steps"):
        return None
    seconds = trace.kernel_s("flash_jet_bwd")
    return 1e3 * seconds / ctx["steps"] if seconds else None
