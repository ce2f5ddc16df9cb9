"""Device time of the ``jet_flash_attention`` kernel per training step, from
the trace."""


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or not ctx.get("steps"):
        return None
    seconds = trace.kernel_s("jet_flash_attention")
    return 1e3 * seconds / ctx["steps"] if seconds else None
