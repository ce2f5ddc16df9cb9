"""Device time of the wavelet-jet kernels per training step, forward and
backward (``wave_jet`` and ``wave_jet_bwd``), from the trace; nothing on a
program whose wave jets run as XLA work."""


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or not ctx.get("steps"):
        return None
    seconds = trace.kernel_s("wave_jet")
    return 1e3 * seconds / ctx["steps"] if seconds else None
