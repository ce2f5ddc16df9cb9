"""Seconds of jaxpr tracing of the program's jitted train step in this
process, from the program's own compile counters: set-up work that no
compile cache saves."""


def read(ctx):
    try:
        from repro.pinn.trainer import TRAIN_STEP_NAME
        from repro.runtime.metrics import snapshot
    except ImportError:              # a program without the counters
        return None
    count, seconds = snapshot()["trace"].get(TRAIN_STEP_NAME, (0, 0.0))
    return seconds if count else None
