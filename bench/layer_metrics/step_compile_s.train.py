"""Seconds of XLA compiling the program's jitted train step in this
process, or of loading it from the persistent compile cache on a hit, from
the program's own compile counters."""


def read(ctx):
    try:
        from repro.pinn.trainer import TRAIN_STEP_NAME
        from repro.runtime.metrics import snapshot
    except ImportError:              # a program without the counters
        return None
    count, seconds = snapshot()["compile"].get(TRAIN_STEP_NAME, (0, 0.0))
    return seconds if count else None
