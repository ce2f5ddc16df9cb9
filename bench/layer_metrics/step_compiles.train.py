"""How many times the program's jitted train step was compiled or loaded
from the persistent cache in this process, from the program's own compile
counters: 1 in a sound run, more when a call retraced the step."""


def read(ctx):
    try:
        from repro.pinn.trainer import TRAIN_STEP_NAME
        from repro.runtime.metrics import snapshot
    except ImportError:              # a program without the counters
        return None
    count, _ = snapshot()["compile"].get(TRAIN_STEP_NAME, (0, 0.0))
    return count or None
