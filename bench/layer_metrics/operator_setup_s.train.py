"""Host seconds in the program's ``pinn.setup`` scope: ``train_operator``
building the network, the boundary values and the step, and its closing
accuracy check, from the program's own span registry."""


def read(ctx):
    try:
        from repro.pinn.trainer import SETUP_SCOPE
        from repro.runtime.metrics import snapshot
    except ImportError:              # a program without the registry
        return None
    count, seconds = snapshot()["span"].get(SETUP_SCOPE, (0, 0.0))
    return seconds if count else None
