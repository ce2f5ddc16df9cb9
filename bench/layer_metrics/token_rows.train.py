"""Token rows per point that the program's derivative table carries through
a network whose output has a token axis (directions x coefficients x
tokens), from the program's own counter ``net.token_rows`` (added once per
table build, at trace time)."""


def read(ctx):
    try:
        from repro.runtime.metrics import snapshot
    except ImportError:              # a program without the registry
        return None
    calls, total = snapshot().get("counter", {}).get("net.token_rows",
                                                     (0, 0.0))
    return total / calls if calls else None
