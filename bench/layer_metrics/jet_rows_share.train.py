"""Jet rows per point that the program's derivative tables run, as a share
of the rows one 2^m-direction polarization pass per mixed partial beside
the grid would run, from the program's own counters ``ntp.rows`` and
``ntp.rows_polarized`` (added once per table build, at trace time)."""


def read(ctx):
    try:
        from repro.runtime.metrics import snapshot
    except ImportError:              # a program without the registry
        return None
    counters = snapshot().get("counter", {})
    _, rows = counters.get("ntp.rows", (0, 0.0))
    _, polarized = counters.get("ntp.rows_polarized", (0, 0.0))
    return 100.0 * rows / polarized if polarized else None
