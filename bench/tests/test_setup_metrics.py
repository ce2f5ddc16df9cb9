"""The set-up readers (``bench/layer_metrics/step_*.train.py`` and
``operator_setup_s.train.py``) in a traced CPU rehearsal of a training
cell: they read the program's own counters, and the step compiles once.

    python -m pytest bench/tests/test_setup_metrics.py
"""

from __future__ import annotations

from .test_bench import rehearse

SETUP_METRICS = ("step_trace_s.train", "step_lower_s.train",
                 "step_compile_s.train", "step_compiles.train",
                 "operator_setup_s.train")


def test_traced_rehearsal_reads_the_setup_counters(monkeypatch):
    from repro.runtime import metrics

    metrics.reset()             # the counters are the process's, as in a run
    _, line = rehearse("ns8x20.train", monkeypatch, trace=True)
    got = line["metrics"]
    assert set(SETUP_METRICS) <= set(got)
    assert got["step_compiles.train"]["value"] == 1
    for name in SETUP_METRICS:
        assert got[name]["value"] > 0, name
