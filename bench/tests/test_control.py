"""The control, at a size a CPU test run holds: the reference computed one
precision step below the configuration's (``"high"``, bfloat16_3x, spelled
out pass by pass off the TPU) in the program's place, judged by the run's
own comparison against each cell's limits.

The network is the cell's own (its widths and depth); only the number of
points is cut.  The control must come out not correct on every seed.  On
the chip, ``bench/control.py`` reads the same at each cell's own size;
``PERF.md`` gives those readings beside the limits they set.
"""

from __future__ import annotations

import pytest

from bench import control, harness

from .test_bench import CELLS

SEEDS = [3, 2 ** 31 + 5, 77]
FEW_POINTS = {"ns8x20.train": {"points": 500},
              "ac4x128.train": {"points": 2000},
              "ac4x128.table": {"points": 4096}}


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = harness.find_cell(name)
    cell.traffic.update(FEW_POINTS[name])
    gen = control.READINGS[cell.traffic["mode"]](cell, SEEDS, program=False)
    verdicts = {}
    for seed, who, nums, _ in gen:
        checks = harness.checks(cell, nums)
        verdicts.setdefault(who, []).append(
            (harness.correct(checks), {c.name: c.value for c in checks}))
    assert len(verdicts["control"]) == len(SEEDS)
    for ok, nums in verdicts["control"]:
        assert not ok, nums
