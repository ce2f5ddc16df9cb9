"""CPU tests of the PINNsFormer cell ``pfns.train``: its work count against
a hand count, small rehearsals of its whole run (sound, with its timed path
broken, and traced), and its control against its limits.

    python -m pytest bench/tests/test_pfns.py

The rehearsals patch ``harness.check_device`` and shrink the network's
widths and the traffic; the Pallas kernels run in interpret mode.  The
control keeps the published widths and cuts only the points.
"""

from __future__ import annotations

import time

import pytest

from bench import harness
from bench.control import CONTROL_SEEDS
from bench.work import pinnsformer as work

from .test_bench import (CPU_DEVICE, SEED, _half_batch,  # noqa: F401
                         _unchanged_state)

CELL = "pfns.train"
SMALL_NET = {"width": 8, "ff": 16, "head": 16, "tokens": 3}
SMALL_TRAFFIC = {"points": 16, "boundary_per_face": 4}
NS_MIXED = ((0, 1), (0, 2), (1, 2), (0, 0, 1), (0, 1, 1))


# ---------------------------------------------------------------- work

def test_directions_are_the_programs():
    """13 directions for Raissi's five mixed partials (3 axes and two per
    partial), 3 for a grid alone; the program's plan agrees."""
    from repro.core.engines import PolarizationPlan

    assert work.directions(3, NS_MIXED) == 13
    assert work.directions(3) == 3
    plan = PolarizationPlan.build(3, NS_MIXED, axes=True)
    assert work.directions(3, NS_MIXED) == len(plan.directions)
    cell = harness.find_cell(CELL)
    assert work.token_rows_per_point(cell.config, 3, NS_MIXED) == 260


def test_counts_match_a_hand_count():
    """d_in 3, d_out 2, width 8, 1 layer each, 2 heads of 4, FF 16, head 16,
    3 tokens; order 3 (4 coefficients); 2 points, so 13 x 2 = 26 sequences
    and 78 token rows."""
    cfg = {"d_in": 3, "d_out": 2, "width": 8, "depth": 1, "n_heads": 2,
           "ff": 16, "head": 16, "tokens": 3}
    calls = work.table_calls(cfg, 2, 3, NS_MIXED)
    # embed 3x8; per layer q, k, v 8x8 and FF 8x16, 16x16, 16x8; head
    # 8x16, 16x16, 16x2
    macs_per_row = 24 + 2 * (3 * 64 + 128 + 256 + 128) + (128 + 256 + 32)
    assert sum(c.flops for c in calls) == 2 * 4 * 78 * macs_per_row
    assert {c.rows for c in calls} == {78} and {c.n1 for c in calls} == {4}
    flash = work.flash_calls(cfg, 2, 3, NS_MIXED)
    assert len(flash) == 2
    # per sequence and head: 10 Cauchy products each of Q K^T and P V
    # (3x4 by 4x3, 3x3 by 3x4: 72 multiply-adds), softmax 9 scores x (3 +
    # 3 + 6 + 9 + 4), division 12 outputs x (12 + 4); per sequence the
    # output projection, 4 coefficients of 3x8 by 8x8
    per_seq = 2 * (2 * 10 * 72 + 9 * 25 + 12 * 16) + 4 * 2 * 3 * 8 * 8
    assert flash[0].flops == 26 * per_seq
    # q, k, v stacks and the output stack, and wo, in float32
    assert flash[0].bytes() == 4 * (3 * 4 * 26 * 2 * 3 * 4 + 4 * 26 * 3 * 8
                                    + 2 * 4 * 8)


# ------------------------------------------------ rehearsals of a run

def small_cell():
    cell = harness.find_cell(CELL)
    cell.config.update(SMALL_NET)
    cell.traffic.update(SMALL_TRAFFIC)
    return cell


def rehearse(monkeypatch, trace=False):
    from bench import run

    cell = small_cell()
    monkeypatch.setattr(harness, "check_device", lambda chips: CPU_DEVICE)
    out = harness.mode_module(cell).run(cell, seed=SEED, seconds=1.0,
                                        trace=trace,
                                        t_start=time.perf_counter())
    return out, run.result_line(cell, out, CPU_DEVICE, trace)


def test_rehearsal_is_correct(monkeypatch):
    from repro.runtime import metrics

    metrics.reset()
    out, line = rehearse(monkeypatch, trace=True)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["busy_s"] == 0.0
    got = line["metrics"]
    # CPU trace: no device plane, so the device readers find nothing; the
    # program's counters and the host-clock readers do
    assert got["token_rows.train"]["value"] == 13 * 4 * 3
    assert got["jet_rows_share.train"]["value"] == pytest.approx(
        100 * 52 / 112)
    assert got["step_compiles.train"]["value"] == 1
    assert "jet_flash_ms.train" not in got
    assert "jet_flash_roofline.train" not in got


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    _, line = rehearse(monkeypatch)
    assert not line["correct"], line["checks"]


# -------------------------------------------------------- the control

def test_control_and_half_batch_are_not_correct():
    """At the published widths on 100 points: the reference at ``"high"``
    in the program's place, and the reference on half of each batch, both
    come out not correct under the cell's limits on every seed."""
    from bench import pfns_control

    cell = harness.find_cell(CELL)
    cell.traffic["points"] = 100
    seeds = [3, 2 ** 31 + 5, 77][:CONTROL_SEEDS]
    verdicts = {}
    for seed, who, nums, _ in pfns_control.readings(cell, seeds,
                                                    program=False):
        checks = harness.checks(cell, nums)
        verdicts.setdefault(who, []).append(
            (harness.correct(checks), {c.name: c.value for c in checks}))
    for who in ("control", "half_batch"):
        assert len(verdicts[who]) == len(seeds)
        for ok, nums in verdicts[who]:
            assert not ok, (who, nums)
