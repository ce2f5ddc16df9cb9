"""CPU tests of the benchmark harness: work counts, peaks, cells found by
file name, runs with no chip, and small rehearsals of each cell's whole
run, sound and with its timed path broken.

    python -m pytest bench/tests

The rehearsals patch ``harness.check_device`` (the one step that needs the
chip) and shrink the configuration and the traffic; the Pallas kernels run
in interpret mode.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, work  # noqa: E402

CELLS = ("ns8x20.train", "ac4x128.train", "ac4x128.table")
SMALL_NET = {"width": 8, "depth": 2}
SMALL_TRAFFIC = {
    "ns8x20.train": {"points": 16, "boundary_per_face": 4},
    "ac4x128.train": {"points": 16, "boundary_per_face": 4},
    "ac4x128.table": {"points": 64},
}
SEED = 2 ** 31 + 12345          # larger than 32 signed bits hold


# ---------------------------------------------------------------- work

def test_rows_per_point():
    ns = harness.find_cell("ns8x20.train")
    ops = {c: harness.reference_operator(harness.find_cell(c))
           for c in ("ns8x20.train", "ac4x128.train")}
    mixed = ops["ns8x20.train"].MIXED
    assert work.rows_per_point(3, 3, mixed) == 112
    assert len(work.jets(3, 3, mixed)) - 1 == 5
    # 31 directional jets: 3 grid + 3 x 4 + 2 x 8 polarization directions
    assert sum(d for d, _ in work.jets(3, 3, mixed)) == 31
    assert work.rows_per_point(2, 2, ops["ac4x128.train"].MIXED) == 6
    assert len(work.dense_maps(ns.config)) == 9


@pytest.mark.parametrize("cell,gflop", [("ns8x20.train", 9.8),
                                        ("ac4x128.train", 35.8),
                                        ("ac4x128.table", 39.0)])
def test_flop_totals(cell, gflop):
    c = harness.find_cell(cell)
    op = harness.reference_operator(c)
    tr = c.traffic
    if tr["mode"] == "train":
        mode = harness.mode_module(c)
        n_bc = len(mode.boundary_grid(op.DOMAIN, tr["boundary_per_face"]))
        flops = work.train_step_flops(c.config, tr["points"], n_bc, op.ORDER,
                                      op.MIXED)
    else:
        flops = sum(k.flops for k in work.table_calls(c.config, tr["points"],
                                                      tr["order"]))
    assert round(flops / 1e9, 1) == gflop


def test_boundary_grid_matches_the_trainer():
    """The reference's face points are the ones the program trains on."""
    from repro.data.collocation import boundary_grid

    for name in ("ns8x20.train", "ac4x128.train"):
        c = harness.find_cell(name)
        op = harness.reference_operator(c)
        n = c.traffic["boundary_per_face"]
        mine = harness.mode_module(c).boundary_grid(op.DOMAIN, n)
        np.testing.assert_allclose(
            mine, np.asarray(boundary_grid(op.DOMAIN, n, "float32")),
            rtol=0, atol=1e-6)


def test_benchmark_operator_matches_its_reference():
    """The operator the benchmark registers with the program and its plain
    reference give the same residual, and the manufactured solution makes
    it vanish."""
    import jax.numpy as jnp

    from repro.core.network import make_network
    from repro.pinn.operators import (get_operator, residual_of_fn,
                                      residual_values)

    from bench import system

    c = harness.find_cell("ns8x20.train")
    c.config.update(SMALL_NET)
    ref = harness.reference_operator(c)
    op = get_operator(harness.program_operator(c))
    assert (op.d_in, op.d_out, op.order) == (3, 2, ref.ORDER)
    assert set(op.mixed) == set(ref.MIXED)
    x = jnp.asarray(np.random.default_rng(0).uniform(
        [b[0] for b in ref.DOMAIN], [b[1] for b in ref.DOMAIN], (32, 3)),
        jnp.float32)
    np.testing.assert_allclose(op.exact(x), ref.exact(x), rtol=1e-6)
    r = residual_of_fn(op, lambda xi: op.exact(xi[None])[0], x)
    assert float(jnp.abs(r).max()) < 1e-4
    layers, params = system.weights(c.config, SEED)
    net = make_network("dense", d_in=3, d_out=2, width=8, depth=2)
    got = residual_values(params, op, x, net=net, engine="autodiff")
    want = ref.residual([(jnp.asarray(w), jnp.asarray(b))
                         for w, b in layers], x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_roofline_picks_the_larger_bound():
    call = work.KernelCall(n1=5, rows=1000, d_in=20, d_out=20)
    t, bound = work.roofline_seconds([call], 1e12, 1e9)
    assert bound == "memory" and t == pytest.approx(call.bytes() / 1e9)
    t, bound = work.roofline_seconds([call], 1e3, 1e12)
    assert bound == "compute" and t == pytest.approx(call.flops / 1e3)


# --------------------------------------------------------------- peaks

def test_peaks_known_and_unknown_kind():
    assert harness.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks("TPU v99")


# ------------------------------------------------------- cells by name

def bench_copy(tmp_path) -> Path:
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return root


def test_every_cell_resolves():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert tuple(w["name"] for w in spec["workloads"]) == CELLS
    for name in CELLS:
        cell = harness.find_cell(name)
        harness.mode_module(cell)
        harness.reference_operator(cell)
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end}
        for m in cell.per_layer:
            assert callable(harness.metric_reader(cell, m["name"]))


def test_new_cell_is_found_by_file_name(tmp_path):
    root = bench_copy(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    spec["workloads"].append({"name": "ac4x128.table.g128",
                              "config": "mcclenny-ac-4x128",
                              "traffic": "ac4x128.table.g128", "chips": 1,
                              "why": "a 128x128 evaluation grid"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    table = json.loads((root / "bench/workloads/ac4x128.table.json")
                       .read_text())
    (root / "bench/workloads/ac4x128.table.g128.json").write_text(
        json.dumps(dict(table, points=16384)))
    (root / "bench/limits/ac4x128.table.g128.json").write_text(
        (root / "bench/limits/ac4x128.table.json").read_text())

    cell = harness.find_cell("ac4x128.table.g128", root)
    assert cell.traffic["points"] == 16384
    assert cell.config["width"] == 128
    assert harness.mode_module(cell).__file__.endswith("modes/table.py")
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    for p, data in before.items():          # no file that was there changed
        assert p.read_bytes() == data


# ------------------------------------------------- runs without a chip

def _run(root, *args, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})),
        capture_output=True, text=True, timeout=300)


def test_run_without_tpu_fails_before_timing():
    p = _run(ROOT, "--workload", "ac4x128.table", "--seed", str(SEED),
             "--seconds", "1")
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_run_in_bare_benchmark_directory_fails(tmp_path):
    root = bench_copy(tmp_path)             # no src/: no program under test
    p = _run(root, "--workload", "ac4x128.table", "--seed", "1",
             "--seconds", "1")
    assert p.returncode != 0
    assert "{" not in p.stdout


# ------------------------------------------------ rehearsals of a run

CPU_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def small_cell(name):
    cell = harness.find_cell(name)
    cell.config.update(SMALL_NET)
    cell.traffic.update(SMALL_TRAFFIC[name])
    return cell


def rehearse(name, monkeypatch, trace=False, seconds=1.0):
    from bench import run

    cell = small_cell(name)
    monkeypatch.setattr(harness, "check_device", lambda chips: CPU_DEVICE)
    out = harness.mode_module(cell).run(cell, seed=SEED, seconds=seconds,
                                        trace=trace,
                                        t_start=time.perf_counter())
    return out, run.result_line(cell, out, CPU_DEVICE, trace)


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_is_correct(name, monkeypatch):
    out, line = rehearse(name, monkeypatch)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in
                                    small_cell(name).end_to_end}
    assert all(v["value"] > 0 for v in line["metrics"].values())


def _unchanged_state(monkeypatch):
    import repro.pinn.trainer as trainer
    monkeypatch.setattr(trainer, "adam_update",
                        lambda grads, state, params, lr: (params, state))


def _half_batch(monkeypatch):
    import repro.pinn.trainer as trainer
    loss = trainer.pinn_loss
    monkeypatch.setattr(trainer, "pinn_loss",
                        lambda p, *, pts, **kw: loss(
                            p, pts=pts[: pts.shape[0] // 2], **kw))


def _altered_answer(monkeypatch):
    from repro.core.engines import DerivativeEngine
    grid = DerivativeEngine.grid

    def altered(self, net, params, x, order):
        out = grid(self, net, params, x, order)
        return out.at[0, order, 0, 0].add(1e-2 * (1.0 + abs(out[0, order, 0, 0])))
    monkeypatch.setattr(DerivativeEngine, "grid", altered)


def _altered_cross(monkeypatch):
    from repro.core.engines import DerivativeEngine
    cross = DerivativeEngine.cross

    def altered(self, net, params, x, axes):
        out = cross(self, net, params, x, axes)
        return out.at[0, 0].add(1.0 + abs(out[0, 0]))
    monkeypatch.setattr(DerivativeEngine, "cross", altered)


@pytest.mark.parametrize("name,fault", [
    ("ns8x20.train", _unchanged_state),
    ("ns8x20.train", _half_batch),
    ("ns8x20.train", _altered_cross),
    ("ac4x128.train", _unchanged_state),
    ("ac4x128.train", _half_batch),
    ("ac4x128.table", _altered_answer),
])
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    _, line = rehearse(name, monkeypatch)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_traced_rehearsal_runs(name, monkeypatch):
    """On the CPU the trace has no TPU plane: the run still ends, and the
    device readers find nothing to read."""
    out, line = rehearse(name, monkeypatch, trace=True)
    assert line["correct"]
    assert line["device"]["busy_s"] == 0.0 and line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
