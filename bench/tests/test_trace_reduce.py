"""``bench/trace_reduce.py`` on a small trace recorded on one TPU v5e:
three jitted calls of ``jet_dense`` (a (5, 1024, 20) x (20, 20) jet, tanh
epilogue) followed by a sum, under ``jax.profiler``."""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace_reduce

TRACE = Path(__file__).parent / "data" / "jet_dense_3calls.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce_file(str(TRACE))


def test_one_chip_busy_and_split(summary):
    assert summary.chips == 1
    assert summary.pallas == {"jet_dense_pallas": pytest.approx(24.549e-6)}
    assert summary.kernel_s("jet_dense") == pytest.approx(24.549e-6)
    assert summary.kernel_s("flash") == 0.0
    assert summary.xla_s == pytest.approx(10.978e-6, rel=1e-6)
    # the ops never overlap here, so busy is their sum
    assert summary.busy_s == pytest.approx(summary.pallas_s + summary.xla_s)


def test_ops_are_summed_by_kind(summary):
    top = summary.top_ops(3)
    assert [name for name, _ in top] == ["jet_dense_pallas", "copy",
                                         "reduce_sum"]
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)


def test_idle_gaps_are_put_down_to_host_events(summary):
    gaps = dict(summary.top_gaps(10))
    assert "DoEnqueueProgram" in gaps
    assert all(s > 0 for s in gaps.values())


@pytest.mark.parametrize("hlo,kind,pallas", [
    ('%jvp_jit_jet_dense_pallas__.73 = f32[5,80000,20]{2,1,0} custom-call('
     'f32[5,80000,20]{2,1,0} %x), custom_call_target="tpu_custom_call"',
     "jvp_jit_jet_dense_pallas__", True),
    ('%custom-call.2 = f32[3,256,200]{1,2,0} custom-call(), '
     'custom_call_target="AllocateBuffer"', "custom-call", False),
    ("%fusion.90 = f32[1,200,200]{2,1,0} fusion(f32[1,20000,200] %a)",
     "fusion", False),
    ("%copy = f32[5,1024,20]{2,1,0} copy(f32[5,1024,20]{1,2,0} %c.1)",
     "copy", False),
])
def test_event_names(hlo, kind, pallas):
    assert trace_reduce.op_kind(hlo) == kind
    assert trace_reduce.is_pallas(hlo) is pallas
