"""Layer scopes and compile counters of ``repro.runtime.metrics``, on the
CPU: a scope names the device operations traced inside it, shows as a host
event in a profiler trace and adds up in the registry; the train step's
compile counters count one compile per shape.  The compile for a described
TPU, where the scopes must cover the compiled step, is in
``test_tpu_compile.py``."""

import glob
import os

import jax
import jax.numpy as jnp

from repro.optim import adam_init
from repro.pinn import OperatorRunConfig, train_operator
from repro.pinn.trainer import SETUP_SCOPE, TRAIN_STEP_NAME
from repro.runtime import metrics


def _count(snap, kind, name):
    return snap[kind].get(name, (0, 0.0))


def test_scope_adds_calls_and_seconds_and_reset_empties():
    before = _count(metrics.snapshot(), "span", "test.layer")
    for _ in range(3):
        with metrics.scope("test.layer"):
            jnp.ones(4).block_until_ready()
    n, s = _count(metrics.snapshot(), "span", "test.layer")
    assert n - before[0] == 3 and s > before[1]
    metrics.reset()
    assert all(not v for v in metrics.snapshot().values())


def test_scope_names_device_operations_under_jit():
    def f(x):
        with metrics.scope("test.outer"):
            y = jnp.sin(x)
            with metrics.scope("test.inner"):
                return y * 2.0

    text = jax.jit(f).lower(jnp.ones(3)).as_text(debug_info=True)
    assert "test.outer/sin" in text
    assert "test.outer/test.inner/mul" in text


def test_scope_is_a_host_event_in_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    x = jnp.ones(8)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with metrics.scope("test.profiled"):
            (x + 1.0).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert "test.profiled" in names


def test_train_step_counts_one_compile_per_shape():
    before = metrics.snapshot()
    res = train_operator(OperatorRunConfig(
        op="heat", width=8, depth=2, n_domain=16, n_bc=4, adam_steps=0,
        engine="ntp", eval_pts_per_axis=2))
    built = metrics.snapshot()
    assert _count(built, "span", SETUP_SCOPE)[0] \
        > _count(before, "span", SETUP_SCOPE)[0]

    def compiles(snap):
        return {k: _count(snap, k, TRAIN_STEP_NAME)
                for k in ("trace", "lower", "compile")}

    p, s = res.params, adam_init(res.params)
    x = jnp.zeros((16, 2), p.w_in.dtype)
    for _ in range(2):
        p, s, _ = res.train_step(p, s, x)
    one = compiles(metrics.snapshot())
    for kind, (n, sec) in one.items():
        n0, s0 = compiles(built)[kind]
        assert n - n0 == 1 and sec > s0, kind
    p, s, _ = res.train_step(p, s, x[:8])
    two = compiles(metrics.snapshot())
    for kind, (n, _) in two.items():
        assert n - compiles(built)[kind][0] == 2, kind
