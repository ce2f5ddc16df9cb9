"""Fault-tolerance runtime: checkpoint/restart, preemption, stragglers,
gradient compression, checkpoint manager semantics."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager
from repro.parallel.compression import dequantize_int8, ef_compress, quantize_int8
from repro.runtime import Trainer, TrainerConfig


def quad_problem(tmp_path, total=40, ckpt_every=10):
    target = jnp.asarray([3.0, -1.0])

    @jax.jit
    def step(state, batch):
        params, opt_t = state
        g = jax.grad(lambda p: jnp.sum((p - target) ** 2))(params)
        return (params - 0.05 * g, opt_t + 1), jnp.sum((params - target) ** 2)

    cfg = TrainerConfig(total_steps=total, ckpt_every=ckpt_every,
                        ckpt_dir=str(tmp_path), max_retries=5)
    return cfg, step, target


def test_trainer_clean_run(tmp_path):
    cfg, step, target = quad_problem(tmp_path)
    tr = Trainer(cfg, step, lambda s: None)
    (params, t), rep = tr.run((jnp.zeros(2), jnp.asarray(0)))
    assert rep.steps_run == 40 and rep.restarts == 0
    assert rep.losses[-1] < rep.losses[0]


def test_trainer_recovers_from_injected_failures(tmp_path):
    cfg, step, target = quad_problem(tmp_path)
    boom = {25}

    def injector(s):
        if s in boom:
            boom.clear()          # fail exactly once
            raise RuntimeError("injected node failure")

    tr = Trainer(cfg, step, lambda s: None)
    (params, t), rep = tr.run((jnp.zeros(2), jnp.asarray(0)), fail_injector=injector)
    assert rep.restarts == 1
    # resumed from step 20 checkpoint and completed
    assert rep.steps_run >= 40 - 20
    assert rep.losses[-1] < 0.5


def test_trainer_preemption_checkpoints_and_exits(tmp_path):
    cfg, step, target = quad_problem(tmp_path, total=1000, ckpt_every=100)
    tr = Trainer(cfg, step, lambda s: None)

    calls = {"n": 0}
    orig_batch = lambda s: None

    def batch_fn(s):
        calls["n"] += 1
        if calls["n"] == 7:
            tr.request_preempt()
        return None

    tr.batch_fn = batch_fn
    state, rep = tr.run((jnp.zeros(2), jnp.asarray(0)))
    assert rep.preempted
    assert tr.ckpt.latest_step() is not None  # state saved at the boundary


def test_straggler_watchdog(tmp_path):
    cfg, step, target = quad_problem(tmp_path, total=20)
    slow = {10}
    hits = []

    def batch_fn(s):
        if s in slow:
            time.sleep(0.3)
        return None

    tr = Trainer(cfg, step, batch_fn,
                 straggler_cb=lambda s, dt, ema: hits.append(s))
    tr.run((jnp.zeros(2), jnp.asarray(0)))
    assert hits and hits[0] == 10


# ---------------------------------------------------------------------------
# checkpoint manager
# ---------------------------------------------------------------------------

def test_ckpt_roundtrip_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": [jnp.zeros(4), jnp.ones(2)]}
    for step in (10, 20, 30):
        mgr.save(step, tree, blocking=True)
    assert mgr.all_steps() == [20, 30]  # keep=2 garbage-collects step 10
    like = jax.tree_util.tree_map(jnp.zeros_like, tree)
    back = mgr.restore(30, like)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(a, b)


def test_ckpt_async_and_atomicity(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    tree = {"w": jnp.full((128, 128), 7.0)}
    mgr.save(5, tree, blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 5
    assert not any(n.endswith(".tmp") for n in os.listdir(tmp_path))


def test_ckpt_elastic_restore_dtype_cast(tmp_path):
    """Restore maps onto a like-tree with different dtype (elastic restarts
    may change precision policy)."""
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": jnp.ones(4, jnp.float32)}, blocking=True)
    back = mgr.restore(1, {"w": jnp.zeros(4, jnp.bfloat16)})
    assert back["w"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_int8_quantization_bounded_error():
    g = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 3.0
    q, scale = quantize_int8(g)
    err = np.abs(np.asarray(dequantize_int8(q, scale) - g))
    assert err.max() <= float(scale) * 0.5 + 1e-6


def test_error_feedback_is_unbiased_over_time():
    """Accumulated EF-compressed updates converge to the true sum."""
    key = jax.random.PRNGKey(1)
    g_true = jax.random.normal(key, (512,))
    err = jnp.zeros((512,), jnp.bfloat16)
    acc = jnp.zeros((512,))
    steps = 50
    for _ in range(steps):
        q, scale, err = ef_compress(g_true, err)
        acc = acc + dequantize_int8(q, scale)
    # average transmitted gradient ~= true gradient (EF guarantee)
    np.testing.assert_allclose(acc / steps, g_true, atol=2e-2)


def test_compressed_psum_multidevice_if_available(tmp_path):
    """Correctness of the compressed psum under shard_map (skips with 1 dev)."""
    if jax.device_count() < 2:
        pytest.skip("single-device container; covered by test_dryrun_subproc")


def test_compile_cache_env_dir_wins_else_fixed_checkout_dir(monkeypatch,
                                                           tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache directory; without
    it the cache sits at a fixed, git-ignored path inside the checkout."""
    from pathlib import Path

    from repro.runtime import compile_cache as cc

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
        assert cc.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)

        monkeypatch.delenv(cc.ENV_VAR)
        assert cc.enable_compile_cache() == str(cc.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(cc.DEFAULT_DIR)
        root = Path(__file__).resolve().parents[1]
        assert cc.DEFAULT_DIR == root / ".jax_cache"
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
