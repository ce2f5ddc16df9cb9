"""Serving subsystem: bucketing, executable cache, microbatcher semantics,
bit-identical served derivative tables, typed overload/timeout errors --
plus regression tests for this PR's bugfix sweep (launch/serve.py CLI,
ckpt/manager.py stale-tmp/leaf-mismatch, pinn/trainer.py loss history)."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _compat import int_grid

from repro.ckpt import CheckpointManager
from repro.core.engines import DerivativeEngine
from repro.core.network import make_network
from repro.serving import (DerivativeServer, ExecutableCache, ExecutableKey,
                           RequestTimeoutError, RequestTooLargeError,
                           ServerClosedError, ServerOverloadedError,
                           pad_fraction, pad_to, pick_bucket)


@pytest.fixture(scope="module")
def net():
    return make_network("dense", d_in=2, d_out=1, width=8, depth=2)


@pytest.fixture(scope="module")
def params(net):
    return net.init(jax.random.PRNGKey(0), dtype=jnp.float64)


@pytest.fixture(scope="module")
def x5():
    return jax.random.uniform(jax.random.PRNGKey(1), (5, 2), jnp.float64)


def direct(engine, net, params, x, order):
    """A direct jitted engine.grid call at ``x``'s shape."""
    return jax.jit(lambda p, xx: engine.grid(net, p, xx, order))(params, x)


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------

def test_pick_bucket_smallest_admissible():
    assert pick_bucket(1, (8, 16, 32)) == 8
    assert pick_bucket(8, (8, 16, 32)) == 8      # exact fit, no pad
    assert pick_bucket(9, (8, 16, 32)) == 16
    assert pick_bucket(32, (32, 8, 16)) == 32    # unsorted config ok


def test_pick_bucket_typed_errors():
    with pytest.raises(RequestTooLargeError):
        pick_bucket(33, (8, 16, 32))
    with pytest.raises(ValueError):
        pick_bucket(0, (8, 16))


def test_pad_to_zero_rows_and_identity(x5):
    padded = pad_to(x5, 8)
    assert padded.shape == (8, 2)
    np.testing.assert_array_equal(np.asarray(padded[:5]), np.asarray(x5))
    np.testing.assert_array_equal(np.asarray(padded[5:]), 0.0)
    assert pad_to(x5, 5) is x5                    # exact fit: no copy
    forced = pad_to(x5, 5, copy=True)             # ...unless the caller (a
    assert forced is not x5                       # donating launch) needs to
    np.testing.assert_array_equal(np.asarray(forced), np.asarray(x5))
    assert pad_fraction(5, 8) == pytest.approx(3 / 8)


# ---------------------------------------------------------------------------
# bucketing properties (hypothesis when installed, dense sweep otherwise)
# ---------------------------------------------------------------------------

@int_grid(("n", 1, 512), ("seed", 0, 10_000))
def test_pick_bucket_pad_to_roundtrip_property(n, seed):
    """For every admissible n: the bucket is the SMALLEST admissible one,
    pad_to round-trips the live rows bit-for-bit, the pad is zeros, and
    pad_fraction reports exactly the wasted share of the launch."""
    from repro.serving.bucketing import DEFAULT_BUCKETS
    b = pick_bucket(n)
    assert b in DEFAULT_BUCKETS and n <= b
    assert all(n > c for c in DEFAULT_BUCKETS if c < b)
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, 3), jnp.float64)
    padded = pad_to(x, b)
    assert padded.shape == (b, 3) and padded.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(padded[:n]), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(padded[n:]), 0.0)
    assert pad_fraction(n, b) == (b - n) / b


@int_grid(("n", 9, 512))
def test_pad_fraction_below_half_above_smallest_bucket(n):
    """The power-of-two ladder caps pad waste: any request larger than the
    smallest bucket lands in a bucket less than 2x its size."""
    from repro.serving.bucketing import DEFAULT_BUCKETS
    assert n > min(DEFAULT_BUCKETS)
    assert 0.0 <= pad_fraction(n, pick_bucket(n)) < 0.5


@int_grid(("extra", 1, 4096))
def test_pick_bucket_too_large_boundary_property(extra):
    """The largest bucket is an exact fit; one row more (and anything
    beyond) is the typed RequestTooLargeError, never a silent clamp."""
    from repro.serving.bucketing import DEFAULT_BUCKETS
    top = max(DEFAULT_BUCKETS)
    assert pick_bucket(top) == top
    with pytest.raises(RequestTooLargeError):
        pick_bucket(top + extra)


# ---------------------------------------------------------------------------
# executable cache
# ---------------------------------------------------------------------------

def _key(tag, bucket=8):
    return ExecutableKey("net", "ntp", "grid", (tag,), bucket, "float64")


def test_cache_hit_miss_counts():
    cache = ExecutableCache(capacity=4)
    fn_a, hit = cache.get_or_build(_key(1), lambda: "A")
    assert (fn_a, hit) == ("A", False)
    fn_a, hit = cache.get_or_build(_key(1), lambda: "A2")   # builder unused
    assert (fn_a, hit) == ("A", True)
    assert cache.stats() == {"hits": 1, "misses": 1, "evictions": 0,
                             "size": 1, "capacity": 4}


def test_equivalent_engine_spellings_share_one_executable(net, params, x5):
    """'ntp' and 'ntp/jnp' are the SAME engine: both servers canonicalize to
    one spec string, so across a shared cache the second spelling reuses the
    first spelling's compiled executable (a hit, not a second compile)."""
    from repro.core import EngineSpec
    assert str(EngineSpec.parse("ntp")) == str(EngineSpec.parse("ntp/jnp"))
    with DerivativeServer(net, params, "ntp", buckets=(8,),
                          flush_window_s=0.0) as a:
        a.grid(x5, 2, timeout=120.0)
        assert a.cache.stats()["misses"] == 1
        with DerivativeServer(net, params, "ntp/jnp", buckets=(8,),
                              flush_window_s=0.0) as b:
            assert b.engine_spec == a.engine_spec == "ntp"
            b.cache = a.cache          # shared cache: spellings must collide
            b.grid(x5, 2, timeout=120.0)
        stats = a.cache.stats()
        assert stats == {"hits": 1, "misses": 1, "evictions": 0,
                         "size": 1, "capacity": 32}


def test_cache_lru_eviction_at_capacity():
    cache = ExecutableCache(capacity=2)
    cache.get_or_build(_key(1), lambda: "A")
    cache.get_or_build(_key(2), lambda: "B")
    cache.get_or_build(_key(1), lambda: "A")     # A is now most-recent
    cache.get_or_build(_key(3), lambda: "C")     # evicts B, not A
    assert _key(1) in cache and _key(3) in cache
    assert _key(2) not in cache
    assert cache.stats()["evictions"] == 1
    _, hit = cache.get_or_build(_key(2), lambda: "B")   # evicted -> rebuild
    assert not hit


# ---------------------------------------------------------------------------
# served tables vs direct engine calls
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", ["ntp", "ntp/pallas"])
def test_served_grid_bit_identical_through_order_4(spec, net, params, x5):
    """The served table is bit-identical to a direct engine.grid call at the
    launch's bucket shape with the pad rows sliced off: padding, the AOT
    compile and the cache change no bit.  (A direct call at the natural
    N=5 shape is a different XLA program and may differ in the last ULP.)"""
    engine = DerivativeEngine.from_spec(spec)
    with DerivativeServer(net, params, spec, buckets=(8, 16),
                          flush_window_s=0.0) as server:
        for order in (0, 3, 4):
            served = server.submit(x5, order=order).result(timeout=120.0)
            assert served.bucket == 8
            want = direct(engine, net, params, pad_to(x5, served.bucket),
                          order)[:, :, :x5.shape[0]]
            np.testing.assert_array_equal(np.asarray(served.table),
                                          np.asarray(want))


def test_served_grid_autodiff_near_exact(net, params, x5):
    """The autodiff engine's vmapped towers vectorize differently at padded
    batch sizes (one-ULP reassociation), so it is pinned to near-exact
    instead of bit-for-bit."""
    engine = DerivativeEngine.from_spec("autodiff")
    with DerivativeServer(net, params, "autodiff", buckets=(8,),
                          flush_window_s=0.0) as server:
        served = server.grid(x5, 2, timeout=120.0)
        np.testing.assert_allclose(
            np.asarray(served),
            np.asarray(direct(engine, net, params, x5, 2)),
            rtol=1e-12, atol=1e-14)


@pytest.mark.parametrize("axes", [(0, 1), (0, 0, 1)])
def test_served_cross_bit_identical(axes, net, params, x5):
    engine = DerivativeEngine.from_spec("ntp")
    ref = jax.jit(lambda p, xx: engine.cross(net, p, xx, axes))(params, x5)
    with DerivativeServer(net, params, "ntp", buckets=(8,),
                          flush_window_s=0.0) as server:
        served = server.cross(x5, axes, timeout=120.0)
    np.testing.assert_array_equal(np.asarray(served), np.asarray(ref))


def test_pad_rows_never_leak_and_requests_coalesce(net, params):
    """Two same-group requests coalesce into ONE bucketed launch; each
    caller gets exactly its own rows back."""
    engine = DerivativeEngine.from_spec("ntp")
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    xa = jax.random.uniform(k1, (3, 2), jnp.float64)
    xb = jax.random.uniform(k2, (10, 2), jnp.float64)
    server = DerivativeServer(net, params, "ntp", buckets=(4, 8, 16),
                              autostart=False)
    try:
        fa = server.submit(xa, order=2)
        fb = server.submit(xb, order=2)
        assert server._drain_once()          # one batch serves both
        ra, rb = fa.result(0), fb.result(0)
        assert ra.bucket == rb.bucket == 16  # 3 + 10 -> smallest admissible
        assert ra.batch_rows == 13
        assert ra.pad_fraction == pytest.approx(3 / 16)
        m = server.metrics()
        assert m["batches"] == 1 and m["requests"] == 2
        assert m["cache"] == {"hits": 0, "misses": 1, "evictions": 0,
                              "size": 1, "capacity": 32}
        assert ra.table.shape == (2, 3, 3, 1)
        assert rb.table.shape == (2, 3, 10, 1)
        np.testing.assert_array_equal(
            np.asarray(ra.table),
            np.asarray(direct(engine, net, params, xa, 2)))
        np.testing.assert_array_equal(
            np.asarray(rb.table),
            np.asarray(direct(engine, net, params, xb, 2)))
    finally:
        server.close()


def test_single_request_picks_smallest_bucket(net, params):
    x = jax.random.uniform(jax.random.PRNGKey(4), (3, 2), jnp.float64)
    server = DerivativeServer(net, params, "ntp", buckets=(4, 8, 16),
                              autostart=False)
    try:
        fut = server.submit(x, order=1)
        server._drain_once()
        assert fut.result(0).bucket == 4
    finally:
        server.close()


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_exact_fit_request_never_lends_client_buffer_to_donation(net, params):
    """A single request whose row count exactly fits a bucket must not reach
    a donating executable as the CLIENT's own array -- donation deletes the
    input buffer in place, and pre-fix the client's jnp array was deleted
    out from under it on accelerator backends (pad_to returns x unchanged
    on an exact fit)."""
    server = DerivativeServer(net, params, "ntp", buckets=(8,),
                              autostart=False)
    server._donate = True       # emulate an accelerator backend on CPU
    launched = {}
    orig = server.cache.get_or_build

    def spy(key, builder):
        fn, hit = orig(key, builder)

        def wrapped(p, xp):
            launched["xp"] = xp
            return fn(p, xp)
        return wrapped, hit

    server.cache.get_or_build = spy
    x = jax.random.uniform(jax.random.PRNGKey(8), (8, 2), jnp.float64)
    try:
        fut = server.submit(x, order=1)
        assert server._drain_once()
        res = fut.result(0)
    finally:
        server.close()
    assert launched["xp"] is not x          # server-owned copy, not an alias
    assert res.table.shape == (2, 2, 8, 1)
    _ = np.asarray(x)   # client's array still alive (a donated-and-deleted
    #                     array raises "Array has been deleted" here)


def test_cancelled_request_is_dropped_not_fatal(net, params, x5):
    """A client cancelling a still-queued future must not kill the worker:
    pre-fix _execute called set_result on the cancelled future, raising
    InvalidStateError through the drain loop."""
    server = DerivativeServer(net, params, "ntp", buckets=(8, 16),
                              autostart=False)
    try:
        f_cancelled = server.submit(x5, order=1)
        assert f_cancelled.cancel()          # gave up while queued
        f_live = server.submit(x5, order=1)  # same group: one batch
        assert server._drain_once()          # pre-fix: InvalidStateError
        assert f_cancelled.cancelled()
        assert f_live.result(0).table.shape == (2, 2, 5, 1)
        # a drain over nothing but cancelled requests runs no batch
        f2 = server.submit(x5, order=1)
        assert f2.cancel()
        assert not server._drain_once()
    finally:
        server.close()


def test_close_tolerates_cancelled_pending(net, params, x5):
    server = DerivativeServer(net, params, "ntp", autostart=False)
    fut = server.submit(x5, order=1)
    assert fut.cancel()
    server.close()                           # pre-fix: InvalidStateError
    assert fut.cancelled()


def test_cache_hits_across_repeated_shapes_and_eviction(net, params):
    xa = jax.random.uniform(jax.random.PRNGKey(5), (3, 2), jnp.float64)
    xb = jax.random.uniform(jax.random.PRNGKey(6), (4, 2), jnp.float64)
    server = DerivativeServer(net, params, "ntp", buckets=(4, 8),
                              cache_capacity=1, autostart=False)
    try:
        for x in (xa, xb):                   # same bucket, same order
            server.submit(x, order=1)
            server._drain_once()
        stats = server.cache.stats()
        assert stats["misses"] == 1 and stats["hits"] == 1

        server.submit(xa, order=2)           # new order -> new executable,
        server._drain_once()                 # evicting order=1 (capacity 1)
        stats = server.cache.stats()
        assert stats["misses"] == 2 and stats["evictions"] == 1
        assert stats["size"] == 1

        server.submit(xa, order=1)           # evicted -> recompile
        server._drain_once()
        assert server.cache.stats()["misses"] == 3
    finally:
        server.close()


# ---------------------------------------------------------------------------
# backpressure, timeout, lifecycle
# ---------------------------------------------------------------------------

def test_queue_overflow_raises_typed_error(net, params, x5):
    server = DerivativeServer(net, params, "ntp", max_queue=2,
                              autostart=False)
    try:
        server.submit(x5, order=1)
        server.submit(x5, order=1)
        with pytest.raises(ServerOverloadedError):
            server.submit(x5, order=1)
    finally:
        server.close()


def test_request_timeout_raises_typed_error(net, params, x5):
    server = DerivativeServer(net, params, "ntp", autostart=False)
    try:
        with pytest.raises(RequestTimeoutError):
            server.grid(x5, 1, timeout=0.05)   # no worker -> deadline hits
    finally:
        server.close()


def test_close_fails_pending_and_rejects_new(net, params, x5):
    server = DerivativeServer(net, params, "ntp", autostart=False)
    fut = server.submit(x5, order=1)
    server.close()
    with pytest.raises(ServerClosedError):
        fut.result(0)
    with pytest.raises(ServerClosedError):
        server.submit(x5, order=1)


def test_submit_validation(net, params, x5):
    server = DerivativeServer(net, params, "ntp", buckets=(8,),
                              autostart=False)
    try:
        with pytest.raises(ValueError):
            server.submit(x5)                          # neither order nor axes
        with pytest.raises(ValueError):
            server.submit(x5, order=1, axes=(0,))      # both
        with pytest.raises(ValueError):
            server.submit(x5[:, :1], order=1)          # wrong d_in
        with pytest.raises(RequestTooLargeError):
            server.submit(jnp.zeros((9, 2)), order=1)  # beyond largest bucket
    finally:
        server.close()


def test_concurrent_clients_through_worker_thread(net, params):
    """End-to-end through the real worker: concurrent clients, coalesced
    or not, every table exact."""
    engine = DerivativeEngine.from_spec("ntp")
    xs = [jax.random.uniform(k, (4, 2), jnp.float64)
          for k in jax.random.split(jax.random.PRNGKey(7), 3)]
    with DerivativeServer(net, params, "ntp", buckets=(4, 8, 16),
                          flush_window_s=0.05) as server:
        results = [None] * len(xs)

        def client(i):
            results[i] = server.grid(xs[i], 2, timeout=120.0)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(xs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        m = server.metrics()
    assert m["requests"] == 3 and 1 <= m["batches"] <= 3
    for x, table in zip(xs, results):
        np.testing.assert_array_equal(
            np.asarray(table), np.asarray(direct(engine, net, params, x, 2)))


# ---------------------------------------------------------------------------
# checkpoint-backed serving
# ---------------------------------------------------------------------------

def test_from_checkpoint_serves_restored_params(tmp_path, net, params, x5):
    CheckpointManager(str(tmp_path)).save(42, params, blocking=True)
    engine = DerivativeEngine.from_spec("ntp")
    with DerivativeServer.from_checkpoint(str(tmp_path), net,
                                          dtype=jnp.float64) as server:
        served = server.grid(x5, 2, timeout=120.0)
    np.testing.assert_array_equal(
        np.asarray(served), np.asarray(direct(engine, net, params, x5, 2)))


def test_from_checkpoint_empty_dir_is_loud(tmp_path, net):
    with pytest.raises(FileNotFoundError):
        DerivativeServer.from_checkpoint(str(tmp_path), net)


# ---------------------------------------------------------------------------
# regression: launch/serve.py CLI (flags undisableable, --greedy unused,
# --prompt-len 0 crash)
# ---------------------------------------------------------------------------

def test_serve_cli_flags_can_be_disabled():
    from repro.launch import serve as serve_cli

    args = serve_cli.parse_args([])
    assert args.reduced is True and args.greedy is True
    args = serve_cli.parse_args(["--no-reduced", "--no-greedy"])
    assert args.reduced is False and args.greedy is False


def test_serve_cli_rejects_empty_prompt():
    from repro.launch import serve as serve_cli

    with pytest.raises(SystemExit):
        serve_cli.parse_args(["--prompt-len", "0"])


def test_serve_cli_select_token_consumes_greedy():
    from repro.launch import serve as serve_cli

    logits = jnp.asarray([[0.0, 10.0, 0.0], [5.0, 0.0, 0.0]])
    tok = serve_cli.select_token(logits, greedy=True)
    assert tok.shape == (2, 1) and tok.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(tok), [[1], [0]])
    # sampling path: sharp logits make the sample deterministic, proving
    # the flag reaches the decode rule (pre-fix it was parsed, never read)
    sampled = serve_cli.select_token(1e6 * logits, greedy=False,
                                     key=jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(sampled), [[1], [0]])
    with pytest.raises(ValueError):
        serve_cli.select_token(logits, greedy=False)   # no key


# ---------------------------------------------------------------------------
# regression: ckpt/manager.py (stale .tmp leak, opaque restore KeyError)
# ---------------------------------------------------------------------------

def test_ckpt_stale_tmp_swept_on_init(tmp_path):
    import os

    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": jnp.ones(2)}, blocking=True)
    stale = tmp_path / "step_0000000002.tmp"      # crashed writer's leftovers
    stale.mkdir()
    (stale / "shard_0.npz").write_bytes(b"partial")
    old = 1_000_000_000                           # long past stale_tmp_age_s
    os.utime(stale, (old, old))

    mgr2 = CheckpointManager(str(tmp_path))
    assert not stale.exists()
    assert mgr2.all_steps() == [1]
    np.testing.assert_array_equal(
        np.asarray(mgr2.restore(1, {"w": jnp.zeros(2)})["w"]), 1.0)


def test_ckpt_fresh_tmp_survives_other_managers(tmp_path):
    """A freshly-touched .tmp dir may belong to a LIVE writer in another
    manager/process (e.g. a server restoring from a directory a trainer is
    checkpointing into) -- constructing a second manager must not delete it;
    only this instance rewriting the SAME step clears its leftovers."""
    live = tmp_path / "step_0000000003.tmp"
    live.mkdir()
    (live / "shard_0.npz").write_bytes(b"in-flight")

    mgr = CheckpointManager(str(tmp_path))        # fresh mtime: not swept
    assert live.exists()

    mgr.save(3, {"w": jnp.ones(2)}, blocking=True)  # same step: tmp cleared,
    assert not live.exists()                        # write lands atomically
    np.testing.assert_array_equal(
        np.asarray(mgr.restore(3, {"w": jnp.zeros(2)})["w"]), 1.0)


def test_ckpt_restore_leaf_mismatch_is_loud(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"a": jnp.ones(2)}, blocking=True)
    # like has a leaf the checkpoint lacks -> named, not a KeyError
    with pytest.raises(ValueError, match="missing from the checkpoint.*'b'"):
        mgr.restore(1, {"a": jnp.zeros(2), "b": jnp.zeros(3)})

    mgr.save(2, {"a": jnp.ones(2), "extra": jnp.ones(1)}, blocking=True)
    with pytest.raises(ValueError, match="absent from `like`.*'extra'"):
        mgr.restore(2, {"a": jnp.zeros(2)})


# ---------------------------------------------------------------------------
# regression: pinn/trainer.py L-BFGS loss_history double count
# ---------------------------------------------------------------------------

def test_lbfgs_loss_history_not_double_counted():
    from repro.pinn import PINNRunConfig, train

    cfg = PINNRunConfig(k=1, width=8, depth=2, n_domain=24, n_origin=8,
                        adam_steps=6, lbfgs_steps=11, log_every=3,
                        resample_every=100)
    res = train(cfg)
    # pre-fix the every-10th L-BFGS callback losses were appended AND the
    # full res.loss_history concatenated, interleaving exact duplicates
    assert len(res.loss_history) == len(set(res.loss_history))
    # lambda is still sampled during the L-BFGS phase (3 adam logs + the
    # every-10th callback)
    assert len(res.lam_history) > 3
