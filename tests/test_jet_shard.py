"""Parity/property layer for the sharded jet engine (repro.parallel.jet_shard).

Two tiers, mirroring the rest of the suite:

* **in-process** (tier-1): everything provable on the default 1-device jax --
  pad/remainder units, mesh-resolution policy, bitwise parity of a 1-device
  ``ShardedEngine`` against its inner engine (the shard_map wrapper itself
  must be a no-op on the numbers), compressor parsing/masking invariants,
  error-feedback unbiasedness, and a sharded train step checked bit-for-bit
  against the plain value_and_grad + Adam loop it claims to equal.
* **multidevice** (own CI job, ``-m multidevice``): subprocess children with
  XLA-forced host devices pin the real claims -- sharded grid/cross tables
  bit-identical (0.0 max abs diff) to the single-device launch through
  order 4 on EVERY registered operator under both ntp impls, including
  batches that don't divide the mesh; cross-process hash equality between a
  1-device and an 8-device interpreter; EF compression convergence over a
  real 8-way psum; a 4x2-mesh trainer smoke (Adam + sharded L-BFGS, with
  and without compression); and sharded serving parity + mesh-aware cache
  keys.  ``run_py`` comes from tests/test_distributed_subproc.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.engines import NTPEngine
from repro.core.network import make_network
from repro.data.collocation import sample_box
from repro.parallel.compression import compressed_psum_tree, topk_mask
from repro.parallel.jet_shard import (DATA_AXIS, ShardedEngine, _compressor,
                                      build_sharded_train_step, pad_rows,
                                      resolve_mesh)
from test_distributed_subproc import run_py


def mesh1():
    return jax.make_mesh((1,), (DATA_AXIS,))


# ---------------------------------------------------------------------------
# padding / mesh resolution units
# ---------------------------------------------------------------------------

def test_pad_rows_remainder_and_identity():
    x = jnp.arange(14.0).reshape(7, 2)
    padded, n = pad_rows(x, 4)
    assert n == 7 and padded.shape == (8, 2)
    np.testing.assert_array_equal(np.asarray(padded[:7]), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(padded[7:]), 0.0)
    # already divisible: the SAME array comes back, no copy, no pad
    same, n2 = pad_rows(x, 7)
    assert same is x and n2 == 7
    with pytest.raises(ValueError, match="multiple"):
        pad_rows(x, 0)


def test_resolve_mesh_policy():
    assert resolve_mesh(None, 0) is None
    assert resolve_mesh(None, None) is None
    m = resolve_mesh(None, 1)
    assert m.shape[DATA_AXIS] == 1
    # an explicit mesh wins, but must carry the data axis
    assert resolve_mesh(mesh1(), 0).shape[DATA_AXIS] == 1
    with pytest.raises(ValueError, match="no 'data' axis"):
        resolve_mesh(jax.make_mesh((1,), ("model",)))
    with pytest.raises(ValueError, match="exceeds"):
        resolve_mesh(None, jax.device_count() + 1)


def test_sharded_engine_rejects_meshes_without_data_axis():
    with pytest.raises(ValueError, match="axis"):
        ShardedEngine(NTPEngine("jnp"), jax.make_mesh((1,), ("model",)))


# ---------------------------------------------------------------------------
# 1-device shard_map wrapper is numerically a no-op
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_one_device_sharded_engine_is_bitwise_identity(impl):
    """ShardedEngine over a (1,) mesh must reproduce the inner engine's
    derivs/grid/cross tables bit-for-bit -- any diff here means the wrapper
    itself (pad, shard_map, slice) perturbs the numbers."""
    eng = NTPEngine(impl)
    sh = ShardedEngine(eng, mesh1())
    assert sh.spec == eng.spec            # the mesh is an execution detail
    assert sh.n_shards == 1
    net = make_network("dense", d_in=2, d_out=1, width=8, depth=2)
    params = net.init(jax.random.PRNGKey(0), dtype=jnp.float64)
    x = sample_box(jax.random.PRNGKey(1), ((-1.0, 1.0), (0.0, 1.0)), 9,
                   jnp.float64)

    ref = eng.grid(net, params, x, 4)
    got = sh.grid(net, params, x, 4)
    assert got.shape == ref.shape == (2, 5, 9, 1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    np.testing.assert_array_equal(
        np.asarray(sh.cross(net, params, x, (0, 1))),
        np.asarray(eng.cross(net, params, x, (0, 1))))

    v = jnp.full_like(x, 0.5)
    np.testing.assert_array_equal(
        np.asarray(sh.derivs(net, params, x, 3, v)),
        np.asarray(eng.derivs(net, params, x, 3, v)))


# ---------------------------------------------------------------------------
# compressor parsing and masking invariants
# ---------------------------------------------------------------------------

def test_compressor_spec_parsing():
    assert _compressor(None) is None
    assert _compressor("") is None
    assert _compressor("none") is None
    assert _compressor("NONE") is None
    assert _compressor("int8") is compressed_psum_tree
    assert callable(_compressor("topk:0.25"))
    with pytest.raises(ValueError, match="unknown grad compression"):
        _compressor("gzip")


def test_topk_mask_keeps_exactly_the_largest():
    # distinct magnitudes, shuffled, alternating signs: no ties to blur k
    mags = np.random.RandomState(0).permutation(np.arange(1.0, 101.0))
    g = jnp.asarray(mags * np.where(np.arange(100) % 2, 1.0, -1.0))
    keep = topk_mask(g, 0.1)
    assert int(keep.sum()) == 10
    assert float(jnp.min(jnp.abs(g[keep]))) > float(jnp.max(jnp.abs(g[~keep])))
    assert bool(topk_mask(g, 1.0).all())
    # at least one entry survives even for vanishing fractions
    assert int(topk_mask(g, 1e-9).sum()) == 1
    for bad in (0.0, -0.1, 1.5):
        with pytest.raises(ValueError, match="k_frac"):
            topk_mask(g, bad)


def _reduce_loop(comp, g, err_dtype, steps):
    """Accumulate ``steps`` compressed reductions of the same per-device
    gradient block over a 1-device mesh; EF makes the running mean converge
    to the true sum."""
    mesh = mesh1()

    def body(gg, ee):
        out, e2 = comp({"g": gg}, {"g": ee}, DATA_AXIS)
        return out["g"], e2["g"]

    red = jax.jit(jax.shard_map(body, mesh=mesh,
                                in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
                                out_specs=(P(DATA_AXIS), P(DATA_AXIS)),
                                check_vma=False))
    err = jnp.zeros(g.shape, err_dtype)
    acc = jnp.zeros(g.shape[1:])
    for _ in range(steps):
        out, err = red(g, err)
        acc = acc + out[0]
    return acc / steps


@pytest.mark.parametrize("spec,tol", [("int8", 0.01), ("topk:0.2", 0.1)])
def test_error_feedback_accumulation_is_unbiased(spec, tol):
    """sum_t compressed(g) / T -> psum(g): the residual carried by error
    feedback bounds the accumulated bias by |err_T| / T."""
    comp = _compressor(spec)
    g = jax.random.normal(jax.random.PRNGKey(0), (1, 96)) * 3.0
    got = _reduce_loop(comp, g, jnp.float32, steps=100)
    rel = float(jnp.max(jnp.abs(got - g[0])) / jnp.max(jnp.abs(g)))
    assert rel < tol, rel


# ---------------------------------------------------------------------------
# sharded train step vs the plain loop it claims to equal
# ---------------------------------------------------------------------------

def _toy_loss(params, pts):
    pred = pts @ params["w"] + params["b"]
    loss = jnp.mean((pred - jnp.sin(pts[:, :1])) ** 2)
    return loss, {"residual": loss}


def test_sharded_train_step_matches_plain_adam():
    """The 1-shard sharded step equals the plain value_and_grad + Adam loop
    to float32 resolution: adam_update deliberately runs its moment/update
    math in fp32 (repro/optim/adam.py), and the two loops are DIFFERENT
    compiled programs whose fp32 rounding order may differ.  The bitwise
    claim lives at the engine level (tables above), not the optimizer."""
    from repro.optim import adam_init, adam_update

    params = {"w": jnp.full((3, 1), 0.1, jnp.float64),
              "b": jnp.zeros((1,), jnp.float64)}
    pts = jax.random.uniform(jax.random.PRNGKey(0), (16, 3), jnp.float64)

    built = build_sharded_train_step(_toy_loss, mesh1(), adam_lr=1e-2)
    assert built.n_shards == 1 and built.compression is None
    err = built.init_err(params)
    p_sh, s_sh = params, adam_init(params)
    p_ref, s_ref = params, adam_init(params)
    for _ in range(4):
        p_sh, s_sh, (loss_sh, aux), err = built.step(p_sh, s_sh, pts, err)
        (loss_ref, _), grads = jax.value_and_grad(
            _toy_loss, has_aux=True)(p_ref, pts)
        p_ref, s_ref = adam_update(grads, s_ref, p_ref, 1e-2)
        np.testing.assert_allclose(float(loss_sh), float(loss_ref),
                                   rtol=1e-6)
        np.testing.assert_allclose(float(aux["residual"]), float(loss_sh),
                                   rtol=1e-12)
    for k in params:
        np.testing.assert_allclose(np.asarray(p_sh[k]), np.asarray(p_ref[k]),
                                   rtol=1e-6, atol=1e-9)
    # the EF state is untouched on the exact-psum path
    assert all(float(jnp.max(jnp.abs(e))) == 0.0
               for e in jax.tree_util.tree_leaves(err))


@pytest.mark.parametrize("compression", ["int8", "topk:0.5"])
def test_sharded_train_step_with_compression_descends(compression):
    from repro.optim import adam_init

    params = {"w": jnp.full((3, 1), 0.1, jnp.float64),
              "b": jnp.zeros((1,), jnp.float64)}
    pts = jax.random.uniform(jax.random.PRNGKey(0), (16, 3), jnp.float64)
    built = build_sharded_train_step(_toy_loss, mesh1(), adam_lr=1e-2,
                                     compression=compression)
    err = built.init_err(params)
    assert all(e.shape[0] == 1 for e in jax.tree_util.tree_leaves(err))
    state = adam_init(params)
    losses = []
    for _ in range(30):
        params, state, (loss, _), err = built.step(params, state, pts, err)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_pinn_loss_mesh_knob_is_bitwise_neutral():
    """pinn_loss(mesh=1-device mesh) must equal the unsharded loss exactly
    -- the knob changes execution, never the objective."""
    from repro.pinn.losses import pinn_loss
    from repro.pinn.operators import exact_values, get_operator

    op = get_operator("heat")
    net = make_network("dense", d_in=op.d_in, d_out=op.d_out, width=8,
                       depth=2)
    params = net.init(jax.random.PRNGKey(0), dtype=jnp.float64)
    pts = sample_box(jax.random.PRNGKey(1), op.domain, 12, jnp.float64)
    bc = sample_box(jax.random.PRNGKey(2), op.domain, 6, jnp.float64)
    kw = dict(op=op, pts=pts, bc_pts=bc,
              bc_vals=exact_values(op, bc, jnp.float64), net=net)
    ref, ref_aux = pinn_loss(params, **kw)
    got, got_aux = pinn_loss(params, mesh=mesh1(), **kw)
    assert float(got) == float(ref)
    assert float(got_aux["residual"]) == float(ref_aux["residual"])


# ---------------------------------------------------------------------------
# multidevice: the real parity claims, one forced-device subprocess each
# ---------------------------------------------------------------------------

@pytest.mark.multidevice
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_sharded_grid_cross_bit_parity_every_operator(impl):
    """THE acceptance criterion: on an 8-device host mesh, sharded grid
    (through order 4) and cross tables are bit-identical (0.0 max abs diff)
    to the un-sharded launch for every registered operator, on a batch of
    19 rows (pad-to-24 remainder) and a 3-row batch (fewer rows than
    devices)."""
    print(run_py(f"""
        import jax, jax.numpy as jnp
        from repro.core.engines import NTPEngine
        from repro.core.network import make_network
        from repro.data.collocation import sample_box
        from repro.parallel.jet_shard import ShardedEngine, resolve_mesh
        from repro.pinn.operators import get_operator, operator_names

        eng = NTPEngine({impl!r})
        sh = ShardedEngine(eng, resolve_mesh(data_parallel=8))
        worst = 0.0
        for name in operator_names():
            op = get_operator(name)
            net = make_network("dense", d_in=op.d_in, d_out=op.d_out,
                               width=6, depth=2)
            params = net.init(jax.random.PRNGKey(0), dtype=jnp.float32)
            x = sample_box(jax.random.PRNGKey(1), op.domain, 19, jnp.float32)
            ref = eng.grid(net, params, x, 4)
            got = sh.grid(net, params, x, 4)
            assert got.shape == ref.shape == (op.d_in, 5, 19, op.d_out)
            dg = float(jnp.max(jnp.abs(got - ref)))
            crosses = op.mixed if op.mixed else \\
                (tuple(range(min(op.d_in, 2))),)
            dc = 0.0
            for axes in crosses:
                refc = eng.cross(net, params, x, axes)
                gotc = sh.cross(net, params, x, axes)
                dc = max(dc, float(jnp.max(jnp.abs(gotc - refc))))
            print(f"{{name}}: grid={{dg}} cross={{dc}} "
                  f"(crosses={{crosses}})")
            worst = max(worst, dg, dc)
        # fewer live rows than devices: 3 rows pad to 8, one row per shard
        op = get_operator("heat")
        net = make_network("dense", d_in=op.d_in, d_out=op.d_out,
                           width=6, depth=2)
        params = net.init(jax.random.PRNGKey(0), dtype=jnp.float32)
        x3 = sample_box(jax.random.PRNGKey(2), op.domain, 3, jnp.float32)
        d3 = float(jnp.max(jnp.abs(sh.grid(net, params, x3, 4)
                                   - eng.grid(net, params, x3, 4))))
        print("tiny-batch grid diff", d3)
        worst = max(worst, d3)
        assert worst == 0.0, worst
        print("bit parity OK, impl={impl}")
    """, devices=8, timeout=600))


@pytest.mark.multidevice
def test_cross_process_bit_parity_1_vs_8_devices():
    """Stronger than in-process parity: a 1-device interpreter and an
    8-device sharded interpreter must print identical result hashes for
    the same order-4 grid -- sharding is invisible even across backends
    initialized with different device counts."""
    child = """
        import hashlib
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.engines import NTPEngine
        from repro.core.network import make_network
        from repro.data.collocation import sample_box
        from repro.parallel.jet_shard import ShardedEngine, resolve_mesh
        from repro.pinn.operators import get_operator

        op = get_operator("heat")
        net = make_network("dense", d_in=op.d_in, d_out=op.d_out,
                           width=8, depth=2)
        params = net.init(jax.random.PRNGKey(0), dtype=jnp.float32)
        x = sample_box(jax.random.PRNGKey(1), op.domain, 19, jnp.float32)
        for impl in ("jnp", "pallas"):
            eng = NTPEngine(impl)
            if jax.device_count() > 1:
                eng = ShardedEngine(eng, resolve_mesh(
                    data_parallel=jax.device_count()))
            table = np.asarray(eng.grid(net, params, x, 4), np.float32)
            print(impl, hashlib.sha256(table.tobytes()).hexdigest())
    """
    single = run_py(child, devices=1, timeout=600)
    sharded = run_py(child, devices=8, timeout=600)
    assert single.split() == sharded.split(), (single, sharded)


@pytest.mark.multidevice
@pytest.mark.parametrize("devices", [4, 8])
def test_error_feedback_convergence_on_real_mesh(devices):
    """int8 and top-k EF reductions over a real N-way psum: the running
    mean of compressed all-reduces converges to the exact fp32 sum."""
    print(run_py(f"""
        import jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import AxisType, PartitionSpec as P
        from repro.parallel.compression import (compressed_psum_tree,
                                                topk_psum_tree)

        D = {devices}
        mesh = jax.make_mesh((D,), ("data",), axis_types=(AxisType.Auto,))
        g = jax.random.normal(jax.random.PRNGKey(0), (D, 128)) * 3.0
        true = jnp.sum(g, 0)
        cases = (("int8", compressed_psum_tree, 0.01),
                 ("topk:0.2",
                  lambda gg, ee, ax: topk_psum_tree(gg, ee, ax, k_frac=0.2),
                  0.05))
        for name, comp, tol in cases:
            red = jax.shard_map(
                lambda gg, ee, _c=comp: tuple(
                    t["g"] for t in _c({{"g": gg}}, {{"g": ee}}, "data")),
                mesh=mesh, in_specs=(P("data"), P("data")),
                out_specs=(P("data"), P("data")), check_vma=False)
            err = jnp.zeros((D, 128), jnp.float32)
            acc = jnp.zeros((128,))
            K = 50
            for _ in range(K):
                out, err = red(g, err)
                acc = acc + out[0]
            rel = float(jnp.max(jnp.abs(acc / K - true))
                        / jnp.max(jnp.abs(true)))
            print(name, "rel", rel)
            assert rel < tol, (name, rel)
    """, devices=devices))


@pytest.mark.multidevice
def test_trainer_smoke_on_4x2_mesh():
    """train_operator end-to-end on a 4x2 ("data", "model") host mesh --
    Adam via the sharded step (plain psum AND int8 EF) plus the sharded
    L-BFGS phase; also pins the n_domain divisibility guard."""
    print(run_py("""
        import jax, numpy as np
        jax.config.update("jax_enable_x64", True)
        from repro.pinn import OperatorRunConfig, train_operator

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        for compression in (None, "int8"):
            cfg = OperatorRunConfig(op="heat", width=8, depth=2, n_domain=32,
                                    n_bc=8, adam_steps=25, lbfgs_steps=3,
                                    adam_lr=2e-3, mesh=mesh, log_every=5,
                                    eval_pts_per_axis=8,
                                    grad_compression=compression)
            res = train_operator(cfg)
            assert np.isfinite(res.loss_history).all(), res.loss_history
            assert res.loss_history[-1] < res.loss_history[0], \\
                res.loss_history
            assert np.isfinite(res.l2_error)
            print(compression, res.loss_history[0], "->",
                  res.loss_history[-1], "l2", res.l2_error)
        try:
            train_operator(OperatorRunConfig(op="heat", n_domain=30,
                                             adam_steps=1, mesh=mesh))
        except ValueError as e:
            print("divisibility guard:", e)
        else:
            raise AssertionError("n_domain=30 on a 4-way data axis "
                                 "must be rejected")
    """, devices=8, timeout=600))


@pytest.mark.multidevice
def test_serving_sharded_parity_and_mesh_keyed_cache():
    """A mesh-backed DerivativeServer serves grid/cross tables bit-identical
    to JITTED direct engine calls (the serving contract since PR 6 -- the
    eager path compiles differently and sits ~1 f32 ULP away); the
    executable-cache key carries the mesh shape and bucket/mesh mismatches
    are rejected at construction."""
    print(run_py("""
        import jax, jax.numpy as jnp
        from repro.core.engines import NTPEngine
        from repro.core.network import make_network
        from repro.serving.server import DerivativeServer

        mesh = jax.make_mesh((4,), ("data",))
        net = make_network("dense", d_in=2, d_out=1, width=8, depth=2)
        params = net.init(jax.random.PRNGKey(0), dtype=jnp.float32)
        eng = NTPEngine("jnp")
        srv = DerivativeServer(net, params, "ntp", buckets=(8, 16),
                               mesh=mesh)
        try:
            assert srv.mesh_key == (("data", 4),), srv.mesh_key
            x = jax.random.uniform(jax.random.PRNGKey(1), (5, 2),
                                   jnp.float32)
            ref_g = jax.jit(
                lambda p, xx: eng.grid(net, p, xx, 3))(params, x)
            ref_c = jax.jit(
                lambda p, xx: eng.cross(net, p, xx, (0, 1)))(params, x)
            dg = float(jnp.max(jnp.abs(srv.grid(x, 3, timeout=120)
                                       - ref_g)))
            dc = float(jnp.max(jnp.abs(srv.cross(x, (0, 1), timeout=120)
                                       - ref_c)))
            print("serving diffs", dg, dc)
            assert dg == 0.0 and dc == 0.0, (dg, dc)
        finally:
            srv.close()
        try:
            DerivativeServer(net, params, "ntp", buckets=(6,), mesh=mesh)
        except ValueError as e:
            print("bucket guard:", e)
        else:
            raise AssertionError("bucket 6 on a 4-way mesh must be "
                                 "rejected")
    """, devices=4, timeout=600))
