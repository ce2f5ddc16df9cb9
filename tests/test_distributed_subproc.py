"""Multi-device integration tests, run in subprocesses with forced host
devices (the main test process must keep the default 1-device jax, so
anything needing a mesh gets its own interpreter with XLA_FLAGS set first).

The whole module is ``multidevice``-marked: deselected from tier-1 (each
test spins its own interpreter, tier-1 shouldn't pay that repeatedly) and
run as its own CI job.  ``run_py(code, devices=N)`` is the one helper every
mesh-shape sweep parametrizes -- tests/test_jet_shard.py reuses it for the
sharded-jet parity layer."""

import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.multidevice

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 480) -> str:
    """Run ``code`` in a fresh interpreter with ``devices`` forced host
    devices; asserts a zero exit and returns the child's stdout.  On
    failure the assertion surfaces BOTH streams -- a child that fails an
    assert after printing diagnostics puts the story in stdout, not just
    the traceback in stderr."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={devices}").strip()
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["TF_CPP_MIN_LOG_LEVEL"] = "2"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout, env=env)
    assert out.returncode == 0, (
        f"child exited {out.returncode}\n"
        f"--- stdout (last 4000) ---\n{out.stdout[-4000:]}\n"
        f"--- stderr (last 4000) ---\n{out.stderr[-4000:]}")
    return out.stdout


def test_small_mesh_train_step_runs():
    """A real (executed, not just compiled) sharded train step on a 4x2 mesh."""
    print(run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType, PartitionSpec as P
        from repro.configs import get_arch
        from repro.configs.base import ShapeCfg
        from repro.launch.sharding import build_train_step
        from repro.data.tokens import synthetic_batch
        from repro.models import init_model
        from repro.optim import adam_init

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = get_arch("qwen3-0.6b").reduced()
        shape = ShapeCfg("t", 32, 8, "train")
        built = build_train_step(cfg, mesh, shape, fsdp=False)
        params, _ = init_model(cfg, jax.random.PRNGKey(0))
        opt = adam_init(params)
        batch = synthetic_batch(cfg, shape, 0)
        with mesh:
            p2, o2, loss, m = built.fn(params, opt, batch)
            p3, o3, loss2, m = built.fn(p2, o2, synthetic_batch(cfg, shape, 1))
        assert jnp.isfinite(loss) and jnp.isfinite(loss2), (loss, loss2)
        print("loss", float(loss), "->", float(loss2))
    """))


def test_small_mesh_serve_step_runs():
    print(run_py("""
        import jax, jax.numpy as jnp
        from repro.configs import get_arch
        from repro.configs.base import ShapeCfg
        from repro.launch.sharding import build_serve_step
        from repro.models import init_model, decode_state_specs

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        cfg = get_arch("rwkv6-3b").reduced()
        shape = ShapeCfg("d", 32, 8, "decode")
        built = build_serve_step(cfg, mesh, shape)
        params, _ = init_model(cfg, jax.random.PRNGKey(0))
        st = decode_state_specs(cfg, 8, 32, abstract=False)
        with mesh:
            lg, st2 = built.fn(params, jnp.zeros((8, 1), jnp.int32), st)
        assert jnp.isfinite(lg.astype(jnp.float32)).all()
        print("decode ok", lg.shape)
    """))


def test_dryrun_lower_compile_small_mesh():
    """The dry-run machinery end-to-end on an 8-device version of the mesh."""
    print(run_py("""
        import jax
        from repro.configs import get_arch, SHAPES
        from repro.configs.base import ShapeCfg
        from repro.launch import sharding as shd
        from repro.launch.hlo_static import analyze

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = get_arch("granite-3-2b").reduced()
        shape = ShapeCfg("t", 64, 8, "train")
        built = shd.build_train_step(cfg, mesh, shape, fsdp=True)
        with mesh:
            lowered = built.fn.lower(*built.arg_specs)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        totals = analyze(compiled.as_text())
        assert totals.flops > 0
        print("flops", totals.flops, "coll", totals.total_collective_bytes)
    """))


def test_compressed_psum_matches_fp32():
    print(run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import AxisType, PartitionSpec as P
        from repro.parallel.compression import compressed_psum_tree, ef_init

        mesh = jax.make_mesh((8,), ("pod",), axis_types=(AxisType.Auto,))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * 2.0
        err = jnp.zeros((8, 64), jnp.bfloat16)

        @partial(jax.shard_map, mesh=mesh, in_specs=(P("pod"), P("pod")),
                 out_specs=(P("pod"), P("pod")))
        def red(g, e):
            out, e2 = compressed_psum_tree({"g": g}, {"g": e}, "pod")
            return out["g"], e2["g"]

        got, err2 = red(g, err)
        want = jnp.sum(g, 0, keepdims=True)  # psum replicates the sum
        rel = float(jnp.max(jnp.abs(got[0] - want[0])) / jnp.max(jnp.abs(want)))
        assert rel < 0.02, rel
        print("compressed psum rel err", rel)
    """))


def test_gpipe_matches_sequential():
    """GPipe microbatch schedule == sequential stage application (4 stages)."""
    print(run_py("""
        import jax, jax.numpy as jnp
        from repro.runtime.pipeline import gpipe

        mesh = jax.make_mesh((4,), ("stage",))
        W = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 16)) * 0.2
        stage = lambda p, x: x + jnp.tanh(x @ p["w"])
        xs = jax.random.normal(jax.random.PRNGKey(1), (6, 8, 16))
        with mesh:
            y = gpipe(stage, mesh)({"w": W}, xs)
        ref = xs
        for s in range(4):
            ref = jax.vmap(lambda mb: stage({"w": W[s]}, mb))(ref)
        err = float(jnp.max(jnp.abs(y - ref)))
        assert err < 1e-5, err
        print("gpipe exact:", err)
    """, devices=4))


def test_elastic_restore_across_meshes(tmp_path):
    """Checkpoint written under a 4x2 mesh restores onto 2x4 (elastic)."""
    print(run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.ckpt import CheckpointManager

        w = jnp.arange(64.0).reshape(8, 8)
        m1 = jax.make_mesh((4, 2), ("data", "model"))
        t1 = jax.device_put(w, NamedSharding(m1, P("data", "model")))
        mgr = CheckpointManager(r"{tmp_path}")
        mgr.save(7, {{"w": t1}}, blocking=True)

        m2 = jax.make_mesh((2, 4), ("data", "model"))
        sh2 = {{"w": NamedSharding(m2, P("model", "data"))}}
        back = mgr.restore(7, {{"w": jnp.zeros((8, 8))}}, shardings=sh2)
        np.testing.assert_array_equal(np.asarray(back["w"]), np.arange(64.0).reshape(8, 8))
        print("elastic restore ok", back["w"].sharding)
    """))
