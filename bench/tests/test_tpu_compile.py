"""The plain references compile for a described TPU v5e, at each cell's own
size, with no chip attached.

The TPU compiler's fusion pass crashed (SIGILL in its priority queue's
cost model) on the Navier-Stokes reference's loss from 1,024 points up,
which ended every run of that cell before its result.  The references
hide each derivative direction behind an optimization barrier
(``bench/reference/mlp.py:tower``); these compiles guard that.

    python -m pytest bench/tests/test_tpu_compile.py

One process at a time may load the TPU compiler, so under pytest-xdist run
with ``--dist loadfile``: the other workers' fixture skips these cases.
"""

from __future__ import annotations

import os

import pytest

from bench import harness, system

from .test_bench import CELLS


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("precision", ["highest", "high"])
@pytest.mark.parametrize("name", CELLS)
def test_reference_compiles_for_tpu(one_chip, name, precision):
    import jax
    import jax.numpy as jnp

    from bench.reference import mlp

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    cell = harness.find_cell(name)
    cfg, tr = cell.config, cell.traffic
    mode = harness.mode_module(cell)
    op = harness.reference_operator(cell)
    sizes = system.layer_sizes(cfg)
    layers = [(spec(a, b), spec(b)) for a, b in zip(sizes[:-1], sizes[1:])]
    if tr["mode"] == "train":
        bc = mode.boundary_grid(op.DOMAIN, tr["boundary_per_face"])
        f = mode._value_and_grad(op, tr["loss_weights"], precision)
        args = (layers, spec(tr["points"], cfg["d_in"]), spec(*bc.shape))
    else:
        f = jax.jit(lambda ls, x: mlp.pure_table(ls, x, tr["order"],
                                                 precision))
        args = (layers, spec(min(mode.REF_BLOCK, tr["points"]),
                             cfg["d_in"]))
    f.lower(*args).compile()
