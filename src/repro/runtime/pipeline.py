"""Pipeline parallelism: a GPipe microbatch schedule on a "stage" mesh axis.

For meshes deeper than the assigned 2x16x16 (or models whose layers exceed
what FSDP+TP can hold), layer groups become pipeline stages.  This module
provides the deterministic schedule as a composable primitive:

  * the model's layer groups are stacked on a leading ``stage`` axis and
    shard_map splits them across the mesh axis;
  * microbatches stream through ``n_stages + n_micro - 1`` ticks; each tick
    every stage applies its block and ``ppermute``s activations rightward
    (the classic GPipe bubble of (P-1)/(P-1+M) idle fraction);
  * outputs collect at the last stage and are returned replicated.

The schedule is forward-only here (inference / activation streaming); for
training one wraps it in jax.grad -- JAX differentiates through ppermute,
yielding the reverse schedule automatically (bubble doubles, as in GPipe).

tests/test_distributed_subproc.py validates it against a sequential apply on
a 4-stage host mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def gpipe(stage_fn: Callable, mesh: jax.sharding.Mesh, *, axis: str = "stage"):
    """Build a pipelined apply: (stage_params, microbatches) -> outputs.

    ``stage_fn(params_one_stage, x_mb) -> y_mb`` must be shape-preserving
    (residual-block style), as every stage runs the same program.
    ``stage_params`` leaves are stacked on a leading axis of size n_stages;
    ``microbatches`` is (n_micro, mb, ...).
    """
    n_stages = mesh.shape[axis]

    def pipelined(stage_params, xs):
        n_micro = xs.shape[0]
        ticks = n_micro + n_stages - 1

        def inner(params, xs):
            # params: this stage's slice (leading axis stripped to size 1);
            # xs arrives fully replicated (in_specs P())
            params = jax.tree_util.tree_map(lambda a: a[0], params)
            sid = jax.lax.axis_index(axis)
            right = [(i, (i + 1) % n_stages) for i in range(n_stages)]

            def tick(buf, t):
                # stage 0 ingests microbatch t (when in range); others take
                # the activation handed over by the previous stage
                mb_idx = jnp.clip(t, 0, n_micro - 1)
                fresh = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0, False)
                inp = jnp.where(sid == 0, fresh, buf)
                out = stage_fn(params, inp)
                handed = jax.lax.ppermute(out, axis, right)
                return handed, out

            _, outs = jax.lax.scan(tick, jnp.zeros_like(xs[0]),
                                   jnp.arange(ticks))
            # microbatch m exits the last stage at tick m + n_stages - 1
            done = jax.lax.dynamic_slice_in_dim(outs, n_stages - 1, n_micro, 0)
            # only the last stage holds real outputs; psum replicates them
            mask = (sid == n_stages - 1).astype(done.dtype)
            return jax.lax.psum(done * mask, axis)

        specs_p = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
        return jax.shard_map(inner, mesh=mesh,
                             in_specs=(specs_p, P()),
                             out_specs=P(), check_vma=False)(stage_params, xs)

    return pipelined


def pipeline_bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe idle fraction: (P-1)/(P-1+M); the scheduling-efficiency term."""
    return (n_stages - 1) / (n_stages - 1 + n_micro)
