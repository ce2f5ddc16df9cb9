"""Data-parallel jet computation and training over a device mesh.

The paper's quasilinear jet forward is embarrassingly data-parallel over
collocation points: every row of a batched jet is computed independently
(dense layers act row-wise, a transformer's token axis is per-point), and
the jet coefficient axis stays local to each point.  That makes the
multi-device story exact, not approximate:

* :class:`ShardedEngine` wraps any :class:`~repro.core.engines.
  DerivativeEngine` so its ``derivs``/``grid``/``cross`` run under
  ``shard_map`` over the ``"data"`` axis of a mesh -- the batch splits
  across devices, parameters are replicated, and (for the ntp engines)
  the result is **bit-identical** to the single-device call, because every
  device runs exactly the per-row arithmetic the single-device launch
  runs.  Batches that don't divide the mesh are zero-padded up front and
  sliced after (pad rows never reach the caller);
* :func:`build_sharded_train_step` jits one whole data-parallel training
  step -- local loss + grad on each device's shard, a gradient
  all-reduce (plain ``psum`` or the int8 / top-k error-feedback
  compressors from :mod:`repro.parallel.compression`), and a replicated
  Adam update -- as a single ``shard_map`` program, so the collocation
  batch never materializes on one device;
* :func:`resolve_mesh` is the one config knob -> mesh policy shared by
  the trainer, the serving layer, and the example CLIs.

Everything here composes with both engine impls: the Pallas kernels run
per-device inside ``shard_map`` exactly as they do single-device (the
kernel never sees the mesh).  ``check_vma=False`` throughout: the fused
kernels are ``custom_vjp`` ops, which the replication checker cannot see
through.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro.core.engines import DerivativeEngine
from repro.core.network import Network

from .compression import compressed_psum_tree, topk_psum_tree

DATA_AXIS = "data"


def auto_axes(mesh: jax.sharding.Mesh) -> jax.sharding.Mesh:
    """The same devices and axis names with every axis ``AxisType.Auto``.

    ``jax.make_mesh`` builds ``Explicit`` axes, whose arrays carry their
    sharding in their type; the engines reshape ``shard_map`` outputs, which
    such types refuse.  The jet paths here shard only through ``shard_map``
    and leave everything else to the compiler."""
    return jax.sharding.Mesh(mesh.devices, mesh.axis_names,
                             axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def resolve_mesh(mesh=None, data_parallel: int = 0,
                 axis: str = DATA_AXIS) -> Optional[jax.sharding.Mesh]:
    """The one knob -> mesh policy: an explicit mesh wins (it must carry the
    data axis), otherwise ``data_parallel=N`` builds a 1-D ``(N,)`` mesh over
    the first N local devices, and 0/None means single-device (no mesh).
    Either way the mesh's axes come back ``Auto`` (:func:`auto_axes`)."""
    if mesh is not None:
        if axis not in mesh.shape:
            raise ValueError(f"mesh {mesh!r} has no {axis!r} axis "
                             f"(axes: {tuple(mesh.shape)})")
        return auto_axes(mesh)
    if not data_parallel:
        return None
    n = int(data_parallel)
    if n < 1:
        raise ValueError(f"data_parallel must be >= 1, got {n}")
    if n > jax.device_count():
        raise ValueError(
            f"data_parallel={n} exceeds the {jax.device_count()} visible "
            f"device(s); set XLA_FLAGS=--xla_force_host_platform_device_count"
            f"={n} before importing jax, or lower the knob")
    return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,))


def pad_rows(x: jnp.ndarray, multiple: int) -> Tuple[jnp.ndarray, int]:
    """Zero-pad the leading (batch) axis of ``x`` up to a multiple of
    ``multiple``; returns (padded, original row count).  The pad rows are
    well-defined inputs (zeros), compute in parallel with the live rows,
    and are sliced off by the caller -- padding never changes live bits
    because every row of the jet forward is batch-independent."""
    n = x.shape[0]
    if multiple < 1:
        raise ValueError(f"multiple must be >= 1, got {multiple}")
    rem = n % multiple
    if rem == 0:
        return x, n
    pad = jnp.zeros((multiple - rem,) + x.shape[1:], x.dtype)
    return jnp.concatenate([x, pad], axis=0), n


@dataclass(frozen=True)
class ShardedEngine(DerivativeEngine):
    """Run any engine's batched jet calls data-parallel over a mesh.

    Only ``derivs`` is sharded directly; ``grid`` and ``cross`` are
    inherited from the base class, which assembles them from ``derivs`` --
    so the direction tiling happens *before* the shard split and every
    (direction, point) row lands on some device with per-row arithmetic
    identical to the single-device launch.  For the ntp engines that makes
    sharded grid/cross tables bit-identical to unsharded ones (pinned by
    tests/test_jet_shard.py); ``AutodiffEngine`` is vmap-vectorized and
    batch-size-dependent at the last ULP, so parity there is near-exact
    rather than bitwise.

    ``spec`` deliberately reports the INNER engine's spec: the sharded
    engine computes the same mathematical function; the mesh is an
    execution detail (surfaces that must distinguish the two -- e.g. the
    serving executable cache -- key on the mesh shape separately).
    """

    inner: DerivativeEngine
    mesh: jax.sharding.Mesh
    axis: str = DATA_AXIS

    def __post_init__(self):
        if self.axis not in self.mesh.shape:
            raise ValueError(f"mesh has no {self.axis!r} axis "
                             f"(axes: {tuple(self.mesh.shape)})")
        object.__setattr__(self, "mesh", auto_axes(self.mesh))

    @property
    def n_shards(self) -> int:
        return self.mesh.shape[self.axis]

    @property
    def spec(self) -> str:
        return self.inner.spec

    def derivs(self, net: Network, params, x: jnp.ndarray, order: int,
               tangent: jnp.ndarray | None = None) -> jnp.ndarray:
        if tangent is None:
            tangent = jnp.ones_like(x)
        xp, n = pad_rows(x, self.n_shards)
        vp, _ = pad_rows(tangent, self.n_shards)
        inner, axis = self.inner, self.axis

        f = jax.shard_map(
            lambda p, xs, vs: inner.derivs(net, p, xs, order, vs),
            mesh=self.mesh, in_specs=(P(), P(axis), P(axis)),
            out_specs=P(None, axis, None), check_vma=False)
        return f(params, xp, vp)[:, :n]

    def _batched_directional(self, net: Network, params, x: jnp.ndarray,
                             dirs: jnp.ndarray, order: int) -> jnp.ndarray:
        out = super()._batched_directional(net, params, x, dirs, order)
        # Replicate before grid/cross assembly.  ``derivs`` leaves its output
        # sharded over the tiled (direction x point) batch axis, so the
        # polarization sum in ``cross`` would reduce over a device-sharded
        # direction axis -- a cross-device accumulation whose summation
        # order differs from the single-device launch (a 1-ULP f32 diff on
        # order-4 polarizations).  The all-gather is pure data
        # movement: every value stays bitwise identical, and the sum then
        # runs in the single-device order.
        return jax.device_put(
            out, jax.sharding.NamedSharding(self.mesh, P()))


# ---------------------------------------------------------------------------
# whole-step data-parallel training
# ---------------------------------------------------------------------------

def _compressor(compression: Optional[str]) -> Optional[Callable]:
    """Spec string -> (grads, err, axis) -> (reduced grads, new err).

    ``None`` selects the plain fp psum; ``"int8"`` the shared-scale int8
    quantizer; ``"topk:F"`` magnitude top-k keeping fraction F (e.g.
    ``"topk:0.1"``).  Both compressors carry error feedback, so the
    *accumulated* update is unbiased (tested in test_jet_shard.py)."""
    if compression is None:
        return None
    spec = str(compression).strip().lower()
    if spec in ("", "none"):
        return None
    if spec == "int8":
        return compressed_psum_tree
    if spec.startswith("topk:"):
        frac = float(spec.split(":", 1)[1])
        return lambda g, e, ax: topk_psum_tree(g, e, ax, k_frac=frac)
    raise ValueError(f"unknown grad compression {compression!r}; want "
                     "None, 'int8', or 'topk:<frac>' (e.g. 'topk:0.1')")


@dataclass
class ShardedTrainStep:
    """One jitted data-parallel train step plus its error-feedback state
    initializer.  ``step(params, opt_state, pts, err)`` -> ``(params,
    opt_state, (loss, aux), err)``; ``pts`` must divide the data axis."""

    step: Callable
    init_err: Callable
    n_shards: int
    compression: Optional[str]


def build_sharded_train_step(loss_fn: Callable, mesh: jax.sharding.Mesh, *,
                             adam_lr: float, compression: Optional[str] = None,
                             axis: str = DATA_AXIS) -> ShardedTrainStep:
    """Jit one whole data-parallel training step as a ``shard_map`` program.

    ``loss_fn(params, pts) -> (loss, aux)`` is the ordinary single-device
    objective (interior residual mean over ``pts`` plus replicated terms
    such as boundary supervision).  Each device evaluates it on its local
    shard scaled by ``1/n_shards``; summing those local losses over the
    mesh reproduces the global objective exactly (equal shard sizes), so
    ``psum(local grads)`` *is* the global gradient and the replicated Adam
    update stays in lockstep on every device without broadcasting.

    ``compression`` routes the gradient all-reduce through
    :mod:`repro.parallel.compression` (``"int8"`` | ``"topk:F"``, error
    feedback carried in a per-device state tree with a stacked leading
    ``n_shards`` axis).  Off (None) by default: the plain psum path adds no
    approximation whatsoever.
    """
    from repro.optim import adam_update

    comp = _compressor(compression)
    n_sh = mesh.shape[axis]

    def local_step(params, opt_state, pts, err):
        def scaled_loss(p, xs):
            loss, aux = loss_fn(p, xs)
            return loss / n_sh, aux

        (loss, aux), grads = jax.value_and_grad(
            scaled_loss, has_aux=True)(params, pts)
        if comp is None:
            grads = jax.lax.psum(grads, axis)
            new_err = err
        else:
            # err leaves carry a leading stacked device axis outside the
            # shard_map; the local block is (1, *leaf.shape)
            local_err = jax.tree_util.tree_map(lambda e: e[0], err)
            grads, local_err = comp(grads, local_err, axis)
            new_err = jax.tree_util.tree_map(lambda e: e[None], local_err)
        loss = jax.lax.psum(loss, axis)
        aux = jax.tree_util.tree_map(lambda a: jax.lax.psum(a / n_sh, axis),
                                     aux)
        params, opt_state = adam_update(grads, opt_state, params, adam_lr)
        return params, opt_state, (loss, aux), new_err

    sharded = jax.shard_map(local_step, mesh=mesh,
                            in_specs=(P(), P(), P(axis), P(axis)),
                            out_specs=(P(), P(), P(), P(axis)),
                            check_vma=False)

    @jax.jit
    def step(params, opt_state, pts, err):
        if pts.shape[0] % n_sh:
            raise ValueError(f"batch of {pts.shape[0]} rows does not divide "
                             f"the {n_sh}-way data axis; pick n_domain "
                             f"divisible by the mesh")
        return sharded(params, opt_state, pts, err)

    def init_err(params) -> Any:
        """Stacked zero error-feedback buffers, (n_shards, *leaf.shape) per
        leaf -- one residual per device (all-zero when compression is off,
        kept so the step signature is uniform)."""
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros((n_sh,) + p.shape, jnp.bfloat16), params)

    return ShardedTrainStep(step=step, init_err=init_err, n_shards=n_sh,
                            compression=compression)
