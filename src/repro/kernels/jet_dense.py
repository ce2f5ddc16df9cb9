"""Pallas TPU kernel: fused n-TangentProp dense layer (MXU + VPU).

One layer of the paper's Algorithm 1 is ``jet -> W @ jet + b -> tanh-jet``.
Done naively that is two HBM round-trips for the ``(n+1, B, D)`` stack (GEMM
out, activation in).  This kernel fuses them:

  * the coefficient axis is folded into the GEMM M-dimension -- each block
    computes ``((n+1)*block_b, block_k) @ (block_k, block_d)`` on the MXU,
    so the derivative stack *rides the systolic array* instead of issuing
    (n+1) strided small matmuls;
  * K is the innermost (``arbitrary``) grid axis accumulating into a VMEM
    f32 scratch; on the last K step the Faa di Bruno epilogue (tanh_jet.py's
    ``act_jet_body``) runs in-register and writes the activated jet once.

Block shapes are chosen for the v5e MXU/VPU: ``block_k = block_d = 128``
multiples (lane dim), ``block_b`` a multiple of 8 (sublane).  bf16/f32 inputs
accumulate in f32 (``preferred_element_type``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.jet import MATMUL_PRECISION

from .tanh_jet import act_jet_body


def _kernel(y_ref, w_ref, b_ref, o_ref, acc_ref, *, activation, n_k):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    y = y_ref[...]                       # (n+1, bb, bk)
    n1, bb, bk = y.shape
    w = w_ref[...]                       # (bk, bd)
    part = jnp.dot(y.reshape(n1 * bb, bk), w, precision=MATMUL_PRECISION,
                   preferred_element_type=acc_ref.dtype)
    acc_ref[...] += part.reshape(n1, bb, -1)

    @pl.when(k == n_k - 1)
    def _epilogue():
        z = acc_ref[...]
        # bias on c_0 only, as a select: Mosaic has no scatter-add lowering
        c0 = jax.lax.broadcasted_iota(jnp.int32, z.shape, 0) == 0
        z = z + jnp.where(c0, b_ref[...].astype(z.dtype), 0.0)
        if activation is None:
            o_ref[...] = z.astype(o_ref.dtype)
        else:
            o_ref[...] = act_jet_body(z, activation).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("activation", "block_b", "block_k",
                                             "block_d", "interpret"))
def jet_dense_pallas(coeffs: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                     activation: str | None = "tanh",
                     block_b: int = 128, block_k: int = 128, block_d: int = 128,
                     interpret: bool = True) -> jnp.ndarray:
    """(n+1, B, Din) x (Din, Dout) -> activated jet (n+1, B, Dout)."""
    n1, bsz, din = coeffs.shape
    dout = w.shape[1]
    bb, bk, bd = min(block_b, bsz), min(block_k, din), min(block_d, dout)
    pb, pk, pd = (-bsz) % bb, (-din) % bk, (-dout) % bd

    y = jnp.pad(coeffs, ((0, 0), (0, pb), (0, pk)))
    wp = jnp.pad(w, ((0, pk), (0, pd)))
    bp = jnp.pad(b, ((0, pd),)).reshape(1, -1)

    grid = (y.shape[1] // bb, wp.shape[1] // bd, wp.shape[0] // bk)
    n_k = grid[2]

    # dimension semantics: parallel over (B, Dout), sequential over K
    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    out = pl.pallas_call(
        functools.partial(_kernel, activation=activation, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n1, bb, bk), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((bk, bd), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bd), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((n1, bb, bd), lambda i, j, k: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((n1, y.shape[1], wp.shape[1]), coeffs.dtype),
        # f32 accumulation for the TPU-realistic dtypes (f32/bf16 in); f64
        # inputs -- the interpret-mode oracle tests -- accumulate in f64
        scratch_shapes=[pltpu.VMEM((n1, bb, bd),
                                   jnp.promote_types(coeffs.dtype,
                                                     jnp.float32))],
        compiler_params=compiler_params,
        interpret=interpret,
        name="jet_dense",
    )(y, wp, bp)
    return out[:, :bsz, :dout]
