"""Public jit'd wrappers around the Pallas kernels.

On a TPU these run compiled; on any other backend they run in
``interpret=True`` mode, which executes the kernel body op by op and is what
the CPU allclose sweeps exercise.  Whether a program ran the kernels compiled
shows in its compiled HLO (``tpu_custom_call``), which ``chip_smoke.py``
checks.  An activation without a kernel table is an error here, never a
silent detour through the reference: modules compose such activations
through the jet algebra themselves (``repro.core.modules.dense_jet``).

This is also the dispatch surface for the compositional module layer
(``repro.core.modules``):

* :func:`jet_dense` / :func:`act_jet` accept **arbitrary leading batch
  axes** -- ``(n+1, *batch, D)`` -- and fold them into the kernel's batch
  dimension, so a transformer block's token axis rides the same fused
  kernel as a flat collocation batch (reshape is free: it never copies and
  is transparent to autodiff);
* :func:`epilogues` is the typed capability registry: one mapping from
  fusable name to :class:`EpilogueKind`.  ``ACTIVATION`` entries are the
  closed-form Taylor tables the dense kernel can run in its Faa di Bruno
  epilogue; ``FUSED_OP`` entries ("rms_norm", "attention_scores",
  "flash_attention", "wave") name dedicated whole-chain kernels reached
  via their own dispatch functions and are NOT valid dense epilogues.  The
  pre-redesign boolean pair ``supports_epilogue`` /
  ``supports_activation_epilogue`` is gone (it survived one PR as
  deprecated shims after the registry landed).
"""

from __future__ import annotations

import enum
import functools
from types import MappingProxyType
from typing import Mapping

import jax
import jax.numpy as jnp

from repro.runtime.metrics import count, scope

from . import ref
from .jet_attention import (FLASH_BLOCK_K, flash_jet_bwd_pallas,
                            jet_attention_scores_pallas,
                            jet_flash_attention_pallas, jet_rms_norm_pallas)
from .jet_dense import jet_dense_pallas
from .tanh_jet import KERNEL_ACTS as _KERNEL_ACTS
from .tanh_jet import act_jet_pallas
from .wave_jet import wave_jet_bwd_pallas, wave_jet_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _check_kernel_activation(activation: str) -> None:
    if activation not in _KERNEL_ACTS:
        raise ValueError(f"no Pallas Taylor table for activation "
                         f"{activation!r} (have {_KERNEL_ACTS}); compose it "
                         f"through repro.core.jet.activation instead")


class EpilogueKind(enum.Enum):
    """What a fusable-name entry in :func:`epilogues` is capable of.

    ``ACTIVATION``
        a closed-form Taylor table the *dense kernel* can evaluate in its
        Faa di Bruno epilogue (also valid standalone via ``act_jet``);
    ``FUSED_OP``
        a dedicated whole-chain kernel (rms_norm, the PR-5 materializing
        attention scores, the tiled flash attention block, the wavelet jet
        with its learned weights) reached through its own dispatch
        function -- never a dense epilogue.
    """

    ACTIVATION = "activation"
    FUSED_OP = "fused_op"


# The typed fused-op registry: every name a module may ask about before
# routing a jet through a Pallas fast path instead of the reference algebra.
_EPILOGUE_KINDS: dict = {
    **{a: EpilogueKind.ACTIVATION for a in _KERNEL_ACTS},
    "rms_norm": EpilogueKind.FUSED_OP,
    "attention_scores": EpilogueKind.FUSED_OP,
    "flash_attention": EpilogueKind.FUSED_OP,
    "wave": EpilogueKind.FUSED_OP,
}


def epilogues() -> Mapping[str, EpilogueKind]:
    """The capability registry: fusable name -> :class:`EpilogueKind`,
    read-only.  ``epilogues().get(name) is EpilogueKind.ACTIVATION`` is the
    question a Dense/Activation leaf asks (can the dense kernel's Faa di
    Bruno epilogue run this activation); ``name in epilogues()`` is the
    broad does-a-fused-path-exist query."""
    return MappingProxyType(_EPILOGUE_KINDS)


def _fold_batch(coeffs: jnp.ndarray, keep: int = 1) -> tuple[jnp.ndarray, tuple]:
    """(n+1, *batch, *trailing) -> ((n+1, prod(batch), *trailing), batch),
    preserving the last ``keep`` axes -- 1 for the 3-D dense/norm kernels,
    2 for the 4-D attention core (token + feature pair stays whole).  The
    inverse is a plain reshape of the kernel output."""
    batch = coeffs.shape[1:-keep]
    flat = 1
    for s in batch:
        flat *= s
    return coeffs.reshape(coeffs.shape[:1] + (flat,) + coeffs.shape[-keep:]), \
        batch


# ---------------------------------------------------------------------------
# custom VJPs: forward runs the fused Pallas kernel; backward *recomputes*
# through the pure-jnp reference.  This is deliberate, not a workaround:
#  - residuals are just the layer inputs -> activation memory stays O(n M),
#    the paper's linear-memory claim, instead of stashing the (n+1)-stack
#    of every intermediate partition product;
#  - the recompute is one extra fused-layer-equivalent of FLOPs, the same
#    trade remat makes for ordinary transformer layers on TPU.
# The custom_vjp cores are 3-D ((n+1, B, D)); the public wrappers fold any
# extra leading batch axes around them.  Each backward runs under a
# ``kernel.<kernel>.bwd`` scope, so the recompute's device operations carry
# that name in their ``op_name``.
# ---------------------------------------------------------------------------

def _act_jet_impl(coeffs: jnp.ndarray, activation: str) -> jnp.ndarray:
    _check_kernel_activation(activation)
    return act_jet_pallas(coeffs, activation, interpret=not _on_tpu())


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _act_jet3(coeffs: jnp.ndarray, activation: str = "tanh") -> jnp.ndarray:
    return _act_jet_impl(coeffs, activation)


def _act_jet_fwd(coeffs, activation):
    return _act_jet_impl(coeffs, activation), coeffs


@scope("kernel.act_jet.bwd")
def _act_jet_bwd(activation, coeffs, g):
    _, vjp = jax.vjp(lambda c: ref.act_jet_ref(c, activation), coeffs)
    return vjp(g)


_act_jet3.defvjp(_act_jet_fwd, _act_jet_bwd)


def act_jet(coeffs: jnp.ndarray, activation: str = "tanh") -> jnp.ndarray:
    """Activation jet (n+1, *batch, W) -> same shape."""
    flat, batch = _fold_batch(coeffs)
    out = _act_jet3(flat, activation)
    return out.reshape(out.shape[:1] + batch + out.shape[-1:])


def _jet_dense_impl(coeffs, w, b, activation):
    if activation is not None:
        _check_kernel_activation(activation)
    return jet_dense_pallas(coeffs, w, b, activation, interpret=not _on_tpu())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _jet_dense3(coeffs: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                activation: str | None = "tanh") -> jnp.ndarray:
    return _jet_dense_impl(coeffs, w, b, activation)


def _jet_dense_fwd(coeffs, w, b, activation):
    return _jet_dense_impl(coeffs, w, b, activation), (coeffs, w, b)


@scope("kernel.jet_dense.bwd")
def _jet_dense_bwd(activation, res, g):
    coeffs, w, b = res
    _, vjp = jax.vjp(lambda c, ww, bb: ref.jet_dense_ref(c, ww, bb, activation),
                     coeffs, w, b)
    return vjp(g)


_jet_dense3.defvjp(_jet_dense_fwd, _jet_dense_bwd)


def jet_dense(coeffs: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
              activation: str | None = "tanh") -> jnp.ndarray:
    """Fused dense layer + activation jet: (n+1, *batch, Din) -> (n+1,
    *batch, Dout).  Extra leading batch axes (e.g. a token axis) fold into
    the kernel's GEMM M-dimension and unfold on the way out."""
    flat, batch = _fold_batch(coeffs)
    out = _jet_dense3(flat, w, b, activation)
    return out.reshape(out.shape[:1] + batch + out.shape[-1:])


# ---------------------------------------------------------------------------
# fused attention scores: Cauchy-product QK^T + scale + softmax recurrence
# in one launch (kernels/jet_attention.py); backward recomputes through the
# straight-line reference like every op above
# ---------------------------------------------------------------------------

def _attention_scores_impl(q, k, scale):
    return jet_attention_scores_pallas(q, k, scale, interpret=not _on_tpu())


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _attention_scores4(q: jnp.ndarray, k: jnp.ndarray,
                       scale: float) -> jnp.ndarray:
    return _attention_scores_impl(q, k, scale)


def _attention_scores_fwd(q, k, scale):
    return _attention_scores_impl(q, k, scale), (q, k)


@scope("kernel.attention_scores.bwd")
def _attention_scores_bwd(scale, res, g):
    q, k = res
    _, vjp = jax.vjp(
        lambda qq, kk: ref.jet_attention_scores_ref(qq, kk, scale), q, k)
    return vjp(g)


_attention_scores4.defvjp(_attention_scores_fwd, _attention_scores_bwd)


def jet_attention_scores(q_coeffs: jnp.ndarray, k_coeffs: jnp.ndarray,
                         scale: float) -> jnp.ndarray:
    """Fused attention-score jet: Q/K stacks (n+1, *batch, T, D) -> the
    softmaxed probability jet (n+1, *batch, Tq, Tk).  Extra leading batch
    axes (collocation batch, head axis) fold into the kernel's gridded batch
    dimension and unfold on the way out."""
    qf, batch = _fold_batch(q_coeffs, keep=2)
    kf, _ = _fold_batch(k_coeffs, keep=2)
    out = _attention_scores4(qf, kf, scale)
    return out.reshape(out.shape[:1] + batch + out.shape[-2:])


# ---------------------------------------------------------------------------
# tiled flash-jet attention: the whole block (scores + masked softmax +
# value contraction + output projection) in ONE launch with an online-
# softmax recurrence over KV blocks generalized to the coefficient axis --
# the "flash_attention" registry entry.  A sequence that fits one key tile
# takes the one-tile backward kernel ``flash_jet_bwd`` (recompute and
# closed-form reverse in VMEM); a longer one recomputes through the
# straight-line reference (materializing, but only under differentiation).
# Each dispatch is counted at trace time: ``kernel.flash_jet_bwd`` or
# ``kernel.flash_jet_bwd.fallback``.
# ---------------------------------------------------------------------------

def _flash_attention_impl(q, k, v, wo, scale, mask):
    kind, window = mask
    return jet_flash_attention_pallas(q, k, v, wo, scale, mask=kind,
                                      window=window,
                                      interpret=not _on_tpu())


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_attention5(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      wo: jnp.ndarray, scale: float,
                      mask: tuple) -> jnp.ndarray:
    return _flash_attention_impl(q, k, v, wo, scale, mask)


def _flash_attention_fwd(q, k, v, wo, scale, mask):
    return _flash_attention_impl(q, k, v, wo, scale, mask), (q, k, v, wo)


@scope("kernel.flash_attention.bwd")
def _flash_attention_bwd(scale, mask, res, g):
    q, k, v, wo = res
    if q.shape[-2] <= FLASH_BLOCK_K:
        count("kernel.flash_jet_bwd", 1)
        kind, window = mask
        return flash_jet_bwd_pallas(q, k, v, wo, g, scale, mask=kind,
                                    window=window, interpret=not _on_tpu())
    count("kernel.flash_jet_bwd.fallback", 1)
    from repro.core.modules import attention_mask
    dense = attention_mask(mask, q.shape[-2])
    _, vjp = jax.vjp(
        lambda qq, kk, vv, ww: ref.jet_flash_attention_ref(
            qq, kk, vv, ww, scale, mask=dense), q, k, v, wo)
    return vjp(g)


_flash_attention5.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def jet_flash_attention(q_coeffs: jnp.ndarray, k_coeffs: jnp.ndarray,
                        v_coeffs: jnp.ndarray, wo: jnp.ndarray, scale: float,
                        mask=None) -> jnp.ndarray:
    """Tiled flash-jet attention block: Q/K/V stacks (n+1, *batch, H, T, Dh)
    plus the output projection ``wo`` -- (H*Dh, Dm) as stored by
    ``SelfAttention`` (head-major rows), or already (H, Dh, Dm) -- to the
    block output jet (n+1, *batch, T, Dm) in one launch, never
    materializing the (Tq, Tk) score jet.  ``mask`` is anything
    ``repro.core.modules.normalize_attention_mask`` accepts.  Extra leading
    batch axes fold into the kernel's gridded batch dimension and unfold on
    the way out."""
    from repro.core.modules import normalize_attention_mask
    mask = normalize_attention_mask(mask)
    h, d = q_coeffs.shape[-3], q_coeffs.shape[-1]
    if wo.ndim == 2:
        wo = wo.reshape(h, d, wo.shape[-1])
    qf, batch = _fold_batch(q_coeffs, keep=3)
    kf, _ = _fold_batch(k_coeffs, keep=3)
    vf, _ = _fold_batch(v_coeffs, keep=3)
    out = _flash_attention5(qf, kf, vf, wo, scale, mask)
    return out.reshape(out.shape[:1] + batch + out.shape[-2:])


# ---------------------------------------------------------------------------
# fused rms_norm: mean-square convolution + rsqrt recurrence + gain in one
# launch (the "rms_norm" epilogue-registry entry)
# ---------------------------------------------------------------------------

def _rms_norm_impl(coeffs, gamma, eps):
    return jet_rms_norm_pallas(coeffs, gamma, eps, interpret=not _on_tpu())


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_norm3(coeffs: jnp.ndarray, gamma: jnp.ndarray,
               eps: float) -> jnp.ndarray:
    return _rms_norm_impl(coeffs, gamma, eps)


def _rms_norm_fwd(coeffs, gamma, eps):
    return _rms_norm_impl(coeffs, gamma, eps), (coeffs, gamma)


@scope("kernel.rms_norm.bwd")
def _rms_norm_bwd(eps, res, g):
    coeffs, gamma = res
    _, vjp = jax.vjp(lambda c, gg: ref.jet_rms_norm_ref(c, gg, eps),
                     coeffs, gamma)
    return vjp(g)


_rms_norm3.defvjp(_rms_norm_fwd, _rms_norm_bwd)


def jet_rms_norm(coeffs: jnp.ndarray, gamma: jnp.ndarray,
                 eps: float = 1e-6) -> jnp.ndarray:
    """Fused rms_norm jet: (n+1, *batch, W) -> same shape, normalized over
    the trailing feature axis and scaled by the (W,) gain.  Leading batch
    axes (token axis included) fold into the kernel batch dimension."""
    flat, batch = _fold_batch(coeffs)
    out = _rms_norm3(flat, gamma, eps)
    return out.reshape(out.shape[:1] + batch + out.shape[-1:])


# ---------------------------------------------------------------------------
# the wavelet jet (PINNsFormer's w1 sin + w2 cos, learned weights): one
# launch forward and one backward (kernels/wave_jet.py), the "wave"
# registry entry.  Unlike the ops above, the backward is a kernel of its
# own, the closed-form transpose of the Faa di Bruno contraction, so the
# only residuals are the stack and the weights and no recompute runs in XLA.
# ---------------------------------------------------------------------------

def _wave_jet_impl(coeffs, w):
    return wave_jet_pallas(coeffs, w, interpret=not _on_tpu())


@jax.custom_vjp
def _wave_jet3(coeffs: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    return _wave_jet_impl(coeffs, w)


def _wave_jet_fwd(coeffs, w):
    return _wave_jet_impl(coeffs, w), (coeffs, w)


@scope("kernel.wave_jet.bwd")
def _wave_jet_bwd(res, g):
    coeffs, w = res
    return wave_jet_bwd_pallas(coeffs, w, g, interpret=not _on_tpu())


_wave_jet3.defvjp(_wave_jet_fwd, _wave_jet_bwd)


def wave_jet(coeffs: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Wavelet activation jet ``w1 S + w2 C`` (S, C the sin- and cos-jets
    of the stack): (n+1, *batch, W) and the (2,) weights -> same shape,
    differentiable in both.  Leading batch axes (a token axis included)
    fold into the kernel's row axis and unfold on the way out."""
    flat, batch = _fold_batch(coeffs)
    out = _wave_jet3(flat, w)
    return out.reshape(out.shape[:1] + batch + out.shape[-1:])
