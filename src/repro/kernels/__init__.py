"""Pallas TPU kernels for the n-TangentProp hot path.

The paper's compute hot-spot is the per-layer jet propagation (stacked GEMM +
Faa di Bruno activation contraction); ``jet_dense`` fuses both into one VMEM
round-trip, ``act_jet`` is the standalone pointwise epilogue.  The
transformer trunk runs ``jet_flash_attention`` -- the WHOLE attention layer
(score Cauchy product, tiled online-softmax jet recurrence, value
contraction, output projection) in a single launch whose working set is
bounded by its block sizes, never the materialized (T, T) score jet -- and
``jet_rms_norm`` (mean-square convolution + rsqrt recurrence + gain).
PINNsFormer's learned wavelet runs ``wave_jet``: one launch forward and one
backward (``wave_jet.py``).
``jet_attention_scores`` is the PR-5 materializing score kernel, kept for
benchmarking against.  ``ref.py`` holds the pure-jnp oracles the test sweeps
compare against; ``ops.epilogues()`` is the typed registry modules consult
before dispatching here.
"""

from . import ops, ref
from .ops import (EpilogueKind, act_jet, epilogues, jet_attention_scores,
                  jet_dense, jet_flash_attention, jet_rms_norm, wave_jet)
