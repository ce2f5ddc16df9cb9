"""Pallas TPU kernels: fused jet attention scores + fused jet RMSNorm.

The transformer trunk's per-layer hot path (``repro.core.modules``) is

    S = (1/sqrt(d)) Q K^T        -- jet x jet Cauchy-convolved contraction
    P = softmax(S, axis=-1)      -- exp / sum / div power-series recurrences

and, around every block, ``rms_norm`` -- a Cauchy square, an rsqrt
recurrence, and a final Cauchy product.  Through the reference jet algebra
each of those steps is its own jnp op over the ``(n+1, ...)`` coefficient
stack, i.e. O(n^2) separate HBM round-trips per layer.  The two kernels here
fuse each chain into ONE launch:

``jet_attention_scores_pallas``
    loads a block of Q-jet and K-jet coefficient stacks into VMEM once, runs
    every Cauchy term of the score convolution as a batched ``dot_general``
    on the MXU, then the softmax exp/sum/div recurrences on the VPU with the
    whole coefficient axis in registers, and writes the probability jet once.

``jet_rms_norm_pallas``
    fuses the mean-square Cauchy convolution, the rsqrt jet (J.C.P. Miller
    recurrence for a^-1/2), the normalizing Cauchy product, and the gain in
    one VPU pass.

Tiling: the folded batch axis (collocation batch x heads for attention,
batch x tokens for rms_norm) is the only gridded dimension -- the token and
feature axes of a PINN transformer are tiny (T = d_in coordinates), so each
block holds them whole, and order k of any recurrence mixes all lower
orders, so the coefficient axis is never split.  Accumulation follows
jet_dense.py: MXU contractions run with ``preferred_element_type=float32``
and the output casts back to the input dtype.
"""

from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.jet import MATMUL_PRECISION

from .wave_jet import SUB_ROWS, over_chunks

# Finite "minus infinity" for masked score positions: large enough that exp
# underflows to exactly 0, small enough that (NEG - NEG) stays 0.0 and no
# inf/NaN can enter the jet recurrences (a true -inf would produce inf-inf).
MASK_NEG = -1e30

# The flash-jet key tile: a sequence no longer than this is one KV block, so
# its backward runs as the one-tile kernel ``flash_jet_bwd``
FLASH_BLOCK_K = 64


def attention_scores_jet_body(q: jnp.ndarray, k: jnp.ndarray,
                              scale: float) -> jnp.ndarray:
    """The fused epilogue on in-VMEM stacks: (n+1, B, T, D) x 2 -> the
    softmaxed score jet (n+1, B, Tq, Tk).

    Shared by the Pallas kernel and (via the test sweeps) checked against
    the independent ``ref.jet_attention_scores_ref`` straight-line oracle.
    """
    n1 = q.shape[0]
    # accumulate in f32 for TPU-realistic dtypes (f32/bf16); float64 inputs
    # (the interpret-mode oracle tests) keep full precision
    acc_t = jnp.promote_types(q.dtype, jnp.float32)

    def qk(i: int, j: int) -> jnp.ndarray:
        # (B, T, D) x (B, T, D) -> (B, Tq, Tk), contracting D, batching B
        return jax.lax.dot_general(
            q[i], k[j],
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            precision=MATMUL_PRECISION,
            preferred_element_type=acc_t) * scale

    # Cauchy-convolved scores: s_k = scale * sum_{i+j=k} Q_i K_j^T
    s = []
    for m in range(n1):
        acc = qk(0, m)
        for i in range(1, m + 1):
            acc = acc + qk(i, m - i)
        s.append(acc)

    # softmax over the key axis via the exp/sum/div recurrences; the shift
    # is t-constant so it only enters e_0 and cancels in the division
    shift = jnp.max(s[0], axis=-1, keepdims=True)
    e = [jnp.exp(s[0] - shift)]
    for m in range(1, n1):
        acc = m * s[m] * e[0]
        for j in range(1, m):
            acc = acc + j * s[j] * e[m - j]
        e.append(acc / m)

    tot = [jnp.sum(em, axis=-1, keepdims=True) for em in e]
    inv0 = 1.0 / tot[0]
    p = [e[0] * inv0]
    for m in range(1, n1):
        acc = e[m]
        for j in range(1, m + 1):
            acc = acc - tot[j] * p[m - j]
        p.append(acc * inv0)
    return jnp.stack(p)


def _scores_kernel(q_ref, k_ref, o_ref, *, scale):
    out = attention_scores_jet_body(q_ref[...], k_ref[...], scale)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_b", "interpret"))
def jet_attention_scores_pallas(q: jnp.ndarray, k: jnp.ndarray, scale: float,
                                block_b: int = 64,
                                interpret: bool = True) -> jnp.ndarray:
    """(n+1, B, T, D) Q/K coefficient stacks -> softmaxed score jet
    (n+1, B, T, T), one launch.  B is the only gridded axis; padded batch
    rows are all-zero (uniform softmax) and sliced away on return."""
    n1, bsz, t, d = q.shape
    if k.shape != q.shape:
        raise ValueError(f"q/k shape mismatch: {q.shape} vs {k.shape}")
    bb = min(block_b, bsz)
    pb = (-bsz) % bb
    qp = jnp.pad(q, ((0, 0), (0, pb), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pb), (0, 0), (0, 0)))
    grid = (qp.shape[1] // bb,)
    out = pl.pallas_call(
        functools.partial(_scores_kernel, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n1, bb, t, d), lambda i: (0, i, 0, 0)),
            pl.BlockSpec((n1, bb, t, d), lambda i: (0, i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((n1, bb, t, t), lambda i: (0, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n1, qp.shape[1], t, t), q.dtype),
        interpret=interpret,
        name="jet_attention_scores",
    )(qp, kp)
    return out[:, :bsz]


# ---------------------------------------------------------------------------
# Flash-jet attention: the full block (scores + softmax + value contraction
# + output projection) in ONE launch, tiled over KV blocks with the online-
# softmax recurrence generalized to the jet coefficient axis.
#
# Per (batch, q-block) the kernel carries three running statistics in VMEM
# scratch across the innermost KV grid axis:
#
#   m  (H, bb, bq)        -- running max of the order-0 masked scores (the
#                            softmax shift; t-constant, so scalar per row)
#   t  (H, n+1, bb, bq)   -- running *total* jet: sum_k e_k of the shifted
#                            exp jet over every key seen so far
#   a  (H, n+1, bb, bq, D)-- running accumulator jet: the Cauchy product
#                            e (*) V summed over every key seen so far
#
# (heads lead, so each head's statistics are one leading-axis slice)
#
# A shift change m -> m' rescales ALL coefficients of e by the same scalar
# alpha = exp(m - m'): the shift is t-constant, so exp(s - m') =
# exp(m - m') * exp(s - m) coefficient-wise.  Hence the flash update
#
#   t <- alpha * t + sum_block e,   a <- alpha * a + e (*) V_block.
#
# Because a = t (*) o (Cauchy), the epilogue recovers the attention output
# by JET DIVISION -- flash attention's "divide by the sum at the end"
# generalized to all orders:
#
#   o_0 = a_0 / t_0,   o_m = (a_m - sum_{j=1..m} t_j o_{m-j}) / t_0
#
# and immediately contracts o with the (H, Dh, Dm) output projection, so
# neither the (Tq, Tk) score jet nor the pre-projection per-head output
# ever materializes in HBM.
# ---------------------------------------------------------------------------


def _flash_block_keep(mask: str, window: int, i, j, block_q: int,
                      block_k: int, t_k: int) -> jnp.ndarray:
    """(bq, bk) boolean keep-matrix for q-block i / kv-block j in GLOBAL
    token coordinates: padded keys are always dropped, then the causal /
    local variant.  ``local(w)`` is a causal sliding window: query q attends
    keys j with q - w < j <= q (the diagonal is always kept, so no query
    row is ever fully masked)."""
    qi = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kj = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    keep = kj < t_k
    if mask == "causal":
        keep = keep & (kj <= qi)
    elif mask == "local":
        keep = keep & (kj <= qi) & (qi - kj < window)
    return keep


def _flash_kernel(q_ref, k_ref, v_ref, wo_ref, o_ref, m_ref, t_ref, a_ref, *,
                  scale, mask, window, t_k, block_q, block_k, n_kv):
    i, j = pl.program_id(1), pl.program_id(2)
    n1, _, n_heads = q_ref.shape[:3]
    acc_t = m_ref.dtype
    neg = jnp.asarray(MASK_NEG, acc_t)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, neg, acc_t)
        t_ref[...] = jnp.zeros(t_ref.shape, acc_t)
        a_ref[...] = jnp.zeros(a_ref.shape, acc_t)

    q = q_ref[...].astype(acc_t)            # (n1, bb, H, bq, D)
    k = k_ref[...].astype(acc_t)            # (n1, bb, H, bk, D)
    v = v_ref[...].astype(acc_t)
    keep = _flash_block_keep(mask, window, i, j, block_q, block_k, t_k)
    keep = keep[None]                       # broadcast over bb

    # one head at a time: Mosaic's matmul takes at most one batch axis, so
    # every contraction below batches over bb alone
    for h in range(n_heads):
        def qk(a_i: int, b_i: int) -> jnp.ndarray:
            # (bb, bq, D) x (bb, bk, D) -> (bb, bq, bk)
            return jax.lax.dot_general(
                q[a_i, :, h], k[b_i, :, h],
                dimension_numbers=(((2,), (2,)), ((0,), (0,))),
                precision=MATMUL_PRECISION,
                preferred_element_type=acc_t) * scale

        # Cauchy-convolved scores for this tile: s_m = scale * sum Q_i K_j^T
        s = []
        for m in range(n1):
            acc = qk(0, m)
            for a_i in range(1, m + 1):
                acc = acc + qk(a_i, m - a_i)
            s.append(acc)

        s0m = jnp.where(keep, s[0], neg)
        m_old = m_ref[h]                    # (bb, bq)
        m_new = jnp.maximum(m_old, jnp.max(s0m, axis=-1))
        alpha = jnp.exp(m_old - m_new)      # rescales every e coefficient

        # shifted exp jet for this tile; masked positions' e-jets are exactly
        # 0: e_0 underflows (exp(NEG - m_new)) and is where'd to 0, and every
        # higher e_m term carries an e-factor that is already 0
        e = [jnp.where(keep, jnp.exp(s0m - m_new[..., None]), 0.0)]
        for m in range(1, n1):
            acc = m * s[m] * e[0]
            for b_j in range(1, m):
                acc = acc + b_j * s[b_j] * e[m - b_j]
            e.append(acc / m)

        def ev(a_i: int, b_i: int) -> jnp.ndarray:
            # (bb, bq, bk) x (bb, bk, D) -> (bb, bq, D)
            return jax.lax.dot_general(
                e[a_i], v[b_i, :, h],
                dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                precision=MATMUL_PRECISION,
                preferred_element_type=acc_t)

        esum, eav = [], []
        for m in range(n1):
            esum.append(jnp.sum(e[m], axis=-1))
            acc = ev(0, m)
            for a_i in range(1, m + 1):
                acc = acc + ev(a_i, m - a_i)
            eav.append(acc)

        m_ref[h] = m_new
        t_ref[h] = alpha[None] * t_ref[h] + jnp.stack(esum)
        a_ref[h] = alpha[None, ..., None] * a_ref[h] + jnp.stack(eav)

    @pl.when(j == n_kv - 1)
    def _epilogue():
        # a = t (*) o  =>  o by jet division, then the output projection,
        # summed over heads.  t_0 >= 1 for every real query row (the row max
        # contributes exp(0)); the floor only catches padded query rows that
        # a local window can leave with zero kept keys, making them 0 not NaN.
        wo = wo_ref[...].astype(acc_t)      # (H, D, Dm)
        out = [None] * n1
        for h in range(n_heads):
            t_h, a_h = t_ref[h], a_ref[h]
            t0 = jnp.maximum(t_h[0], jnp.asarray(1e-37, acc_t))
            inv0 = 1.0 / t0[..., None]
            o = [a_h[0] * inv0]
            for m in range(1, n1):
                acc = a_h[m]
                for b_j in range(1, m + 1):
                    acc = acc - t_h[b_j][..., None] * o[m - b_j]
                o.append(acc * inv0)
            wo_h = jnp.broadcast_to(wo[h], (o[0].shape[0],) + wo.shape[1:])
            for m in range(n1):
                # (bb, bq, D) x (bb, D, Dm) -> (bb, bq, Dm)
                proj = jax.lax.dot_general(
                    o[m], wo_h,
                    dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                    precision=MATMUL_PRECISION,
                    preferred_element_type=acc_t)
                out[m] = proj if out[m] is None else out[m] + proj
        o_ref[...] = jnp.stack(out).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "mask", "window", "block_q", "block_k", "block_b", "interpret"))
def jet_flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                               wo: jnp.ndarray, scale: float,
                               mask: str = "none", window: int = 0,
                               block_q: int = 64,
                               block_k: int = FLASH_BLOCK_K,
                               block_b: int = 8,
                               interpret: bool = True) -> jnp.ndarray:
    """Tiled flash-jet attention: Q/K/V coefficient stacks (n+1, B, H, T, Dh)
    plus the output projection (H, Dh, Dm) -> the attention-block output jet
    (n+1, B, T, Dm), one launch, no materialized (Tq, Tk) score jet.

    Grid is (batch, q-blocks, kv-blocks) with KV innermost; the running
    max / total-jet / accumulator-jet live in VMEM scratch and carry across
    the KV axis (TPU grids execute sequentially).  Peak memory is set by the
    block sizes, not T^2.  ``mask`` in {"none", "causal", "local"}; "local"
    attends the causal window ``q - window < key <= q``.  Padded batch rows
    are all-zero (uniform softmax over valid keys) and padded query rows may
    contain garbage; both slice away on return."""
    n1, bsz, h, t, d = q.shape
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(f"q/k/v shape mismatch: {q.shape} vs {k.shape} "
                         f"vs {v.shape}")
    if wo.ndim != 3 or wo.shape[:2] != (h, d):
        raise ValueError(f"wo shape {wo.shape} incompatible with (H, Dh) = "
                         f"({h}, {d})")
    if mask not in ("none", "causal", "local"):
        raise ValueError(f"unknown mask variant {mask!r}")
    if mask == "local" and window < 1:
        raise ValueError(f"local mask needs window >= 1, got {window}")
    dm = wo.shape[2]
    bq = min(block_q, t)
    bk = min(block_k, t)
    # the per-step working set grows with bb * bq: long query blocks take
    # fewer batch rows so the score and accumulator tiles fit in VMEM
    bb = min(block_b, bsz, max(1, 64 // bq))
    pb, pq, pk = (-bsz) % bb, (-t) % bq, (-t) % bk
    qp = jnp.pad(q, ((0, 0), (0, pb), (0, 0), (0, pq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pb), (0, 0), (0, pk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pb), (0, 0), (0, pk), (0, 0)))
    n_kv = (t + pk) // bk
    grid = ((bsz + pb) // bb, (t + pq) // bq, n_kv)
    acc_t = jnp.promote_types(q.dtype, jnp.float32)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, mask=mask,
                          window=window, t_k=t, block_q=bq, block_k=bk,
                          n_kv=n_kv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n1, bb, h, bq, d), lambda b, i, j: (0, b, 0, i, 0)),
            pl.BlockSpec((n1, bb, h, bk, d), lambda b, i, j: (0, b, 0, j, 0)),
            pl.BlockSpec((n1, bb, h, bk, d), lambda b, i, j: (0, b, 0, j, 0)),
            pl.BlockSpec((h, d, dm), lambda b, i, j: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((n1, bb, bq, dm), lambda b, i, j: (0, b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n1, bsz + pb, t + pq, dm), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((h, bb, bq), acc_t),
            pltpu.VMEM((h, n1, bb, bq), acc_t),
            pltpu.VMEM((h, n1, bb, bq, d), acc_t),
        ],
        interpret=interpret,
        name="jet_flash_attention",
    )(qp, kp, vp, wo)
    return out[:, :bsz, :t]


# ---------------------------------------------------------------------------
# The flash-jet backward for a sequence that fits one key tile (T <= block_k):
# per batch block, recompute the forward's jets in VMEM as _flash_kernel
# spells them, with no online rescaling (one tile, so the running max is the
# row max and alpha is 1), then run the closed-form reverse of each step in
# the opposite order:
#
#   out_m = sum_h o_m[h] wo[h]         ->  do_m = g_m wo^T,  dwo += o_m^T g_m
#   a = t (*) o   (jet division)       ->  da, dt
#   a_m = sum_i e_i v_{m-i}            ->  de, dv
#   t_m = sum_keys e_m                 ->  de += dt
#   e_m = (1/m) sum_j j s_j e_{m-j}    ->  ds, and ds_0 = de_0 e_0
#   s_m = scale sum_i q_i k_{m-i}^T    ->  dq, dk
#
# The row max only shifts e_0 by a t-constant factor that the division
# cancels, so no gradient flows through it: dropping it is exact.  Masked
# keys keep e-jets of exactly 0, so every ds term there carries a 0 factor.
# ---------------------------------------------------------------------------


def _total(terms):
    return functools.reduce(operator.add, terms)


def _flash_bwd_kernel(q_ref, k_ref, v_ref, wo_ref, g_ref,
                      dq_ref, dk_ref, dv_ref, dwo_ref, *,
                      scale, mask, window, rows, sub):
    n1, bb, n_heads, t = q_ref.shape[:4]
    acc_t = dwo_ref.dtype
    neg = jnp.asarray(MASK_NEG, acc_t)
    keep = _flash_block_keep(mask, window, 0, 0, t, t, t)[None]

    def mm(x, y, cx: int, cy: int) -> jnp.ndarray:
        # one contraction batched over the rows alone, as in the forward
        return jax.lax.dot_general(
            x, y, dimension_numbers=(((cx,), (cy,)), ((0,), (0,))),
            precision=MATMUL_PRECISION, preferred_element_type=acc_t)

    def cauchy(x, y, cx: int, cy: int) -> list:
        # [sum_{i+j=m} x_i . y_j for m = 0..n]
        return [_total(mm(x[i], y[m - i], cx, cy) for i in range(m + 1))
                for m in range(n1)]

    def transposed(dx, y, cx: int, cy: int) -> list:
        # [sum_{m >= j} dx_m . y_{m-j} for j = 0..n]: cauchy's reverse in x
        return [_total(mm(dx[m], y[m - j], cx, cy) for m in range(j, n1))
                for j in range(n1)]

    def head(r, h, g):
        q = [q_ref[m, r, h].astype(acc_t) for m in range(n1)]  # (sub, T, D)
        k = [k_ref[m, r, h].astype(acc_t) for m in range(n1)]
        v = [v_ref[m, r, h].astype(acc_t) for m in range(n1)]

        # recompute: scores, exp jet, totals, value jet, o by jet division
        s = [x * scale for x in cauchy(q, k, 2, 2)]           # (sub, T, T)
        s0m = jnp.where(keep, s[0], neg)
        e = [jnp.where(keep, jnp.exp(
            s0m - jnp.max(s0m, axis=-1, keepdims=True)), 0.0)]
        for m in range(1, n1):
            acc = m * s[m] * e[0]
            for j in range(1, m):
                acc = acc + j * s[j] * e[m - j]
            e.append(acc / m)
        tot = [jnp.sum(em, axis=-1, keepdims=True) for em in e]  # (sub, T, 1)
        a = cauchy(e, v, 2, 1)                                # (sub, T, D)
        inv0 = 1.0 / jnp.maximum(tot[0], jnp.asarray(1e-37, acc_t))
        o = [a[0] * inv0]
        for m in range(1, n1):
            acc = a[m]
            for j in range(1, m + 1):
                acc = acc - tot[j] * o[m - j]
            o.append(acc * inv0)

        # the output projection: do_m = g_m wo[h]^T, dwo[h] = sum o_m^T g_m
        wo_h = jnp.broadcast_to(wo_ref[h].astype(acc_t),
                                (g[0].shape[0],) + wo_ref.shape[1:])
        do = [mm(g[m], wo_h, 2, 2) for m in range(n1)]
        dwo = _total(mm(o[m], g[m], 1, 1) for m in range(n1))  # (sub, D, Dm)

        # the jet division o_m = (a_m - sum_{j>=1} t_j o_{m-j}) / t_0
        da, dtot = [None] * n1, [None] * n1
        for m in reversed(range(n1)):
            r_m = do[m] * inv0
            da[m] = r_m
            for j in range(m + 1):
                d = -jnp.sum(r_m * o[m - j], axis=-1, keepdims=True)
                dtot[j] = d if dtot[j] is None else dtot[j] + d
                if j:
                    do[m - j] = do[m - j] - tot[j] * r_m

        # the value product, then the totals
        dv = [_total(mm(e[m - j], da[m], 1, 1) for m in range(j, n1))
              for j in range(n1)]                             # (sub, T, D)
        de = [x + dt for x, dt in zip(transposed(da, v, 2, 2), dtot)]

        # the exp recurrence, highest order first
        ds = [None] * n1
        for m in range(n1 - 1, 0, -1):
            u = de[m] / m
            for j in range(1, m + 1):
                d = j * u * e[m - j]
                ds[j] = d if ds[j] is None else ds[j] + d
                de[m - j] = de[m - j] + j * u * s[j]
        ds[0] = de[0] * e[0]
        ds = [x * scale for x in ds]

        # the score product
        dq = transposed(ds, k, 2, 1)
        dk = transposed(ds, q, 1, 1)
        for m in range(n1):
            dq_ref[m, r, h] = dq[m].astype(dq_ref.dtype)
            dk_ref[m, r, h] = dk[m].astype(dk_ref.dtype)
            dv_ref[m, r, h] = dv[m].astype(dv_ref.dtype)
        return dwo

    first = pl.program_id(0) * bb

    def chunk(r, sums):
        g = [g_ref[m, r].astype(acc_t) for m in range(n1)]     # (sub, T, Dm)
        out = []
        for h in range(n_heads):
            dwo = head(r, h, g)
            if rows % bb:
                # the ragged last block reads past the batch's end: keep
                # those rows out of the weight sum
                row = first + r.start + jax.lax.broadcasted_iota(
                    jnp.int32, dwo.shape, 0)
                dwo = jnp.where(row < rows, dwo, 0.0)
            out.append(sums[h] + dwo)
        return tuple(out)

    n = bb if sub >= bb or bb % sub else sub
    zero = jnp.zeros((n,) + dwo_ref.shape[2:], acc_t)
    sums = over_chunks(bb, sub, chunk, (zero,) * n_heads)
    for h in range(n_heads):
        dwo_ref[0, h] = jnp.sum(sums[h], axis=0).astype(dwo_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "mask", "window", "block_b", "interpret"))
def flash_jet_bwd_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         wo: jnp.ndarray, g: jnp.ndarray, scale: float,
                         mask: str = "none", window: int = 0,
                         block_b: int = 16, interpret: bool = True):
    """The VJP of :func:`jet_flash_attention_pallas` at ``(q, k, v, wo)``
    against the output cotangent ``g`` (n+1, B, T, Dm), for a sequence that
    fits one key tile (T <= ``FLASH_BLOCK_K``): ``(dq, dk, dv, dwo)``, one
    launch over batch blocks, ``dwo`` summed from one (H, Dh, Dm) partial
    per block.  A ragged last block's rows past B are dropped on write and
    kept out of ``dwo``."""
    n1, bsz, h, t, d = q.shape
    if t > FLASH_BLOCK_K:
        raise ValueError(f"{t} tokens do not fit one key tile of "
                         f"{FLASH_BLOCK_K}")
    dm = wo.shape[2]
    if g.shape != (n1, bsz, t, dm):
        raise ValueError(f"cotangent shape {g.shape} != "
                         f"{(n1, bsz, t, dm)}")
    # the working set grows with bb * T^2: long sequences take fewer rows
    bb = min(block_b, bsz, max(1, block_b * 8 // max(t, 8)))
    grid = pl.cdiv(bsz, bb)
    acc_t = jnp.promote_types(q.dtype, jnp.float32)
    stack = pl.BlockSpec((n1, bb, h, t, d), lambda i: (0, i, 0, 0, 0))
    dq, dk, dv, parts = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, scale=scale, mask=mask,
                          window=window, rows=bsz, sub=SUB_ROWS),
        grid=(grid,),
        in_specs=[stack, stack, stack,
                  pl.BlockSpec((h, d, dm), lambda i: (0, 0, 0)),
                  pl.BlockSpec((n1, bb, t, dm), lambda i: (0, i, 0, 0))],
        out_specs=[stack, stack, stack,
                   pl.BlockSpec((1, h, d, dm), lambda i: (i, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] * 3
        + [jax.ShapeDtypeStruct((grid, h, d, dm), acc_t)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="flash_jet_bwd",
    )(q, k, v, wo, g.astype(q.dtype))
    return dq, dk, dv, jnp.sum(parts, axis=0).astype(wo.dtype)


def rms_norm_jet_body(x: jnp.ndarray, gamma: jnp.ndarray,
                      eps: float) -> jnp.ndarray:
    """Fused rms_norm jet on an in-VMEM stack: (n+1, B, W) -> same shape.

    mean-square Cauchy convolution -> rsqrt via the J.C.P. Miller recurrence
    (r = -1/2) -> normalizing Cauchy product -> gain.  Pure VPU work."""
    n1 = x.shape[0]

    ms = []
    for m in range(n1):
        acc = jnp.mean(x[0] * x[m], axis=-1, keepdims=True)
        for i in range(1, m + 1):
            acc = acc + jnp.mean(x[i] * x[m - i], axis=-1, keepdims=True)
        ms.append(acc)
    ms[0] = ms[0] + eps

    # Miller recurrence for ms^(-1/2): the r = -1/2 coefficient (r+1)j - m
    # simplifies to (0.5 j - m), spelled identically in ref.jet_rms_norm_ref
    inv0 = 1.0 / ms[0]
    inv = [jax.lax.rsqrt(ms[0])]
    for m in range(1, n1):
        acc = (0.5 - m) * ms[1] * inv[m - 1]            # j = 1 term
        for j in range(2, m + 1):
            acc = acc + (0.5 * j - m) * ms[j] * inv[m - j]
        inv.append(acc * inv0 / m)

    out = []
    for m in range(n1):
        acc = x[m] * inv[0]
        for j in range(1, m + 1):
            acc = acc + x[m - j] * inv[j]
        out.append(acc * gamma)
    return jnp.stack(out)


def _rms_norm_kernel(x_ref, g_ref, o_ref, *, eps):
    out = rms_norm_jet_body(x_ref[...], g_ref[...][0], eps)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_b", "interpret"))
def jet_rms_norm_pallas(coeffs: jnp.ndarray, gamma: jnp.ndarray,
                        eps: float = 1e-6, block_b: int = 256,
                        interpret: bool = True) -> jnp.ndarray:
    """(n+1, B, W) coefficient stack + (W,) gain -> rms_norm jet, one launch.
    The feature axis W is the reduction axis so each block holds it whole."""
    n1, bsz, w = coeffs.shape
    if gamma.shape != (w,):
        raise ValueError(f"gamma shape {gamma.shape} != ({w},)")
    bb = min(block_b, bsz)
    pb = (-bsz) % bb
    xp = jnp.pad(coeffs, ((0, 0), (0, pb), (0, 0)))
    # padded rows are all-zero: ms_0 = eps > 0, so the rsqrt recurrence
    # stays finite and the padding slices away cleanly
    grid = (xp.shape[1] // bb,)
    out = pl.pallas_call(
        functools.partial(_rms_norm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n1, bb, w), lambda i: (0, i, 0)),
            pl.BlockSpec((1, w), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((n1, bb, w), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(xp.shape, coeffs.dtype),
        interpret=interpret,
        name="jet_rms_norm",
    )(xp, gamma.reshape(1, -1))
    return out[:, :bsz]
