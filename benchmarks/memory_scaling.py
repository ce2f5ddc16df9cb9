"""Paper section III-B memory claim: n-TangentProp is O(n M) while nested
autodiff's graph is O(M^n).  Measured here as compiled temp-buffer bytes from
XLA's memory analysis (no wall clock needed), on the paper's 3x24
``DenseMLP``; the autodiff side is ``AutodiffEngine``, nested ``jax.grad``
for this scalar-output net.  CPU figures, not chip evidence.

The second sweep makes the flash-attention memory claim the same way: the
PR-5 materializing score kernel's temp footprint grows with T^2 (it holds
the whole (n+1, B*H, T, T) probability jet), while the tiled flash-jet
kernel's grows with its BLOCK sizes -- at fixed T, halving block_q/block_k
shrinks it; at fixed blocks, growing T leaves the per-tile working set
unchanged (only the linear-in-T output remains)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import AutodiffEngine, DenseMLP, NTPEngine

from .common import csv_row


def _temp_bytes(fn, *args) -> int:
    mem = jax.jit(fn).lower(*args).compile().memory_analysis()
    return int(getattr(mem, "temp_size_in_bytes", 0) or 0)


def _attention_rows(order: int):
    """Flash-jet vs materializing attention temp bytes, T x block sweep."""
    from repro.kernels.jet_attention import (jet_attention_scores_pallas,
                                             jet_flash_attention_pallas)

    n1, bsz, heads, dh, dm = order + 1, 2, 2, 8, 16
    interpret = jax.default_backend() != "tpu"
    scale = 1.0 / math.sqrt(dh)
    rows = []
    for t in (64, 256):
        kq = jax.random.PRNGKey(t)
        q, k, v = (jax.random.normal(kk, (n1, bsz, heads, t, dh), jnp.float32)
                   for kk in jax.random.split(kq, 3))
        wo = jax.random.normal(jax.random.PRNGKey(1), (heads, dh, dm),
                               jnp.float32)
        m_scores = _temp_bytes(
            lambda qq, kk: jet_attention_scores_pallas(
                qq, kk, scale, interpret=interpret),
            q.reshape(n1, bsz * heads, t, dh), k.reshape(n1, bsz * heads, t, dh))
        rows.append(csv_row(f"membytes_attn_scores_T{t}", m_scores / 1e6,
                            f"bytes={m_scores};order={order};flash=0"))
        for bq in (32, 64):
            m_flash = _temp_bytes(
                lambda qq, kk, vv, ww, bq=bq: jet_flash_attention_pallas(
                    qq, kk, vv, ww, scale, block_q=bq, block_k=bq,
                    interpret=interpret), q, k, v, wo)
            rows.append(csv_row(
                f"membytes_attn_flash_T{t}_blk{bq}", m_flash / 1e6,
                f"bytes={m_flash};order={order};flash=1;"
                f"vs_scores_x={m_flash / max(m_scores, 1):.3f}"))
    return rows


def run(max_order: int = 6, batch: int = 256, attn_order: int = 2):
    net = DenseMLP(1, 24, 3, 1)
    ntp, autodiff = NTPEngine(), AutodiffEngine()
    params = net.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    x = jax.random.uniform(jax.random.PRNGKey(1), (batch, 1), jnp.float32, -1, 1)
    rows = []
    for n in (1, 2, 4, max_order):
        m_ntp = _temp_bytes(lambda p, x, n=n: ntp.derivs(net, p, x, n),
                            params, x)
        m_ad = _temp_bytes(lambda p, x, n=n: autodiff.derivs(net, p, x, n),
                           params, x)
        rows.append(csv_row(f"membytes_ntp_n{n}", m_ntp / 1e6,
                            f"bytes={m_ntp}"))
        rows.append(csv_row(f"membytes_autodiff_n{n}", m_ad / 1e6,
                            f"bytes={m_ad};ratio={m_ad / max(m_ntp, 1):.2f}"))
    rows.extend(_attention_rows(attn_order))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
