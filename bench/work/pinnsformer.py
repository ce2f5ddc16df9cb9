"""Operations and bytes of a PINNsFormer training step, from shapes alone.

One derivative table of ``n_points`` points runs one jet forward: each
point is ``directions`` directional jets of ``order + 1`` coefficient rows
(the coordinate axes and the distinct polarization directions of the
mixed partials, counted here as the program runs them), and each jet row is
a pseudo-sequence of ``tokens`` token rows.  Through that forward:

* ``jet_dense`` (``bench.work.KernelCall``) runs every dense map but the
  attention output projection: the embedding; per encoder layer the q, k, v
  projections and the three FF maps; per decoder layer the q projection,
  the k, v projections of the encoder output and the three FF maps; the
  head's three maps;
* ``jet_flash_attention`` (:class:`FlashCall`) runs each attention block
  from its q, k, v stacks: the Cauchy products of Q K^T and of P V, the
  softmax jet and the output projection, one call per layer.

Operations count 2 per multiply-add of a contraction, plus the softmax
jet's element operations as the kernel body spells them; bytes are what a
call must read and write at least, unpadded.  The boundary term is a plain
forward of every token of the face points.
"""

from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from typing import List, Sequence, Tuple

from bench.work import F32, KernelCall


def directions(d_in: int, mixed: Sequence[Sequence[int]] = ()) -> int:
    """Distinct directional jets of a table: the coordinate axes, and for
    each mixed partial of axes a_1..a_m the nonzero directions
    sum_k eps_k e_{a_k} with eps_1 = +1 (-v gives the same term as v),
    each reduced to its primitive integer direction."""
    seen = {tuple(int(i == a) for i in range(d_in)) for a in range(d_in)}
    for term in mixed:
        for tail in itertools.product((1, -1), repeat=len(term) - 1):
            v = [0] * d_in
            for e, a in zip((1,) + tail, term):
                v[a] += e
            if any(v):
                c = math.gcd(*v) * (1 if next(a for a in v if a) > 0 else -1)
                seen.add(tuple(a // c for a in v))
    return len(seen)


def rows_per_point(d_in: int, order: int, mixed=()) -> int:
    """Jet rows per point of one table (before the token axis)."""
    return directions(d_in, mixed) * (order + 1)


def token_rows_per_point(cfg: dict, order: int, mixed=()) -> int:
    return rows_per_point(cfg["d_in"], order, mixed) * cfg["tokens"]


def dense_maps(cfg: dict) -> List[Tuple[int, int]]:
    """(fan_in, fan_out) of every ``jet_dense`` call of one forward."""
    d, f, h = cfg["width"], cfg["ff"], cfg["head"]
    ff = [(d, f), (f, f), (f, d)]
    layer = [(d, d)] * 3 + ff                   # q, k, v, then FF
    return ([(cfg["d_in"], d)] + layer * (2 * cfg["depth"])
            + [(d, h), (h, h), (h, cfg["d_out"])])


@dataclass(frozen=True)
class FlashCall:
    """One ``jet_flash_attention`` launch: ``batch`` sequences of ``tokens``
    tokens, ``heads`` heads of ``head_dim``, ``n1`` coefficients, output
    projection to ``d_model``."""

    n1: int
    batch: int
    heads: int
    tokens: int
    head_dim: int
    d_model: int

    @property
    def flops(self) -> int:
        n, t, dh = self.n1 - 1, self.tokens, self.head_dim
        cauchy = self.n1 * (self.n1 + 1) // 2      # products over orders
        matmul = 2 * cauchy * 2 * t * t * dh       # Q K^T and P V
        # per score: max, shift, exp; 3m per order-m exp term; n1 sums
        softmax = t * t * (3 + 3 * n * (n + 1) // 2 + self.n1)
        # per output element: the jet division's n(n+1) terms and n1 scales
        division = t * dh * (n * (n + 1) + self.n1)
        per_row = self.heads * (matmul + softmax + division)
        proj = self.n1 * 2 * t * self.heads * dh * self.d_model
        return self.batch * (per_row + proj)

    def bytes(self, itemsize: int = F32) -> int:
        qkv = 3 * self.n1 * self.batch * self.heads * self.tokens * self.head_dim
        out = self.n1 * self.batch * self.tokens * self.d_model
        wo = self.heads * self.head_dim * self.d_model
        return itemsize * (qkv + out + wo)


def table_calls(cfg: dict, n_points: int, order: int, mixed=()
                ) -> List[KernelCall]:
    """The ``jet_dense`` calls of one table of ``n_points`` points."""
    rows = directions(cfg["d_in"], mixed) * n_points * cfg["tokens"]
    return [KernelCall(order + 1, rows, fi, fo) for fi, fo in dense_maps(cfg)]


def flash_calls(cfg: dict, n_points: int, order: int, mixed=()
                ) -> List[FlashCall]:
    """The ``jet_flash_attention`` calls of one table: one per layer."""
    call = FlashCall(order + 1, directions(cfg["d_in"], mixed) * n_points,
                     cfg["n_heads"], cfg["tokens"],
                     cfg["width"] // cfg["n_heads"], cfg["width"])
    return [call] * (2 * cfg["depth"])


def forward_flops(cfg: dict, n_points: int) -> int:
    """Operations of a plain forward of ``n_points`` points: every token
    through every dense map, and each attention block's scores, values
    and output projection."""
    rows = n_points * cfg["tokens"]
    dense = sum(2 * rows * fi * fo for fi, fo in dense_maps(cfg))
    t, d = cfg["tokens"], cfg["width"]
    attn = n_points * (2 * 2 * t * t * d + 2 * t * d * d)
    return dense + 2 * cfg["depth"] * attn


def train_step_flops(cfg: dict, n_points: int, n_boundary: int, order: int,
                     mixed=()) -> int:
    """Operations of one training step: the residual's table and the
    boundary forward, forward and backward (3 x forward), as
    ``bench.work.train_step_flops`` counts them."""
    fwd = sum(c.flops for c in table_calls(cfg, n_points, order, mixed))
    fwd += sum(c.flops for c in flash_calls(cfg, n_points, order, mixed))
    return 3 * (fwd + forward_flops(cfg, n_boundary))
