"""Matmul operations and bytes of the n-TangentProp jet, from shapes alone.

An order-n directional jet of a point is n+1 coefficient rows; every dense
map of the network multiplies each row by its weight matrix.  A derivative
table asks for one jet per direction:

* ``grid`` (pure derivatives through order n): d_in directions, n+1 rows;
* each mixed partial of m axes: 2^m polarization directions, m+1 rows.

The Pallas kernel ``jet_dense`` runs one dense map of one jet forward, with
the direction axis folded into its batch, so a forward pass through a
network of L maps is L kernel calls.  Operations count 2 per
multiply-add of the matmuls (the activation's Faa di Bruno epilogue is not
counted); bytes are what a call must read and write at least: its input
and output coefficient stacks, the weights and the bias, unpadded.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence, Tuple

F32 = 4


@dataclass(frozen=True)
class KernelCall:
    """One ``jet_dense`` launch: (n1, rows, d_in) x (d_in, d_out)."""

    n1: int
    rows: int
    d_in: int
    d_out: int

    @property
    def flops(self) -> int:
        return 2 * self.n1 * self.rows * self.d_in * self.d_out

    def bytes(self, itemsize: int = F32) -> int:
        return itemsize * (self.n1 * self.rows * (self.d_in + self.d_out)
                           + self.d_in * self.d_out + self.d_out)


def dense_maps(cfg: dict) -> List[Tuple[int, int]]:
    """(fan_in, fan_out) of each dense map of a ``dense`` network: ``depth``
    hidden layers of ``width``, so depth + 1 maps."""
    w = cfg["width"]
    return ([(cfg["d_in"], w)] + [(w, w)] * (cfg["depth"] - 1)
            + [(w, cfg["d_out"])])


def jets(d_in: int, order: int, mixed: Sequence[Sequence[int]] = ()
         ) -> List[Tuple[int, int]]:
    """(directions, rows per direction) of each jet forward of a table:
    the grid, then each mixed partial."""
    out = [(d_in, order + 1)]
    out += [(2 ** len(m), len(m) + 1) for m in mixed]
    return out


def rows_per_point(d_in: int, order: int, mixed=()) -> int:
    return sum(n * r for n, r in jets(d_in, order, mixed))


def table_calls(cfg: dict, n_points: int, order: int, mixed=()
                ) -> List[KernelCall]:
    """The kernel calls of one derivative table of ``n_points`` points."""
    return [KernelCall(r, dirs * n_points, fi, fo)
            for dirs, r in jets(cfg["d_in"], order, mixed)
            for fi, fo in dense_maps(cfg)]


def forward_flops(cfg: dict, n_rows: int) -> int:
    """Matmul operations of a plain forward of ``n_rows`` points."""
    return sum(2 * n_rows * fi * fo for fi, fo in dense_maps(cfg))


def train_step_flops(cfg: dict, n_points: int, n_boundary: int, order: int,
                     mixed=()) -> int:
    """Model operations of one training step: the residual's table and the
    boundary forward, forward and backward (3 x forward).  The backward's
    recomputation of the forward is not counted."""
    fwd = sum(c.flops for c in table_calls(cfg, n_points, order, mixed))
    return 3 * (fwd + forward_flops(cfg, n_boundary))


def roofline_seconds(calls: Sequence[KernelCall], peak_flops: float,
                     peak_bytes: float) -> Tuple[float, str]:
    """Least time the chip could take for ``calls``, summed call by call
    as max(ops / peak, bytes / bandwidth), and which bound held for most
    of that time ("compute" or "memory")."""
    total, by = 0.0, {"compute": 0.0, "memory": 0.0}
    for c in calls:
        tc, tm = c.flops / peak_flops, c.bytes() / peak_bytes
        t = max(tc, tm)
        by["compute" if tc >= tm else "memory"] += t
        total += t
    return total, max(by, key=by.get)
