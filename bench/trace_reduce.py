"""Reduce a profiler trace (``.xplane.pb``) of a window to device numbers.

* busy: the union of the intervals in which an operation ran on a chip
  (its ``XLA Ops`` line), averaged over the chips in the trace;
* device time by operation name, split into Pallas kernels and the rest
  (XLA's own fusions, copies and collectives);
* idle gaps: the stretches between busy intervals, the longest of them
  each put down to what the host was doing in its middle (the shortest host
  event that spans it), summed by that name.

An event of a chip's ``XLA Ops`` line is named by its HLO instruction
(``%jvp_jit_jet_dense_pallas__.73 = f32[...] custom-call(...), ...``).  A
Pallas kernel is a custom call whose target is ``tpu_custom_call``; its
instruction is named after the ``pallas_call``'s jitted wrapper
(``jet_dense_pallas``).  Operations are summed by instruction name with the
numeric suffix dropped (``jvp_jit_jet_dense_pallas__``, ``fusion``,
``copy``), so a name stands for one kind of operation across the window.
"""

from __future__ import annotations

import glob
import os
from bisect import bisect_right
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
IDLE_HOST = "(no host event)"
SHORT_GAPS = "(shorter gaps)"
LABELLED_GAPS = 2000        # the longest gaps of each chip get a host label


PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


def is_pallas(hlo: str) -> bool:
    """A Pallas kernel's event: a custom call to ``tpu_custom_call``."""
    return PALLAS_TARGET in hlo


def op_kind(hlo: str) -> str:
    """``%fusion.90 = f32[...] fusion(...)`` -> ``fusion``."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


@dataclass
class Summary:
    busy_s: float = 0.0
    chips: int = 0
    ops: Dict[str, float] = field(default_factory=dict)      # kind -> s
    pallas: Dict[str, float] = field(default_factory=dict)   # kind -> s
    gaps: Dict[str, float] = field(default_factory=dict)     # host -> s

    @property
    def pallas_s(self) -> float:
        return sum(self.pallas.values()) / max(self.chips, 1)

    @property
    def xla_s(self) -> float:
        return (sum(self.ops.values()) / max(self.chips, 1)) - self.pallas_s

    def kernel_s(self, kernel: str) -> float:
        """Device seconds per chip of the Pallas events whose name holds
        ``kernel``."""
        return sum(s for n, s in self.pallas.items()
                   if kernel in n) / max(self.chips, 1)

    def top_ops(self, n: int) -> List[Tuple[str, float]]:
        return sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n: int) -> List[Tuple[str, float]]:
        return sorted(self.gaps.items(), key=lambda kv: -kv[1])[:n]


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


class _HostIndex:
    """Host events, for "what was the host doing at time t": the shortest
    event that spans t, among the last ``BACK`` events to start before it
    and every event longer than ``LONG_NS``."""

    BACK = 512
    LONG_NS = 1_000_000

    def __init__(self, events: List[Tuple[int, int, str]]):
        self.events = sorted(events)
        self.starts = [s for s, _, _ in self.events]
        self.long = [ev for ev in self.events if ev[1] - ev[0] >= self.LONG_NS]

    def at(self, t: int) -> str:
        best, best_len = IDLE_HOST, None
        i = bisect_right(self.starts, t)
        for s, e, name in self.events[max(0, i - self.BACK):i] + self.long:
            if s <= t <= e and (best_len is None or e - s < best_len):
                best, best_len = name, e - s
        return best


def reduce_file(path: str) -> Summary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = Summary()
    busy_all: List[List[Tuple[int, int]]] = []
    host: List[Tuple[int, int, str]] = []
    for plane in data.planes:
        if plane.name.startswith(HOST_PLANE):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    host.append((s, s + int(ev.duration_ns), ev.name))
            continue
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        spans = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, d = int(ev.start_ns), int(ev.duration_ns)
                spans.append((s, s + d))
                kind = op_kind(ev.name)
                out.ops[kind] = out.ops.get(kind, 0.0) + d * 1e-9
                if is_pallas(ev.name):
                    out.pallas[kind] = out.pallas.get(kind, 0.0) + d * 1e-9
        if spans:
            busy_all.append(_merge(spans))
    out.chips = len(busy_all)
    index = _HostIndex(host)
    for merged in busy_all:
        out.busy_s += sum(e - s for s, e in merged) * 1e-9
        gaps = sorted(((s1 - e0, (e0 + s1) // 2) for (_, e0), (s1, _)
                       in zip(merged, merged[1:])), reverse=True)
        for rank, (length, mid) in enumerate(gaps):
            label = index.at(mid) if rank < LABELLED_GAPS else SHORT_GAPS
            out.gaps[label] = out.gaps.get(label, 0.0) + length * 1e-9
    if out.chips:
        out.busy_s /= out.chips
        out.gaps = {k: v / out.chips for k, v in out.gaps.items()}
    return out


def reduce_dir(directory: str) -> Summary:
    """Reduce the one ``.xplane.pb`` the profiler wrote under
    ``directory``."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {directory}, "
                                f"found {paths}")
    return reduce_file(paths[0])
