"""Reduce a JAX profiler trace (``*.xplane.pb``) to the benchmark's device
numbers.

* busy: the union of the intervals in which an operation ran on a device
  (events of the ``XLA Modules`` / ``XLA Ops`` lines of each
  ``/device:<kind>:<n>`` plane), averaged over the devices, less what
  falls inside a ``bench.generate`` span: the source making the next
  version off the window's clock, whose device time is counted apart
  (``generate_busy``);
* idle gaps: the complement of busy inside the window, each gap attributed
  to the innermost ``bench.*`` host span (``jax.profiler.TraceAnnotation``)
  that holds its midpoint, summed by span name;
* device ops: device time by operation (HLO instruction name);
* roofline share: the least time the chip needs to move ``bytes`` at its
  peak HBM bandwidth (``peaks.json``), over the busy time given.

The window is the ``bench.window`` host span when the trace has one,
otherwise the extent of every event.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
DEVICE_LINES = ("XLA Modules", "XLA Ops", "Async XLA Ops")
WINDOW_SPAN = "bench.window"
OFF_CLOCK_SPAN = "bench.generate"
SPAN_PREFIX = "bench."

Interval = Tuple[int, int]          # [start_ns, end_ns)


def peaks_for(kind: str, path: str = os.path.join(HERE, "peaks.json")
              ) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind raises."""
    with open(path) as f:
        table = json.load(f)
    if kind not in table["kinds"]:
        raise KeyError(f"no peaks for device kind {kind!r} in {path} "
                       f"(known: {sorted(table['kinds'])})")
    return table["kinds"][kind]


def roofline_pct(nbytes: int, busy_s: float, kind: str) -> Optional[float]:
    """Bandwidth-bound roofline share in percent, or None without work."""
    if nbytes <= 0 or busy_s <= 0:
        return None
    return 100.0 * (nbytes / peaks_for(kind)["hbm_bytes_per_s"]) / busy_s


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``intervals``."""
    out: List[List[int]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """The parts of the disjoint sorted intervals ``a`` outside those of
    ``b``."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > lo:
                out.append((lo, b[k][0]))
            lo = max(lo, b[k][1])
            k += 1
        if lo < hi:
            out.append((lo, hi))
    return out


def overlap_ns(a: Sequence[Interval], b: Sequence[Interval]) -> int:
    """Length of the intersection of two disjoint sorted interval lists."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def short_op_name(name: str) -> str:
    """``%gear_hash_pallas.1 = s32[...] custom-call(...)`` -> the name."""
    return name.split(" = ", 1)[0].strip()


class Trace:
    """Device intervals, device op times and ``bench.*`` host spans."""

    def __init__(self, profile):
        self.device_busy: Dict[str, List[Interval]] = {}
        self.op_ns: Dict[str, int] = {}
        self.spans: Dict[str, List[Interval]] = {}
        lo, hi = None, None
        for plane in profile.planes:
            is_device = plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CUSTOM")
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    lo = s if lo is None else min(lo, s)
                    hi = e if hi is None else max(hi, e)
                    if is_device and line.name in DEVICE_LINES:
                        self.device_busy.setdefault(plane.name, []).append(
                            (s, e))
                        if line.name != "XLA Modules":
                            op = short_op_name(ev.name)
                            self.op_ns[op] = self.op_ns.get(op, 0) + e - s
                    elif not is_device and ev.name.startswith(SPAN_PREFIX):
                        self.spans.setdefault(ev.name, []).append((s, e))
        for dev, iv in self.device_busy.items():
            self.device_busy[dev] = union(iv)
        for name, iv in self.spans.items():
            self.spans[name] = sorted(iv)
        window = self.spans.get(WINDOW_SPAN)
        if window:
            self.window = (window[0][0], window[-1][1])
        else:
            self.window = (lo or 0, hi or 0)
        self.off_clock = union(self.spans.get(OFF_CLOCK_SPAN, []))

    @classmethod
    def load(cls, path: str) -> "Trace":
        """From an ``.xplane.pb`` file or a directory the profiler wrote."""
        from jax.profiler import ProfileData
        if os.path.isdir(path):
            found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                     recursive=True))
            if not found:
                raise FileNotFoundError(f"no .xplane.pb under {path}")
            path = found[-1]
        return cls(ProfileData.from_file(path))

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _on_clock(self, iv: Sequence[Interval]) -> List[Interval]:
        """A device's busy intervals inside the window and outside every
        ``bench.generate`` span."""
        return subtract(clip(iv, *self.window), self.off_clock)

    def busy(self, within: Optional[Sequence[Interval]] = None) -> float:
        """Seconds a device was busy inside the window and on its clock
        (and inside ``within``, when given), averaged over the devices."""
        if not self.device_busy:
            return 0.0
        total = 0
        for iv in self.device_busy.values():
            iv = self._on_clock(iv)
            total += overlap_ns(iv, union(within)) if within is not None \
                else sum(b - a for a, b in iv)
        return total / len(self.device_busy) / 1e9

    def generate_busy(self) -> float:
        """Seconds a device was busy inside the window's ``bench.generate``
        spans, off its clock, averaged over the devices."""
        if not self.device_busy:
            return 0.0
        total = sum(overlap_ns(clip(iv, *self.window), self.off_clock)
                    for iv in self.device_busy.values())
        return total / len(self.device_busy) / 1e9

    def busy_in_spans(self, name: str) -> float:
        return self.busy(self.spans.get(name, []))

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """Idle seconds inside the window by what the host was doing: each
        piece of a gap goes to the innermost host span around it (``other``
        where no span is open), largest first; the first device's gaps."""
        if not self.device_busy:
            return []
        busy = self._on_clock(next(iter(self.device_busy.values())))
        edges = [self.window[0]] + [x for iv in busy for x in iv] \
            + [self.window[1]]
        spans = sorted((b - a, a, b, name) for name, iv in self.spans.items()
                       if name != WINDOW_SPAN for a, b in iv)
        by_name: Dict[str, int] = {}
        for lo, hi in zip(edges[::2], edges[1::2]):
            cuts = sorted({lo, hi} | {x for _, a, b, _ in spans
                                      for x in (a, b) if lo < x < hi})
            for a, b in zip(cuts, cuts[1:]):
                mid = (a + b) / 2
                name = next((n for _, s, e, n in spans if s <= mid < e),
                            "other")
                by_name[name] = by_name.get(name, 0) + b - a
        ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        return [(name, ns / 1e9) for name, ns in ranked]

    def device_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        """Device seconds by operation, largest first, averaged over the
        devices."""
        n = max(1, len(self.device_busy))
        ranked = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:top]
        return [(name, ns / n / 1e9) for name, ns in ranked]

