"""Reduce a profiler trace (``.xplane.pb``) to device busy time, kernel
time and idle gaps, on one clock with the benchmark's own host spans.

The device side is the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane:
busy time is the union of its op intervals, so nested or overlapping ops
count once. The host side is every event whose name starts with
``bench.``: the ``jax.profiler.TraceAnnotation`` spans the harness puts
around its own calls into the program, which the profiler records on the
same clock as the device ops.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def merge(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo: float, hi: float, ends=None) -> float:
    """Length of ``[lo, hi)`` covered by disjoint sorted intervals
    (``ends``, the intervals' end points, lets it skip to ``lo``)."""
    total = 0.0
    start = 0 if ends is None else bisect.bisect_right(ends, lo)
    for i in range(start, len(merged)):
        s, e = merged[i]
        if e <= lo:
            continue
        if s >= hi:
            break
        total += min(e, hi) - max(s, lo)
    return total


@dataclasses.dataclass
class Trace:
    """Device ops per chip and the harness's host spans, in ns."""

    ops: dict[str, list[tuple[str, float, float]]]   # plane -> (name, s, e)
    spans: dict[str, list[tuple[float, float]]]      # span name -> (s, e)

    def __post_init__(self):
        self._busy = {p: merge((s, e) for _, s, e in ev)
                      for p, ev in self.ops.items()}
        self._ends = {p: [e for _, e in m] for p, m in self._busy.items()}

    @property
    def chips(self) -> int:
        return len(self.ops)

    def window(self, name: str = "bench.window") -> tuple[float, float]:
        """The one span that brackets the measured window."""
        spans = self.spans.get(name, [])
        if len(spans) != 1:
            raise ValueError(f"expected one {name!r} span, found {len(spans)}")
        return spans[0]

    def busy_ns(self, lo: float, hi: float) -> float:
        """Device busy time in ``[lo, hi)``, averaged over the chips."""
        if not self._busy:
            return 0.0
        return sum(covered(m, lo, hi, self._ends[p])
                   for p, m in self._busy.items()) / len(self._busy)

    def spans_in(self, name: str, lo: float, hi: float):
        return [(s, e) for s, e in self.spans.get(name, [])
                if s >= lo and e <= hi]

    def busy_in_spans_ns(self, name: str, lo: float, hi: float) -> float:
        """Device busy time inside the ``name`` spans of ``[lo, hi)``,
        averaged over the chips."""
        return sum(self.busy_ns(s, e) for s, e in self.spans_in(name, lo, hi))

    def op_time_ns(self, pattern: str, lo: float, hi: float) -> float:
        """Summed device time of ops whose name matches ``pattern``
        (``re.search``), clipped to ``[lo, hi)``, averaged over chips."""
        rx = re.compile(pattern)
        total = 0.0
        for ev in self.ops.values():
            total += sum(max(0.0, min(e, hi) - max(s, lo))
                         for n, s, e in ev if rx.search(n))
        return total / max(1, len(self.ops))

    def op_breakdown(self, lo: float, hi: float, top: int = 10):
        """[[op, seconds], ...]: the device ops that took most time in the
        window, by HLO op name (the text before `` = ``), per chip."""
        acc: dict[str, float] = {}
        for ev in self.ops.values():
            for n, s, e in ev:
                d = min(e, hi) - max(s, lo)
                if d > 0:
                    key = n.split(" = ")[0]
                    acc[key] = acc.get(key, 0.0) + d
        n_chips = max(1, len(self.ops))
        rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / n_chips / 1e9] for k, v in rows]

    def idle_gaps(self, lo: float, hi: float, top: int = 10):
        """[[host span, seconds], ...]: the longest gaps with no device op
        on the first chip, each named by the host span that overlaps it
        most (``host`` where no harness span does)."""
        if not self._busy:
            return []
        busy = next(iter(self._busy.values()))
        gaps, t = [], lo
        for s, e in busy:
            if e <= lo:
                continue
            if s >= hi:
                break
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for gs, ge in gaps[:top]:
            best, label = 0.0, "host"
            for name, spans in self.spans.items():
                if name == "bench.window":
                    continue
                ov = sum(max(0.0, min(e, ge) - max(s, gs)) for s, e in spans)
                if ov > best:
                    best, label = ov, name
            out.append([label, (ge - gs) / 1e9])
        return out


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(paths)}")
    return paths[0]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file (or the one under a trace directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    ops: dict = {}
    spans: dict = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    for v in spans.values():
        v.sort()
    return Trace(ops=ops, spans=spans)
