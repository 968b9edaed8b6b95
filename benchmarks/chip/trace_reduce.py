"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The JAX profiler writes one XSpace per traced run: a plane per TPU chip
(``/device:TPU:<i>``) whose ``XLA Ops`` line holds every operation that
ran on the chip, and a host plane (``/host:CPU``) whose lines hold the
host threads' events, among them the benchmark's own spans
(``bench.*``, opened by ``common.span``). Host and device events share
one clock in the XSpace.

``reduce_trace`` takes the window that the ``bench.window`` span
brackets and gives:

* ``busy_s``: the union of the intervals in which an operation ran on a
  chip, clipped to the window, averaged over the chips;
* ``window_s``: the window's length;
* ``top_ops``: device self time by operation (averaged over the chips),
  an operation named ``<HLO instruction> <opcode>`` (``sub.1 subtract``);
  a loop's self time leaves out the operations nested in it;
* ``idle_gaps``: the window's idle device time on chip 0, each stretch
  named by the innermost benchmark span that covers it, summed by name.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

from common import SPAN_PREFIX, SPAN_WINDOW

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
NO_SPAN = "(outside bench spans)"

Interval = Tuple[int, int]  # [start_ns, end_ns)


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                      # mean over chips
    chips: int
    top_ops: List[Tuple[str, float]]   # (name, seconds), longest first
    idle_gaps: List[Tuple[str, float]]  # (host span, seconds), longest first
    op_seconds: Dict[str, float]       # every op name, mean over chips

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals; returns them sorted and disjoint."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The stretches of [lo, hi) that ``busy`` (sorted, disjoint) leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


HLO_TEXT = re.compile(r"^%?([^\s=]+) = .*?\b([a-z][a-z0-9-]*)\(")


def op_name(text: str) -> str:
    """``%sub.1 = f32[8]{0} subtract(...)`` -> ``sub.1 subtract``."""
    m = HLO_TEXT.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text[:80]


def _events(line):
    for ev in line.events:
        yield ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)


def self_times(ops: Sequence[Tuple[str, int, int]]) -> Dict[str, int]:
    """Nanoseconds by op name, each op less the ops nested inside it
    (a loop holds its body's ops on the same trace line)."""
    out: Dict[str, int] = {}
    stack: List[List] = []  # open ops: [name, start, end, nested ns]

    def close():
        name, s, e, nested = stack.pop()
        out[name] = out.get(name, 0) + (e - s) - nested

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            close()
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0])
    while stack:
        close()
    return out


def read_planes(pd) -> Tuple[Dict[int, List[Tuple[str, int, int]]],
                             List[Tuple[str, int, int]]]:
    """(device ops by chip index, benchmark host spans) of a ProfileData."""
    devices: Dict[int, List[Tuple[str, int, int]]] = {}
    spans: List[Tuple[str, int, int]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops.extend((op_name(n), s, e)
                               for n, s, e in _events(line))
            devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(ev for ev in _events(line)
                             if ev[0].startswith(SPAN_PREFIX))
    return devices, spans


class _SpanIndex:
    """Benchmark host spans, for naming a stretch of time by the
    innermost span that covers it."""

    def __init__(self, spans: Sequence[Tuple[str, int, int]]):
        self.spans = sorted(spans, key=lambda s: s[1])
        self.starts = [s[1] for s in self.spans]
        self.longest = max((e - s for _, s, e in self.spans), default=0)

    def attribute(self, lo: int, hi: int) -> Dict[str, int]:
        """Nanoseconds of [lo, hi) by innermost covering span name."""
        j = bisect.bisect_left(self.starts, hi)
        cand = []
        i = j - 1
        while i >= 0 and self.spans[i][1] >= lo - self.longest:
            name, s, e = self.spans[i]
            if e > lo and s < hi:
                cand.append((max(s, lo), min(e, hi), e - s, name))
            i -= 1
        cuts = sorted({lo, hi, *(c[0] for c in cand), *(c[1] for c in cand)})
        out: Dict[str, int] = {}
        for a, b in zip(cuts, cuts[1:]):
            cover = [c for c in cand if c[0] <= a and c[1] >= b]
            name = min(cover, key=lambda c: c[2])[3] if cover else NO_SPAN
            out[name] = out.get(name, 0) + (b - a)
        return out


def reduce_trace(pd, window_span: str = SPAN_WINDOW,
                 top: int = 10) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData`` to the window's numbers."""
    devices, spans = read_planes(pd)
    windows = [s for s in spans if s[0] == window_span]
    if len(windows) != 1:
        raise ValueError(f"expected one {window_span!r} span in the trace, "
                         f"found {len(windows)}")
    _, lo, hi = windows[0]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    busy_ns, op_ns = [], {}
    for ops in devices.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if e > lo and s < hi]
        busy = union((s, e) for _, s, e in inside)
        busy_ns.append(sum(e - s for s, e in busy))
        for n, v in self_times(inside).items():
            op_ns[n] = op_ns.get(n, 0) + v
    chips = len(devices)
    op_s = {n: v / chips / 1e9 for n, v in op_ns.items()}

    first = devices[min(devices)]
    busy0 = union(clip(((s, e) for _, s, e in first), lo, hi))
    index = _SpanIndex([s for s in spans if s[0] != window_span])
    idle: Dict[str, int] = {}
    for a, b in gaps(busy0, lo, hi):
        for name, ns in index.attribute(a, b).items():
            idle[name] = idle.get(name, 0) + ns

    def ranked(d: Dict[str, float], scale: float) -> List[Tuple[str, float]]:
        return sorted(((n, v / scale) for n, v in d.items()),
                      key=lambda x: -x[1])[:top]

    return Reduced(window_s=(hi - lo) / 1e9,
                   busy_s=sum(busy_ns) / chips / 1e9,
                   chips=chips,
                   top_ops=ranked(op_s, 1.0),
                   idle_gaps=ranked(idle, 1e9),
                   op_seconds=op_s)


def reduce_dir(log_dir: str, **kw) -> Reduced:
    """Reduce the trace the profiler wrote under ``log_dir``."""
    from jax.profiler import ProfileData

    return reduce_trace(ProfileData.from_file(find_xplane(log_dir)), **kw)

