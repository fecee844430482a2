"""Device trace of a measured window, and its reduction to numbers.

A traced run records the window with the JAX profiler. The benchmark's own
host phases are `jax.profiler.TraceAnnotation` spans named ``bench.*``;
the window itself is the span ``bench.window``. The reduction reads, per
chip, the operations the device ran (the "XLA Ops" line of each
``/device:TPU:<n>`` plane) within that window:

* busy: the union of their intervals; idle share is 1 - busy / window;
* kernel time: the summed durations of operations whose name matches a
  pattern (Pallas kernels are named after their kernel functions);
* exposed collective time: the part of the collective operations'
  intervals during which no other operation runs on that chip;
* the longest idle gaps, each put down to the ``bench.*`` host span that
  covers most of it.

Pure functions over (start, end) lists do the arithmetic, so tests can run
them on synthetic events.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# collectives by HLO name; XLA:TPU runs the exchange's reduce-scatter as an
# async pair whose done op is the core's wait for it
COLLECTIVE = re.compile(
    r"all-gather|reduce-scatter|all-to-all|all-reduce|collective-permute"
    r"|async-collective|psum|all_gather|reduce_scatter|all_to_all"
    r"|all_reduce")


# --------------------------------------------------------------- arithmetic
def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of union(a) not covered by union(b)."""
    a, b = merge(a), merge(b)
    left, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                left += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            left += e - cur
    return left


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of [lo, hi) between the busy ones."""
    out, cur = [], lo
    for s, e in merge(clip(busy, lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


# --------------------------------------------------------------- summary
@dataclass
class Op:
    name: str
    start: float        # seconds
    end: float


@dataclass
class TraceSummary:
    window: Interval                          # seconds, host clock of trace
    ops: Dict[int, List[Op]]                  # chip -> ops in the window
    host: List[Op] = field(default_factory=list)   # bench.* spans

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def chips(self) -> List[int]:
        return sorted(self.ops)

    def idle_share(self) -> Optional[float]:
        """1 - busy / window, mean over the chips (None with no chip)."""
        if not self.ops or self.window_s <= 0:
            return None
        return sum(1.0 - self.busy_s(c) / self.window_s
                   for c in self.ops) / len(self.ops)

    def busy_s(self, chip: int) -> float:
        return total((o.start, o.end) for o in self.ops[chip])

    def kernel_s(self, chip: int, pattern: str) -> Optional[float]:
        """Summed device time of ops whose name matches; None if none."""
        rx = re.compile(pattern)
        hits = [o.end - o.start for o in self.ops[chip] if rx.search(o.name)]
        return sum(hits) if hits else None

    def exposed_collective_s(self, chip: int) -> Optional[float]:
        coll = [(o.start, o.end) for o in self.ops[chip]
                if COLLECTIVE.search(o.name)]
        if not coll:
            return None
        other = [(o.start, o.end) for o in self.ops[chip]
                 if not COLLECTIVE.search(o.name)]
        return subtract(coll, other)

    def top_ops(self, n: int = 10) -> List[list]:
        """Device ops taking most time, summed by name, mean over chips."""
        by: Dict[str, float] = {}
        for chip in self.ops:
            for o in self.ops[chip]:
                by[o.name] = by.get(o.name, 0.0) + (o.end - o.start)
        k = max(1, len(self.ops))
        return [[name, s / k] for name, s in
                sorted(by.items(), key=lambda x: -x[1])[:n]]

    def idle_by_host(self, n: int = 10) -> List[list]:
        """Idle time of the first chip, summed by the bench.* host span
        covering most of each gap ("none" where no span covers it)."""
        if not self.ops:
            return []
        chip = self.chips()[0]
        busy = [(o.start, o.end) for o in self.ops[chip]]
        by: Dict[str, float] = {}
        for s, e in gaps(busy, *self.window):
            best, cover = "none", 0.0
            for h in self.host:
                c = min(e, h.end) - max(s, h.start)
                if c > cover:
                    best, cover = h.name, c
            by[best] = by.get(best, 0.0) + (e - s)
        return [[name, t] for name, t in
                sorted(by.items(), key=lambda x: -x[1])[:n]]


def op_name(name: str) -> str:
    """An op event's name: the HLO instruction's name, without the text of
    its shapes and operands that a TPU trace carries after " = " (operand
    names would otherwise match another op's pattern)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line):
    for ev in line.events:
        yield (op_name(ev.name), ev.start_ns * 1e-9,
               (ev.start_ns + ev.duration_ns) * 1e-9)


def reduce_profile(pd) -> TraceSummary:
    """`jax.profiler.ProfileData` -> TraceSummary of the bench.window."""
    host: List[Op] = []
    window = None
    devices: Dict[int, List[Op]] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(Op(n, s, e) for n, s, e in _events(line))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for n, s, e in _events(line):
                    if n == WINDOW:
                        window = (s, e)
                    elif n.startswith("bench."):
                        host.append(Op(n, s, e))
    if window is None:
        raise RuntimeError(f"trace has no {WINDOW} span")
    lo, hi = window
    ops = {c: [Op(o.name, *iv) for o in v
               for iv in clip([(o.start, o.end)], lo, hi)]
           for c, v in devices.items()}
    return TraceSummary(window, ops, host)


def load(trace_dir: str) -> TraceSummary:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise RuntimeError(f"no trace written under {trace_dir}")
    return reduce_profile(ProfileData.from_file(files[-1]))


def describe(trace_dir: str) -> List[str]:
    """Planes and lines of a trace, with event counts (for a first look)."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    pd = ProfileData.from_file(files[-1])
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})[:12]
            span = ((evs[0].start_ns, evs[-1].start_ns) if evs else None)
            out.append(f"{plane.name} | {line.name} | {len(evs)} events | "
                       f"{span} | {names}")
    return out
