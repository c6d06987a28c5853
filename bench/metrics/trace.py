"""Reduction of a JAX profiler trace to device busy time, kernel time and
idle gaps.

`load` reads the ``.xplane.pb`` that ``jax.profiler.trace`` wrote, with
nothing but JAX (`jax.profiler.ProfileData`), into a `Trace`: the device
operations of each chip (the ``XLA Ops`` line of every ``/device:TPU:<i>``
plane) and the host events (every line of the ``/host:CPU`` plane), all as
``(start_ns, end_ns, name)`` on the profiler's one clock.  The functions
below it are pure arithmetic on such lists, so the tests in
``bench/tests`` check them on hand-made intervals and on a small trace
recorded on a v5e chip.

Definitions:

* window: from the start of the first to the end of the last host span
  named ``bench.window``, which the runners open around their measured
  window;
* busy: the union of the intervals in which an operation ran on a chip,
  clipped to the window, averaged over chips;
* idle share: 1 - busy / window;
* operation names: the HLO instruction's name (``%hist_tiles_pallas.72``,
  the text before `` = `` of the trace's event name); a loop or call
  (``%while.216``) is an event that holds its body's operations;
* kernel time: the union of the device intervals of the operations whose
  name contains one of the kernel's names, clipped to the window; the
  rest of the busy time is the other operations';
* idle gap: a stretch of the window in which no operation ran, labelled by
  the innermost ``bench.*`` host span and the shortest other host event
  that cover its midpoint (what the host was doing).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

Interval = Tuple[float, float, str]           # (start_ns, end_ns, name)

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"


class Trace(NamedTuple):
    device: Dict[int, List[Interval]]          # chip id -> operations
    host: List[Interval]                       # every host event


def op_name(event_name: str) -> str:
    """``%name.N`` of an HLO instruction from its trace event name, which
    may carry the whole instruction text."""
    return event_name.split(" = ", 1)[0].strip()


def load(trace_dir: str) -> Trace:
    """Read the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    device: Dict[int, List[Interval]] = {}
    host: List[Interval] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    ops.append((float(e.start_ns),
                                float(e.start_ns + e.duration_ns),
                                op_name(e.name)))
            device[int(m.group(1))] = sorted(ops)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    host.append((float(e.start_ns),
                                 float(e.start_ns + e.duration_ns), e.name))
    return Trace(device=device, host=sorted(host))


def window(trace: Trace) -> Optional[Tuple[float, float]]:
    """``(lo, hi)`` ns of the measured window, from the runner's spans."""
    spans = [(s, e) for s, e, n in trace.host if n == WINDOW_SPAN]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi), n) for s, e, n in intervals
            if e > lo and s < hi and min(e, hi) > max(s, lo)]


def union(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Disjoint, sorted stretches covered by the intervals in [lo, hi]."""
    merged: List[List[float]] = []
    for s, e, _ in sorted(clip(intervals, lo, hi)):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(intervals, lo, hi))


def gaps(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Stretches of [lo, hi] in which no interval runs."""
    out, t = [], lo
    for s, e in union(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def matches(name: str, patterns: Sequence[str]) -> bool:
    return any(p in name for p in patterns)


def kernel_ns(intervals: Iterable[Interval], patterns: Sequence[str],
              lo: float, hi: float) -> float:
    """Device time of the operations that match ``patterns``."""
    return busy_ns([x for x in intervals if matches(x[2], patterns)], lo, hi)


def other_ns(intervals: Sequence[Interval], patterns: Sequence[str],
             lo: float, hi: float) -> float:
    """Busy time in which none of the matching operations ran."""
    return busy_ns(intervals, lo, hi) - kernel_ns(intervals, patterns, lo,
                                                  hi)


def self_ns(intervals: Iterable[Interval]) -> List[Interval]:
    """Each operation's own time: its interval less the intervals of the
    operations nested in it (a loop less its body)."""
    out: List[list] = []
    stack: List[list] = []
    for s, e, n in sorted(intervals, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        rec = [s, e, n, e - s]
        if stack and e <= stack[-1][1]:
            stack[-1][3] -= e - s
        stack.append(rec)
        out.append(rec)
    return [(s, s + own, n) for s, _, n, own in out]


def label(host: Sequence[Interval], t: float) -> str:
    """What the host was doing at ``t``: the innermost ``bench.*`` span and
    the shortest other host event covering it."""
    covering = [(e - s, n) for s, e, n in host if s <= t <= e]
    ours = sorted((d, n) for d, n in covering if n.startswith("bench."))
    theirs = sorted((d, n) for d, n in covering
                    if not n.startswith("bench."))
    parts = [ours[0][1] if ours else "no bench span"]
    if theirs:
        parts.append(theirs[0][1])
    return " / ".join(parts)


def top_ops(intervals: Iterable[Interval], lo: float, hi: float,
            k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` operation names with the most device time of their own
    (`self_ns`), in seconds."""
    total: Dict[str, float] = {}
    for s, e, n in self_ns(clip(intervals, lo, hi)):
        total[n] = total.get(n, 0.0) + (e - s)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [(n, t * 1e-9) for n, t in ranked]


def top_gaps(intervals: Iterable[Interval], host: Sequence[Interval],
             lo: float, hi: float, k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` longest idle gaps, in seconds, labelled by `label`."""
    gs = sorted(gaps(intervals, lo, hi), key=lambda g: g[0] - g[1])[:k]
    return [(label(host, (s + e) / 2), (e - s) * 1e-9) for s, e in gs]


class Reduction(NamedTuple):
    """What the per-layer readers take from a trace."""
    window_s: float
    busy_s: float                  # averaged over chips
    ops: List[Interval]            # chip 0's operations in the window
    lo: float
    hi: float
    host: List[Interval]

    def kernel_s(self, patterns: Sequence[str]) -> float:
        return kernel_ns(self.ops, patterns, self.lo, self.hi) * 1e-9

    def other_s(self, patterns: Sequence[str]) -> float:
        return other_ns(self.ops, patterns, self.lo, self.hi) * 1e-9

    def breakdown(self) -> Dict[str, list]:
        return {"device_ops": [list(x) for x in
                               top_ops(self.ops, self.lo, self.hi)],
                "idle_gaps": [list(x) for x in
                              top_gaps(self.ops, self.host, self.lo,
                                       self.hi)]}


def reduce(trace: Trace, chips: Sequence[int] = (0,)) -> Optional[Reduction]:
    """Window, busy time and chip 0's operations; None when the trace holds
    no window span or no device operation in the window."""
    win = window(trace)
    if win is None:
        return None
    lo, hi = win
    busy = [busy_ns(trace.device.get(c, []), lo, hi) for c in chips]
    ops = clip(trace.device.get(chips[0], []), lo, hi)
    if not ops:
        return None
    return Reduction(window_s=(hi - lo) * 1e-9,
                     busy_s=sum(busy) / len(busy) * 1e-9, ops=ops, lo=lo,
                     hi=hi, host=trace.host)
