"""Reduce a JAX profiler trace to the benchmark's device numbers.

The harness wraps its measured window in a host span named `WINDOW`.
From the trace this module takes, per chip, the intervals in which a
device operation ran, and reduces them to:

- busy seconds: the union of those intervals inside the window,
  averaged over the chips used;
- the window's length, from the host span;
- each compiled module's device time (the "XLA Modules" line), so a
  metric can time one program by its name;
- a breakdown: the operations that took most device time, and the idle
  gaps inside the window labelled by the innermost host span that
  covers each gap's middle (what the host was doing meanwhile).

The reduction is plain arithmetic on (start, end) intervals, so it is
checked on hand-counted intervals and on a trace recorded on the CPU
(`test_chipbench_trace.py`).
"""
from __future__ import annotations

import glob
import heapq
import os
from typing import Dict, List, Tuple

WINDOW = "chipbench.window"

# Where a platform's device operations and modules are in the trace:
# (plane name prefix, line name prefix).
DEVICE_OPS = {"tpu": ("/device:TPU:", "XLA Ops")}
DEVICE_MODULES = {"tpu": ("/device:TPU:", "XLA Modules")}
HOST_PLANE = "/host:CPU"

Interval = Tuple[float, float]


def merge(intervals: List[Interval]) -> List[Interval]:
    """Sorted, disjoint union of (start, end) intervals."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals: List[Interval], lo: float, hi: float) -> float:
    """Length of [lo, hi] covered by the union of `intervals`."""
    return sum(max(0.0, min(e, hi) - max(s, lo))
               for s, e in merge(intervals))


def gaps(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that no interval covers, in order."""
    out, cur = [], lo
    for s, e in merge(intervals):
        if e <= cur:
            continue
        if s >= hi:
            break
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def label_gaps(gap_list: List[Interval],
               host: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Total gap length per label: the name of the shortest host span
    that covers the gap's middle, or "no host span"."""
    events = sorted(host, key=lambda h: h[1])
    active: list = []       # heap of (end, duration, name)
    nxt = 0
    totals: Dict[str, float] = {}
    for s, e in sorted(gap_list):
        mid = 0.5 * (s + e)
        while nxt < len(events) and events[nxt][1] <= mid:
            name, hs, he = events[nxt]
            heapq.heappush(active, (he, he - hs, name))
            nxt += 1
        while active and active[0][0] < mid:
            heapq.heappop(active)
        label = min(active, key=lambda a: a[1])[2] if active else \
            "no host span"
        totals[label] = totals.get(label, 0.0) + (e - s)
    return totals


def _events(pd, plane_prefix: str, line_prefix: str, by_line=False):
    """{plane (or plane/line) name: [(name, start_ns, end_ns), ...]} for
    the matching lines."""
    out: Dict[str, list] = {}
    for plane in pd.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        for line in plane.lines:
            if line.name.startswith(line_prefix):
                key = plane.name + ("/" + line.name if by_line else "")
                out.setdefault(key, []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
    return out


def op_name(name: str) -> str:
    """An HLO operation's instruction name, without its text."""
    return name.split(" = ", 1)[0].lstrip("%")


class Trace:
    """One recorded trace, reduced on the chips in `chips` (plane names
    in sorted order; the first `n_chips` hold the run's work)."""

    def __init__(self, pd, ops: Tuple[str, str], modules: Tuple[str, str],
                 n_chips: int = 1):
        per_plane = _events(pd, *ops)
        self.chips = [per_plane[k] for k in sorted(per_plane)][:n_chips]
        self.modules = [ev for evs in _events(pd, *modules).values()
                        for ev in evs]
        host_lines = _events(pd, HOST_PLANE, "", by_line=True)
        # the thread that ran the window: its spans label the idle gaps
        self.main = next((evs for evs in host_lines.values()
                          if any(n == WINDOW for n, _, _ in evs)), [])
        spans = sorted((s, e) for n, s, e in self.main if n == WINDOW)
        if not spans:
            raise ValueError(f"the trace holds no {WINDOW!r} span")
        self.window: Interval = spans[0]

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9

    def busy_s(self) -> float:
        """Device busy seconds in the window, the mean over the chips."""
        if not self.chips:
            return 0.0
        lo, hi = self.window
        return sum(covered([(s, e) for _, s, e in evs], lo, hi)
                   for evs in self.chips) / len(self.chips) * 1e-9

    def module_s(self, prefix: str) -> List[float]:
        """Device seconds of each execution, on any chip, of the modules
        whose name starts with `prefix`."""
        return [(e - s) * 1e-9 for n, s, e in self.modules
                if n.startswith(prefix)]

    def top_ops(self, k: int = 10) -> List[list]:
        """The k operations with most device seconds in the window."""
        lo, hi = self.window
        tot: Dict[str, float] = {}
        for evs in self.chips:
            for name, s, e in evs:
                d = min(e, hi) - max(s, lo)
                if d > 0:
                    key = op_name(name)
                    tot[key] = tot.get(key, 0.0) + d * 1e-9
        return [[n, t] for n, t in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """Idle seconds in the window on the first chip, summed by what
        the host was doing, largest first."""
        lo, hi = self.window
        evs = self.chips[0] if self.chips else []
        g = gaps([(s, e) for _, s, e in evs], lo, hi)
        host = [(n, s, e) for n, s, e in self.main if n != WINDOW]
        totals = label_gaps(g, host)
        return [[n, t * 1e-9] for n, t in sorted(totals.items(),
                                                 key=lambda kv: -kv[1])[:k]]


def load(trace_dir: str, platform: str, n_chips: int,
         ops: Tuple[str, str] = None, modules: Tuple[str, str] = None):
    """Read the newest `.xplane.pb` under `trace_dir` and reduce it."""
    import jax

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    return Trace(pd, ops or DEVICE_OPS[platform],
                 modules or DEVICE_MODULES[platform], n_chips)
