"""Arithmetic shared by the metric readers in `metrics/`.

Each reader returns None when its run holds nothing to read (no trace,
no call), and the harness then leaves the metric out of the line.
"""
from __future__ import annotations

import numpy as np


def graphs(calls) -> int:
    return sum(c.graphs for c in calls)


def traced_busy_s(run):
    """Device busy seconds in the traced window, or None."""
    if run.trace is None or not run.traced or not run.trace.chips:
        return None
    return run.trace.busy_s()


def busy_ms_per(run, per_graph: bool):
    """Device busy ms per graph (or per call) of the traced window."""
    busy = traced_busy_s(run)
    if busy is None or busy <= 0:
        return None
    n = graphs(run.traced) if per_graph else len(run.traced)
    return 1e3 * busy / n


def idle_pct(run):
    """100 x (1 - busy / window) over the traced window."""
    busy = traced_busy_s(run)
    if busy is None or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / run.trace.window_s)


def latency_pct_ms(run, q: float):
    """The q-th percentile (numpy's linear rule) of the window's call
    latencies, in ms."""
    if not run.calls:
        return None
    return float(np.percentile([c.t1 - c.t0 for c in run.calls], q)) * 1e3
