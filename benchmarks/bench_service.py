"""Closed-loop serving benchmark for `SparsifyService` (PR 6).

A seeded Poisson arrival process generates mixed-size traffic (three
graph families across three pow2 buckets, mixed explicit/None budgets).
The client is CLOSED-LOOP: it sleeps until each request's scheduled
arrival, submits the accumulated burst as one `sparsify` call, and
clocks completion when results are back on the host. Per-request
latency = completion - arrival, so queueing delay behind a slow chunk
is charged to every request waiting on it — exactly what the async
plane is supposed to shrink.

Modes: sync, async, async+donate, and (when >1 device is visible,
e.g. XLA_FLAGS=--xla_force_host_platform_device_count=8)
async+donate+sharded. Warmup dispatches are excluded from timing;
every mode's results are parity-checked against per-graph
`lgrass_sparsify` before its numbers are reported.

Rows (benchmarks/run.py format): name, us_per_call = mean per-request
latency, derived = p50/p99 latency (ms) + graphs/sec + speedup vs sync.
"""
from __future__ import annotations

import time
from typing import List, Optional, Tuple

import numpy as np


def _traffic(n_requests: int, seed: int):
    """Seeded mixed-size request stream + Poisson arrival offsets (s)."""
    from repro.core.graph import (powergrid_like_graph,
                                  random_connected_graph, trivial_graph)

    rng = np.random.default_rng(seed)
    graphs, budgets = [], []
    for i in range(n_requests):
        kind = rng.integers(0, 10)
        if kind < 4:
            g = random_connected_graph(int(rng.integers(16, 28)), 24,
                                       seed=int(rng.integers(1 << 16)))
        elif kind < 7:
            g = random_connected_graph(int(rng.integers(34, 60)), 64,
                                       seed=int(rng.integers(1 << 16)))
        elif kind < 9:
            g = powergrid_like_graph(7, 0.5, seed=int(rng.integers(1 << 16)))
        else:
            g = trivial_graph()
        graphs.append(g)
        budgets.append(int(rng.integers(2, 9)) if rng.random() < 0.5
                       else None)
    # Poisson arrivals: exponential inter-arrival gaps
    gaps = rng.exponential(scale=1.0, size=n_requests)
    arrivals = np.cumsum(gaps)
    return graphs, budgets, arrivals


def _reference(graphs, budgets):
    from repro.core import lgrass_sparsify

    return [lgrass_sparsify(g, budget=b) if g.m else None
            for g, b in zip(graphs, budgets)]


def _check_parity(graphs, results, ref, mode: str) -> None:
    for k, (g, r) in enumerate(zip(graphs, results)):
        if g.m == 0:
            assert r.n_accepted == 0 and r.edge_mask.shape == (0,), (mode, k)
        elif not (np.array_equal(r.edge_mask, ref[k].edge_mask)
                  and r.n_accepted == ref[k].n_accepted):
            raise AssertionError(f"parity violation in mode={mode} at "
                                 f"request {k}")


def _closed_loop(svc, graphs, budgets, sched):
    """One closed-loop pass; returns (results, latencies (s), wall (s))."""
    results: List[object] = [None] * len(graphs)
    lat = np.zeros(len(graphs))
    t0 = time.perf_counter()
    i = 0
    while i < len(graphs):
        now = time.perf_counter() - t0
        if now < sched[i]:
            time.sleep(sched[i] - now)
        # submit every request that has arrived by now as one burst
        j = i + 1
        now = time.perf_counter() - t0
        while j < len(graphs) and sched[j] <= now:
            j += 1
        out = svc.sparsify(graphs[i:j], budget=budgets[i:j])
        done = time.perf_counter() - t0
        for k in range(i, j):
            results[k] = out[k - i]
            lat[k] = done - sched[k]
        i = j
    wall = time.perf_counter() - t0
    return results, lat, wall


def _run_mode(mode: str, graphs, budgets, arrivals, rate_hz: float,
              warm_sizes, warm_batches, warm_budgets, n_passes: int = 5):
    """Warm a service, run `n_passes` closed loops, report the
    median-wall pass (per-pass wall is tens of ms on a noisy CPU box;
    the median keeps one descheduled pass from deciding the row).
    Every pass's results are returned for parity checking."""
    from repro.serve.sparsify_service import SparsifyService

    # chunks of 4: the latency-oriented serving config. Per-chunk device
    # programs are then ~1-3ms on these request sizes, so the host-side
    # work async mode overlaps (staging fill, dispatch bookkeeping,
    # result scatter) is a real fraction of the chunk — which is exactly
    # the regime the async plane targets. With big chunks the program
    # dominates and every mode converges to the same device-bound wall.
    kw = dict(max_batch_size=4)
    if mode != "sync":
        kw["async_dispatch"] = True
    if "donate" in mode:
        kw["donate"] = True
    if "shard" in mode:
        from repro.core.distributed import batch_mesh
        kw["mesh"] = batch_mesh()
    svc = SparsifyService(**kw)
    svc.warmup(warm_sizes, batch_sizes=warm_batches, budgets=warm_budgets)

    sched = arrivals / rate_hz  # seconds from t0
    passes = [_closed_loop(svc, graphs, budgets, sched)
              for _ in range(n_passes)]
    walls = [p[2] for p in passes]
    results, lat, wall = passes[int(np.argsort(walls)[len(walls) // 2])]
    all_results = [p[0] for p in passes]
    return results, lat, wall, svc.stats, all_results


def run(quick: bool = False) -> List[Tuple[str, float, str]]:
    import jax

    # arrival rate is set far above service capacity (a single-graph
    # dispatch is ~2-4ms, so capacity is a few hundred Hz) — the serving
    # plane, not the arrival process, is the bottleneck; bursts then grow
    # until chunks fill and the async/donate overlap is what the numbers
    # see. The closed loop still charges queueing delay per request.
    n_requests = 32 if quick else 160
    rate_hz = 4000.0 if quick else 8000.0
    graphs, budgets, arrivals = _traffic(n_requests, seed=20260808)
    ref = _reference(graphs, budgets)

    # warm every bucket signature the stream can produce so on-path
    # compiles never pollute the timing (asserted below)
    warm_sizes = sorted({(g.n, g.m) for g in graphs})
    warm_batches = (1, 2, 4)  # every B_pad a max_batch_size=4 chunk can hit
    warm_budgets = [8]  # covers explicit budgets 2..8

    modes = ["sync", "async", "async_donate"]
    if len(jax.devices()) >= 2:
        modes.append("async_donate_shard")

    rows: List[Tuple[str, float, str]] = []
    sync_wall: Optional[float] = None
    for mode in modes:
        results, lat, wall, stats, all_results = _run_mode(
            mode, graphs, budgets, arrivals, rate_hz,
            warm_sizes, warm_batches, warm_budgets)
        for pass_results in all_results:
            _check_parity(graphs, pass_results, ref, mode)
        assert stats.n_on_path_compiles == 0, (
            f"{mode}: {stats.n_on_path_compiles} on-path compiles — "
            "warmup does not cover the traffic")
        if mode == "sync":
            sync_wall = wall
        gps = n_requests / wall
        speedup = sync_wall / wall if sync_wall else 1.0
        derived = (f"p50={np.percentile(lat, 50) * 1e3:.1f}ms "
                   f"p99={np.percentile(lat, 99) * 1e3:.1f}ms "
                   f"graphs_per_s={gps:.1f} speedup_vs_sync={speedup:.2f}x "
                   f"dispatches={stats.n_dispatches} "
                   f"pad={stats.padding_overhead:.2f}")
        rows.append((f"service.{mode}", float(np.mean(lat) * 1e6), derived))
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small request count (CI)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for name, us, derived in run(quick=args.smoke):
        print(f"{name},{us:.1f},{derived}")
