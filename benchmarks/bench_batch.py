"""Batched vs sequential sparsification throughput (the batching win).

8 small graphs, one padded `GraphBatch` dispatch vs 8 sequential
`lgrass_sparsify` calls. Two numbers:

  * steady state — both paths pre-compiled; the batch wins because one
    vmapped program replaces 8 loop dispatches over tiny operands.
  * cold start, mixed sizes — 8 distinct (n, L) shapes served through
    `SparsifyService`: sequential jit compiles one program per shape,
    the service buckets every graph into one padded shape and compiles
    once. This is the number that matters for serving traffic.

    PYTHONPATH=src python benchmarks/bench_batch.py [--smoke]
"""
import argparse
import time

import numpy as np

from repro.core import lgrass_sparsify, lgrass_sparsify_batch
from repro.core.graph import GraphBatch, random_connected_graph
from repro.serve.sparsify_service import SparsifyService

BATCH = 8
K_CAP = 32
BUDGET = 8


def _graphs_same_shape(n=64, extra=128):
    return [random_connected_graph(n, extra, seed=100 + i, weight="lognormal")
            for i in range(BATCH)]


def _graphs_mixed():
    # 8 distinct (n, L) shapes inside one power-of-two bucket
    return [random_connected_graph(40 + 3 * i, 80 + 5 * i, seed=200 + i)
            for i in range(BATCH)]


def _time(fn, reps):
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return min(ts)


def run(quick: bool = False):
    reps = 2 if quick else 5
    graphs = _graphs_same_shape()
    batch = GraphBatch.from_graphs(graphs)

    def sequential():
        return [lgrass_sparsify(g, budget=BUDGET, k_cap=K_CAP)
                for g in graphs]

    def batched():
        return lgrass_sparsify_batch(batch, budget=BUDGET, k_cap=K_CAP)

    # warm both paths (compile), and check equivalence while at it
    for a, b in zip(sequential(), batched()):
        assert np.array_equal(a.edge_mask, b.edge_mask)

    t_seq = _time(sequential, reps)
    t_bat = _time(batched, reps)

    rows = [
        (f"batch.steady.sequential_x{BATCH}", t_seq * 1e6, ""),
        (f"batch.steady.batched_x{BATCH}", t_bat * 1e6, ""),
        ("batch.steady.speedup", 0.0, round(t_seq / t_bat, 2)),
    ]

    if not quick:
        mixed = _graphs_mixed()
        t0 = time.perf_counter()
        r_seq = [lgrass_sparsify(g, budget=BUDGET, k_cap=K_CAP)
                 for g in mixed]
        t_cold_seq = time.perf_counter() - t0  # 8 shapes -> 8 compiles

        svc = SparsifyService(k_cap=K_CAP)
        t0 = time.perf_counter()
        r_svc = svc.sparsify(mixed, budget=BUDGET)
        t_cold_svc = time.perf_counter() - t0  # 1 bucket -> 1 compile
        for a, b in zip(r_seq, r_svc):
            assert np.array_equal(a.edge_mask, b.edge_mask)
        rows += [
            (f"batch.cold_mixed.sequential_x{BATCH}", t_cold_seq * 1e6, ""),
            (f"batch.cold_mixed.service_x{BATCH}", t_cold_svc * 1e6,
             f"{svc.stats.n_dispatches} dispatch(es)"),
            ("batch.cold_mixed.speedup", 0.0,
             round(t_cold_seq / t_cold_svc, 2)),
        ]
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes / few reps (CI smoke job)")
    args = ap.parse_args()
    rows = run(quick=args.smoke)
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    steady = rows[2][2]
    print(f"steady state: batched is {steady}x sequential "
          f"({'WIN' if steady > 1 else 'LOSS'})")
