"""Batched sparsification quickstart: many graphs, one device dispatch.

Builds a mixed-size request batch, serves it through the bucketing
`SparsifyService`, and verifies every result is bit-identical to the
single-graph path.

    PYTHONPATH=src python examples/batch_sparsify.py
"""
import time

import numpy as np

from repro.core import lgrass_sparsify
from repro.core.graph import powergrid_like_graph, random_connected_graph
from repro.serve.sparsify_service import SparsifyService


def main():
    rng = np.random.default_rng(0)
    graphs = []
    for i in range(12):
        if i % 3 == 0:
            graphs.append(powergrid_like_graph(int(rng.integers(5, 9)),
                                               0.3, seed=i))
        else:
            n = int(rng.integers(24, 64))
            graphs.append(random_connected_graph(n, 2 * n, seed=i))
    print(f"request batch: {len(graphs)} graphs, "
          f"n in [{min(g.n for g in graphs)}, {max(g.n for g in graphs)}], "
          f"L in [{min(g.m for g in graphs)}, {max(g.m for g in graphs)}]")

    svc = SparsifyService()
    t0 = time.perf_counter()
    results = svc.sparsify(graphs)
    t_serve = time.perf_counter() - t0

    kept = [int(r.edge_mask.sum()) for r in results]
    print(f"served in {t_serve:.2f}s (incl. jit) with "
          f"{svc.stats.n_dispatches} device dispatch(es) over "
          f"{len(svc.stats.bucket_counts)} shape bucket(s); "
          f"padding overhead {svc.stats.padding_overhead:.0%}")
    for key, cnt in sorted(svc.stats.bucket_counts.items()):
        print(f"  bucket n<={key[0]:4d} L<={key[1]:4d}: {cnt} graph(s)")
    print(f"kept edges per graph: {kept}")

    for g, r in zip(graphs, results):
        assert np.array_equal(
            r.edge_mask, lgrass_sparsify(g).edge_mask
        )
    print("all results bit-identical to single-graph lgrass_sparsify")


if __name__ == "__main__":
    main()
