"""Quickstart: sparsify a power-grid-style graph with LGRASS and verify
the output is bit-identical to the baseline program's semantics.

    PYTHONPATH=src python examples/quickstart.py
"""
import time

import numpy as np

from repro.core import (baseline_sparsify, lgrass_sparsify,
                        powergrid_like_graph)


def main():
    # a ~1.6K-node power-grid-like case (official cases are 4K/7K/16K)
    g = powergrid_like_graph(40, 0.25, seed=0)
    print(f"graph: {g.n} nodes, {g.m} edges")

    t0 = time.perf_counter()
    result = lgrass_sparsify(g, k_cap=8)
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    result = lgrass_sparsify(g, k_cap=8)   # steady state
    t_lgrass = time.perf_counter() - t0
    print(f"LGRASS: kept {int(result.edge_mask.sum())}/{g.m} edges "
          f"({result.n_accepted} off-tree) in {t_lgrass*1e3:.1f} ms "
          f"steady-state ({t_compile:.1f}s incl. first-call jit; "
          f"{result.n_groups} marking groups)")

    t0 = time.perf_counter()
    base = baseline_sparsify(g)
    t_base = time.perf_counter() - t0
    print(f"baseline semantics (host python/numpy): {t_base*1e3:.1f} ms")

    identical = np.array_equal(base.edge_mask, result.edge_mask)
    print(f"outputs identical: {identical}")
    assert identical


if __name__ == "__main__":
    main()
