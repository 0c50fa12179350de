"""End-to-end behaviour tests for the LGRASS system."""
import numpy as np
import pytest

from repro.core import (Graph, baseline_sparsify, default_budget,
                        lgrass_sparsify, official_case,
                        random_connected_graph)


def test_official_case_shapes():
    g = official_case("case1")
    assert 3900 <= g.n <= 4200          # ~4K nodes as in the IPCC task
    g.validate()


def test_end_to_end_case1_reduced():
    """Full pipeline on a (scaled-down) official-style case: the linear
    LGRASS output equals the baseline's on a power-grid topology."""
    from repro.core.graph import powergrid_like_graph
    g = powergrid_like_graph(12, 0.25, seed=42)   # 144 nodes
    b = baseline_sparsify(g)
    r = lgrass_sparsify(g)
    assert np.array_equal(b.edge_mask, r.edge_mask)
    kept = r.edge_mask.sum() / g.m
    assert 0.3 < kept < 1.0  # it actually sparsifies


def test_larger_graph_runs_and_is_consistent():
    g = random_connected_graph(400, 1200, seed=21)
    r1 = lgrass_sparsify(g, budget=30)
    r2 = lgrass_sparsify(g, budget=30, recovery="host")
    assert np.array_equal(r1.edge_mask, r2.edge_mask)
    assert r1.n_accepted <= 30


def test_default_budget():
    assert default_budget(1000) == 50
    assert default_budget(10) == 1
