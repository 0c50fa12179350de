"""THE reproduction test: LGRASS (linear, parallel) must output the exact
sparsifier of the baseline program's semantics (Algorithm 1/3 greedy) —
the competition's own correctness criterion ("outputs the same result as
provided program")."""
import numpy as np
import pytest

from _prop import cases, integers, sampled_from
from repro.core import (baseline_sparsify, lgrass_sparsify,
                        powergrid_like_graph, random_connected_graph)


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("weight", ["lognormal", "ties"])
def test_lgrass_equals_baseline(seed, weight):
    g = random_connected_graph(45, 90, seed=seed, weight=weight)
    b = baseline_sparsify(g, budget=8)
    for recovery in ("device", "host"):
        r = lgrass_sparsify(g, budget=8, recovery=recovery)
        assert np.array_equal(b.edge_mask, r.edge_mask), (
            f"seed={seed} weight={weight} recovery={recovery}")


def test_lgrass_overflow_recovery_exact():
    """k_cap=1 forces overflow in nearly every group; the recovery stage
    must still reproduce the oracle bit-exactly."""
    g = random_connected_graph(40, 110, seed=9)
    b = baseline_sparsify(g, budget=20)
    r = lgrass_sparsify(g, budget=20, k_cap=1)
    assert np.array_equal(b.edge_mask, r.edge_mask)
    assert r.n_overflow_groups >= 0


def test_lgrass_powergrid_case():
    g = powergrid_like_graph(9, 0.4, seed=2)
    b = baseline_sparsify(g, budget=10)
    r = lgrass_sparsify(g, budget=10)
    assert np.array_equal(b.edge_mask, r.edge_mask)


@pytest.mark.parametrize(
    "seed,budget,weight",
    cases(integers(0, 100_000), integers(2, 30),
          sampled_from(["lognormal", "ties"]), n_cases=20, seed=77),
)
def test_lgrass_equals_baseline_property(seed, budget, weight):
    g = random_connected_graph(36, 80, seed=seed, weight=weight)
    b = baseline_sparsify(g, budget=budget)
    r = lgrass_sparsify(g, budget=budget)
    assert np.array_equal(b.edge_mask, r.edge_mask)


def test_sparsifier_invariants():
    g = random_connected_graph(60, 150, seed=11)
    r = lgrass_sparsify(g, budget=12)
    # contains the spanning tree
    assert np.all(r.edge_mask[r.tree_mask])
    assert r.tree_mask.sum() == g.n - 1
    # accepted edges are off-tree and within budget
    assert not np.any(r.accepted_mask & r.tree_mask)
    assert r.n_accepted <= 12
    # sparsifier connects the graph (tree does already)
    # edge count = n-1 + accepted
    assert r.edge_mask.sum() == g.n - 1 + r.n_accepted


def test_budget_monotone():
    g = random_connected_graph(50, 120, seed=13)
    prev = None
    for budget in (1, 4, 8, 16):
        r = lgrass_sparsify(g, budget=budget)
        assert r.n_accepted <= budget
        if prev is not None:
            # greedy prefix property: smaller budget = prefix of larger
            assert np.all(r.accepted_mask[prev.accepted_mask] |
                          (prev.n_accepted <= r.n_accepted))
        prev = r
