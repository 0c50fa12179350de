"""The benchmark's copies of the generators and of the plain reference
agree with the program's own, at small sizes."""
import numpy as np
import pytest

from chipbench import generate
from chipbench.references import baseline_greedy
from repro.core import graph as G
from repro.core.baseline import baseline_sparsify, default_budget

SEEDS = [0, 7, 2**31 + 11, 2**40 + 3]


def _same(a, b):
    assert a.n == b.n
    for x, y in ((a.u, b.u), (a.v, b.v), (a.w, b.w)):
        assert x.dtype == y.dtype and np.array_equal(x, y)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("side,frac", [(6, 0.25), (12, 0.2), (20, 0.25),
                                       (31, 0.2), (64, 0.25)])
def test_powergrid_copy_matches_program(side, frac, seed):
    _same(generate.powergrid_like_graph(side, frac, seed=seed),
          G.powergrid_like_graph(side, frac, seed=seed))
    assert generate.powergrid_shape(side, frac) == G.powergrid_shape(
        side, frac)


def test_budget_rule_matches_program():
    cfg = {"budget_share": 0.05}
    for n in list(range(1, 200)) + [4096, 7056, 16129]:
        assert generate.budget(cfg, n) == default_budget(n)


@pytest.mark.parametrize("seed", [1, 5, 11, 2**31 + 3, 2**33 + 1,
                                  2**35 + 9, 2**40 + 7, 2**62 + 1])
def test_reference_copy_matches_program(seed):
    rng = np.random.default_rng(seed)
    for _ in range(3):
        g = generate.powergrid_like_graph(int(rng.integers(4, 16)), 0.25,
                                          seed=int(rng.integers(0, 2**63 - 1)))
        for budget in (1, generate.budget({"budget_share": 0.05}, g.n), 10):
            want = baseline_sparsify(G.Graph(g.n, g.u, g.v, g.w),
                                     budget=budget).edge_mask
            got = baseline_greedy.sparsify(g, budget)
            assert got.dtype == bool and np.array_equal(got, want)


CFG = {"family": "powergrid", "budget_share": 0.05,
       "cases": {"c": {"n_side": 7, "chord_frac": 0.25}}}
TRAFFIC = {"case": "c", "graphs_per_call": 3, "pool_calls": 4,
           "graph_seed": 5}


def _edges(g):
    return sorted(zip(g.u.tolist(), g.v.tolist(), g.w.tolist()))


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 2**40 + 1])
def test_make_calls_is_a_function_of_the_seed(seed):
    a = generate.make_calls(CFG, TRAFFIC, seed)
    b = generate.make_calls(CFG, TRAFFIC, seed)
    assert len(a) == 4 and all(len(gs) == 3 for gs, _ in a)
    for (ga, ba), (gb, bb) in zip(a, b):
        assert ba == bb == [generate.budget(CFG, 49)] * 3
        for x, y in zip(ga, gb):
            _same(x, y)
            assert x.n == 49 and x.m == generate.powergrid_shape(7)[1]


@pytest.mark.parametrize("seed,other", [(1, 2), (2**31 + 5, 2**31 + 6)])
def test_every_seed_sends_the_same_graphs_in_another_edge_order(seed, other):
    a = generate.make_calls(CFG, TRAFFIC, seed)
    c = generate.make_calls(CFG, TRAFFIC, other)
    for (ga, _), (gc, _) in zip(a, c):
        for x, z in zip(ga, gc):
            assert x.n == z.n and _edges(x) == _edges(z)
    assert any(not np.array_equal(x.u, z.u)
               for (ga, _), (gc, _) in zip(a, c) for x, z in zip(ga, gc))
    graphs = [_edges(g) for gs, _ in a for g in gs]
    assert len({tuple(e) for e in graphs}) == len(graphs)
