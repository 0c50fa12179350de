"""Direct spectral quality of the sparsifier — dense ground truth.

Everything else in the suite asserts *self-consistency*: device paths
against host oracles against the baseline greedy. None of it would
notice if the whole family of implementations drifted to a spectrally
worse algorithm in lockstep. This tier pins the output against the
O(n^3) dense formulation (`core.resistance` numpy helpers, float64
pseudoinverse) on small graphs:

  * the device RES stage (root-path sums + LCA) must reproduce the
    textbook effective resistance of the spanning tree;
  * the sparsifier's Laplacian must preserve quadratic forms at least
    as well as the baseline greedy's (they are bit-identical today, so
    the bound is tight — a refactor that degrades quality while keeping
    its own oracles self-consistent trips these);
  * Rayleigh-monotonicity sanity: subgraphs only increase effective
    resistance, added edges only improve the preservation ratio.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from _prop import cases, integers, sampled_from
from repro.core import baseline_sparsify, lgrass_sparsify
from repro.core.graph import (feeder_like_graph, powergrid_like_graph,
                              random_connected_graph)
from repro.core.resistance import (dense_effective_resistance_np,
                                   dense_laplacian_np, spectral_bounds_np)
from repro.core.sparsify import phase1_device


def _dense_er(g, mask, qu, qv):
    L = dense_laplacian_np(g.n, g.u, g.v, g.w, mask=mask)
    return dense_effective_resistance_np(L, qu, qv)


@pytest.mark.parametrize(
    "seed,weight",
    cases(integers(0, 100_000), sampled_from(["lognormal", "uniform"]),
          n_cases=6, seed=53),
)
def test_tree_resistance_matches_dense_pinv(seed, weight):
    """The linear-time tree effective resistance (root-path sums + LCA,
    float32 on device) equals the dense pseudoinverse ER of the spanning
    tree to float32 accuracy — ties the RES stage to ground truth."""
    g = random_connected_graph(24, 50, seed=seed, weight=weight)
    d = {k: np.asarray(v) for k, v in phase1_device(
        jnp.asarray(g.u, jnp.int32), jnp.asarray(g.v, jnp.int32),
        jnp.asarray(g.w, jnp.float32), g.n).items()}
    tree = d["tree_mask"].astype(bool)
    offtree = ~tree
    # device criticality = w * R_T(u, v) on off-tree edges
    r_dev = d["crit"][offtree] / g.w[offtree]
    r_dense = _dense_er(g, tree, g.u[offtree], g.v[offtree])
    np.testing.assert_allclose(r_dev, r_dense, rtol=2e-4, atol=1e-5)


def _quality(g, mask):
    """(lam_min, lam_max) of the pencil sparsifier-vs-full Laplacian."""
    L_full = dense_laplacian_np(g.n, g.u, g.v, g.w)
    L_sub = dense_laplacian_np(g.n, g.u, g.v, g.w, mask=mask)
    return spectral_bounds_np(L_full, L_sub)


@pytest.mark.parametrize(
    "seed,budget",
    cases(integers(0, 100_000), sampled_from([4, 8, 14]),
          n_cases=6, seed=59),
)
def test_sparsifier_quality_bounded_by_baseline(seed, budget):
    g = random_connected_graph(30, 70, seed=seed)
    base = baseline_sparsify(g, budget=budget)
    dev = lgrass_sparsify(g, budget=budget)
    lo_b, hi_b = _quality(g, base.edge_mask)
    lo_d, hi_d = _quality(g, dev.edge_mask)
    # subgraph sparsifier: the pencil lives in [0, 1]
    assert -1e-9 <= lo_d and hi_d <= 1.0 + 1e-9
    # connectivity preserved: the sparsifier never collapses a direction
    assert lo_d > 1e-6
    # LGRASS must be at least as good as the baseline greedy (bit-equal
    # today; the tolerance leaves room only for eigensolver noise)
    assert lo_d >= lo_b - 1e-9
    assert hi_d <= hi_b + 1e-9


@pytest.mark.parametrize("family", ["powergrid", "feeder"])
def test_sparsifier_improves_on_bare_tree(family):
    """Adding the accepted off-tree edges must improve (or preserve) the
    quadratic-form lower bound vs the spanning tree alone — the whole
    point of spending the budget."""
    if family == "powergrid":
        g, budget = powergrid_like_graph(5, 0.5, seed=7), 4
    else:
        g, budget = feeder_like_graph(48, 24, span=5, seed=7), 4
    dev = lgrass_sparsify(g, budget=budget)
    assert dev.n_accepted > 0  # budget actually spent on this input
    lo_tree, _ = _quality(g, dev.tree_mask)
    lo_sp, _ = _quality(g, dev.edge_mask)
    assert lo_sp >= lo_tree - 1e-12


def test_effective_resistance_rayleigh_monotone():
    """R is monotone under edge removal (Rayleigh): ER in the sparsifier
    >= ER in the full graph, and ER in the tree >= ER in the sparsifier,
    for every off-tree edge's endpoint pair."""
    g = random_connected_graph(26, 60, seed=3)
    dev = lgrass_sparsify(g, budget=6)
    off = ~dev.tree_mask
    qu, qv = g.u[off], g.v[off]
    r_full = _dense_er(g, np.ones(g.m, bool), qu, qv)
    r_sp = _dense_er(g, dev.edge_mask, qu, qv)
    r_tree = _dense_er(g, dev.tree_mask, qu, qv)
    assert (r_sp >= r_full - 1e-9).all()
    assert (r_tree >= r_sp - 1e-9).all()


def test_quality_identical_across_schedules():
    """Every block size and recovery tail is bit-identical, so its
    spectral quality must be exactly equal — a cheap guard that a
    schedule-specific bug cannot pass the parity tier by breaking both
    sides equally. The matrix covers the MARK block size (one-slot
    blocks, 4, the default), the host and device recovery tails, and
    the batched dispatch (each graph's lane vs its own single-graph
    run)."""
    from repro.core import lgrass_sparsify_batch

    gs = [random_connected_graph(30, 70, seed=11),
          feeder_like_graph(32, 16, span=6, seed=11)]
    for g in gs:
        ref = lgrass_sparsify(g, budget=8, p1_chunk=1).edge_mask
        q_ref = _quality(g, ref)
        for cfg in (dict(p1_chunk=4), dict(), dict(recovery="host")):
            m = lgrass_sparsify(g, budget=8, **cfg).edge_mask
            assert np.array_equal(ref, m), cfg
            assert q_ref == _quality(g, m), cfg
    # batched: one vmapped dispatch, every lane == its single-graph run
    for g, res in zip(gs, lgrass_sparsify_batch(gs, budget=8)):
        single = lgrass_sparsify(g, budget=8)
        assert np.array_equal(res.edge_mask, single.edge_mask)
        assert _quality(g, res.edge_mask) == _quality(g, single.edge_mask)