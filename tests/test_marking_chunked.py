"""Chunked phase-1 marking: every block size must be BIT-IDENTICAL.

The contract: `phase1_chunked` at any block size, the numpy oracle
(`_host.phase1_np`) and the batched vmapped path all produce the same
per-slot accept decisions and per-group overflow flags — across graph
families (feeder included), chunk sizes {1, 3, C > L, pow2} and the
k_cap=1 overflow regime — and the whole pipeline lands on the baseline
greedy. Plus the degenerate-layout regressions: L == 0 and
zero-crossing inputs must flow through marking AND recovery without
NaN/garbage.

Shapes are reused across sweep cases so the run costs a handful of XLA
compiles, not one per case.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from _prop import cases, integers, sampled_from
from repro.core import (baseline_sparsify, lgrass_sparsify,
                        lgrass_sparsify_batch)
from repro.core import _host as H
from repro.core.graph import (Graph, feeder_like_graph,
                              powergrid_like_graph, random_connected_graph)
from repro.core.sparsify import phase1_device, phase1_device_batched

CHUNKS = (1, 3, 16, 4096)  # 1, odd, pow2, C > L


def _phase1(g, **kw):
    d = phase1_device(
        jnp.asarray(g.u, jnp.int32), jnp.asarray(g.v, jnp.int32),
        jnp.asarray(g.w, jnp.float32), g.n, **kw)
    return {k: np.asarray(val) for k, val in d.items()}


def _oracle(d, k_cap=32):
    perm = d["perm"].astype(np.int64)
    active = d["crossing"].astype(bool)[perm]
    return H.phase1_np(
        d["up"], d["depth_t"], d["u_sorted"], d["v_sorted"],
        d["beta"][perm], d["gidx"], active, k_cap)


def _assert_schedules_agree(g, k_cap=32):
    """Every block size (and the default) agrees, and with the oracle."""
    ref = _phase1(g, k_cap=k_cap)
    for c in CHUNKS:
        chk = _phase1(g, k_cap=k_cap, p1_chunk=c)
        assert np.array_equal(ref["accept_sorted"], chk["accept_sorted"]), c
        assert np.array_equal(ref["group_overflow"],
                              chk["group_overflow"]), c
    perm = ref["perm"].astype(np.int64)
    ref["u_sorted"] = g.u.astype(np.int64)[perm]
    ref["v_sorted"] = g.v.astype(np.int64)[perm]
    want_acc, want_ovf = _oracle(ref, k_cap=k_cap)
    assert np.array_equal(ref["accept_sorted"], want_acc)
    # device overflow is per dense group; oracle marks the same groups
    assert np.array_equal(ref["group_overflow"].astype(bool), want_ovf)
    return ref


@pytest.mark.parametrize(
    "seed,weight",
    cases(integers(0, 100_000), sampled_from(["lognormal", "ties"]),
          n_cases=8, seed=47),
)
def test_chunked_parity_random_sweep(seed, weight):
    g = random_connected_graph(36, 80, seed=seed, weight=weight)
    _assert_schedules_agree(g)


def test_chunked_parity_powergrid():
    _assert_schedules_agree(powergrid_like_graph(6, 0.4, seed=2))


@pytest.mark.parametrize("seed", [0, 3])
def test_chunked_parity_feeder(seed):
    """Chain-heavy feeder graphs: almost everything is non-crossing, so
    the active prefix is short — the chunked while_loop must stop at
    ceil(n_crossing / C) blocks yet still agree bit-for-bit."""
    _assert_schedules_agree(feeder_like_graph(96, 48, span=6, seed=seed))


def test_chunked_parity_overflow_k_cap_1():
    """k_cap=1 overflows nearly every group: the chunked engine's
    mid-block count arithmetic must raise exactly the same overflow
    flags as the per-slot scan."""
    g = random_connected_graph(40, 110, seed=9)
    ref = _assert_schedules_agree(g, k_cap=1)
    assert ref["group_overflow"].astype(bool).any()


@pytest.mark.parametrize("p1_chunk", [1, 16])
def test_chunked_e2e_matches_baseline(p1_chunk):
    """Through the fused device program (marking + recovery) the chunked
    schedule must still land exactly on the baseline greedy."""
    g = random_connected_graph(45, 90, seed=1, weight="ties")
    base = baseline_sparsify(g, budget=8)
    dev = lgrass_sparsify(g, budget=8, p1_chunk=p1_chunk)
    assert np.array_equal(dev.edge_mask, base.edge_mask)
    host = lgrass_sparsify(g, budget=8, p1_chunk=p1_chunk,
                           recovery="host")
    assert np.array_equal(dev.edge_mask, host.edge_mask)


def test_chunked_batched_matches_scan_batched():
    """The vmapped batched path: the default blocks == one-slot blocks
    (the per-slot scan order) == baseline per graph."""
    graphs = [
        random_connected_graph(30, 60, seed=0, weight="lognormal"),
        powergrid_like_graph(6, 0.4, seed=3),
        feeder_like_graph(64, 32, span=5, seed=1),
        random_connected_graph(45, 110, seed=1, weight="ties"),
    ]
    chk = lgrass_sparsify_batch(graphs, budget=6)
    scn = lgrass_sparsify_batch(graphs, budget=6, p1_chunk=1)
    for g, a, b in zip(graphs, chk, scn):
        assert np.array_equal(a.edge_mask, b.edge_mask)
        assert np.array_equal(
            a.edge_mask, baseline_sparsify(g, budget=6).edge_mask)
        assert (a.n_accepted, a.n_groups, a.n_overflow_groups, a.n_dirty) \
            == (b.n_accepted, b.n_groups, b.n_overflow_groups, b.n_dirty)


def test_chunked_batched_phase1_views_match_single():
    """Raw batched phase-1 outputs agree with per-graph runs slot by
    slot (padding invisible), for the chunked schedule."""
    graphs = [
        random_connected_graph(30, 60, seed=5),
        random_connected_graph(24, 40, seed=6),
    ]
    from repro.core.graph import GraphBatch

    batch = GraphBatch.from_graphs(graphs)
    d = phase1_device_batched(
        jnp.asarray(batch.u, jnp.int32), jnp.asarray(batch.v, jnp.int32),
        jnp.asarray(batch.w, jnp.float32),
        jnp.asarray(batch.edge_valid, bool), batch.n_max, p1_chunk=8)
    d = {k: np.asarray(val) for k, val in d.items()}
    for i, g in enumerate(graphs):
        single = _phase1(g, p1_chunk=8)
        # sorted-slot outputs: real slots lead (padding sorts last)
        assert np.array_equal(d["accept_sorted"][i][: g.m],
                              single["accept_sorted"])
        assert np.array_equal(d["perm"][i][: g.m], single["perm"])


def test_chunked_euler_lca_backend_parity():
    """The Euler-tour O(1)-LCA distance tables behind the cover tables
    land on the baseline greedy, random and feeder graphs alike."""
    for seed in (0, 4):
        g = random_connected_graph(36, 80, seed=seed)
        assert np.array_equal(lgrass_sparsify(g, budget=7).edge_mask,
                              baseline_sparsify(g, budget=7).edge_mask)
    g = feeder_like_graph(96, 48, span=6, seed=1)
    assert np.array_equal(lgrass_sparsify(g, budget=6).edge_mask,
                          baseline_sparsify(g, budget=6).edge_mask)


def test_chunked_tree_kernel_backend_parity():
    """The cover tables' distances through the fused program, host and
    device recovery alike, equal the baseline greedy's."""
    g = random_connected_graph(24, 40, seed=5)
    base = baseline_sparsify(g, budget=5).edge_mask
    for recovery in ("device", "host"):
        assert np.array_equal(
            lgrass_sparsify(g, budget=5, recovery=recovery).edge_mask, base)


# --- degenerate GroupLayout regressions (L == 0 / zero crossing) --------


def _star_graph(n=8):
    return Graph(n=n, u=np.zeros(n - 1, np.int32),
                 v=np.arange(1, n, dtype=np.int32),
                 w=np.ones(n - 1, np.float32))


def _chain_noncrossing():
    """Chain + chords whose LCA is an endpoint: zero crossing edges."""
    u = np.array([0, 1, 2, 3, 4, 0, 2], np.int32)
    v = np.array([1, 2, 3, 4, 5, 2, 4], np.int32)
    return Graph(n=6, u=u, v=v, w=np.ones(7, np.float32))


# the default blocks, and one-slot blocks (the per-slot scan order)
P1_CHUNKS = [pytest.param(None, id="chunked"),
             pytest.param(1, id="p1_chunk=1")]


@pytest.mark.parametrize("p1_chunk", P1_CHUNKS)
def test_degenerate_star_all_tree(p1_chunk):
    """Every edge is a tree edge: no crossing groups, nothing accepted,
    and the final mask is exactly the tree."""
    g = _star_graph()
    base = baseline_sparsify(g, budget=2)
    r = lgrass_sparsify(g, budget=2, p1_chunk=p1_chunk)
    assert np.array_equal(r.edge_mask, base.edge_mask)
    assert r.edge_mask.all() and r.n_accepted == 0
    assert r.n_overflow_groups == 0 and r.n_dirty == 0


@pytest.mark.parametrize("p1_chunk", P1_CHUNKS)
def test_degenerate_all_noncrossing(p1_chunk):
    """Zero crossing edges: the whole layout is the inactive tail group;
    recovery alone must decide the chords, matching the baseline."""
    g = _chain_noncrossing()
    base = baseline_sparsify(g, budget=2)
    for recovery in ("device", "host"):
        r = lgrass_sparsify(g, budget=2, p1_chunk=p1_chunk,
                            recovery=recovery)
        assert np.array_equal(r.edge_mask, base.edge_mask)


@pytest.mark.parametrize("p1_chunk", P1_CHUNKS)
def test_degenerate_zero_edges(p1_chunk):
    """L == 0 (isolated node): the empty-layout branch must flow through
    marking AND recovery — this used to raise IndexError in
    build_group_layout (`.at[0]` on an empty array)."""
    g = Graph(n=1, u=np.zeros(0, np.int32), v=np.zeros(0, np.int32),
              w=np.zeros(0, np.float32))
    for recovery in ("device", "host"):
        r = lgrass_sparsify(g, budget=1, p1_chunk=p1_chunk,
                            recovery=recovery)
        assert r.edge_mask.shape == (0,)
        assert r.n_accepted == 0 and r.n_groups == 0


def test_degenerate_no_garbage_reaches_recovery():
    """The phase-1 views handed to recovery must be finite and in-range
    for zero-crossing inputs: no NaN criticality keys on off-tree slots,
    every group index in [-1, L), no spurious dirty seeds."""
    from repro.core.sparsify import phase1_views_np

    for g in (_star_graph(), _chain_noncrossing()):
        d = _phase1(g)
        tree, crossing, accept, group, dirty0, order = phase1_views_np(
            d, g.m)
        offtree = ~tree
        assert np.isfinite(d["crit"][: g.m][offtree]).all()
        assert not crossing.any()
        assert not accept.any() and not dirty0.any()
        assert (group == -1).all()
        assert sorted(order.tolist()) == list(range(g.m))
