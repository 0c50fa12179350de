"""Batched multi-graph sparsification: `GraphBatch` + vmapped phase 1.

The contract under test: every graph in a padded batch yields an
`edge_mask` BIT-IDENTICAL to (a) its single-graph `lgrass_sparsify` run
and (b) the `baseline_sparsify` oracle — padding must be invisible.
Covers three graph families (random lognormal, random ties, power-grid),
mixed sizes in one batch, per-graph budgets, the k_cap overflow/dirty
recovery path, and the serving bucketing layer.
"""
import numpy as np
import pytest

from _prop import cases, integers
from repro.core import (baseline_sparsify, lgrass_sparsify,
                        lgrass_sparsify_batch)
from repro.core.graph import (GraphBatch, PAD_ENDPOINT, PAD_WEIGHT,
                              powergrid_like_graph, random_connected_graph)
from repro.serve.sparsify_service import SparsifyService, next_pow2


def _mixed_families():
    """Mixed sizes across >= 3 families, deliberately not sorted by size."""
    return [
        random_connected_graph(30, 60, seed=0, weight="lognormal"),
        random_connected_graph(45, 110, seed=1, weight="ties"),
        powergrid_like_graph(6, 0.4, seed=3),
        random_connected_graph(24, 40, seed=2, weight="lognormal"),
        powergrid_like_graph(8, 0.3, seed=4),
        random_connected_graph(40, 95, seed=5, weight="ties"),
    ]


def test_graphbatch_padding_layout():
    graphs = _mixed_families()
    batch = GraphBatch.from_graphs(graphs)
    assert batch.batch_size == len(graphs)
    assert batch.n_max == max(g.n for g in graphs)
    assert batch.L_max == max(g.m for g in graphs)
    for i, g in enumerate(graphs):
        assert np.array_equal(batch.u[i, : g.m], g.u)
        assert np.array_equal(batch.v[i, : g.m], g.v)
        assert np.array_equal(batch.w[i, : g.m], g.w)
        assert batch.edge_valid[i, : g.m].all()
        # padding slots: sentinel self loops, masked out
        assert not batch.edge_valid[i, g.m:].any()
        assert (batch.u[i, g.m:] == PAD_ENDPOINT).all()
        assert (batch.v[i, g.m:] == PAD_ENDPOINT).all()
        assert (batch.w[i, g.m:] == PAD_WEIGHT).all()


def test_graphbatch_rejects_too_small_bucket():
    g = random_connected_graph(20, 30, seed=0)
    with pytest.raises(ValueError):
        GraphBatch.from_graphs([g], n_max=8)
    with pytest.raises(ValueError):
        GraphBatch.from_graphs([g], L_max=10)
    with pytest.raises(ValueError):
        GraphBatch.from_graphs([])


@pytest.mark.parametrize("k_cap", [1, 32])
def test_batch_bit_identical_to_single_and_baseline(k_cap):
    """k_cap=1 overflows groups, so the dirty replay decides them."""
    graphs = _mixed_families()
    results = lgrass_sparsify_batch(graphs, budget=8, k_cap=k_cap)
    for i, (g, r) in enumerate(zip(graphs, results)):
        single = lgrass_sparsify(g, budget=8, k_cap=k_cap)
        base = baseline_sparsify(g, budget=8)
        assert np.array_equal(r.edge_mask, single.edge_mask), i
        assert np.array_equal(r.tree_mask, single.tree_mask), i
        assert np.array_equal(r.accepted_mask, single.accepted_mask), i
        assert np.array_equal(r.edge_mask, base.edge_mask), i
        assert r.n_accepted == single.n_accepted
        assert r.n_groups == single.n_groups
        assert r.n_overflow_groups == single.n_overflow_groups
        assert r.n_dirty == single.n_dirty


def test_batch_per_graph_default_budgets():
    graphs = _mixed_families()
    results = lgrass_sparsify_batch(graphs)  # budget=None -> per-graph
    for g, r in zip(graphs, results):
        single = lgrass_sparsify(g)
        assert np.array_equal(r.edge_mask, single.edge_mask)


def test_batch_budget_sequence():
    graphs = _mixed_families()
    budgets = [2, 4, 6, 8, 3, 5]
    results = lgrass_sparsify_batch(graphs, budget=budgets)
    for g, b, r in zip(graphs, budgets, results):
        assert r.n_accepted <= b
        single = lgrass_sparsify(g, budget=b)
        assert np.array_equal(r.edge_mask, single.edge_mask)
    with pytest.raises(ValueError):
        lgrass_sparsify_batch(graphs, budget=[1, 2])


def test_batch_overflow_recovery_dirty_path():
    """k_cap=1 overflows nearly every group; the recovery tail must still
    reproduce the oracle bit-exactly, through the batched path."""
    dense = random_connected_graph(40, 110, seed=9)
    graphs = [dense, powergrid_like_graph(6, 0.4, seed=3)]
    results = lgrass_sparsify_batch(graphs, budget=20, k_cap=1)
    assert results[0].n_overflow_groups > 0
    assert results[0].n_dirty > 0
    for g, r in zip(graphs, results):
        base = baseline_sparsify(g, budget=20)
        assert np.array_equal(r.edge_mask, base.edge_mask)
        single = lgrass_sparsify(g, budget=20, k_cap=1)
        assert r.n_overflow_groups == single.n_overflow_groups
        assert r.n_dirty == single.n_dirty


@pytest.mark.parametrize("seed", cases(integers(0, 100_000), n_cases=6,
                                       seed=123))
def test_batch_property_sweep(seed):
    """Random batch compositions stay bit-identical to single-graph runs."""
    rng = np.random.default_rng(seed)
    graphs = [
        random_connected_graph(
            int(rng.integers(16, 48)),
            int(rng.integers(20, 90)),
            seed=int(rng.integers(0, 2**31)),
            weight=["lognormal", "ties"][int(rng.integers(2))],
        )
        for _ in range(int(rng.integers(2, 5)))
    ]
    for r, g in zip(lgrass_sparsify_batch(graphs, budget=6), graphs):
        assert np.array_equal(
            r.edge_mask, lgrass_sparsify(g, budget=6).edge_mask
        )


def test_next_pow2():
    assert [next_pow2(x) for x in (1, 2, 3, 4, 5, 63, 64, 65)] == [
        1, 2, 4, 4, 8, 64, 64, 128]


def test_sparsify_service_buckets_and_order():
    graphs = _mixed_families()
    svc = SparsifyService(min_n_bucket=16, min_L_bucket=32)
    results = svc.sparsify(graphs, budget=8)
    # request order preserved, results exact
    for g, r in zip(graphs, results):
        single = lgrass_sparsify(g, budget=8)
        assert np.array_equal(r.edge_mask, single.edge_mask)
    # bucketing bounds the number of dispatched shapes
    assert svc.stats.n_graphs == len(graphs)
    assert svc.stats.n_dispatches == len(svc.stats.bucket_counts)
    assert svc.stats.n_dispatches < len(graphs)
    assert 0.0 <= svc.stats.padding_overhead < 1.0
    # keys are pow2 buckets that fit their graphs
    for (nb, lb), cnt in svc.stats.bucket_counts.items():
        assert nb == next_pow2(nb) and lb == next_pow2(lb)


def test_sparsify_service_chunks_large_batches():
    graphs = [random_connected_graph(20, 30, seed=s) for s in range(5)]
    svc = SparsifyService(max_batch_size=2)
    results = svc.sparsify(graphs, budget=4)
    assert svc.stats.n_dispatches == 3  # 5 graphs, one bucket, chunks of 2
    for g, r in zip(graphs, results):
        assert np.array_equal(
            r.edge_mask,
            lgrass_sparsify(g, budget=4).edge_mask,
        )


def test_sparsify_service_ndarray_budget():
    graphs = [random_connected_graph(20, 30, seed=s) for s in range(3)]
    svc = SparsifyService()
    results = svc.sparsify(graphs, budget=np.array([2, 3, 4]))
    for g, b, r in zip(graphs, (2, 3, 4), results):
        assert np.array_equal(
            r.edge_mask,
            lgrass_sparsify(g, budget=b).edge_mask,
        )
    # numpy scalar broadcasts like a python int
    r0 = svc.sparsify(graphs[:1], budget=np.int64(4))[0]
    assert np.array_equal(
        r0.edge_mask,
        lgrass_sparsify(graphs[0], budget=4).edge_mask,
    )


def test_sparsify_service_pads_batch_axis():
    """Odd chunk sizes are padded to pow2 with placeholder rows that must
    not leak into the results (and keep compiled shapes shared)."""
    graphs = [random_connected_graph(20, 30, seed=s) for s in range(3)]
    svc = SparsifyService()
    results = svc.sparsify(graphs, budget=4)   # one chunk of 3 -> B=4
    assert len(results) == len(graphs)
    assert svc.stats.n_dispatches == 1
    _, L_bucket = svc.bucket_key(graphs[0])
    assert svc.stats.n_padded_edge_slots == 4 * L_bucket  # B padded to 4
    for g, r in zip(graphs, results):
        assert np.array_equal(
            r.edge_mask,
            lgrass_sparsify(g, budget=4).edge_mask,
        )


def test_sparsify_service_warmup_precompiles():
    """warmup() compiles the bucket program off the request path; the
    request then reuses it (no new jit cache entry) and results stay
    exact. Warmup never touches the request-path stats."""
    from repro.core.sparsify import lgrass_device_batched

    svc = SparsifyService()
    size_before = lgrass_device_batched._cache_size()
    n_disp = svc.warmup([(20, 30), (22, 31)])  # same pow2 bucket
    assert n_disp == 1
    assert svc.stats.n_warmup_dispatches == 1
    assert svc.stats.warmup_seconds > 0.0
    assert svc.stats.n_graphs == 0 and svc.stats.n_dispatches == 0
    size_warm = lgrass_device_batched._cache_size()
    assert size_warm == size_before + 1

    g = random_connected_graph(20, 30, seed=3)
    [r] = svc.sparsify([g])
    assert lgrass_device_batched._cache_size() == size_warm  # cache hit
    assert np.array_equal(
        r.edge_mask, lgrass_sparsify(g).edge_mask
    )


def test_sparsify_service_host_recovery_mode():
    """The oracle tail stays available behind recovery='host'."""
    graphs = [random_connected_graph(20, 30, seed=s) for s in range(2)]
    svc = SparsifyService(recovery="host")
    for g, r in zip(graphs, svc.sparsify(graphs, budget=4)):
        assert np.array_equal(
            r.edge_mask,
            lgrass_sparsify(g, budget=4, recovery="host").edge_mask,
        )


def test_sparsify_service_mixed_budgets():
    graphs = _mixed_families()[:3]
    svc = SparsifyService()
    results = svc.sparsify(graphs, budget=[None, 5, None])
    assert np.array_equal(
        results[0].edge_mask, lgrass_sparsify(graphs[0]).edge_mask
    )
    assert np.array_equal(
        results[1].edge_mask,
        lgrass_sparsify(graphs[1], budget=5).edge_mask,
    )
    assert np.array_equal(
        results[2].edge_mask, lgrass_sparsify(graphs[2]).edge_mask
    )
