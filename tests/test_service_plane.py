"""The serving plane (PR 6): async dispatch, buffer donation, batch-axis
sharding, staging-pool reuse, on-path-compile accounting, padding-stat
split, and the trivial-graph (edgeless / single-node) service path.

The load-bearing contract: every serving mode — sync, async, donated,
sharded — returns results in request order, bit-identical to per-graph
`lgrass_sparsify`, across mixed sizes, explicit+None budgets, chunk
boundaries, and placeholder tails.
"""
import jax
import numpy as np
import pytest

from repro.core import lgrass_sparsify, lgrass_sparsify_batch
from repro.core.baseline import default_budget
from repro.core.distributed import batch_mesh, mesh_size
from repro.core.graph import (GraphBatch, powergrid_like_graph,
                              random_connected_graph, trivial_graph)
from repro.serve.sparsify_service import ServiceStats, SparsifyService

MULTIDEV = len(jax.devices()) >= 2


def _mixed_graphs():
    """Mixed sizes/families across several pow2 buckets, with trivial
    (edgeless) requests interleaved mid-stream."""
    gs = [
        random_connected_graph(30, 60, seed=0, weight="lognormal"),
        random_connected_graph(45, 110, seed=1, weight="ties"),
        powergrid_like_graph(6, 0.4, seed=3),
        trivial_graph(),
        random_connected_graph(24, 40, seed=2),
        random_connected_graph(18, 25, seed=7),
        trivial_graph(),
        random_connected_graph(40, 95, seed=5, weight="ties"),
    ]
    budgets = [8, None, 5, None, 3, None, 2, 7]
    return gs, budgets


def _reference(graphs, budgets):
    return [
        lgrass_sparsify(g, budget=b) if g.m else None
        for g, b in zip(graphs, budgets)
    ]


def _assert_request_order_parity(graphs, budgets, results, ref):
    assert len(results) == len(graphs)
    for k, (g, r) in enumerate(zip(graphs, results)):
        if g.m == 0:
            assert r.edge_mask.shape == (0,), k
            assert r.tree_mask.shape == (0,), k
            assert r.accepted_mask.shape == (0,), k
            assert r.n_accepted == 0, k
        else:
            assert np.array_equal(r.edge_mask, ref[k].edge_mask), k
            assert np.array_equal(r.tree_mask, ref[k].tree_mask), k
            assert np.array_equal(r.accepted_mask, ref[k].accepted_mask), k
            assert r.n_accepted == ref[k].n_accepted, k


# ------------------------------------------------------------------ modes

@pytest.mark.parametrize("mode", ["sync", "async", "async_donate"])
def test_service_mode_parity(mode):
    """Mixed sizes, explicit+None budgets, chunk boundaries (chunks of
    3), and placeholder tails stay bit-identical to per-graph runs for
    every serving mode — including on a SECOND call, which exercises
    staging-pool reuse in steady state."""
    graphs, budgets = _mixed_graphs()
    ref = _reference(graphs, budgets)
    svc = SparsifyService(
        max_batch_size=3,
        async_dispatch=(mode != "sync"),
        donate=(mode == "async_donate"),
    )
    for _ in range(2):
        results = svc.sparsify(graphs, budget=budgets)
        _assert_request_order_parity(graphs, budgets, results, ref)


@pytest.mark.skipif(not MULTIDEV, reason="needs >= 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
@pytest.mark.parametrize("mode", ["sync", "async_donate"])
def test_service_sharded_parity(mode):
    """Batch-axis sharding across the mesh is invisible in the results:
    bit-identical to per-graph runs, composing with async + donation."""
    graphs, budgets = _mixed_graphs()
    ref = _reference(graphs, budgets)
    svc = SparsifyService(
        max_batch_size=4, mesh=batch_mesh(),
        async_dispatch=(mode != "sync"), donate=(mode == "async_donate"),
    )
    for _ in range(2):
        results = svc.sparsify(graphs, budget=budgets)
        _assert_request_order_parity(graphs, budgets, results, ref)


@pytest.mark.skipif(not MULTIDEV, reason="needs >= 2 devices")
def test_service_sharded_pad_batch_mesh_multiple():
    """With a mesh, the batch pad target is a whole multiple of the mesh
    size so every shard gets equal rows."""
    mesh = batch_mesh()
    ms = mesh_size(mesh)
    svc = SparsifyService(mesh=mesh)
    for n_chunk in (1, 2, ms - 1, ms, ms + 1, 3 * ms):
        B = svc._pad_batch(n_chunk)
        assert B >= n_chunk and B % ms == 0, (n_chunk, B)


def test_service_single_device_mesh_path():
    """mesh=batch_mesh(1) runs the sharded code path on one device —
    results identical, pad target unchanged (pow2)."""
    graphs, budgets = _mixed_graphs()
    ref = _reference(graphs, budgets)
    svc = SparsifyService(mesh=batch_mesh(1),
                          async_dispatch=True)
    results = svc.sparsify(graphs, budget=budgets)
    _assert_request_order_parity(graphs, budgets, results, ref)
    assert svc._pad_batch(3) == 4


def test_host_recovery_rejects_serving_modes():
    """The host oracle tail blocks per chunk by design; the serving-plane
    modes require the fused device program."""
    for kw in (dict(async_dispatch=True), dict(donate=True),
               dict(mesh=batch_mesh(1))):
        with pytest.raises(ValueError):
            SparsifyService(recovery="host", **kw)
    with pytest.raises(ValueError):
        SparsifyService(recovery="nope")
    # plain host mode still serves
    g = random_connected_graph(20, 30, seed=3)
    svc = SparsifyService(recovery="host")
    [r] = svc.sparsify([g], budget=4)
    assert np.array_equal(
        r.edge_mask,
        lgrass_sparsify(g, budget=4, recovery="host").edge_mask,
    )


# -------------------------------------------------- trivial-graph bugfix

def test_trivial_graph_direct_and_batched():
    """Edgeless / single-node graphs return empty masks through the
    direct API and the batched path (L_max == 0 program)."""
    g1 = trivial_graph()
    import dataclasses
    g5 = dataclasses.replace(trivial_graph(), n=5)  # isolated nodes
    for g in (g1, g5):
        r = lgrass_sparsify(g)
        assert r.edge_mask.shape == (0,) and r.n_accepted == 0
    batch = GraphBatch.from_graphs([g1, g5])
    assert batch.L_max == 0
    for r in lgrass_sparsify_batch(batch):
        assert r.edge_mask.shape == (0,) and r.n_accepted == 0


def test_trivial_graph_service_regression():
    """The service path: edgeless requests bucket through next_pow2(0)
    and the device m==0 guards without crashing, mixed with real
    traffic, empty request lists, and — the regression — small buckets
    whose placeholder must be the (n=1, m=0) trivial graph (the old
    (n=2, m=1) filler crashed min_n_bucket=1 buckets with
    'bucket too small')."""
    svc = SparsifyService()
    assert svc.sparsify([]) == []

    g = random_connected_graph(20, 30, seed=1)
    ref = lgrass_sparsify(g, budget=5)
    results = svc.sparsify([trivial_graph(), g, trivial_graph()],
                           budget=[None, 5, None])
    assert results[0].edge_mask.shape == (0,)
    assert results[2].edge_mask.shape == (0,)
    assert np.array_equal(results[1].edge_mask, ref.edge_mask)

    # the placeholder-fill regression: 3 trivial graphs in a (1, 1)
    # bucket force a placeholder row into the smallest possible bucket
    svc_min = SparsifyService(min_n_bucket=1, min_L_bucket=1)
    out = svc_min.sparsify([trivial_graph()] * 3)
    assert [r.edge_mask.shape for r in out] == [(0,)] * 3
    # warmup accepts trivial sizes too
    assert svc_min.warmup([(1, 0)]) == 1


# ------------------------------------------------------- stats: padding

def test_padding_overhead_split_pinned():
    """batch_pad (placeholder rows) vs shape_pad (real rows' tail) on a
    known request set, pinned exactly.

    Set: 3x (n=20, m=49) -> bucket (32, 64), one chunk padded B=4
         1x (n=40, m=109) -> bucket (64, 128), one chunk of B=1
    """
    graphs = [random_connected_graph(20, 30, seed=s) for s in range(3)]
    graphs.append(random_connected_graph(40, 70, seed=9))
    assert [g.m for g in graphs] == [49, 49, 49, 109]
    svc = SparsifyService()
    svc.sparsify(graphs, budget=4)
    s = svc.stats
    assert s.n_dispatches == 2
    assert s.bucket_counts == {(32, 64): 3, (64, 128): 1}
    assert s.n_padded_edge_slots == 4 * 64 + 1 * 128          # 384
    assert s.n_real_edge_slots == 3 * 49 + 109                # 256
    assert s.n_batch_pad_edge_slots == 1 * 64                 # 1 filler row
    assert s.n_shape_pad_edge_slots == (3 * 64 - 147) + (128 - 109)  # 64
    assert s.batch_pad_overhead == pytest.approx(64 / 384)
    assert s.shape_pad_overhead == pytest.approx(64 / 384)
    # the two kinds are disjoint and account for every non-real slot
    assert s.padding_overhead == pytest.approx((64 + 64) / 384)
    assert (s.n_real_edge_slots + s.n_batch_pad_edge_slots
            + s.n_shape_pad_edge_slots) == s.n_padded_edge_slots


def test_padding_overhead_empty_stats():
    s = ServiceStats()
    assert s.padding_overhead == 0.0
    assert s.batch_pad_overhead == 0.0
    assert s.shape_pad_overhead == 0.0


# -------------------------------------------- stats: on-path compiles

def test_on_path_compile_accounting():
    """warmup covering the traffic's dispatch signatures => zero on-path
    compiles; a request whose explicit budget exceeds the bucket default
    widens b_cap into a program warmup never compiled => counted ONCE."""
    graphs = [random_connected_graph(20, 30, seed=s) for s in range(3)]
    svc = SparsifyService()
    svc.warmup([(graphs[0].n, graphs[0].m)],   # B_pad 4, default b_cap
               batch_sizes=(3,))
    res = svc.sparsify(graphs)                 # one chunk of 3 -> B=4
    assert svc.stats.n_on_path_compiles == 0
    assert all(r is not None for r in res)

    # explicit budget 30 > default_budget(32) = 2: b_cap widens 8 -> 32
    svc.sparsify([graphs[0]], budget=30)
    assert svc.stats.n_on_path_compiles == 1
    svc.sparsify([graphs[0]], budget=30)       # same signature: not recounted
    assert svc.stats.n_on_path_compiles == 1

    # warming the wide-budget program up front keeps the path clean
    svc2 = SparsifyService()
    svc2.warmup([(graphs[0].n, graphs[0].m)], batch_sizes=(1, 3),
                budgets=[30])
    svc2.sparsify(graphs, budget=30)
    svc2.sparsify([graphs[0]], budget=30)
    assert svc2.stats.n_on_path_compiles == 0


def test_warmup_warms_the_traffic_program_variant():
    """warmup goes through the SAME dispatch funnel as traffic, so the
    donated program (a distinct jit cache) is what gets compiled when
    donate=True."""
    from repro.core.sparsify import (lgrass_device_batched,
                                     lgrass_device_batched_donated)

    g = random_connected_graph(20, 30, seed=3)
    svc = SparsifyService(async_dispatch=True, donate=True)
    before_plain = lgrass_device_batched._cache_size()
    before_don = lgrass_device_batched_donated._cache_size()
    svc.warmup([(g.n, g.m)])
    assert lgrass_device_batched._cache_size() == before_plain
    assert lgrass_device_batched_donated._cache_size() == before_don + 1
    [r] = svc.sparsify([g])
    assert lgrass_device_batched_donated._cache_size() == before_don + 1
    assert svc.stats.n_on_path_compiles == 0
    assert np.array_equal(
        r.edge_mask, lgrass_sparsify(g).edge_mask)


# ------------------------------------------------------- staging pool

def test_staging_pool_steady_state_no_growth():
    """The fence-guarded pool grows only while dispatches are in flight;
    repeat traffic reuses the same buffer sets (zero-alloc steady
    state), and results stay exact throughout."""
    graphs, budgets = _mixed_graphs()
    ref = _reference(graphs, budgets)
    svc = SparsifyService(max_batch_size=3, async_dispatch=True,
                          donate=True)
    _assert_request_order_parity(
        graphs, budgets, svc.sparsify(graphs, budget=budgets), ref)
    sets_after_first = svc._pool.n_buffer_sets
    for _ in range(3):
        _assert_request_order_parity(
            graphs, budgets, svc.sparsify(graphs, budget=budgets), ref)
    assert svc._pool.n_buffer_sets <= sets_after_first + 1


def test_async_budget_isolation_across_chunks():
    """Regression for the staging race: chunks of the SAME bucket carry
    different budgets; with async dispatch the later chunk's staging
    refill must not leak into the earlier in-flight dispatch."""
    graphs = [random_connected_graph(20, 30, seed=s) for s in range(6)]
    budgets = [2, 3, 4, 5, 6, 7]
    svc = SparsifyService(max_batch_size=2, async_dispatch=True)
    for _ in range(2):
        results = svc.sparsify(graphs, budget=budgets)
        for g, b, r in zip(graphs, budgets, results):
            single = lgrass_sparsify(g, budget=b)
            assert np.array_equal(r.edge_mask, single.edge_mask), b
            assert r.n_accepted == single.n_accepted, b
