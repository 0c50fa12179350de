"""The device programs' loop-round counters (`loop_rounds`) against what
each loop's own statement says it runs, per lane of the vmapped
program, and as `ServiceStats` sums them."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lgrass_sparsify, lgrass_sparsify_batch
from repro.core.graph import powergrid_like_graph
from repro.core.sparsify import LOOPS, phase1_device
from repro.serve.sparsify_service import ServiceStats, SparsifyService


def _grids(k, side=8):
    # equal (n, m): a batch of them carries no shape padding, so each
    # lane runs exactly the single-graph program's loops
    return [powergrid_like_graph(side, 0.25, seed=s) for s in range(1, k + 1)]


def _phase1(g, **kw):
    return phase1_device(jnp.asarray(g.u, jnp.int32),
                         jnp.asarray(g.v, jnp.int32),
                         jnp.asarray(g.w, jnp.float32), g.n, **kw)


@pytest.mark.parametrize("p1_chunk", [1, 3, 16])
def test_mark_rounds_are_the_crossing_blocks(p1_chunk):
    """marking.phase1_chunked: the outer loop runs ceil(n_crossing / C)
    blocks."""
    g = _grids(1)[0]
    d = _phase1(g, p1_chunk=p1_chunk)
    n_crossing = int(np.asarray(d["crossing"]).sum())
    want = math.ceil(n_crossing / p1_chunk)
    assert n_crossing > 0
    assert int(d["loop_rounds"][LOOPS.index("mark")]) == want
    r = lgrass_sparsify(g, p1_chunk=p1_chunk)
    assert r.loop_rounds["mark"] == want


@pytest.mark.parametrize("budget", [3, None, 10_000])
def test_rec_rounds_stay_within_the_offtree_blocks(budget):
    """The replay's outer loop never runs past the block holding the
    last off-tree edge, and stops once the budget is met."""
    g = _grids(1)[0]
    r = lgrass_sparsify(g, budget=None if budget is None else
                        min(budget, g.m), chunk=8)
    n_off = int((~r.tree_mask).sum())
    assert 1 <= r.loop_rounds["rec"] <= math.ceil(n_off / 8)
    if budget == 10_000:  # never met: every off-tree block is replayed
        assert r.loop_rounds["rec"] == math.ceil(n_off / 8)


def test_every_loop_counted_and_tree_only_under_levels():
    g = _grids(1)[0]
    dbl = lgrass_sparsify(g).loop_rounds
    lev = lgrass_sparsify(g, bfs_engine="levels").loop_rounds
    assert list(dbl) == list(LOOPS) == list(lev)
    assert dbl["tree"] == 0 and lev["tree"] > 0
    assert all(dbl[k] > 0 for k in LOOPS if k != "tree")
    # level-sync BFS: one round per level, and one to find none left
    assert lev["bfs"] >= dbl["bfs"]
    assert {k: lev[k] for k in ("mst", "mst_jump", "mark", "rec")} == \
        {k: dbl[k] for k in ("mst", "mst_jump", "mark", "rec")}


def test_host_recovery_reports_phase1_loops():
    g = _grids(1)[0]
    dev = lgrass_sparsify(g).loop_rounds
    host = lgrass_sparsify(g, recovery="host").loop_rounds
    assert host["rec"] == 0
    assert {k: v for k, v in host.items() if k != "rec"} == \
        {k: v for k, v in dev.items() if k != "rec"}


@pytest.mark.parametrize("recovery", ["device", "host"])
def test_batched_lanes_count_their_own_graph(recovery):
    graphs = _grids(3)
    batch = lgrass_sparsify_batch(graphs, recovery=recovery)
    for g, rb in zip(graphs, batch):
        assert rb.loop_rounds == lgrass_sparsify(
            g, recovery=recovery).loop_rounds


def test_count_loops_sums_real_rows_and_their_lockstep():
    stats = ServiceStats()
    k = len(LOOPS)
    rows = np.array([np.arange(k) + 1, np.full(k, 5), np.full(k, 9)])
    stats.count_loops(rows, n_real=2)   # row 2: a placeholder lane
    stats.count_loops(np.array([np.full(k, 4)]), n_real=1)
    for j, name in enumerate(LOOPS):
        assert stats.loop_rounds[name] == (j + 1) + 5 + 4
        assert stats.loop_lane_rounds[name] == 2 * 9 + 1 * 4


@pytest.mark.parametrize("async_dispatch", [False, True])
def test_service_stats_sum_the_lanes_by_hand(async_dispatch):
    graphs = _grids(4)
    svc = SparsifyService(async_dispatch=async_dispatch, max_batch_size=2)
    first = svc.sparsify(graphs)         # two chunks of 2
    second = svc.sparsify(graphs[:3])    # 2, then 1
    third = SparsifyService(async_dispatch=async_dispatch)
    padded = third.sparsify(graphs[:3])  # 3 real rows and a placeholder
    for name in LOOPS:
        per = [r.loop_rounds[name] for r in first + second]
        assert svc.stats.loop_rounds[name] == sum(per)
        chunks = [per[0:2], per[2:4], per[4:6], per[6:7]]
        assert svc.stats.loop_lane_rounds[name] == sum(
            len(c) * max(c) for c in chunks)
        assert svc.stats.loop_lane_rounds[name] >= sum(per)
        # the placeholder is a one-node graph, whose loops end no later
        # than a real graph's: it adds lane rounds, never its own rounds
        real = [r.loop_rounds[name] for r in padded]
        assert third.stats.loop_rounds[name] == sum(real)
        assert third.stats.loop_lane_rounds[name] == 3 * max(real)
