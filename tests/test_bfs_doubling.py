"""BFS parity: the hop-doubling engine and the Euler-tour tree rooting
must be BIT-IDENTICAL (depth AND parent) to the numpy oracle's
level-synchronous BFS across graph families — including the
padded-batch vmap path, tree-restricted masks, and disconnected
forests — and the full pipeline must equal the baseline greedy.

Shapes are reused across cases so the sweep costs a handful of XLA
compiles, not one per case.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import _host as H
from repro.core import baseline_sparsify, lgrass_sparsify, lgrass_sparsify_batch
from repro.core.bfs import (
    bfs_doubling,
    effective_weights,
    finite_depth,
    root_tree_euler,
    select_root,
)
from repro.core.graph import (
    Graph,
    GraphBatch,
    feeder_like_graph,
    powergrid_like_graph,
    random_connected_graph,
)


def _families(n_chain=96, seed=0):
    """One representative per family, shared across the parity tests."""
    chain = feeder_like_graph(n_chain, 0, span=4, seed=seed)  # pure chain
    feeder = feeder_like_graph(n_chain, n_chain // 2, span=8, seed=seed)
    grid = powergrid_like_graph(9, 0.3, seed=seed)
    rand = random_connected_graph(80, 180, seed=seed)
    return [("chain", chain), ("feeder", feeder), ("grid", grid),
            ("random", rand)]


def _disconnected(seed=0):
    """Two components; the BFS root lands in the larger one."""
    ga = feeder_like_graph(60, 20, span=6, seed=seed)
    gb = random_connected_graph(30, 45, seed=seed + 1)
    return Graph(
        n=90,
        u=np.concatenate([ga.u, gb.u + 60]).astype(np.int32),
        v=np.concatenate([ga.v, gb.v + 60]).astype(np.int32),
        w=np.concatenate([ga.w, gb.w]).astype(np.float32),
    )


def _assert_bfs_matches_oracle(g, emask=None):
    root = H.select_root_np(g.u, g.v, g.n)
    dn, pn = H.bfs_np(g.u, g.v, g.n, root, emask)
    u = jnp.asarray(g.u, jnp.int32)
    v = jnp.asarray(g.v, jnp.int32)
    em = None if emask is None else jnp.asarray(emask)
    dd, pd = bfs_doubling(u, v, g.n, jnp.int32(root), em)
    assert np.array_equal(np.asarray(dd), dn)
    assert np.array_equal(np.asarray(pd), pn)
    return root, dn, pn


@pytest.mark.parametrize("seed", [0, 1])
def test_bfs_matches_oracle_all_families(seed):
    for _, g in _families(seed=seed):
        _assert_bfs_matches_oracle(g)


def test_bfs_doubling_shuffled_ids():
    """Node ids decorrelated from the chain layout: the monotone-id
    chains stop helping and the re-anchored climb must carry
    convergence — the output still equals the oracle's."""
    g0 = feeder_like_graph(200, 120, span=10, seed=3)
    perm = np.random.default_rng(7).permutation(g0.n).astype(np.int32)
    g = Graph(n=g0.n, u=perm[g0.u], v=perm[g0.v], w=g0.w)
    _assert_bfs_matches_oracle(g)


def _tree_mask_from_bfs(g, root, pn):
    """A deterministic spanning-tree mask (the BFS tree itself)."""
    tmask = np.zeros(g.m, bool)
    used = np.zeros(g.n, bool)
    for i in range(g.m):
        a, b = int(g.u[i]), int(g.v[i])
        if pn[b] == a and not used[b]:
            tmask[i] = True
            used[b] = True
        elif pn[a] == b and not used[a]:
            tmask[i] = True
            used[a] = True
    return tmask


@pytest.mark.parametrize("seed", [0, 2])
def test_tree_restricted_masks_and_root_tree(seed):
    """The BFS under a tree edge mask ≡ oracle ≡ `root_tree_euler` (the
    O(log n) Euler rooting the pipeline's second pass uses)."""
    for _, g in _families(seed=seed):
        root = H.select_root_np(g.u, g.v, g.n)
        _, pn = H.bfs_np(g.u, g.v, g.n, root)
        tmask = _tree_mask_from_bfs(g, root, pn)
        _, dt, pt = _assert_bfs_matches_oracle(g, tmask)
        de, pe, _ = root_tree_euler(
            jnp.asarray(g.u, jnp.int32), jnp.asarray(g.v, jnp.int32),
            g.n, jnp.int32(root), jnp.asarray(tmask))
        assert np.array_equal(np.asarray(de), dt)
        assert np.array_equal(np.asarray(pe), pt)


def test_disconnected_forest_parity_and_finite_weights():
    """Regression: unreachable nodes keep INF depth,
    and `effective_weights` clamps them instead of multiplying
    float32(2^31-1) into the weights (device and numpy mirror agree)."""
    g = _disconnected()
    root, dn, _ = _assert_bfs_matches_oracle(g)
    # exactly the non-root component is unreachable
    assert (dn == np.iinfo(np.int32).max).sum() in (30, 60)
    u = jnp.asarray(g.u, jnp.int32)
    v = jnp.asarray(g.v, jnp.int32)
    w = jnp.asarray(g.w, jnp.float32)
    dd, _ = bfs_doubling(u, v, g.n, jnp.int32(root))
    eff = np.asarray(effective_weights(u, v, w, dd, g.n))
    assert np.all(np.isfinite(eff))
    assert eff.max() < 1e6  # no 2.1e9-scale poison
    eff_np = H.effective_weights_np(g.u, g.v, g.w, dn)
    assert np.array_equal(eff, eff_np)
    # unreachable component's edges degrade to eff == w (depth treated 0)
    un = (dn[g.u] == np.iinfo(np.int32).max)
    assert np.allclose(eff[un], g.w[un])
    # the shared clamp helper itself
    assert np.array_equal(
        np.asarray(finite_depth(jnp.asarray(dn))), np.where(
            dn == np.iinfo(np.int32).max, 0, dn))
    # root_tree_euler on a spanning forest tours only the root's component
    _, pn = H.bfs_np(g.u, g.v, g.n, root)
    tmask = _tree_mask_from_bfs(g, root, pn)
    dtn, ptn = H.bfs_np(g.u, g.v, g.n, root, tmask)
    de, pe, _ = root_tree_euler(u, v, g.n, jnp.int32(root),
                                jnp.asarray(tmask))
    assert np.array_equal(np.asarray(de), dtn)
    assert np.array_equal(np.asarray(pe), ptn)


def test_padded_batch_vmap_parity():
    """The BFS vmapped over a padded GraphBatch, jitted and traced
    inline: real-slot outputs equal the unpadded per-graph oracle runs;
    padded nodes stay unreachable."""
    graphs = [
        random_connected_graph(40, 90, seed=0),
        feeder_like_graph(50, 25, span=6, seed=1),
        powergrid_like_graph(6, 0.4, seed=2),
    ]
    batch = GraphBatch.from_graphs(graphs, n_max=64, L_max=160)
    ub = jnp.asarray(batch.u, jnp.int32)
    vb = jnp.asarray(batch.v, jnp.int32)
    evb = jnp.asarray(batch.edge_valid)
    roots = jnp.asarray(
        [H.select_root_np(g.u, g.v, g.n) for g in graphs], jnp.int32)
    dB, pB = jax.vmap(
        lambda a, b, r, m: bfs_doubling(a, b, 64, r, m))(ub, vb, roots, evb)
    for i, g in enumerate(graphs):
        dn, pn = H.bfs_np(g.u, g.v, g.n, int(roots[i]))
        assert np.array_equal(np.asarray(dB[i])[:g.n], dn)
        assert np.array_equal(np.asarray(pB[i])[:g.n], pn)
        # padding nodes can never be reached from the real graph
        assert np.all(np.asarray(dB[i])[g.n:] == np.iinfo(np.int32).max)


def test_bfs_doubling_unpacked_key_branch():
    """n past the int32 packing bound ((n+1)^2 >= 2^31) exercises the
    two-scatter relax/witness fallback: a small graph embedded in a
    huge sparse id space, parity vs the oracle."""
    n = 46_400  # (n+1)^2 > 2^31 -> packed=False
    rng = np.random.default_rng(11)
    ids = np.sort(rng.choice(n, size=600, replace=False)).astype(np.int32)
    uu = [ids[i] for i in range(599)]
    vv = [ids[i + 1] for i in range(599)]
    seen = set(zip(uu, vv))
    while len(uu) < 750:  # some long-range chords
        a, b = rng.choice(ids, 2)
        key = (min(a, b), max(a, b))
        if a == b or key in seen:
            continue
        seen.add(key)
        uu.append(key[0])
        vv.append(key[1])
    g = Graph(n=n, u=np.array(uu, np.int32), v=np.array(vv, np.int32),
              w=np.ones(len(uu), np.float32))
    _assert_bfs_matches_oracle(g)


def test_select_root_unchanged_by_engine_refactor():
    g = random_connected_graph(60, 140, seed=4)
    assert int(select_root(jnp.asarray(g.u, jnp.int32),
                           jnp.asarray(g.v, jnp.int32), g.n)) == \
        H.select_root_np(g.u, g.v, g.n)


@pytest.mark.parametrize("family_seed", [0, 1])
def test_pipeline_identical_under_both_engines(family_seed):
    """The whole sparsifier equals the baseline greedy under either
    recovery tail, with the same tree and counters."""
    g = random_connected_graph(36, 80, seed=family_seed)
    base = baseline_sparsify(g, budget=7)
    rd = lgrass_sparsify(g, budget=7, recovery="device")
    rh = lgrass_sparsify(g, budget=7, recovery="host")
    for r in (rd, rh):
        assert np.array_equal(r.edge_mask, base.edge_mask)
    assert np.array_equal(rd.tree_mask, rh.tree_mask)
    assert rd.n_groups == rh.n_groups
    assert rd.n_dirty == rh.n_dirty


def test_pipeline_feeder_engine_parity():
    """The diameter-bound family the doubling engine targets."""
    g = feeder_like_graph(96, 48, span=6, seed=5)
    assert np.array_equal(lgrass_sparsify(g, budget=6).edge_mask,
                          baseline_sparsify(g, budget=6).edge_mask)


def test_batched_pipeline_engine_parity():
    graphs = [
        random_connected_graph(30, 60, seed=0),
        feeder_like_graph(50, 25, span=6, seed=1),
        powergrid_like_graph(6, 0.4, seed=2),
    ]
    for g, r in zip(graphs, lgrass_sparsify_batch(graphs, budget=6)):
        assert np.array_equal(
            r.edge_mask, baseline_sparsify(g, budget=6).edge_mask)
