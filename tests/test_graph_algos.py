"""BFS / MST / LCA / resistance: JAX implementations vs host oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _prop import cases, integers, sampled_from
from repro.core import _host as H
from repro.core.bfs import bfs_doubling, effective_weights, select_root
from repro.core.graph import random_connected_graph
from repro.core.lca import (build_lifting, lca, lca_with_shortcut, subroot,
                            tree_distance)
from repro.core.mst import boruvka_mst, kruskal_mst_numpy
from repro.core.resistance import (edge_resistance, node_parent_inv_w,
                                   root_path_sums)
from repro.core.sort import sort_f32_desc_stable


def _setup(n=60, m=120, seed=0, weight="lognormal"):
    g = random_connected_graph(n, m, seed=seed, weight=weight)
    u = jnp.asarray(g.u, jnp.int32)
    v = jnp.asarray(g.v, jnp.int32)
    w = jnp.asarray(g.w, jnp.float32)
    return g, u, v, w


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bfs_matches_oracle(seed):
    g, u, v, w = _setup(seed=seed)
    root = int(select_root(u, v, g.n))
    assert root == H.select_root_np(g.u, g.v, g.n)
    d, p = bfs_doubling(u, v, g.n, jnp.int32(root))
    dn, pn = H.bfs_np(g.u, g.v, g.n, root)
    assert np.array_equal(np.asarray(d), dn)
    assert np.array_equal(np.asarray(p), pn)


@pytest.mark.parametrize(
    "seed,weight",
    cases(integers(0, 10_000), sampled_from(["lognormal", "ties"]),
          n_cases=20, seed=2024),
)
def test_boruvka_equals_kruskal(seed, weight):
    g, u, v, w = _setup(n=40, m=90, seed=seed, weight=weight)
    root = int(select_root(u, v, g.n))
    d, _ = bfs_doubling(u, v, g.n, jnp.int32(root))
    eff = effective_weights(u, v, w, d, g.n)
    perm = sort_f32_desc_stable(eff)
    rank = np.empty(g.m, np.int32)
    rank[np.asarray(perm)] = np.arange(g.m)
    tree_dev = np.asarray(boruvka_mst(u, v, jnp.asarray(rank), g.n))
    tree_ref = kruskal_mst_numpy(g.u, g.v, rank, g.n)
    assert np.array_equal(tree_dev, tree_ref)
    assert tree_dev.sum() == g.n - 1


def test_lca_brute_force():
    g, u, v, w = _setup(n=50, m=100, seed=3)
    root = int(select_root(u, v, g.n))
    d, p = bfs_doubling(u, v, g.n, jnp.int32(root))
    t = build_lifting(p, d, g.n)
    dn, pn = np.asarray(d), np.asarray(p)

    def brute(a, b):
        pa, pb = a, b
        seen = set()
        while pa != -1:
            seen.add(pa)
            pa = pn[pa] if pn[pa] >= 0 else -1
        seen.add(root)
        while pb not in seen:
            pb = pn[pb]
        return pb

    rng = np.random.default_rng(0)
    a = rng.integers(0, g.n, 80).astype(np.int32)
    b = rng.integers(0, g.n, 80).astype(np.int32)
    got = np.asarray(lca(t, jnp.asarray(a), jnp.asarray(b)))
    want = np.array([brute(int(x), int(y)) for x, y in zip(a, b)])
    assert np.array_equal(got, want)
    # shortcut variant agrees
    got2 = np.asarray(lca_with_shortcut(t, jnp.int32(root), jnp.asarray(a),
                                        jnp.asarray(b)))
    assert np.array_equal(got2, want)
    # numpy mirror agrees
    up_np = H.build_lifting_np(pn, dn, g.n)
    got3 = H.lca_np(up_np, dn, a, b)
    assert np.array_equal(got3, want)


def test_resistance_vs_dense_laplacian():
    """R_tree from root-path sums == pseudo-inverse of the tree Laplacian."""
    g, u, v, w = _setup(n=30, m=60, seed=4)
    root = int(select_root(u, v, g.n))
    d0, _ = bfs_doubling(u, v, g.n, jnp.int32(root))
    eff = effective_weights(u, v, w, d0, g.n)
    perm = sort_f32_desc_stable(eff)
    rank = np.empty(g.m, np.int32)
    rank[np.asarray(perm)] = np.arange(g.m)
    tmask = boruvka_mst(u, v, jnp.asarray(rank), g.n)
    dt, pt = bfs_doubling(u, v, g.n, jnp.int32(root), edge_mask=tmask)
    t = build_lifting(pt, dt, g.n)
    inv_w = node_parent_inv_w(u, v, w, tmask, pt, g.n)
    r = root_path_sums(t, inv_w)
    el = lca(t, u, v)
    rdev = np.asarray(edge_resistance(t, r, u, v, el))

    # dense ground truth
    lap = np.zeros((g.n, g.n))
    tm = np.asarray(tmask)
    for i in range(g.m):
        if tm[i]:
            a, b, wt = int(g.u[i]), int(g.v[i]), float(g.w[i])
            lap[a, a] += wt
            lap[b, b] += wt
            lap[a, b] -= wt
            lap[b, a] -= wt
    pinv = np.linalg.pinv(lap)
    for i in range(g.m):
        a, b = int(g.u[i]), int(g.v[i])
        want = pinv[a, a] + pinv[b, b] - 2 * pinv[a, b]
        assert abs(rdev[i] - want) < 1e-3 * max(1.0, abs(want))


def test_subroot_depth1():
    g, u, v, w = _setup(n=40, m=80, seed=5)
    root = int(select_root(u, v, g.n))
    d, p = bfs_doubling(u, v, g.n, jnp.int32(root))
    t = build_lifting(p, d, g.n)
    nodes = jnp.arange(g.n, dtype=jnp.int32)
    sr = np.asarray(subroot(t, nodes))
    dn, pn = np.asarray(d), np.asarray(p)
    for x in range(g.n):
        if x == root:
            assert sr[x] == root
        else:
            y = x
            while dn[y] > 1:
                y = pn[y]
            assert sr[x] == y
