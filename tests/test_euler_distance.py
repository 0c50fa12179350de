"""Euler-tour tree distances from the depth-minimum table (`lca.dmin`).

`tree_distance_euler` and the cover tables of `marking.ball_pair_table`
answer a pair's distance with two `dmin` reads once each endpoint's
(first, depth) is known. They must equal the position-table formula
`depth[a] + depth[b] - 2 * depth[lca_euler(a, b)]` bit for bit — on
every node pair of random trees, a chain, a star and padded forests
(off-tour nodes: first = P - 1, INF depth, so the int32 sum wraps),
single and under `vmap` over lanes of different sizes — and equal the
numpy lifting-table distances (`_host.tree_dist_np`) on the BFS trees
of random graphs. `dmin` itself is checked against a brute-force range
minimum, and a structural guard pins the cover table at two per-pair
gathers.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis.jaxpr_audit import collect_eqns
from repro.core import _host as H
from repro.core.bfs import root_tree_euler
from repro.core.lca import (build_euler, lca_euler, tables_from_tour,
                            tree_distance_euler)
from repro.core.marking import ball_pair_table

INF = np.iinfo(np.int32).max


def _ref_distance(e, a, b):
    """Today's formula on the position table, kept here as the oracle."""
    return e.depth[a] + e.depth[b] - 2 * e.depth[lca_euler(e, a, b)]


def _ref_cover(e, xs, ys, cu, cv, cb):
    d = lambda p, q: _ref_distance(e, p, q)
    x, y = xs[:, None], ys[:, None]
    if cu.ndim == 1:
        cu, cv, cb = cu[None, :], cv[None, :], cb[None, :]
    return ((d(x, cu) <= cb) & (d(y, cv) <= cb)) | (
        (d(x, cv) <= cb) & (d(y, cu) <= cb))


def _tree(kind, n_real, n, seed=0):
    """(parent, depth) over n nodes; nodes >= n_real are padding (off
    the tour, parent -1, INF depth). Root 0."""
    rng = np.random.default_rng(seed)
    parent = np.full(n, -1, np.int32)
    for i in range(1, n_real):
        if kind == "chain":
            parent[i] = i - 1
        elif kind == "star":
            parent[i] = 0
        else:
            parent[i] = rng.integers(0, i)
    # relabel real nodes so ids do not follow depth order
    if kind == "random" and n_real > 2:
        perm = np.concatenate([[0], 1 + rng.permutation(n_real - 1),
                               np.arange(n_real, n)]).astype(np.int32)
        p2 = np.full(n, -1, np.int32)
        for i in range(1, n_real):
            p2[perm[i]] = perm[parent[i]]
        parent = p2
    depth = np.full(n, INF, np.int32)
    depth[0] = 0
    for _ in range(n_real):
        ok = (parent >= 0) & (depth[np.maximum(parent, 0)] < INF)
        depth = np.where(ok, depth[np.maximum(parent, 0)] + 1, depth)
        depth[0] = 0
    return parent, depth.astype(np.int32)


def _euler(kind, n_real, n, seed=0):
    parent, depth = _tree(kind, n_real, n, seed)
    return build_euler(jnp.asarray(parent), jnp.asarray(depth),
                       jnp.int32(0), n)


def _all_pairs(n):
    a, b = np.meshgrid(np.arange(n, dtype=np.int32),
                       np.arange(n, dtype=np.int32), indexing="ij")
    return jnp.asarray(a), jnp.asarray(b)


TREES = [
    ("random", 17, 17, 0),
    ("random", 40, 40, 1),
    ("random", 33, 33, 2),
    ("chain", 12, 12, 0),
    ("star", 12, 12, 0),
    ("random", 1, 1, 0),
    ("random", 2, 2, 0),
    # padded forests: nodes past n_real are off the tour
    ("random", 9, 16, 3),
    ("chain", 5, 11, 0),
    ("star", 6, 13, 0),
    ("random", 1, 6, 0),
]


@pytest.mark.parametrize("kind,n_real,n,seed", TREES)
def test_tree_distance_euler_all_pairs_bit_identical(kind, n_real, n, seed):
    e = _euler(kind, n_real, n, seed)
    a, b = _all_pairs(n)
    got = np.asarray(tree_distance_euler(e, a, b))
    ref = np.asarray(_ref_distance(e, a, b))
    assert np.array_equal(got, ref)
    if n_real < n:  # off-tour pairs exercise the INF / wrap path
        assert int(e.first[n - 1]) == 2 * n - 2


def _bfs_tree(n, extra, seed):
    """A random connected graph's BFS tree: the numpy lifting table and
    depths, and the Euler tables `build_euler` makes from it."""
    from repro.core.graph import random_connected_graph

    g = random_connected_graph(n, extra, seed=seed)
    u64, v64 = g.u.astype(np.int64), g.v.astype(np.int64)
    root = H.select_root_np(u64, v64, g.n)
    depth, parent = H.bfs_np(u64, v64, g.n, root)
    up = H.build_lifting_np(parent, depth, g.n)
    e = build_euler(jnp.asarray(parent), jnp.asarray(depth),
                    jnp.int32(root), g.n)
    return up, depth, e


@pytest.mark.parametrize("n,m", [(40, 64), (60, 300), (100, 257)])
def test_tree_distance_euler_matches_lifting_oracle(n, m):
    """Random query pairs on random BFS trees: the Euler distance equals
    the numpy binary-lifting distance exactly."""
    up, depth, e = _bfs_tree(n, 2 * n, seed=n)
    rng = np.random.default_rng(m)
    a = rng.integers(0, n, m).astype(np.int32)
    b = rng.integers(0, n, m).astype(np.int32)
    got = np.asarray(tree_distance_euler(e, jnp.asarray(a), jnp.asarray(b)))
    assert np.array_equal(got, H.tree_dist_np(up, depth, a, b))


def test_tree_distance_euler_identical_and_adjacent():
    """Edge cases: d(x, x) = 0; d(child, parent) = 1."""
    up, depth, e = _bfs_tree(30, 40, seed=5)
    nodes = jnp.arange(30, dtype=jnp.int32)
    assert np.all(np.asarray(tree_distance_euler(e, nodes, nodes)) == 0)
    parents = jnp.asarray(up[0], jnp.int32)
    d = np.asarray(tree_distance_euler(e, nodes, parents))
    assert np.all(d == (np.asarray(depth) > 0).astype(int))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_root_tree_euler_padded_forest_bit_identical(seed):
    """Tables built by `bfs.root_tree_euler` (another tour order) over a
    padded edge list whose masked edges leave nodes off the tour."""
    n_real, n = 14, 20
    parent, _ = _tree("random", n_real, n, seed)
    kids = np.arange(1, n_real, dtype=np.int32)
    u = np.concatenate([parent[kids], np.zeros(4, np.int32)])
    v = np.concatenate([kids, np.arange(n_real, n_real + 4,
                                        dtype=np.int32)])
    mask = np.concatenate([np.ones(n_real - 1, bool), np.zeros(4, bool)])
    _, _, e = root_tree_euler(jnp.asarray(u), jnp.asarray(v), n,
                              jnp.int32(0), jnp.asarray(mask))
    a, b = _all_pairs(n)
    assert np.array_equal(np.asarray(tree_distance_euler(e, a, b)),
                          np.asarray(_ref_distance(e, a, b)))


@pytest.mark.parametrize("sizes", [(5, 9, 12), (12, 3, 1), (7, 7, 2)])
def test_tree_distance_euler_vmap_lanes_bit_identical(sizes):
    n = 12
    trees = [_tree("random", s, n, seed=i) for i, s in enumerate(sizes)]
    parent = jnp.asarray(np.stack([p for p, _ in trees]))
    depth = jnp.asarray(np.stack([d for _, d in trees]))
    roots = jnp.zeros((len(sizes),), jnp.int32)
    e = jax.vmap(lambda p, d, r: build_euler(p, d, r, n))(parent, depth,
                                                          roots)
    a, b = _all_pairs(n)
    got = jax.vmap(lambda el: tree_distance_euler(el, a, b))(e)
    ref = jax.vmap(lambda el: _ref_distance(el, a, b))(e)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("kind,n_real,n,seed", TREES)
def test_ball_pair_table_euler_bit_identical(kind, n_real, n, seed):
    """Both candidate layouts: (K,) shared columns (recovery, MARK's
    block vs block) and (C, K) per-row columns (MARK's buffers)."""
    e = _euler(kind, n_real, n, seed)
    rng = np.random.default_rng(seed + 100)
    c, k = 6, 9
    xs, ys = (jnp.asarray(rng.integers(0, n, c), jnp.int32)
              for _ in range(2))
    for shape in ((k,), (c, k)):
        cu, cv = (jnp.asarray(rng.integers(0, n, shape), jnp.int32)
                  for _ in range(2))
        cb = jnp.asarray(rng.integers(-1, 6, shape), jnp.int32)
        got = ball_pair_table(e, xs, ys, cu, cv, cb)
        assert got.shape == (c, k)
        assert np.array_equal(np.asarray(got),
                              np.asarray(_ref_cover(e, xs, ys, cu, cv, cb)))


def test_ball_pair_table_euler_vmap_lanes_bit_identical():
    n, c, k = 12, 4, 7
    sizes = (5, 12, 2)
    trees = [_tree("random", s, n, seed=i) for i, s in enumerate(sizes)]
    e = jax.vmap(lambda p, d: build_euler(p, d, jnp.int32(0), n))(
        jnp.asarray(np.stack([p for p, _ in trees])),
        jnp.asarray(np.stack([d for _, d in trees])))
    rng = np.random.default_rng(7)
    B = len(sizes)
    xs, ys = (jnp.asarray(rng.integers(0, n, (B, c)), jnp.int32)
              for _ in range(2))
    cu, cv = (jnp.asarray(rng.integers(0, n, (B, k)), jnp.int32)
              for _ in range(2))
    cb = jnp.asarray(rng.integers(-1, 5, (B, k)), jnp.int32)
    got = jax.vmap(ball_pair_table)(e, xs, ys, cu, cv, cb)
    ref = jax.vmap(_ref_cover)(e, xs, ys, cu, cv, cb)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("kind,n_real,n,seed", [
    ("random", 9, 9, 0), ("random", 17, 17, 5), ("chain", 6, 6, 0),
    ("star", 8, 8, 0), ("random", 5, 11, 2), ("random", 1, 3, 0),
])
def test_dmin_is_brute_force_range_minimum(kind, n_real, n, seed):
    e = _euler(kind, n_real, n, seed)
    dseq = np.asarray(e.dseq)
    dmin = np.asarray(e.dmin)
    P = dseq.shape[0]
    assert dmin.shape == np.asarray(e.table).shape == (dmin.shape[0], P)
    assert np.array_equal(dmin[0], dseq)
    for k in range(dmin.shape[0]):
        for i in range(P):
            assert dmin[k, i] == dseq[i:min(i + (1 << k), P)].min()
    # every query range [l, r] from its two covering cells
    for l in range(P):
        for r in range(l, P):
            k = (r - l + 1).bit_length() - 1
            got = min(dmin[k, l], dmin[k, r + 1 - (1 << k)])
            assert got == dseq[l:r + 1].min()


def test_dmin_matches_position_table_values():
    """dmin[k][i] is the depth at the position table's argmin."""
    tour = jnp.asarray([0, 1, 2, 1, 0, 3, 0, 0, 0], jnp.int32)
    depth = jnp.asarray([0, 1, 2, 1, INF], jnp.int32)
    e = tables_from_tour(tour, jnp.int32(6), depth, 5)
    assert np.array_equal(np.asarray(e.dmin),
                          np.asarray(e.dseq)[np.asarray(e.table)])


@pytest.mark.parametrize("c,k,per_row", [
    (32, 1056, False),   # recovery: C block slots, b_cap + C columns
    (64, 32, True),      # MARK block vs per-row buffers
    (64, 64, False),     # MARK block vs block
])
def test_cover_table_has_two_per_pair_gathers(c, k, per_row):
    """Structural guard: on the Euler path only the two `dmin` reads are
    per (row, column) pair; endpoint lookups stay O(C + K) (C·K for
    per-row columns), never 4·C·K."""
    n = 64
    e = _euler("random", n, n, 0)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    cols = i32(c, k) if per_row else i32(k)

    closed = jax.make_jaxpr(ball_pair_table)(e, i32(c), i32(c), cols,
                                             cols, cols)
    sizes = [int(np.prod(o.aval.shape))
             for q in collect_eqns(closed) if q.primitive.name == "gather"
             for o in q.outvars]
    assert sizes.count(4 * c * k) == 2
    assert max(sizes) == 4 * c * k
