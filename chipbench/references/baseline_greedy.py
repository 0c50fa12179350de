"""Plain reference: the competition's baseline greedy, in numpy.

A copy of `baseline_sparsify` (`src/repro/core/baseline.py`) and the
`_host` helpers and Kruskal oracle it calls, kept with the benchmark so
that a change to the program cannot change what it is judged by. It
imports nothing of the program. `test_chipbench_copies.py` checks that
it still agrees with the program's copy.

Semantics (see `src/repro/core/graph.py` for the full list): root is the
max-degree node; effective weight w * (depth_u + depth_v + 1) from the
graph BFS; maximum spanning tree under (eff desc, id asc); criticality
w * R_tree(u, v); greedy over off-tree edges in (crit desc, id asc)
order, each accept marking the off-tree edges between the two tree
balls of radius beta around its ends, until `budget` accepts.

`precision` rounds every float result: "float32" is the configuration's
own arithmetic; "bfloat16" is the control, the same reference one
precision lower, which the comparison has to refuse.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

INF_I32 = np.iinfo(np.int32).max


def _rounder(precision: str):
    if precision == "float32":
        return lambda x: np.asarray(x).astype(np.float32)
    if precision == "bfloat16":
        return lambda x: np.asarray(x).astype(ml_dtypes.bfloat16).astype(
            np.float32)
    raise ValueError(f"unknown precision {precision!r}")


def _log2_ceil(n: int) -> int:
    k = 1
    while (1 << k) < n:
        k += 1
    return k


def _bfs(u, v, n, root, edge_mask=None):
    """Level-synchronous BFS; a node's parent is its smallest-id
    neighbour in the previous level."""
    src = np.concatenate([u, v])
    dst = np.concatenate([v, u])
    emask = (np.ones_like(src, dtype=bool) if edge_mask is None
             else np.concatenate([edge_mask, edge_mask]))
    depth = np.full(n, INF_I32, np.int32)
    parent = np.full(n, -1, np.int32)
    depth[root] = 0
    frontier = np.zeros(n, bool)
    frontier[root] = True
    level = 0
    while frontier.any():
        active = frontier[src] & emask
        cand = np.full(n, INF_I32, np.int64)
        np.minimum.at(cand, dst[active], src[active])
        newly = (cand != INF_I32) & (depth == INF_I32)
        parent[newly] = cand[newly]
        depth[newly] = level + 1
        frontier = newly
        level += 1
    return depth, parent


def _desc_stable_order(keys_f32):
    """(key desc, index asc) order on the float32 keys' sortable bits."""
    bits = keys_f32.astype(np.float32).view(np.uint32)
    k = np.where(bits >> 31 == 1, ~bits, bits | np.uint32(0x80000000))
    return np.argsort(~k, kind="stable")


def _kruskal_max(u, v, rank, n):
    order = np.argsort(rank, kind="stable")
    parent = list(range(n))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    mask = np.zeros(len(u), dtype=bool)
    cnt = 0
    for e in order:
        a, b = find(int(u[e])), find(int(v[e]))
        if a != b:
            parent[max(a, b)] = min(a, b)
            mask[e] = True
            cnt += 1
            if cnt == n - 1:
                break
    return mask


def _lifting(parent, n):
    up = np.zeros((_log2_ceil(n + 1), n), np.int32)
    up[0] = np.where(parent < 0, np.arange(n, dtype=np.int32), parent)
    for k in range(1, up.shape[0]):
        up[k] = up[k - 1][up[k - 1]]
    return up


def _kth_ancestor(up, node, k):
    cur = np.asarray(node).copy()
    for i in range(up.shape[0]):
        cur = np.where((k >> i) & 1 == 1, up[i][cur], cur)
    return cur


def _lca(up, depth, a, b):
    da, db = depth[a], depth[b]
    a2 = _kth_ancestor(up, a, np.maximum(da - db, 0))
    b2 = _kth_ancestor(up, b, np.maximum(db - da, 0))
    for k in range(up.shape[0] - 1, -1, -1):
        ua, ub = up[k][a2], up[k][b2]
        jump = (a2 != b2) & (ua != ub)
        a2 = np.where(jump, ua, a2)
        b2 = np.where(jump, ub, b2)
    return np.where(a2 == b2, a2, up[0][a2])


def _root_path_sums(up, depth, inv_w, n, q):
    """Resistance from each node to the root, summed in the device's
    doubling order."""
    log = up.shape[0]
    ws = np.zeros((log, n), np.float32)
    ups = np.zeros((log, n), np.int32)
    cur_up = up[0].copy()
    cur_ws = q(inv_w)
    for k in range(log):
        ups[k] = cur_up
        ws[k] = cur_ws
        cur_ws = q(cur_ws + cur_ws[cur_up])
        cur_up = cur_up[cur_up]
    rd = np.zeros(n, np.float32)
    cur = np.arange(n, dtype=np.int32)
    rem = depth.astype(np.int32).copy()
    for k in range(log - 1, -1, -1):
        take = ((rem >> k) & 1) == 1
        rd = q(rd + np.where(take, ws[k][cur], np.float32(0.0)))
        cur = np.where(take, ups[k][cur], cur)
        rem = rem & ~(1 << k)
    return rd


def _ball(adj, center: int, radius: int) -> list:
    seen = {center}
    frontier = [center]
    for _ in range(radius):
        nxt = []
        for x in frontier:
            for y in adj[x]:
                if y not in seen:
                    seen.add(y)
                    nxt.append(y)
        frontier = nxt
        if not frontier:
            break
    return list(seen)


def sparsify(g, budget: int, precision: str = "float32") -> np.ndarray:
    """The sparsifier's edge mask (tree plus accepted off-tree edges)."""
    q = _rounder(precision)
    n = g.n
    u = np.asarray(g.u).astype(np.int64)
    v = np.asarray(g.v).astype(np.int64)
    w = q(g.w)

    deg = np.zeros(n, np.int64)
    np.add.at(deg, u, 1)
    np.add.at(deg, v, 1)
    root = int(np.argmax(deg))

    # EFF: graph BFS depths scale the weights
    depth_g, _ = _bfs(u, v, n, root)
    d = np.where(depth_g == INF_I32, 0, depth_g).astype(np.float32)
    eff = q(w * q(q(d[u] + d[v]) + np.float32(1.0)))

    # MST: maximum spanning tree under (eff desc, id asc)
    order_eff = _desc_stable_order(eff)
    rank_eff = np.empty(len(order_eff), np.int32)
    rank_eff[order_eff] = np.arange(len(order_eff), dtype=np.int32)
    tree_mask = _kruskal_max(u, v, rank_eff, n)

    # tree BFS, lifting tables, LCA
    depth_t, parent_t = _bfs(u, v, n, root, edge_mask=tree_mask)
    up = _lifting(parent_t, n)
    edge_lca = _lca(up, depth_t, u, v)

    # RES: criticality of each edge from root-path resistance sums
    inv_w = np.zeros(n, np.float32)
    for arr_c, arr_p in ((u, v), (v, u)):
        is_child = tree_mask & (parent_t[arr_c] == arr_p)
        inv_w[arr_c[is_child]] = q(np.float32(1.0) / w[is_child])
    rd = _root_path_sums(up, depth_t, inv_w, n, q)
    r = q(q(rd[u] + rd[v]) - q(np.float32(2.0) * rd[edge_lca]))
    crit = q(w * r)
    beta = np.maximum(
        np.minimum(depth_t[u], depth_t[v]) - depth_t[edge_lca], 1)

    # SORT: off-tree edges by (crit desc, id asc)
    offtree = ~tree_mask
    keys = np.where(offtree, crit, np.float32(-np.inf)).astype(np.float32)
    order = _desc_stable_order(keys)[: int(offtree.sum())]

    # MARK: the greedy with ball-pair marking
    adj = [[] for _ in range(n)]
    for c in range(n):
        p = parent_t[c]
        if p >= 0:
            adj[c].append(p)
            adj[p].append(c)
    marked = np.zeros(len(u), bool)
    out = np.zeros(len(u), bool)
    accepted = 0
    for e in order:
        e = int(e)
        if marked[e]:
            continue
        out[e] = True
        accepted += 1
        if accepted == budget:
            break
        m1 = np.zeros(n, bool)
        m2 = np.zeros(n, bool)
        m1[_ball(adj, int(u[e]), int(beta[e]))] = True
        m2[_ball(adj, int(v[e]), int(beta[e]))] = True
        marked |= offtree & ((m1[u] & m2[v]) | (m2[u] & m1[v]))
    return tree_mask | out
