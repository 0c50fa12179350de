"""Batched LCA via binary lifting (TPU adaptation of LGRASS §3.2/§4.3).

The paper uses an online sequential LCA (Schieber–Vishkin flavoured) plus
the root-subtree shortcut. A sequential O(1)-per-query LCA is the wrong
shape for a TPU; the data-parallel equivalent is binary lifting — all L
queries are answered simultaneously with O(log depth) gathers each, which
is a handful of fully-vectorised rounds over (L,) arrays. The paper's
root-subtree shortcut *is* kept: queries whose endpoints live in different
root subtrees return `root` without climbing (`subroot` below), which in
the IPCC inputs answers the majority of queries in O(1).

Tables are (LOG, n) int32 in HBM; every query round is a gather — exactly
the access pattern TPUs stream well.

Two query engines live here:

  * binary lifting (`LiftingTables`, `lca`) — O(log depth) gathers per
    query, cheap O(n log n) construction (one scan).
  * Euler tour + sparse-table RMQ (`EulerLCA`, `lca_euler`) — O(1)
    gathers per query after an O(n log n) device-side construction: the
    tour is derived from per-arc successor pointers ranked by pointer
    doubling (the classic list-ranking formulation, fully vectorised),
    and range-minimum queries over the tour's depth sequence answer LCA
    with two sparse-table gathers. Worth building once per graph when a
    stage issues many batched distance queries (the chunked phase-1
    marking scheduler's cover tables). A distance needs only the LCA's
    depth, so a second table of depth minima (`dmin`) answers it with
    two gathers per pair once the endpoints' `first`/`depth` are known
    (`euler_endpoints`, `euler_distance`).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.pow2 import log2_ceil as _log2_ceil


class LiftingTables(NamedTuple):
    up: jax.Array     # (LOG, n) int32 — 2^k-th ancestor (root loops to itself)
    depth: jax.Array  # (n,) int32


@functools.partial(jax.jit, static_argnames=("n", "levels"))
def build_lifting(parent: jax.Array, depth: jax.Array, n: int,
                  levels: int | None = None) -> LiftingTables:
    """levels: optional depth bound — must satisfy 2^levels > max(depth).
    The default ceil(log2(n+1)) is always safe; a measured bound shrinks
    every LCA climb proportionally (§Perf 'lift_bound': tree depth of the
    power-grid/random cases is O(sqrt N)/O(log N), far below N)."""
    log = levels if levels is not None else _log2_ceil(n + 1)
    up0 = jnp.where(parent < 0, jnp.arange(n, dtype=jnp.int32), parent)

    def step(carry, _):
        nxt = carry[carry]
        return nxt, carry

    _, ups = jax.lax.scan(step, up0, None, length=log)
    return LiftingTables(up=ups, depth=depth)


@jax.jit
def kth_ancestor(t: LiftingTables, node: jax.Array, k: jax.Array) -> jax.Array:
    """Vectorised: ancestor `k` hops above `node` (clamped at root).

    The climb is unrolled over the (static) level count: every `up[i]`
    is a static slice, so XLA sees LOG plain gathers instead of a loop
    of dynamic-slice + gather — ~3x faster on gather-bound backends and
    bit-identical.
    """
    log = t.up.shape[0]
    cur = node
    for i in range(log):
        bit = (k >> i) & 1
        cur = jnp.where(bit == 1, t.up[i][cur], cur)
    return cur


@jax.jit
def lca(t: LiftingTables, a: jax.Array, b: jax.Array) -> jax.Array:
    """Vectorised LCA for query arrays a, b (same shape)."""
    log = t.up.shape[0]
    da, db = t.depth[a], t.depth[b]
    # lift the deeper endpoint
    a2 = kth_ancestor(t, a, jnp.maximum(da - db, 0))
    b2 = kth_ancestor(t, b, jnp.maximum(db - da, 0))
    for i in range(log):
        k = log - 1 - i
        ua, ub = t.up[k][a2], t.up[k][b2]
        jump = (a2 != b2) & (ua != ub)
        a2 = jnp.where(jump, ua, a2)
        b2 = jnp.where(jump, ub, b2)
    return jnp.where(a2 == b2, a2, t.up[0][a2])


@jax.jit
def tree_distance(t: LiftingTables, a: jax.Array, b: jax.Array) -> jax.Array:
    w = lca(t, a, b)
    return t.depth[a] + t.depth[b] - 2 * t.depth[w]


@jax.jit
def tree_distance_with_lca(
    t: LiftingTables, a: jax.Array, b: jax.Array, w: jax.Array
) -> jax.Array:
    """Distance when the LCA is already known (saves the climb)."""
    return t.depth[a] + t.depth[b] - 2 * t.depth[w]


@jax.jit
def subroot(t: LiftingTables, node: jax.Array) -> jax.Array:
    """Ancestor at depth 1 (the root-subtree id); root maps to itself.

    This implements the paper's LCA shortcut: two nodes in different root
    subtrees have LCA == root, no climb needed.
    """
    d = t.depth[node]
    return kth_ancestor(t, node, jnp.maximum(d - 1, 0))


@jax.jit
def lca_with_shortcut(
    t: LiftingTables, root: jax.Array, a: jax.Array, b: jax.Array
) -> jax.Array:
    """LGRASS §3.2: if a, b sit in different root subtrees, LCA = root."""
    sa, sb = subroot(t, a), subroot(t, b)
    different = sa != sb
    full = lca(t, a, b)
    return jnp.where(different, root, full)


class EulerLCA(NamedTuple):
    """Euler tour + sparse-table RMQ — O(1) gathers per LCA query.

    Sized for a tree over <= n nodes: P = 2n - 1 tour positions. With a
    padded node range (batched pipeline) only the reachable tree is
    toured; trailing positions carry INT32_MAX depth so range minima
    never select them.
    """

    tour: jax.Array   # (P,) int32 — node at each tour position
    dseq: jax.Array   # (P,) int32 — depth along the tour (INF past the end)
    first: jax.Array  # (n,) int32 — first tour position of each node
    table: jax.Array  # (LOGP, P) int32 — position of the depth min in
    #                   [i, i + 2^k) (clamped at the tour end)
    depth: jax.Array  # (n,) int32 — node depths (distance arithmetic)
    dmin: jax.Array   # (LOGP, P) int32 — the depth min itself over the
    #                   same ranges: dseq[table[k][i]]


def tables_from_tour(tour: jax.Array, T: jax.Array, depth: jax.Array,
                     n: int) -> EulerLCA:
    """EulerLCA tables from an already-materialised tour.

    `tour` is the (P = 2n-1,) node sequence with positions 0..T real
    (T = tour length - 1); any valid Euler tour of the (sub)tree works —
    the range minimum between two first occurrences is the unique LCA
    node regardless of child visit order. Shared by `build_euler` and
    `bfs.root_tree_euler`, so there is exactly ONE definition of the
    table layout `lca_euler` queries.
    """
    P = 2 * n - 1
    INF = jnp.iinfo(jnp.int32).max
    piota = jnp.arange(P, dtype=jnp.int32)
    real = piota <= T  # positions 0..T hold the tour (length T + 1)
    dseq = jnp.where(real, depth[tour], INF)
    first = jnp.full((n,), P - 1, jnp.int32).at[
        jnp.where(real, tour, n)].min(piota, mode="drop")
    tabs = [piota]
    mins = [dseq]
    for k in range(1, _log2_ceil(P) + 1 if P > 1 else 1):
        h = 1 << (k - 1)
        prev = tabs[-1]
        other = prev[jnp.minimum(piota + h, P - 1)]
        tabs.append(jnp.where(dseq[other] < dseq[prev], other, prev))
        # the same recurrence on values, from a static shifted slice
        # (h < P always): no gather
        m = mins[-1]
        shifted = jnp.concatenate([m[h:], jnp.broadcast_to(m[-1:], (h,))])
        mins.append(jnp.minimum(m, shifted))
    return EulerLCA(tour=tour, dseq=dseq, first=first,
                    table=jnp.stack(tabs), depth=depth,
                    dmin=jnp.stack(mins))


@functools.partial(jax.jit, static_argnames=("n",))
def build_euler(parent: jax.Array, depth: jax.Array, root: jax.Array,
                n: int) -> EulerLCA:
    """Build the Euler-tour LCA tables on device.

    parent/depth: tree BFS outputs ((n,) int32, parent < 0 for the root
    and for unreachable padding nodes — only the reachable tree is
    toured). The tour is the node sequence of a DFS that orders children
    by ascending id; it is materialised without any sequential DFS:

      1. per-arc successor pointers (enter-first-child / advance-to-next-
         sibling / retreat-to-parent) from two scatter passes over the
         (parent, id)-sorted child list,
      2. arc positions by pointer-doubling list ranking (log rounds of
         gathers over the 2n arc slots),
      3. one scatter builds the node sequence; a scatter-min gives each
         node's first occurrence,
      4. a sparse table of range-depth-min positions over the tour.
    """
    from repro.core.sort import radix_argsort_u64pair

    P = 2 * n - 1
    INF = jnp.iinfo(jnp.int32).max
    nodes = jnp.arange(n, dtype=jnp.int32)
    valid_c = parent >= 0

    # -- 1. successor pointers ------------------------------------------
    # children sorted by (parent, id); invalid entries sort last
    hi = jnp.where(valid_c, parent.astype(jnp.uint32),
                   jnp.uint32(0xFFFFFFFF))
    S = radix_argsort_u64pair(hi, nodes.astype(jnp.uint32))
    Sv = valid_c[S]
    Sp = jnp.where(Sv, parent[S], -1)
    is_first = Sv & ((nodes == 0) | (Sp != jnp.roll(Sp, 1)))
    first_child = jnp.full((n,), -1, jnp.int32).at[
        jnp.where(is_first, Sp, n)].set(S, mode="drop")
    has_next = (nodes < n - 1) & Sv & (Sp == jnp.roll(Sp, -1))
    next_sib = jnp.full((n,), -1, jnp.int32).at[
        jnp.where(has_next, S, n)].set(jnp.roll(S, -1), mode="drop")

    # arc ids: down-arc of c (parent -> c) is c; up-arc (c -> parent) is
    # n + c. After entering c: descend to its first child, else climb
    # back. After leaving c: advance to its next sibling, else keep
    # climbing; the up-arc of the root's last child terminates the tour
    # (successor = itself, the list-ranking sentinel).
    arc_ids = jnp.arange(2 * n, dtype=jnp.int32)
    succ_down = jnp.where(first_child >= 0, first_child, n + nodes)
    at_end = (parent == root) & (next_sib < 0)
    succ_up = jnp.where(
        next_sib >= 0, next_sib,
        jnp.where(at_end, n + nodes, n + jnp.maximum(parent, 0)),
    )
    arc_valid = jnp.concatenate([valid_c, valid_c])
    succ = jnp.where(arc_valid,
                     jnp.concatenate([succ_down, succ_up]), arc_ids)

    # -- 2. list ranking by pointer doubling ----------------------------
    d = jnp.where(succ != arc_ids, 1, 0).astype(jnp.int32)
    nxt = succ
    for _ in range(_log2_ceil(2 * n) + 1):
        d = d + d[nxt]
        nxt = nxt[nxt]
    start = jnp.maximum(first_child[root], 0)  # root's first down-arc
    T = jnp.where(first_child[root] >= 0, d[start] + 1, 0)  # tour arcs
    pos = T - 1 - d  # pos[start] == 0; invalid arcs masked below

    # -- 3. node sequence -----------------------------------------------
    heads = jnp.concatenate([nodes, jnp.maximum(parent, 0)])
    wpos = jnp.where(arc_valid, pos + 1, P)
    tour = (jnp.zeros((P,), jnp.int32).at[0].set(root)
            .at[wpos].set(heads, mode="drop"))

    # -- 4. depth sequence, first occurrences, sparse RMQ table ---------
    return tables_from_tour(tour, T, depth, n)


def _rmq_rows(logp: int, P: int, l: jax.Array, r: jax.Array):
    """Flat indices of the two sparse-table cells covering [l, r]."""
    span = r - l + 1
    # floor(log2(span)) without clz: count the powers of two <= span
    k = jnp.zeros_like(span)
    for j in range(1, logp):
        k = k + (span >= (1 << j)).astype(span.dtype)
    return k * P + l, k * P + (r + 1 - jnp.left_shift(jnp.int32(1), k))


@jax.jit
def lca_euler(e: EulerLCA, a: jax.Array, b: jax.Array) -> jax.Array:
    """Vectorised LCA in O(1) gathers per query (any query shape)."""
    logp, P = e.table.shape
    l = jnp.minimum(e.first[a], e.first[b])
    r = jnp.maximum(e.first[a], e.first[b])
    j1, j2 = _rmq_rows(logp, P, l, r)
    flat = e.table.reshape(-1)
    i1, i2 = flat[j1], flat[j2]
    w = jnp.where(e.dseq[i2] < e.dseq[i1], i2, i1)
    return e.tour[w]


def euler_endpoints(e: EulerLCA, a: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(first tour position, depth) of each node in `a` — the only
    per-endpoint values `euler_distance` needs. Gather them before any
    broadcast to pair shape, so each endpoint is looked up once."""
    return e.first[a], e.depth[a]


def euler_distance(e: EulerLCA, fa: jax.Array, da: jax.Array,
                   fb: jax.Array, db: jax.Array) -> jax.Array:
    """Tree distance from the endpoints' `euler_endpoints` values: two
    `dmin` gathers per pair give the LCA's depth directly.

    The result is bit-identical to depth[a] + depth[b] - 2 * depth[w]
    with w = `lca_euler(a, b)`. For a real tour position dmin holds
    exactly depth[tour[w]]; the one range whose minimum is INF is
    l = r = P - 1 past the tour's end (both endpoints off the tour),
    where the position table answers depth[tour[P - 1]], used here
    as the same scalar. The int32 sum wraps identically for INF depths.
    """
    logp, P = e.dmin.shape
    l = jnp.minimum(fa, fb)
    r = jnp.maximum(fa, fb)
    j1, j2 = _rmq_rows(logp, P, l, r)
    flat = e.dmin.reshape(-1)
    dl = jnp.minimum(flat[j1], flat[j2])
    dl = jnp.where(dl == jnp.iinfo(jnp.int32).max, e.depth[e.tour[P - 1]],
                   dl)
    return da + db - 2 * dl


@jax.jit
def tree_distance_euler(e: EulerLCA, a: jax.Array,
                        b: jax.Array) -> jax.Array:
    return euler_distance(e, *euler_endpoints(e, a), *euler_endpoints(e, b))
