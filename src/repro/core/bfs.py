"""BFS in JAX (TPU adaptation of LGRASS §4.4).

The paper's parallel BFS uses concurrent queues + atomics on a CPU;
there is no TPU analogue for dynamic work lists. The dense engine here
is hop-doubling (``bfs_doubling``): each round fuses an edge-parallel
Bellman–Ford relaxation with pointer doubling over the tentative-depth
forest, so depth information jumps 2^k-length chains per round instead
of one hop. Three pointer families carry the doubling; the loop runs to
the relaxation fixpoint, which is reached in O(log n) rounds on
chain-like inputs, never takes more rounds than the diameter, and is
*provably exact* on every input: tentative depths are always upper
bounds on the true BFS depth, and any relaxation fixpoint of upper
bounds equals the true depth. The deterministic smallest-id parent is
derived afterwards in ONE edge-parallel pass — exact depths uniquely
determine the parent under the shared rule (parent = smallest-id
neighbour one level up), so depth AND parent equal the numpy oracle's
level-synchronous BFS bit for bit (tests/test_bfs_doubling.py).

For the *tree-restricted* second pass of the pipeline no fixpoint
iteration is needed at all: ``root_tree_euler`` roots the spanning tree
in a fixed O(log n)-round program by materialising the Euler tour
directly from the undirected tree edge list (per-arc successor pointers
+ pointer-doubling list ranking, the same machinery as
``lca.build_euler``) and reading depths off a prefix sum over the tour.

Both thread the optional edge mask, never index with booleans, and keep
every shape static — safe under jit AND vmap (the padded ``GraphBatch``
pipeline).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.pow2 import log2_ceil as _log2_ceil

INF = jnp.iinfo(jnp.int32).max


def packed_key_bound(n: int) -> int:
    """Largest packed relaxation key `bfs_doubling` can produce at `n`.

    The fused scatter-min key is dist·(n+1) + id with dist clamped to
    [0, n] and id in [0, n]; the maximum is n·(n+1) + n = (n+1)² − 1.
    This is the symbolic bound the static range checker
    (repro.analysis.ranges) re-derives from the traced program.
    """
    return (n + 1) * (n + 1) - 1


# Largest n for which the packed key provably fits int32:
# (n+1)² − 1 <= INT32_MAX  <=>  n <= isqrt(2³¹) − 1  ==  46339.
# Beyond this the relaxation runs unpacked as two scatter-mins
# (bit-identical, one extra scatter per round). Exported so the range
# checker asserts the switch point instead of trusting an inlined magic
# number; tests/test_bfs_doubling.py pins both sides of the boundary.
PACKED_KEY_MAX_N = math.isqrt(2 ** 31) - 1

# Largest n for which `root_tree_euler` can pack an arc's (tail, head)
# pair into one u32 radix key (16 bits each); beyond it the u64 pair
# sort runs instead. Same contract: exported for the range checker.
EULER_PACK_MAX_N = 0xFFFF


def finite_depth(depth: jax.Array) -> jax.Array:
    """Clamp unreachable (INF) BFS depths to 0.

    The single guard every consumer of raw BFS depths goes through:
    INT32_MAX cast to float32 is ≈2.1e9 and silently poisons any
    arithmetic it touches (effective weights, depth-bound estimates).
    Disconnected inputs are legal for the BFS stage, so the clamp lives
    here, once, instead of ad hoc at call sites.
    """
    return jnp.where(depth == INF, 0, depth)


@functools.partial(jax.jit, static_argnames=("n",))
def bfs_doubling(
    u: jax.Array,
    v: jax.Array,
    n: int,
    root: jax.Array,
    edge_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Hop-doubling BFS over the undirected edge list from `root`.

    Returns depth (n,) int32 (INF for unreachable) and parent (n,)
    int32 (-1 for root / unreachable). `edge_mask`: optional (L,) bool,
    True edges participate (a tree-restricted BFS; padding edges in the
    batched pipeline).

    State: tentative depths ``dist`` (INF = not yet bounded) plus three
    pointer families over the tentative-parent forest, each carrying a
    walk-length offset so a pull ``dist[v] <- dist[p[v]] + off[v]`` is
    always a valid upper bound (a real walk exists, or the offset has
    been clamped to n, which also upper-bounds every true depth):

      * two *static monotone chains* — every node points at its
        smallest-id and largest-id neighbour; squaring them each round
        makes the chains jump 2^k hops, which is what carries depth
        information across O(n)-diameter stretches in O(log n) rounds
        (the reach mechanism; on feeder inputs node ids follow the
        chain, so the two directions cover both sides of the root);
      * a *re-anchored climb* — each round the tentative-parent forest
        (every node points at its minimum-dist neighbour) is rebuilt
        from the current bounds and climbed with log n unrolled
        doubling steps. Where bounds carry a locally uniform error the
        chain's hop count telescopes to the exact bound difference, so
        whole regions snap to the exact depth the round after their
        chain first touches an exact node (the correction mechanism —
        this is what makes arbitrary-id inputs converge fast too).

    Every candidate ever written is ≥ the true depth (walk lengths, or
    the clamp n ≥ depth+1), so at the relaxation fixpoint — the loop
    exit — ``dist`` *equals* the true BFS depth: standard Bellman–Ford
    induction along shortest paths. Rounds are additionally bounded by
    the diameter (relaxation alone fixes level k by round k), so the
    engine never runs more rounds than a level-synchronous BFS; on
    chain-like inputs it runs O(log n). All values stay in
    [0, n] ∪ {INF}: int32-safe.

    Per-round cost is kept to ONE scatter: the relaxation minimum and
    the climb's re-anchor witness come out of a single scatter-min of
    the packed key dist[u]·(n+1) + u (dist is clamped to ≤ n, so the
    key fits int32 up to n = PACKED_KEY_MAX_N; beyond that the same
    pass runs unpacked as two scatter-mins). The climb is truncated to ~0.6·log n
    steps — correction jumps of 2^0.6·log ≫ the per-round reach growth,
    measured faster at every size with identical convergence.

    The parent is derived after the loop in one edge-parallel pass:
    parent[v] = smallest-id neighbour u with depth[u] == depth[v] - 1 —
    exactly the level-sync rule, evaluated on exact depths.
    """
    return bfs_counted(u, v, n, root, edge_mask)[:2]


def bfs_counted(u, v, n, root, edge_mask=None):
    """`bfs_doubling` plus the int32 number of rounds its while loop
    ran."""
    src = jnp.concatenate([u, v])
    dst = jnp.concatenate([v, u])
    if edge_mask is not None:
        emask = jnp.concatenate([edge_mask, edge_mask])
    else:
        emask = jnp.ones_like(src, dtype=bool)
    iota = jnp.arange(n, dtype=jnp.int32)
    nn = jnp.int32(n)
    log = _log2_ceil(n + 1)
    climb_len = max(2, (3 * log) // 5)
    packed = n <= PACKED_KEY_MAX_N  # packed_key_bound(n) fits int32
    base = jnp.int32(n + 1)
    KINF = jnp.iinfo(jnp.int32).max

    # static monotone chains: smallest- / largest-id neighbour
    lo_nbr = jnp.full((n,), INF, jnp.int32).at[dst].min(
        jnp.where(emask, src, INF)
    )
    hi_nbr = jnp.full((n,), -1, jnp.int32).at[dst].max(
        jnp.where(emask, src, -1)
    )
    has_lo = lo_nbr != INF
    fallback = jnp.where(has_lo, lo_nbr, iota)
    pl0 = fallback
    ol0 = jnp.where(pl0 != iota, 1, 0).astype(jnp.int32)
    pr0 = jnp.where(hi_nbr >= 0, hi_nbr, iota)
    or0 = jnp.where(pr0 != iota, 1, 0).astype(jnp.int32)
    dist0 = jnp.full((n,), INF, jnp.int32).at[root].set(0)

    def pull(dist, p, o):
        c = jnp.where(dist[p] < INF, jnp.minimum(dist[p] + o, nn), INF)
        return jnp.minimum(dist, c)

    def relax_witness(dist):
        """(min-neighbour dist, smallest-id argmin) in one scatter."""
        if packed:
            key = jnp.where(emask & (dist[src] < INF),
                            dist[src] * base + src, KINF)
            kmin = jnp.full((n,), KINF, jnp.int32).at[dst].min(key)
            has = kmin < KINF
            mnb = jnp.where(has, kmin // base, INF)
            wit = jnp.where(has, kmin % base, n)
            return mnb, wit
        mnb = jnp.full((n,), INF, jnp.int32).at[dst].min(
            jnp.where(emask, dist[src], INF))
        wit = jnp.full((n,), n, jnp.int32).at[dst].min(
            jnp.where(emask & (dist[src] == mnb[dst]), src, n))
        wit = jnp.where(mnb < INF, wit, n)
        return mnb, wit

    def body(state):
        dist, pl, ol, pr, orr, _, rounds = state
        d_in = dist
        # edge-parallel relaxation + climb re-anchor, one scatter-min
        mnb, wit = relax_witness(dist)
        dist = jnp.minimum(dist, jnp.where(mnb < INF,
                                           jnp.minimum(mnb + 1, nn), INF))
        # static chains: pull, then square the pointers
        dist = pull(dist, pl, ol)
        dist = pull(dist, pr, orr)
        ol = jnp.minimum(ol + ol[pl], nn)
        pl = pl[pl]
        orr = jnp.minimum(orr + orr[pr], nn)
        pr = pr[pr]
        # re-anchored climb over the tentative-parent forest
        ptc = jnp.where(wit < n, wit, fallback)
        ptc = jnp.where(iota == root, root, ptc)
        jmp = ptc
        joff = jnp.where(jmp != iota, 1, 0).astype(jnp.int32)
        for _ in range(climb_len):
            dist = pull(dist, jmp, joff)
            joff = jnp.minimum(joff + joff[jmp], nn)
            jmp = jmp[jmp]
        return dist, pl, ol, pr, orr, jnp.any(dist != d_in), rounds + 1

    def cond(state):
        return state[-2]

    dist, *_, rounds = jax.lax.while_loop(
        cond, body,
        (dist0, pl0, ol0, pr0, or0, jnp.bool_(True), jnp.int32(0))
    )

    # one edge-parallel pass: smallest-id neighbour one level up
    prev = emask & (dist[src] < INF) & (dist[dst] < INF) \
        & (dist[src] + 1 == dist[dst])
    cand = jnp.full((n,), INF, jnp.int32).at[dst].min(
        jnp.where(prev, src, INF)
    )
    parent = jnp.where((dist > 0) & (dist < INF) & (cand < INF), cand, -1)
    return dist, parent.astype(jnp.int32), rounds


def _euler_tables(tour: jax.Array, T: jax.Array, depth: jax.Array,
                  n: int):
    """`lca.tables_from_tour` — the ONE definition of the table layout
    `lca_euler` queries, shared with `build_euler` (local import only to
    keep bfs.py importable without the lca module at module load)."""
    from repro.core.lca import tables_from_tour

    return tables_from_tour(tour, T, depth, n)


@functools.partial(jax.jit, static_argnames=("n",))
def root_tree_euler(
    u: jax.Array,
    v: jax.Array,
    n: int,
    root: jax.Array,
    tree_mask: jax.Array,
):
    """Root the spanning tree at `root` in O(log n) rounds — no BFS.

    Returns (depth, parent, euler) with (depth, parent) bit-identical
    to ``bfs_doubling(u, v, n, root, edge_mask=tree_mask)``: in a tree
    the depth is unique and each non-root node has exactly one neighbour
    one level up, so the smallest-id parent rule is vacuous — rooting IS
    the answer. The construction materialises the Euler tour straight
    from the undirected edge list (``lca.build_euler`` starts from
    parent pointers, which is exactly what we don't have yet):

      1. arcs: edge i yields ``i`` (u→v) and ``L+i`` (v→u); sort arcs
         by (tail, head) so each node's out-arcs form one sorted block
         (one u32 radix key when ids fit 16 bits, the u64 pair sort
         otherwise);
      2. successor pointers: succ(x→y) = the arc after (y→x) in y's
         block, circular — the classic Euler-circuit rule; the arc that
         would close the circuit back to the root's first out-arc is
         made a self-loop terminator instead;
      3. pointer-doubling list ranking over the 2L arc slots gives each
         tour arc its rank (and membership: only arcs in the root's
         component reach the terminator — a padded or disconnected
         forest is toured exactly as far as level-sync BFS would walk);
      4. depth = prefix sum of +1 (down-arc) / −1 (up-arc) over the
         ranked tour; a down arc (x→y) is one with rank < its reversal
         and assigns parent[y] = x.

    The already-ranked tour also becomes the `lca.EulerLCA` sparse
    tables (`_euler_tables`) — the pipeline's O(1)-LCA backend without a second tour construction. (The tour
    enters each node's children after-the-parent circularly instead of
    build_euler's from-the-smallest; both are valid Euler tours, and
    every LCA/distance query answers identically — the range minimum
    between two first occurrences is the unique LCA node either way.)

    Everything is sort/gather/scatter with static shapes — vmap-safe
    for the padded batched pipeline (tree_mask already excludes padding
    edges, so padded slots sort to the invalid tail).
    """
    from repro.core.sort import radix_argsort_u32, radix_argsort_u64pair

    L = u.shape[0]
    depth0 = jnp.full((n,), INF, jnp.int32).at[root].set(0)
    parent0 = jnp.full((n,), -1, jnp.int32)
    if L == 0:
        tour0 = jnp.zeros((2 * n - 1,), jnp.int32).at[0].set(root)
        return depth0, parent0, _euler_tables(tour0, jnp.int32(0), depth0,
                                              n)
    A = 2 * L
    aiota = jnp.arange(A, dtype=jnp.int32)
    tail = jnp.concatenate([u, v]).astype(jnp.int32)
    head = jnp.concatenate([v, u]).astype(jnp.int32)
    valid = jnp.concatenate([tree_mask, tree_mask])
    rev = jnp.where(aiota < L, aiota + L, aiota - L)

    # -- 1. sorted out-arc blocks ---------------------------------------
    if n <= EULER_PACK_MAX_N:  # (tail, head) packs into one u32 key
        key = (tail.astype(jnp.uint32) << 16) | head.astype(jnp.uint32)
        S = radix_argsort_u32(jnp.where(valid, key,
                                        jnp.uint32(0xFFFFFFFF)))
    else:
        hi = jnp.where(valid, tail.astype(jnp.uint32),
                       jnp.uint32(0xFFFFFFFF))
        S = radix_argsort_u64pair(hi, head.astype(jnp.uint32))
    pos = jnp.zeros((A,), jnp.int32).at[S].set(aiota)
    st = jnp.where(valid[S], tail[S], -1)
    is_first = valid[S] & ((aiota == 0) | (st != jnp.roll(st, 1)))
    is_last = valid[S] & ((aiota == A - 1) | (st != jnp.roll(st, -1)))
    stc = jnp.clip(st, 0, n - 1)
    start_pos = jnp.zeros((n,), jnp.int32).at[
        jnp.where(is_first, stc, n)].set(aiota, mode="drop")
    first_arc = jnp.full((n,), -1, jnp.int32).at[
        jnp.where(is_first, stc, n)].set(S, mode="drop")

    # -- 2. successor pointers + terminator -----------------------------
    succ_pos = jnp.where(is_last, start_pos[stc],
                         jnp.minimum(aiota + 1, A - 1))
    succ = jnp.where(valid, S[succ_pos[pos[rev]]], aiota)
    s0 = first_arc[root]          # root's first out-arc (-1: bare root)
    has_tour = s0 >= 0
    is_term = valid & (succ == s0) & has_tour
    term = jnp.argmax(is_term).astype(jnp.int32)
    succ = jnp.where(is_term, aiota, succ)

    # -- 3. list ranking by pointer doubling ----------------------------
    d = jnp.where(succ != aiota, 1, 0).astype(jnp.int32)
    nxt = succ
    for _ in range(_log2_ceil(A) + 1):
        d = d + d[nxt]
        nxt = nxt[nxt]
    in_tour = has_tour & valid & (nxt == term)
    T = jnp.where(has_tour, d[jnp.maximum(s0, 0)] + 1, 0)
    rank = T - 1 - d  # rank(s0) == 0, rank(term) == T - 1

    # -- 4. depth prefix sum + parents ----------------------------------
    down = in_tour & (d > d[rev])
    seq = jnp.zeros((A,), jnp.int32).at[
        jnp.where(in_tour, rank, A)].set(
        jnp.where(down, 1, -1), mode="drop")
    csum = jnp.cumsum(seq)
    hsafe = jnp.where(down, head, n)
    parent = parent0.at[hsafe].set(tail, mode="drop")
    depth = depth0.at[hsafe].set(
        csum[jnp.clip(rank, 0, A - 1)], mode="drop")
    # arc of rank r contributes its head at tour position r + 1
    P = 2 * n - 1
    wpos = jnp.where(in_tour, jnp.minimum(rank + 1, P), P)
    tour = (jnp.zeros((P,), jnp.int32).at[0].set(root)
            .at[wpos].set(head, mode="drop"))
    return depth, parent, _euler_tables(tour, T, depth, n)


@functools.partial(jax.jit, static_argnames=("n",))
def degrees(
    u: jax.Array, v: jax.Array, n: int, edge_valid: Optional[jax.Array] = None
) -> jax.Array:
    one = (
        jnp.ones_like(u)
        if edge_valid is None
        else edge_valid.astype(jnp.int32)
    )
    deg = jnp.zeros((n,), dtype=jnp.int32)
    deg = deg.at[u].add(one)
    deg = deg.at[v].add(one)
    return deg


@functools.partial(jax.jit, static_argnames=("n",))
def select_root(
    u: jax.Array, v: jax.Array, n: int, edge_valid: Optional[jax.Array] = None
) -> jax.Array:
    """Max-degree node, ties -> smallest id (matches Graph.root()).

    edge_valid: optional (L,) padding mask — padding edges contribute no
    degree, so padded nodes (degree 0) can never win against any node of
    the real, connected graph.
    """
    deg = degrees(u, v, n, edge_valid)
    return jnp.argmax(deg).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n",))
def effective_weights(
    u: jax.Array,
    v: jax.Array,
    w: jax.Array,
    depth: jax.Array,
    n: int,
    edge_valid: Optional[jax.Array] = None,
) -> jax.Array:
    """feGRASS-style depth-scaled effective weight (the EFF subroutine).

    eff(e) = w(e) * (depth[u] + depth[v] + 1). Any fixed monotone
    combination works for the pipeline; this one is shared with the
    oracle. Unreachable (INF) depths are clamped to 0 first — on a
    disconnected input the raw INT32_MAX would cast to float32 ≈ 2.1e9
    and poison every weight it touches (`finite_depth`; the numpy
    mirror applies the same guard).

    edge_valid: optional (L,) padding mask — padding slots are zeroed
    so their (garbage-endpoint) gathers can never leak a value out.
    Downstream consumers mask again (the criticality sort forces
    invalid keys to -inf), so threading the mask here changes no real
    slot.
    """
    d = finite_depth(depth).astype(jnp.float32)
    eff = w * (d[u] + d[v] + 1.0)
    if edge_valid is not None:
        eff = jnp.where(edge_valid, eff, 0.0)
    return eff
