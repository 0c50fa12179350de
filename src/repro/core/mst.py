"""Maximum spanning tree via Borůvka in JAX (the MST subroutine).

The baseline program computes the spanning tree sequentially (Kruskal
over sorted effective weights). Borůvka is the parallel-native choice:
every round each component picks its best incident inter-component edge
with one segmented min — a pure scatter-min over the edge list — then
components contract by pointer jumping. O(log N) rounds of O(L) work,
all fully vectorised (the TPU adaptation of sequential union-find, whose
pointer chasing does not vectorise).

Edges are compared by a precomputed *rank* (position in the
(eff-weight desc, edge-id asc) total order, from `sort.sort_f32_desc_stable`).
Because the order is total, the maximum spanning tree is unique, and
Borůvka and Kruskal provably return the same edge set — the python oracle
uses Kruskal, tests assert equality.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

INF = jnp.iinfo(jnp.int32).max


@functools.partial(jax.jit, static_argnames=("n",))
def boruvka_mst(
    u: jax.Array,
    v: jax.Array,
    rank: jax.Array,
    n: int,
    edge_valid: jax.Array | None = None,
) -> jax.Array:
    """Returns (L,) bool mask of spanning-tree edges.

    rank: (L,) int32, a total order (0 = best edge). The tree minimises
    total rank, i.e. maximises effective weight under our ordering.

    edge_valid: optional (L,) bool padding mask (batched pipeline) —
    padding edges are never inter-component candidates, so they can never
    enter the tree, and the termination test ignores them.
    """
    return boruvka_mst_counted(u, v, rank, n, edge_valid)[0]


def boruvka_mst_counted(u, v, rank, n, edge_valid=None):
    """`boruvka_mst` plus two int32 round counts: the Borůvka rounds, and
    the pointer-jumping rounds of their contractions, summed."""
    if edge_valid is None:
        edge_valid = jnp.ones_like(u, dtype=bool)

    def pointer_jump(ptr, jumps):
        def cond(state):
            p, _ = state
            return jnp.any(p[p] != p)

        def body(state):
            p, k = state
            return p[p], k + 1

        return jax.lax.while_loop(cond, body, (ptr, jumps))

    def round_cond(state):
        comp = state[0]
        return jnp.any((comp[u] != comp[v]) & edge_valid)

    def round_body(state):
        comp, tree_mask, rounds, jumps = state
        cu, cv = comp[u], comp[v]
        inter = (cu != cv) & edge_valid
        key = jnp.where(inter, rank, INF)
        best = jnp.full((n,), INF, dtype=jnp.int32)
        best = best.at[cu].min(key)
        best = best.at[cv].min(key)
        chosen = inter & ((rank == best[cu]) | (rank == best[cv]))
        tree_mask = tree_mask | chosen
        # hook: each component points to the smallest neighbouring component
        ptr = jnp.arange(n, dtype=jnp.int32)
        ptr = ptr.at[cu].min(jnp.where(chosen, cv, INF))
        ptr = ptr.at[cv].min(jnp.where(chosen, cu, INF))
        ptr = jnp.minimum(ptr, jnp.arange(n, dtype=jnp.int32))
        # break mutual 2-cycles deterministically (smaller id wins)
        ids = jnp.arange(n, dtype=jnp.int32)
        mutual = (ptr[ptr] == ids) & (ptr != ids)
        ptr = jnp.where(mutual & (ids < ptr), ids, ptr)
        ptr, jumps = pointer_jump(ptr, jumps)
        return ptr[comp], tree_mask, rounds + 1, jumps

    comp0 = jnp.arange(n, dtype=jnp.int32)
    mask0 = jnp.zeros_like(u, dtype=bool)
    zero = jnp.int32(0)
    _, tree_mask, rounds, jumps = jax.lax.while_loop(
        round_cond, round_body, (comp0, mask0, zero, zero))
    return tree_mask, rounds, jumps


def kruskal_mst_numpy(u, v, rank, n):
    """Host Kruskal on the same total order — oracle / test reference."""
    import numpy as np

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
