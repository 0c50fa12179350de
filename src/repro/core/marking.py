"""Edge marking — LGRASS §3.1 + §4.2, the paper's core contribution.

The baseline marks edges with an O(N^2 L) triple loop (Alg. 1). LGRASS's
insight is twofold:

  1. *Node* marks instead of *edge* marks (Alg. 2/3): an accepted edge
     (u, v) with ball radius beta covers candidate (x, y) iff x and y lie
     in the paired balls B(u, beta) / B(v, beta).
  2. Crossing edges only interact within the same LCA (Lemma 3.1/3.2), so
     the greedy is partitioned into independent per-LCA subtasks, with
     root-LCA edges further split by their (subtree, subtree) pair — the
     paper's two-step mapping F(u, v) (§4.2).

TPU adaptation: instead of per-thread dynamic task queues we keep a
bounded table of accepted edges per group, (G, K) in HBM, and evaluate the
cover test *analytically* — dist(x, u_j) <= beta_j via batched LCA — which
replaces ball materialisation (pointer chasing) with dense gathers.

`phase1_chunked` processes the sorted slots in blocks of C. Per block,
ONE batched distance query builds the cover table of all block
candidates against (a) each slot's per-group accepted-buffer snapshot
and (b) every other block slot; an arithmetic-only inner lax.scan then
replays the block's accept/reject decisions with pure table lookups (no
per-slot gathers), and the per-(L, K) tables are updated with one
batched scatter per block. Crossing slots occupy a prefix of the sorted
layout, so the outer while_loop runs ceil(n_crossing / C) blocks — the
step count collapses from L to n_crossing / C (pdGRASS's density-aware
batching, mapped from thread queues to lane blocks). Groups are
independent and each block preserves the within-group criticality
order, so every block size gives the numpy oracle's decisions
(tests/test_marking_chunked.py).

Groups whose accepted count exceeds K overflow; the host recovery stage
(recovery.py) re-checks those exactly, so K is a performance knob, never a
correctness knob.

Non-crossing edges are excluded here and replayed in recovery (Alg. 6),
exactly as the paper keeps that stage sequential (Fig. 1c).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.lca import (
    EulerLCA,
    LiftingTables,
    euler_distance,
    euler_endpoints,
    subroot,
)
from repro.core.sort import (
    block_view,
    radix_argsort_u64pair,
    sort_f32_desc_stable,
)

UMAX = jnp.uint32(0xFFFFFFFF)


class GroupLayout(NamedTuple):
    perm: jax.Array         # (L,) int32 — edge ids sorted by (group, crit-rank)
    gidx: jax.Array         # (L,) int32 — dense group index per sorted slot
    group_start: jax.Array  # (L,) int32 — first sorted slot of each group
    group_size: jax.Array   # (L,) int32
    active: jax.Array       # (L,) bool  — sorted slot holds a crossing edge
    n_groups: jax.Array     # scalar int32 (incl. possibly one inactive tail)


@functools.partial(jax.jit, static_argnames=())
def group_keys(
    t: LiftingTables,
    root: jax.Array,
    u: jax.Array,
    v: jax.Array,
    edge_lca: jax.Array,
    is_offtree: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The paper's two-step partition key F(u, v) as a (hi, lo) uint32 pair.

    hi = 0, lo = lca                      if lca != root
    hi = s1 + 1, lo = s2                  if lca == root (crossing)
    (UMAX, UMAX)                          inactive (tree / non-crossing)

    where s1 >= s2 are the compact root-subtree indices of u, v. Using a
    key *pair* instead of N + 1 + C(s1, 2) + s2 avoids the paper's int
    overflow at large root degree while keeping the identical partition.
    """
    n = t.depth.shape[0]
    crossing = is_offtree & (edge_lca != u) & (edge_lca != v)
    is_child = t.depth == 1
    child_rank = jnp.cumsum(is_child.astype(jnp.int32)) - 1
    # ONE subroot climb over the n nodes, then two gathers per edge —
    # climbing (L,)-shaped endpoint arrays repeats every ancestor gather
    # ~2L/n times for nothing
    sub_all = subroot(t, jnp.arange(n, dtype=jnp.int32))
    s_u = child_rank[sub_all[u]]
    s_v = child_rank[sub_all[v]]
    s1 = jnp.maximum(s_u, s_v).astype(jnp.uint32)
    s2 = jnp.minimum(s_u, s_v).astype(jnp.uint32)
    at_root = edge_lca == root
    hi = jnp.where(at_root, s1 + 1, 0).astype(jnp.uint32)
    lo = jnp.where(at_root, s2, edge_lca.astype(jnp.uint32))
    hi = jnp.where(crossing, hi, UMAX)
    lo = jnp.where(crossing, lo, UMAX)
    return hi, lo, crossing


@jax.jit
def build_group_layout(
    crit: jax.Array,
    hi: jax.Array,
    lo: jax.Array,
    crossing: jax.Array,
    edge_valid: jax.Array | None = None,
) -> GroupLayout:
    """Sort edges by (group, criticality desc, id asc); derive group spans.

    edge_valid: optional (L,) padding mask (batched pipeline). Padding
    edges are forced out of every crossing group: they land in the
    inactive (UMAX, UMAX) tail group together with tree / non-crossing
    edges, where `active` is False, so phase 1 never inspects them and
    the dense group indices of real crossing groups are unchanged.

    Degenerate inputs are well-defined: with L == 0 (an isolated-node
    graph) every field is empty and n_groups == 0 — the static-shape
    branch below exists because `.at[0]` on an empty array raises even
    under jit. With zero crossing edges (star / chain topologies) the
    whole layout is the single inactive (UMAX, UMAX) tail group:
    `active` is all-False, so no schedule ever inspects a slot and no
    garbage reaches recovery (tests/test_marking_chunked.py pins both).
    """
    if edge_valid is not None:
        crossing = crossing & edge_valid
    m = crit.shape[0]
    if m == 0:
        zi = jnp.zeros((0,), jnp.int32)
        return GroupLayout(perm=zi, gidx=zi, group_start=zi, group_size=zi,
                           active=jnp.zeros((0,), bool),
                           n_groups=jnp.int32(0))
    p1 = sort_f32_desc_stable(jnp.where(crossing, crit, -jnp.inf))
    p2 = radix_argsort_u64pair(hi[p1], lo[p1])  # stable => keeps crit order
    perm = p1[p2]
    sh, sl = hi[perm], lo[perm]
    first = jnp.zeros((m,), dtype=bool).at[0].set(True)
    bnd = first | (sh != jnp.roll(sh, 1)) | (sl != jnp.roll(sl, 1))
    gidx = jnp.cumsum(bnd.astype(jnp.int32)) - 1
    group_start = jnp.full((m,), jnp.int32(m)).at[gidx].min(
        jnp.arange(m, dtype=jnp.int32)
    )
    group_size = jnp.zeros((m,), jnp.int32).at[gidx].add(1)
    active = crossing[perm]
    return GroupLayout(
        perm=perm,
        gidx=gidx,
        group_start=group_start,
        group_size=group_size,
        active=active,
        n_groups=gidx[-1] + 1,
    )


def ball_pair_table(
    euler: EulerLCA,
    xs: jax.Array,
    ys: jax.Array,
    cols_u: jax.Array,
    cols_v: jax.Array,
    cols_b: jax.Array,
) -> jax.Array:
    """Ball-pair cover table for a block of edges vs a set of candidates.

    xs, ys: (C,) block edge endpoints. cols_*: candidate accepted edges
    (u, v, beta) — either (K,) shared across the block (recovery's
    buffer snapshot ++ block endpoints) or (C, K) per-row (phase 1's
    per-group accepted-buffer gathers). Returns (C, K) bool — candidate
    j's ball pair covers block edge i:

        cover <=> (d(x,u_j) <= b_j and d(y,v_j) <= b_j) or swapped.

    The 4·C·K tree distances are ONE fused batched query on the
    Euler-tour depth-minimum table. This is where chunked schedules pay
    for their blocks: the query is amortised over the whole (C, K)
    table instead of one edge's row. Each endpoint's (first, depth) is
    gathered once per row and per column (C·K for per-row candidates),
    so a pair costs two gathers.
    """
    c = xs.shape[0]
    k = cols_u.shape[-1]

    def cover(d):
        return ((d[0] <= cols_b) & (d[1] <= cols_b)) | (
            (d[2] <= cols_b) & (d[3] <= cols_b)
        )

    # endpoint values once per row and per column, before anything is
    # broadcast to pair shape: only the two `dmin` reads are per pair
    def rows(x, y):
        return jnp.broadcast_to(jnp.stack([x, y, x, y])[:, :, None],
                                (4, c, k))

    def cols(u, v):
        return jnp.broadcast_to(
            jnp.stack([u, v, v, u]).reshape(4, -1, k), (4, c, k))

    (fx, dx), (fy, dy), (fu, du), (fv, dv) = (
        euler_endpoints(euler, a) for a in (xs, ys, cols_u, cols_v))
    return cover(euler_distance(euler, rows(fx, fy), rows(dx, dy),
                                cols(fu, fv), cols(du, dv)))


class Phase1Result(NamedTuple):
    accept: jax.Array          # (L,) bool — per *sorted slot*
    group_overflow: jax.Array  # (L,) bool — per dense group index
    rounds: jax.Array          # int32 — steps of the schedule's loop


def _empty_phase1() -> "Phase1Result":
    """The L == 0 result (isolated-node graphs; see build_group_layout)."""
    return Phase1Result(accept=jnp.zeros((0,), bool),
                        group_overflow=jnp.zeros((0,), bool),
                        rounds=jnp.int32(0))


@jax.jit
def phase1_edge_views(
    perm: jax.Array,
    gidx: jax.Array,
    accept_sorted: jax.Array,
    group_overflow: jax.Array,
    crossing: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Scatter phase-1's sorted-slot outputs back to edge-id order.

    The recovery stage consumes per-edge views: the phase-1 accept
    decision, the dense group index (-1 for anything that is not a
    crossing edge — tree, non-crossing, padding), and the initial dirty
    set (every crossing edge of an overflowed group). This is the glue
    between MARK and REC; the host tail computes the same three arrays
    in numpy (`_recovery_tail`), asserted equal by the parity tests.
    """
    L = perm.shape[0]
    accept_by_edge = jnp.zeros((L,), bool).at[perm].set(accept_sorted)
    group_of_edge = jnp.full((L,), -1, jnp.int32).at[perm].set(
        gidx.astype(jnp.int32)
    )
    group_of_edge = jnp.where(crossing, group_of_edge, -1)
    dirty0 = jnp.zeros((L,), bool).at[perm].set(
        group_overflow[gidx] & crossing[perm]
    )
    return accept_by_edge, group_of_edge, dirty0


@functools.partial(jax.jit, static_argnames=("k_cap", "chunk"))
def phase1_chunked(
    euler: EulerLCA,
    su: jax.Array,
    sv: jax.Array,
    sbeta: jax.Array,
    layout: GroupLayout,
    k_cap: int = 32,
    chunk: int = 32,
) -> Phase1Result:
    """Two-level chunked greedy — the recovery-style replay for phase 1.

    Sorted slots are processed in blocks of `chunk`. Per block, ONE
    batched distance query answers every cover test the block can need:

      * block vs buffer — each slot i against the (k_cap,) accepted
        snapshot of *its own* group (slots only interact within a
        group), gathered as (C, K) per-row candidate tables;
      * block vs block — each slot i against every other block slot j,
        masked to same-group strictly-earlier accepted entries.

    The inner lax.scan then resolves the block's accept/reject chain
    with pure arithmetic on (C,)/(K,) vectors: coverage is a row lookup,
    the running per-group count is cnt-at-block-start plus a masked
    popcount of the stored-so-far vector, overflow is a compare. All
    table updates land in ONE batched scatter per block (distinct
    (group, slot) targets, rejects parked on row L and dropped).

    Crossing slots occupy a prefix of the sorted layout (non-crossing /
    tree / padding slots share the (UMAX, UMAX) tail group, which sorts
    last), so the outer while_loop runs ceil(n_crossing / chunk) blocks
    — never the full L. Decisions are integer comparisons throughout,
    hence bit-identical to the numpy oracle at every block size
    (tests/test_marking_chunked.py).

    `euler`: the Euler-tour O(1)-LCA tables (lca.py) the distance
    queries read.
    """
    m = su.shape[0]
    if m == 0:
        return _empty_phase1()
    c = max(min(chunk, m), 1)
    act_all = layout.active
    x_pad = block_view(jnp.where(act_all, su, 0).astype(jnp.int32), c, 0)
    y_pad = block_view(jnp.where(act_all, sv, 0).astype(jnp.int32), c, 0)
    b_pad = block_view(sbeta.astype(jnp.int32), c, -1)
    g_pad = block_view(layout.gidx, c, 0)
    act_pad = block_view(act_all, c, False)
    n_blocks = g_pad.shape[0]
    blocks_needed = (jnp.sum(act_all.astype(jnp.int32)) + c - 1) // c
    kiota = jnp.arange(k_cap, dtype=jnp.int32)
    ciota = jnp.arange(c, dtype=jnp.int32)

    def inner(store_vec, xs):
        cov_buf_i, pair_row, same_row, act_i, cnt0_i, i = xs
        hit = store_vec & same_row           # stored same-group, earlier
        cov = cov_buf_i | jnp.any(pair_row & hit)
        accept = act_i & ~cov
        cnt_here = cnt0_i + jnp.sum(hit.astype(jnp.int32))
        full = cnt_here >= k_cap
        store = accept & ~full
        store_vec = store_vec | ((ciota == i) & store)
        return store_vec, (accept, store, accept & full, cnt_here)

    def cond(state):
        return state[0] < blocks_needed

    def body(state):
        blk, acc_u, acc_v, acc_b, cnt, ovf, out = state
        pick = lambda a: jax.lax.dynamic_index_in_dim(a, blk,
                                                      keepdims=False)
        g, act = pick(g_pad), pick(act_pad)
        x, y, b = pick(x_pad), pick(y_pad), pick(b_pad)
        cnt0 = cnt[g]
        pair_buf = ball_pair_table(euler, x, y, acc_u[g], acc_v[g],
                                   acc_b[g])
        cov_buf = jnp.any(pair_buf & (kiota[None, :] < cnt0[:, None]),
                          axis=1)
        pair_blk = ball_pair_table(euler, x, y, x, y, b)
        same_prior = (g[:, None] == g[None, :]) & (
            ciota[None, :] < ciota[:, None]
        )
        _, (accept, store, oflag, cnt_at) = jax.lax.scan(
            inner, jnp.zeros((c,), bool),
            (cov_buf, pair_blk, same_prior, act, cnt0, ciota),
        )
        park = jnp.where(store, g, m)
        slot = jnp.minimum(cnt_at, k_cap - 1)
        acc_u = acc_u.at[park, slot].set(x, mode="drop")
        acc_v = acc_v.at[park, slot].set(y, mode="drop")
        acc_b = acc_b.at[park, slot].set(b, mode="drop")
        cnt = cnt.at[park].add(1, mode="drop")
        ovf = ovf.at[jnp.where(oflag, g, m)].set(True, mode="drop")
        out = jax.lax.dynamic_update_slice(out, accept, (blk * c,))
        return blk + 1, acc_u, acc_v, acc_b, cnt, ovf, out

    init = (
        jnp.int32(0),
        jnp.zeros((m, k_cap), jnp.int32),
        jnp.zeros((m, k_cap), jnp.int32),
        jnp.full((m, k_cap), -1, jnp.int32),  # -1 beta matches nothing
        jnp.zeros((m,), jnp.int32),
        jnp.zeros((m,), bool),
        jnp.zeros((n_blocks * c,), bool),
    )
    rounds, _, _, _, _, ovf, out = jax.lax.while_loop(cond, body, init)
    return Phase1Result(accept=out[:m], group_overflow=ovf, rounds=rounds)
