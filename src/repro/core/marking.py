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
replaces ball materialisation (pointer chasing) with dense gathers. Three
schedules are provided:

  * `phase1_chunked`  — the default: sorted slots are processed in
    blocks of C. Per block, ONE batched LCA call builds the cover table
    of all block candidates against (a) each slot's per-group accepted-
    buffer snapshot and (b) every other block slot; an arithmetic-only
    inner lax.scan then replays the block's accept/reject decisions with
    pure table lookups (no per-slot gathers), and the per-(L, K) tables
    are updated with one batched scatter per block. Crossing slots
    occupy a prefix of the sorted layout, so the outer while_loop runs
    ceil(n_crossing / C) blocks — the step count collapses from L to
    n_crossing / C (pdGRASS's density-aware batching, mapped from
    thread queues to lane blocks).
  * `phase1_basic`    — one lax.scan over edges in global criticality
    order (the paper's "basic LGRASS", Fig. 1b).
  * `phase1_parallel` — rank-lockstep over groups: at step r every group
    processes its r-th edge simultaneously (the paper's parallel edge
    marking, Fig. 2, mapped from thread-parallel to lane-parallel).

All three schedules are bit-identical (groups are independent and each
schedule preserves the within-group criticality order; tests/
test_marking_chunked.py sweeps them against the numpy oracle). The
`run_phase1` dispatcher selects one via `schedule="chunked" | "scan"`
(the latter picking basic or lockstep via `parallel`).

Groups whose accepted count exceeds K overflow; the host recovery stage
(recovery.py) re-checks those exactly, so K is a performance knob, never a
correctness knob.

Non-crossing edges are excluded here and replayed in recovery (Alg. 6),
exactly as the paper keeps that stage sequential (Fig. 1c).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.lca import (
    EulerLCA,
    LiftingTables,
    euler_distance,
    euler_endpoints,
    kth_ancestor,
    lca,
    subroot,
    tree_distance,
)
from repro.core.pow2 import auto_chunk
from repro.core.sort import (
    block_view,
    float32_sort_key,
    radix_argsort_u32,
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
    t: LiftingTables,
    xs: jax.Array,
    ys: jax.Array,
    cols_u: jax.Array,
    cols_v: jax.Array,
    cols_b: jax.Array,
    use_tree_kernel: bool = False,
    euler: Optional[EulerLCA] = None,
) -> jax.Array:
    """Ball-pair cover table for a block of edges vs a set of candidates.

    xs, ys: (C,) block edge endpoints. cols_*: candidate accepted edges
    (u, v, beta) — either (K,) shared across the block (recovery's
    buffer snapshot ++ block endpoints) or (C, K) per-row (phase 1's
    per-group accepted-buffer gathers). Returns (C, K) bool — candidate
    j's ball pair covers block edge i:

        cover <=> (d(x,u_j) <= b_j and d(y,v_j) <= b_j) or swapped.

    The 4·C·K tree distances are ONE fused batched query — a binary-
    lifting climb by default, the Euler-tour depth-minimum table when
    `euler` is given, or the Pallas tree-distance kernel under
    `use_tree_kernel`. This is where chunked schedules pay for their
    blocks: the climb's sequential latency is amortised over the whole
    (C, K) table instead of one edge's row. On the Euler path each
    endpoint's (first, depth) is gathered once per row and per column
    (C·K for per-row candidates), so a pair costs two gathers.
    """
    c = xs.shape[0]
    k = cols_u.shape[-1]

    def cover(d):
        return ((d[0] <= cols_b) & (d[1] <= cols_b)) | (
            (d[2] <= cols_b) & (d[3] <= cols_b)
        )

    if euler is not None and not use_tree_kernel:
        # endpoint values once per row and per column, before anything
        # is broadcast to pair shape: only the two `dmin` reads are per
        # pair
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
    if cols_u.ndim == 1:
        cols_u = jnp.broadcast_to(cols_u[None, :], (c, k))
        cols_v = jnp.broadcast_to(cols_v[None, :], (c, k))
        cols_b = jnp.broadcast_to(cols_b[None, :], (c, k))
    qa = jnp.broadcast_to(jnp.stack([xs, ys, xs, ys])[:, :, None],
                          (4, c, k))
    qb = jnp.stack([cols_u, cols_v, cols_v, cols_u])
    if use_tree_kernel:
        from repro.kernels.ops import tree_dist_pairs

        d = tree_dist_pairs(t.up, t.depth, qa.ravel(),
                            jnp.broadcast_to(qb, (4, c, k)).ravel())
        d = d.reshape(4, c, k)
    else:
        d = tree_distance(t, qa, qb)
    return cover(d)


def _ball_pair_covered(
    t: LiftingTables,
    x: jax.Array,
    y: jax.Array,
    row_u: jax.Array,
    row_v: jax.Array,
    row_b: jax.Array,
    cnt: jax.Array,
) -> jax.Array:
    """Paired-ball cover test against a (…, K) accepted-edge table.

    covered <=> exists j < cnt:
        (d(x,u_j) <= b_j and d(y,v_j) <= b_j) or
        (d(x,v_j) <= b_j and d(y,u_j) <= b_j)

    Distances are tree hop distances via batched LCA — this is Alg. 3's
    check, evaluated analytically instead of via materialised ball sets.
    """
    k = row_u.shape[-1]
    xb = jnp.broadcast_to(x[..., None], row_u.shape)
    yb = jnp.broadcast_to(y[..., None], row_u.shape)

    def dist(a, b):
        w = lca(t, a, b)
        return t.depth[a] + t.depth[b] - 2 * t.depth[w]

    dxu = dist(xb, row_u)
    dxv = dist(xb, row_v)
    dyu = dist(yb, row_u)
    dyv = dist(yb, row_v)
    pair = ((dxu <= row_b) & (dyv <= row_b)) | ((dxv <= row_b) & (dyu <= row_b))
    valid = jnp.arange(k, dtype=jnp.int32) < cnt[..., None]
    return jnp.any(pair & valid, axis=-1)


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


@functools.partial(jax.jit, static_argnames=("k_cap",))
def phase1_basic(
    t: LiftingTables,
    su: jax.Array,
    sv: jax.Array,
    sbeta: jax.Array,
    layout: GroupLayout,
    k_cap: int = 32,
) -> Phase1Result:
    """Sequential greedy (basic LGRASS): one lax.scan over sorted slots."""
    m = su.shape[0]
    if m == 0:
        return _empty_phase1()
    acc_u = jnp.zeros((m, k_cap), jnp.int32)
    acc_v = jnp.zeros((m, k_cap), jnp.int32)
    acc_b = jnp.full((m, k_cap), -1, jnp.int32)
    cnt = jnp.zeros((m,), jnp.int32)
    ovf = jnp.zeros((m,), bool)

    def step(carry, i):
        acc_u, acc_v, acc_b, cnt, ovf = carry
        g = layout.gidx[i]
        act = layout.active[i]
        x = jnp.where(act, su[i], 0)
        y = jnp.where(act, sv[i], 0)
        cov = _ball_pair_covered(t, x, y, acc_u[g], acc_v[g], acc_b[g], cnt[g])
        accept = act & ~cov
        full = cnt[g] >= k_cap
        ovf = ovf.at[g].set(ovf[g] | (accept & full))
        slot = jnp.minimum(cnt[g], k_cap - 1)
        store = accept & ~full
        acc_u = acc_u.at[g, slot].set(jnp.where(store, x, acc_u[g, slot]))
        acc_v = acc_v.at[g, slot].set(jnp.where(store, y, acc_v[g, slot]))
        acc_b = acc_b.at[g, slot].set(
            jnp.where(store, sbeta[i], acc_b[g, slot])
        )
        cnt = cnt.at[g].add(store.astype(jnp.int32))
        return (acc_u, acc_v, acc_b, cnt, ovf), accept

    (acc_u, acc_v, acc_b, cnt, ovf), accept = jax.lax.scan(
        step, (acc_u, acc_v, acc_b, cnt, ovf), jnp.arange(m, dtype=jnp.int32)
    )
    return Phase1Result(accept=accept, group_overflow=ovf,
                        rounds=jnp.int32(m))


@functools.partial(jax.jit, static_argnames=("k_cap",))
def phase1_parallel(
    t: LiftingTables,
    su: jax.Array,
    sv: jax.Array,
    sbeta: jax.Array,
    layout: GroupLayout,
    k_cap: int = 32,
) -> Phase1Result:
    """Rank-lockstep greedy (parallel LGRASS): all groups advance together.

    Step r processes the r-th edge of every group as one vectorised lane
    batch — the TPU analogue of the paper's dynamic task dispatch. Total
    steps = max group size; each step is O(G * K * log N) dense work.
    """
    m = su.shape[0]
    if m == 0:
        return _empty_phase1()
    garange = jnp.arange(m, dtype=jnp.int32)
    lane_live = garange < layout.n_groups
    # Trip count: longest *active* group only. Inactive slots (tree /
    # non-crossing / padding) all share the (UMAX, UMAX) tail group whose
    # lane never fires (`layout.active` is False there), so letting its
    # size — O(L) — drive the loop would only add no-op rounds.
    group_active = layout.active[jnp.minimum(layout.group_start, m - 1)]
    max_r = jnp.max(
        jnp.where(lane_live & group_active, layout.group_size, 0)
    )

    acc_u = jnp.zeros((m, k_cap), jnp.int32)
    acc_v = jnp.zeros((m, k_cap), jnp.int32)
    acc_b = jnp.full((m, k_cap), -1, jnp.int32)
    cnt = jnp.zeros((m,), jnp.int32)
    ovf = jnp.zeros((m,), bool)
    out = jnp.zeros((m,), bool)

    def cond(state):
        r = state[0]
        return r < max_r

    def body(state):
        r, acc_u, acc_v, acc_b, cnt, ovf, out = state
        gs = layout.group_start[garange]
        i = jnp.minimum(gs + r, m - 1)
        lane_act = lane_live & (r < layout.group_size[garange])
        lane_act = lane_act & layout.active[i]
        x = jnp.where(lane_act, su[i], 0)
        y = jnp.where(lane_act, sv[i], 0)
        cov = _ball_pair_covered(t, x, y, acc_u, acc_v, acc_b, cnt)
        accept = lane_act & ~cov
        full = cnt >= k_cap
        ovf = ovf | (accept & full)
        slot = jnp.minimum(cnt, k_cap - 1)
        store = accept & ~full
        acc_u = acc_u.at[garange, slot].set(jnp.where(store, x, acc_u[garange, slot]))
        acc_v = acc_v.at[garange, slot].set(jnp.where(store, y, acc_v[garange, slot]))
        acc_b = acc_b.at[garange, slot].set(
            jnp.where(store, sbeta[i], acc_b[garange, slot])
        )
        cnt = cnt + store.astype(jnp.int32)
        write_i = jnp.where(lane_act, i, m)  # dropped when inactive
        out = out.at[write_i].set(accept, mode="drop")
        return r + 1, acc_u, acc_v, acc_b, cnt, ovf, out

    rounds, acc_u, acc_v, acc_b, cnt, ovf, out = jax.lax.while_loop(
        cond, body, (jnp.int32(0), acc_u, acc_v, acc_b, cnt, ovf, out)
    )
    return Phase1Result(accept=out, group_overflow=ovf, rounds=rounds)


@functools.partial(jax.jit,
                   static_argnames=("k_cap", "chunk", "use_tree_kernel"))
def phase1_chunked(
    t: LiftingTables,
    su: jax.Array,
    sv: jax.Array,
    sbeta: jax.Array,
    layout: GroupLayout,
    k_cap: int = 32,
    chunk: int = 32,
    use_tree_kernel: bool = False,
    euler: Optional[EulerLCA] = None,
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
    hence bit-identical to `phase1_basic` / `phase1_parallel` / the
    numpy oracle (tests/test_marking_chunked.py).

    `euler`: optional Euler-tour O(1)-LCA tables (lca.py) backing the
    distance queries — O(1) gathers per query instead of O(log n).
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
        pair_buf = ball_pair_table(t, x, y, acc_u[g], acc_v[g], acc_b[g],
                                   use_tree_kernel, euler)
        cov_buf = jnp.any(pair_buf & (kiota[None, :] < cnt0[:, None]),
                          axis=1)
        pair_blk = ball_pair_table(t, x, y, x, y, b, use_tree_kernel,
                                   euler)
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


def run_phase1(
    t: LiftingTables,
    su: jax.Array,
    sv: jax.Array,
    sbeta: jax.Array,
    layout: GroupLayout,
    k_cap: int = 32,
    schedule: str = "chunked",
    parallel: bool = True,
    chunk: Optional[int] = None,
    use_tree_kernel: bool = False,
    euler: Optional[EulerLCA] = None,
) -> Phase1Result:
    """Schedule dispatcher — the one entry every pipeline goes through.

    schedule="chunked" (default) runs `phase1_chunked` with an automatic
    pow2 block size (`core.pow2.auto_chunk`, ~sqrt(L)) unless `chunk`
    pins one; schedule="scan" keeps the legacy per-slot engines, with
    `parallel` picking rank-lockstep vs the basic sequential scan. All
    choices are bit-identical; this is purely a performance knob.
    """
    if schedule == "chunked":
        c = auto_chunk(int(su.shape[0])) if chunk is None else int(chunk)
        return phase1_chunked(t, su, sv, sbeta, layout, k_cap=k_cap,
                              chunk=c, use_tree_kernel=use_tree_kernel,
                              euler=euler)
    if schedule != "scan":
        raise ValueError(f"unknown phase-1 schedule {schedule!r}")
    fn = phase1_parallel if parallel else phase1_basic
    return fn(t, su, sv, sbeta, layout, k_cap=k_cap)
