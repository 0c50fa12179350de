"""Recovery of non-crossing edges and their after-effects (Algorithm 6).

Phase 1 (marking.py) resolves crossing edges per independent LCA group.
Non-crossing edges, overflowed groups and the global budget cut are
replayed here in global criticality order. The replay reuses phase-1
decisions wherever they are provably final and re-derives them only where
a *dirty* flag says an interaction outside phase 1's model occurred:

  * an accepted non-crossing edge dirties every off-tree edge it covers
    ("enforced"/"withdrawn" propagation, Alg. 6 lines 11-19);
  * a crossing edge whose final decision flips w.r.t. phase 1 dirties the
    later edges of its group (their phase-1 checks consulted a stale
    accepted set);
  * groups that overflowed the K-slot accept table are fully dirty.

Dirty or non-crossing edges are decided by the exact ball-pair test
against the accepted-so-far set, so the result equals the baseline greedy
(tests assert bit-equality against baseline.py on random graphs).

Two implementations of the identical semantics live here:

  * `recover_host` — the numpy oracle, mirroring the paper's own
    sequential Algorithm 6 tail (Fig. 1c). Kept as the ground truth the
    device program is asserted against.
  * `recover_device` — a jit/vmap-able chunked `lax.scan` over the
    criticality-ordered edge stream. The accepted set lives in a
    budget-bounded (b_cap,) buffer; the ball-pair coverage test is
    vectorised via analytic tree distances (`x in B(c, beta)` iff
    `tree_dist(x, c) <= beta`, so no ball is ever materialised) —
    answered by the Euler-tour O(1)-LCA tables (the fused program's
    own; the standalone entries rebuild them on device from up[0]) —
    with one batched LCA per block of `chunk` edges
    (marking.ball_pair_table, the cover-table helper shared with the
    chunked phase-1 scheduler that later ported this exact scheme)
    answering every block-vs-buffer and block-vs-block query at once;
    and the after-effects dirty propagation is *lazy*: instead of the
    host's eager "dirty every edge this ball pair covers" BFS scatter,
    each edge derives its own dirty bit at processing time from (a) the
    overflow seed, (b) a per-group flip flag maintained with O(1)
    scatters, and (c) coverage by any accepted *non-crossing* buffer
    entry — coverage is time-invariant once the tree is fixed, so
    deferring the test is exact. Decisions are integer comparisons
    throughout, hence bit-identical to the host replay.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import _host as H
from repro.core.lca import EulerLCA, build_euler
from repro.core.marking import ball_pair_table
from repro.core.sort import block_view


def recover_host(
    n: int,
    u: np.ndarray,
    v: np.ndarray,
    tree_mask: np.ndarray,
    parent_t: np.ndarray,
    depth_t: np.ndarray,
    up: np.ndarray,
    beta: np.ndarray,
    crossing: np.ndarray,
    crit_order: np.ndarray,
    phase1_accept: np.ndarray,
    group_of_edge: np.ndarray,
    dirty0: np.ndarray,
    budget: int,
) -> np.ndarray:
    """Returns (L,) bool — final accepted off-tree edges.

    phase1_accept: (L,) bool, meaningful for crossing edges only.
    group_of_edge: (L,) int64 dense group index, -1 for non-crossing.
    dirty0: (L,) bool — initial dirty set (overflowed groups).
    """
    L = len(u)
    offtree = ~tree_mask
    adj = H.tree_adjacency(parent_t, n)
    dirty = dirty0.copy()
    out = np.zeros(L, bool)

    # accepted set: preallocated at the budget bound (the greedy stops at
    # `budget` accepts, so no growth/rebuild ever happens mid-replay)
    cap = max(int(budget), 1)
    acc_u = np.zeros(cap, np.int64)
    acc_v = np.zeros(cap, np.int64)
    acc_b = np.zeros(cap, np.int64)

    def covered_by_any(e: int, count: int) -> bool:
        if count == 0:
            return False
        au, av, ab = acc_u[:count], acc_v[:count], acc_b[:count]
        x, y = int(u[e]), int(v[e])
        dxu = H.tree_dist_np(up, depth_t, x, au)
        dxv = H.tree_dist_np(up, depth_t, x, av)
        dyu = H.tree_dist_np(up, depth_t, y, au)
        dyv = H.tree_dist_np(up, depth_t, y, av)
        pair = ((dxu <= ab) & (dyv <= ab)) | ((dxv <= ab) & (dyu <= ab))
        return bool(pair.any())

    count = 0
    for e in crit_order:
        e = int(e)
        if count == budget:
            break
        if crossing[e] and not dirty[e]:
            dec = bool(phase1_accept[e])
        else:
            dec = not covered_by_any(e, count)
        if crossing[e] and dec != bool(phase1_accept[e]):
            # flip: later same-group phase-1 decisions are stale
            dirty |= group_of_edge == group_of_edge[e]
        if dec:
            out[e] = True
            acc_u[count] = int(u[e])
            acc_v[count] = int(v[e])
            acc_b[count] = int(beta[e])
            count += 1
            if not crossing[e]:
                # Alg. 6 after-effects: dirty everything this edge covers
                s1 = H.ball_np(adj, int(u[e]), int(beta[e]))
                s2 = H.ball_np(adj, int(v[e]), int(beta[e]))
                m1 = np.zeros(n, bool)
                m2 = np.zeros(n, bool)
                m1[list(s1)] = True
                m2[list(s2)] = True
                cov = offtree & ((m1[u] & m2[v]) | (m2[u] & m1[v]))
                dirty |= cov
    return out


def rec_widths(b_cap: int, c: int) -> tuple:
    """The static accept-buffer widths a replay block's cover table can
    take: multiples of g = max(b_cap // 8, c) below b_cap, then b_cap
    itself. A block takes the smallest width that holds every filled
    buffer slot; with b_cap <= c there is one width, the whole buffer."""
    g = max(b_cap // 8, c)
    return tuple(range(g, b_cap, g)) + (b_cap,)


def _recover_scan(
    euler: EulerLCA,
    u: jax.Array,
    v: jax.Array,
    beta: jax.Array,
    offtree: jax.Array,
    crossing: jax.Array,
    order: jax.Array,
    phase1_accept: jax.Array,
    group_of_edge: jax.Array,
    dirty0: jax.Array,
    budget: jax.Array,
    b_cap: int,
    chunk: int = 32,
    batched: bool = False,
):
    """The device replay: a chunked two-level lax.scan over rank slots.

    `euler`: the lca.EulerLCA tables the per-block cover tables read
    (the fused program passes the ones it built for marking), O(1)
    gathers a distance.

    `order` is a full (L,) permutation — (crit desc, id asc) with tree /
    padding slots forced to -inf keys, so they trail every off-tree edge
    and are skipped via the gathered `offtree` flag. `budget` is a traced
    scalar; `b_cap` (static) bounds the accept buffer and must satisfy
    b_cap >= budget (the greedy never holds more than `budget` accepts).
    Because `budget` is traced, that precondition cannot raise here; it
    is enforced by clamping budget to b_cap — the result is then exact
    for the clamped budget instead of silently corrupting the buffer
    (the `lgrass_sparsify(_batch)` wrappers validate and raise on the
    host side before ever reaching this).

    Scheduling: slots are processed in blocks of `chunk`. Per block, ONE
    batched LCA evaluates the cover table of all block edges against
    (a) the buffer's first W slots and (b) every other block edge —
    exploiting the pdGRASS observation that the sweep's interactions are
    local. W is the smallest of `rec_widths` that holds the `cnt` slots
    filled before the block; a `lax.switch` picks the table of that
    static width. Slots filled during the block hold block edges, so
    `cnt` at the block's start bounds every buffer column the block
    reads, and the empty columns it leaves out cover nothing. The
    filled buffer columns are then read once per block: one reduction
    over the (C, W) table gives each block edge's cover by the
    pre-block entries (any, and non-crossing). The inner scan replays
    the block's decisions from that and from column W + j of the block
    edges j accepted so far. Group-flip dirt is a per-*group* flag
    updated with O(1) scatters (index L is the never-set parking slot
    for non-crossing edges). The block's accepts enter the buffer after
    its scan, in order. Distances are integers, so chunking and the
    width change nothing observable: decisions are bit-identical to the
    host replay.

    `batched` (static) marks a trace under vmap. There a switch on a
    per-lane `cnt` would run every width, so the table keeps the single
    width b_cap.

    The outer loop is a while_loop gated on `cnt < budget`: once the
    budget is exhausted nothing later in the stream can change any
    output (the host replay breaks out at the same point), so the
    common case — budgets of a few percent of n, filled within the top
    criticality ranks — touches only the leading blocks. Under vmap the
    loop runs the union of the lanes' needed blocks, with finished
    lanes' carries frozen by the batching rule.

    The loop also stops after the last block that holds an off-tree
    edge: later blocks hold tree and padding slots only, which decide
    nothing.

    Returns (accepted (L,) bool, n_accepted int32, rounds int32: blocks
    the outer loop ran, table_cols int32: the columns W + C of the
    blocks' cover tables, summed).
    """
    L = u.shape[0]
    if L == 0:  # isolated-node graph: nothing to replay
        return (jnp.zeros((0,), bool), jnp.int32(0), jnp.int32(0),
                jnp.int32(0))
    budget = jnp.minimum(jnp.asarray(budget, jnp.int32), jnp.int32(b_cap))
    c = max(min(chunk, L), 1)
    n_blocks = -(-L // c)
    order_pad = block_view(order.astype(jnp.int32), c, 0)
    svalid_pad = block_view(jnp.ones((L,), bool), c, False)
    last_slot = jnp.max(jnp.where(offtree[order],
                                  jnp.arange(L, dtype=jnp.int32), -1))
    blocks_needed = jnp.minimum(last_slot // c + 1, n_blocks)
    widths = (b_cap,) if batched else rec_widths(b_cap, c)

    def table(w, ops):
        """Block edges against the buffer's first `w` slots and the
        block: (cover by a filled slot, by a filled non-crossing slot,
        the (C, C) block columns)."""
        buf_u, buf_v, buf_b, buf_nc, cnt, bx, by, bb = ops
        tbl = ball_pair_table(
            euler, bx, by, jnp.concatenate([buf_u[:w], bx]),
            jnp.concatenate([buf_v[:w], by]),
            jnp.concatenate([buf_b[:w], bb]))
        pre = tbl[:, :w] & (jnp.arange(w, dtype=jnp.int32) < cnt)
        return (jnp.any(pre, axis=1), jnp.any(pre & buf_nc[:w], axis=1),
                tbl[:, w:])

    tables = [functools.partial(table, w) for w in widths]

    def outer(state):
        blk, buf_u, buf_v, buf_b, buf_nc, cnt, gflag, out, cols = state
        eids = jax.lax.dynamic_index_in_dim(order_pad, blk, keepdims=False)
        svalid = jax.lax.dynamic_index_in_dim(svalid_pad, blk,
                                              keepdims=False)
        a0 = svalid & offtree[eids]
        bx = jnp.where(a0, u[eids], 0).astype(jnp.int32)
        by = jnp.where(a0, v[eids], 0).astype(jnp.int32)
        bb = beta[eids].astype(jnp.int32)
        blk_nc = ~crossing[eids]
        ops = (buf_u, buf_v, buf_b, buf_nc, cnt, bx, by, bb)
        if len(widths) == 1:
            w_idx = 0
            pre_any, pre_nc, blk_tbl = tables[0](ops)
        else:
            w_idx = jnp.sum(jnp.asarray(widths, jnp.int32) < cnt)
            pre_any, pre_nc, blk_tbl = jax.lax.switch(w_idx, tables, ops)

        def inner(carry, xs):
            cnt, gflag, acc = carry
            e, a, row, p_any, p_nc, i = xs
            active = a & (cnt < budget)
            hit = row & acc                  # block edges accepted so far
            cov_any = p_any | jnp.any(hit)
            cov_nc = p_nc | jnp.any(hit & blk_nc)

            cr = crossing[e]
            g = group_of_edge[e]
            gsafe = jnp.where(g < 0, L, g).astype(jnp.int32)
            dirty_e = dirty0[e] | gflag[gsafe] | cov_nc
            dec = active & jnp.where(cr & ~dirty_e, phase1_accept[e],
                                     ~cov_any)

            # flip w.r.t. phase 1: dirty the rest of the group (O(1) scatter)
            flip = active & cr & (dec != phase1_accept[e])
            gflag = gflag.at[gsafe].max(flip)
            acc = acc.at[i].set(dec)
            return (cnt + dec.astype(jnp.int32), gflag, acc), None

        (cnt_end, gflag, acc), _ = jax.lax.scan(
            inner, (cnt, gflag, jnp.zeros((c,), bool)),
            (eids, a0, blk_tbl, pre_any, pre_nc,
             jnp.arange(c, dtype=jnp.int32)))
        # the block's accepts fill slots cnt, cnt + 1, ... in block order;
        # the rest land on index b_cap and are dropped
        slot = jnp.where(acc, cnt + jnp.cumsum(acc) - 1, b_cap)
        buf_u = buf_u.at[slot].set(bx, mode="drop")
        buf_v = buf_v.at[slot].set(by, mode="drop")
        buf_b = buf_b.at[slot].set(bb, mode="drop")
        buf_nc = buf_nc.at[slot].set(blk_nc, mode="drop")
        out = out.at[eids].max(acc)  # max: padding re-visits edge id 0
        cols = cols + jnp.asarray(widths, jnp.int32)[w_idx] + c
        return (blk + 1, buf_u, buf_v, buf_b, buf_nc, cnt_end, gflag, out,
                cols)

    def cond(state):
        blk, cnt = state[0], state[5]
        return (blk < blocks_needed) & (cnt < budget)

    init = (
        jnp.int32(0),                          # block index
        jnp.zeros((b_cap,), jnp.int32),        # buf_u
        jnp.zeros((b_cap,), jnp.int32),        # buf_v
        jnp.full((b_cap,), -1, jnp.int32),     # buf_b (-1: matches nothing)
        jnp.zeros((b_cap,), bool),             # buf_nc (non-crossing entry)
        jnp.int32(0),                          # cnt
        jnp.zeros((L + 1,), bool),             # per-group flip flag
        jnp.zeros((L,), bool),                 # out
        jnp.int32(0),                          # cover-table columns
    )
    rounds, _, _, _, _, cnt, _, out, cols = jax.lax.while_loop(
        cond, outer, init)
    return out, cnt, rounds, cols


def _euler_from_lifting(up: jax.Array, depth_t: jax.Array):
    """Rebuild the Euler-tour O(1)-LCA tables from lifting-table inputs.

    The standalone recovery entries only receive `up`/`depth_t`, so the
    tree shape the fused program already had is reconstructed on device:
    `parent` is up[0] with its self-loops (root, unreachable padding)
    mapped back to -1, and the root is the unique depth-0 node
    (`argmin` — padding carries INF depth, so the real root always
    wins). One `build_euler` then gives the exact tables the fused
    pipeline shares with its replay; vmap-safe (pure gathers/scatters).
    """
    n = up.shape[-1]
    nodes = jnp.arange(n, dtype=jnp.int32)
    parent = jnp.where(up[0] == nodes, -1, up[0])
    root = jnp.argmin(depth_t).astype(jnp.int32)
    return build_euler(parent, depth_t, root, n)


@functools.partial(jax.jit, static_argnames=("b_cap", "chunk"))
def recover_device(
    up: jax.Array,
    depth_t: jax.Array,
    u: jax.Array,
    v: jax.Array,
    beta: jax.Array,
    tree_mask: jax.Array,
    crossing: jax.Array,
    order: jax.Array,
    phase1_accept: jax.Array,
    group_of_edge: jax.Array,
    dirty0: jax.Array,
    budget: jax.Array,
    b_cap: int,
    edge_valid: jax.Array | None = None,
    chunk: int = 32,
):
    """Standalone jitted recovery tail (the unit bench_recovery.py times).

    Same argument conventions as `recover_host` except the order is the
    full (L,) sort permutation and `budget` is a device scalar. Returns
    (accepted (L,) bool, n_accepted int32 scalar).

    The tree is reconstructed from `up[0]` and the Euler-tour O(1)-LCA
    tables built on device, the backend the fused `lgrass_device`
    replay reads (decisions are identical integers; parity vs
    `recover_host` in tests/test_recovery_device.py).
    """
    offtree = ~tree_mask if edge_valid is None else (~tree_mask) & edge_valid
    return _recover_scan(
        _euler_from_lifting(up, depth_t), u, v, beta, offtree, crossing,
        order, phase1_accept, group_of_edge, dirty0,
        jnp.asarray(budget, jnp.int32), b_cap, chunk,
    )[:2]


@functools.partial(jax.jit, static_argnames=("b_cap", "chunk"))
def recover_device_batched(
    up: jax.Array,
    depth_t: jax.Array,
    u: jax.Array,
    v: jax.Array,
    beta: jax.Array,
    tree_mask: jax.Array,
    crossing: jax.Array,
    order: jax.Array,
    phase1_accept: jax.Array,
    group_of_edge: jax.Array,
    dirty0: jax.Array,
    budget: jax.Array,
    b_cap: int,
    edge_valid: jax.Array | None = None,
    chunk: int = 32,
):
    """`recover_device` vmapped over a leading batch axis.

    All array args carry a (B, ...) batch dimension (`budget` is (B,)).
    One dispatch replays every graph's recovery — the standalone unit
    for pipelines that keep phase-1 outputs device-resident, and the one
    bench_recovery.py times against the sync + per-graph host loop.
    Each lane rebuilds its own Euler tables from `up[0]` (see
    `recover_device`); the build is plain gathers/scatters, so the whole
    reconstruction vmaps into the one dispatch.
    """
    def one(bup, bdep, bu, bv, bbeta, btree, bcross, border, bacc, bgrp,
            bdirty, bb, bev):
        return _recover_scan(
            _euler_from_lifting(bup, bdep), bu, bv, bbeta, (~btree) & bev,
            bcross, border, bacc, bgrp, bdirty, bb, b_cap, chunk,
            batched=True,
        )[:2]

    if edge_valid is None:  # all-true mask ≡ the unmasked offtree
        edge_valid = jnp.ones_like(tree_mask, dtype=bool)
    return jax.vmap(one)(
        up, depth_t, u, v, beta, tree_mask, crossing, order,
        phase1_accept, group_of_edge, dirty0,
        jnp.asarray(budget, jnp.int32), edge_valid)
