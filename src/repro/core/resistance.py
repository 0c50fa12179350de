"""Tree effective resistance in O(L) (LGRASS §3.2, after feGRASS).

For a spanning tree T, the effective resistance between u and v is the sum
of 1/w along the unique tree path:

    R_T(u, v) = rd[u] + rd[v] - 2 * rd[lca(u, v)]

where rd[x] = sum of 1/w on the root->x path. rd is computed with the same
binary-lifting tables as the LCA (a weighted variant), so every node
evaluates its root-path sum in O(log depth) fully-vectorised rounds — the
TPU equivalent of the paper's linear sequential accumulation.

Criticality of an off-tree edge (the sort key, §3.3):  w(e) * R_T(u, v).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.lca import LiftingTables


class ResistanceTables(NamedTuple):
    rd: jax.Array  # (n,) float32 — root-path resistance sum


@functools.partial(jax.jit, static_argnames=("n",))
def node_parent_inv_w(
    u: jax.Array,
    v: jax.Array,
    w: jax.Array,
    tree_mask: jax.Array,
    parent: jax.Array,
    n: int,
) -> jax.Array:
    """inv_w[c] = 1/w of the tree edge (c, parent[c]); 0 for the root."""
    child_u = jnp.where(tree_mask & (parent[u] == v), u, -1)
    child_v = jnp.where(tree_mask & (parent[v] == u), v, -1)
    inv = jnp.zeros((n,), dtype=jnp.float32)
    inv = inv.at[jnp.where(child_u >= 0, child_u, n)].set(
        jnp.where(child_u >= 0, 1.0 / w, 0.0), mode="drop"
    )
    inv = inv.at[jnp.where(child_v >= 0, child_v, n)].set(
        jnp.where(child_v >= 0, 1.0 / w, 0.0), mode="drop"
    )
    return inv


@jax.jit
def root_path_sums(t: LiftingTables, inv_w: jax.Array) -> ResistanceTables:
    """rd[x] = sum of inv_w along root->x, via weighted binary lifting."""
    log, n = t.up.shape

    def build(carry, _):
        up_k, ws_k = carry
        ws_next = ws_k + ws_k[up_k]
        up_next = up_k[up_k]
        return (up_next, ws_next), (up_k, ws_k)

    (_, _), (ups, wsums) = jax.lax.scan(
        build, (t.up[0], inv_w), None, length=log
    )

    nodes = jnp.arange(n, dtype=jnp.int32)
    rem = t.depth

    def climb(i, state):
        cur, acc, rem = state
        k = log - 1 - i
        take = (rem >> k) & 1
        acc = acc + jnp.where(take == 1, wsums[k][cur], 0.0)
        cur = jnp.where(take == 1, ups[k][cur], cur)
        return cur, acc, rem & ~(1 << k)

    _, rd, _ = jax.lax.fori_loop(
        0, log, climb, (nodes, jnp.zeros((n,), jnp.float32), rem)
    )
    return ResistanceTables(rd=rd)


@jax.jit
def edge_resistance(
    t: LiftingTables, r: ResistanceTables, u: jax.Array, v: jax.Array,
    edge_lca: jax.Array,
) -> jax.Array:
    return r.rd[u] + r.rd[v] - 2.0 * r.rd[edge_lca]


@jax.jit
def criticality(
    t: LiftingTables,
    r: ResistanceTables,
    u: jax.Array,
    v: jax.Array,
    w: jax.Array,
    edge_lca: jax.Array,
) -> jax.Array:
    """Spectral criticality w(e) * R_T(e) — the greedy's sort key."""
    return w * edge_resistance(t, r, u, v, edge_lca)


# ---------------------------------------------------------------------------
# Dense ground truth (host / numpy, float64): the O(n^3) pseudoinverse
# formulation the linear pipeline is validated against. Small-n only —
# tests/test_spectral_quality.py uses these to pin the sparsifier's
# *spectral* quality directly, so a refactor cannot silently degrade
# output while staying self-consistent with its own oracle.
# ---------------------------------------------------------------------------

import numpy as np  # noqa: E402  (host-only helpers below)


def dense_laplacian_np(n, u, v, w, mask=None) -> np.ndarray:
    """(n, n) float64 graph Laplacian of the (optionally masked) edges."""
    L = np.zeros((n, n), np.float64)
    if mask is None:
        mask = np.ones(len(u), bool)
    for x, y, ww, keep in zip(np.asarray(u), np.asarray(v),
                              np.asarray(w, np.float64), np.asarray(mask)):
        if not keep:
            continue
        x, y = int(x), int(y)
        L[x, x] += ww
        L[y, y] += ww
        L[x, y] -= ww
        L[y, x] -= ww
    return L


def dense_effective_resistance_np(L_dense: np.ndarray, u, v) -> np.ndarray:
    """Effective resistances R(u_i, v_i) via the Laplacian pseudoinverse.

    R(a, b) = (e_a - e_b)^T L^+ (e_a - e_b) — the textbook definition the
    tree-path sums of `root_path_sums` + LCA reproduce exactly when the
    graph *is* a tree (asserted by the quality tests).
    """
    P = np.linalg.pinv(L_dense, hermitian=True)
    u = np.asarray(u)
    v = np.asarray(v)
    return P[u, u] + P[v, v] - 2.0 * P[u, v]


def spearman_np(a, b) -> float:
    """Tie-aware Spearman rank correlation (no scipy in the pinned
    environment; ties get average ranks, the textbook convention)."""
    def _ranks(x):
        x = np.asarray(x, np.float64)
        _, inv, cnt = np.unique(x, return_inverse=True, return_counts=True)
        start = np.cumsum(cnt) - cnt
        return (start + (cnt - 1) / 2.0)[inv]

    ra, rb = _ranks(a), _ranks(b)
    ra -= ra.mean()
    rb -= rb.mean()
    denom = np.sqrt((ra * ra).sum() * (rb * rb).sum())
    if denom == 0.0:  # constant ranks on either side: define as perfect
        return 1.0
    return float((ra * rb).sum() / denom)


def probe_calibration_np(n, u, v, w, qu, qv, qw, r_hat,
                         mask=None) -> dict:
    """The calibration seam between the solver-free estimator
    (core/spectral_probe.py) and this module's dense pinv oracle.

    Computes the dense ground-truth R(qu_i, qv_i) on the (optionally
    masked) graph and scores `r_hat` against it: Spearman rank
    correlation of the raw resistances AND of the criticality ordering
    (qw · R — the quantity the sparsifier actually sorts by), plus
    relative-error quantiles. Small n only (the point of the seam:
    the estimator earns trust here, then runs where this cannot).
    """
    L = dense_laplacian_np(n, u, v, w, mask=mask)
    r_dense = dense_effective_resistance_np(L, qu, qv)
    r_hat = np.asarray(r_hat, np.float64)
    qw = np.asarray(qw, np.float64)
    rel = np.abs(r_hat - r_dense) / np.maximum(r_dense, 1e-12)
    return dict(
        r_dense=r_dense,
        spearman_er=spearman_np(r_hat, r_dense),
        spearman_crit=spearman_np(qw * r_hat, qw * r_dense),
        med_rel_err=float(np.median(rel)) if len(rel) else 0.0,
        max_rel_err=float(rel.max()) if len(rel) else 0.0,
    )


def spectral_bounds_np(L_full: np.ndarray, L_sub: np.ndarray,
                       tol: float = 1e-9):
    """(lam_min, lam_max) of the pencil x^T L_sub x / x^T L_full x.

    Restricted to range(L_full) (the all-ones null space — and any
    disconnected-component null directions — are projected out): with
    L_full = U diag(d) U^T, W = U_+ diag(d_+^{-1/2}), the pencil spectrum
    is eig(W^T L_sub W). For a subgraph sparsifier 0 <= lam <= 1, and
    lam_min is the quality figure: how much of every quadratic form the
    sparsifier preserves.
    """
    d, U = np.linalg.eigh(L_full)
    keep = d > tol * max(float(d[-1]), 1.0)
    W = U[:, keep] / np.sqrt(d[keep])[None, :]
    M = W.T @ L_sub @ W
    lam = np.linalg.eigvalsh((M + M.T) / 2.0)
    return float(lam[0]), float(lam[-1])
