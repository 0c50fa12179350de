"""Solver-free effective-resistance estimation (after SF-GRASS,
arXiv:2008.07633) — spectral quality at sizes the dense oracle cannot
reach.

The quality tier's ground truth is the dense Laplacian pseudoinverse
(`resistance.dense_effective_resistance_np`): O(n³), dead around 10⁴
nodes. This module estimates the same quantities with nothing but spmv,
fully device-resident and jit/vmap-able:

    R(a, b) = ‖W^{1/2} B L⁺ (e_a − e_b)‖²        (Spielman–Srivastava)

Sketch the edge dimension with P Rademacher probes ξ_p ∈ {±1}^m, lift
them to nodes (y_p = Bᵀ W^{1/2} ξ_p — one scatter-add), and run k
rounds of weighted-Jacobi or Chebyshev iteration on L x_p = y_p (one
spmv per round). Then

    R̂(a, b) = (1/P) Σ_p (x_p[a] − x_p[b])²,     E_ξ[R̂] → R as k → ∞.

Both iterations are polynomial filters p_k(λ) ≈ 1/λ on the
degree-normalised spectrum [0, 2]. The residual 1 − λ·p_k(λ) stays in
[0, 1] for every λ ≥ 0 — for ω ≤ 1 Jacobi trivially, for Chebyshev
because the residual is T_k((θ−λ)/δ)/T_k(θ/δ), which is 1 at λ = 0 and
bounded by 1 in magnitude on [0, 2θ] — so the estimator can truncate
smooth modes but never amplify anything: finite on ANY input, including
disconnected forests (each component's probe load is balanced; null
modes only shift per-component constants, which cancel in endpoint
differences). Two error terms, two knobs:

  * truncation — p_k saturates below a cutoff: Chebyshev resolves 1/λ
    down to λ ≳ lam_min (auto 8/k², the point where k sweeps of the
    accelerated recurrence stop converging), Jacobi down to λ ≳ 1/(ωk).
    Truncation only ever *underestimates* R (p_k(λ) ≤ 1/λ).
  * variance — the Hutchinson sketch carries relative noise ~ sqrt(2/P)
    per edge. Rank fidelity of the criticality ordering is the
    contract: tests/test_spectral_probe.py calibrates against the dense
    pinv at small n (Spearman ≥ 0.95) and records the probe/error
    tradeoff; benchmarks/bench_spectral.py records quality-vs-budget.

Because tr(L_G⁺ L_H) = Σ_{e ∈ H} w_e R_G(u_e, v_e), the per-edge
estimates double as a sparsifier quality score (`trace_similarity`):
bounded by n − #components with equality at H = G, and — estimates
being truncated from below — a lower bound in expectation: preservation
the score reports is preservation the sparsifier actually has.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

# spectrum of D^{-1} L lives in [0, 2]; the filters are built for it
LAM_MAX = 2.0


def auto_lam_min(n_iters: int) -> float:
    """Smallest eigenvalue k Chebyshev rounds can resolve: the interval
    [α, 2] with k·sqrt(2α) ≈ 4 keeps T_k(θ/δ) ≈ cosh(4), i.e. the
    residual uniformly ≲ 0.07 on [α, 2] — tighter α would leave the
    low end unconverged, looser wastes resolution."""
    return min(0.5, 8.0 / float(max(n_iters, 1)) ** 2)


def weighted_degree(u: jax.Array, v: jax.Array, w: jax.Array, n: int,
                    edge_valid: Optional[jax.Array] = None) -> jax.Array:
    """(n,) float32 weighted degrees (padding edges contribute 0)."""
    wm = w if edge_valid is None else jnp.where(edge_valid, w, 0.0)
    wm = wm.astype(jnp.float32)
    deg = jnp.zeros((n,), jnp.float32)
    return deg.at[u].add(wm).at[v].add(wm)


def laplacian_spmv(u: jax.Array, v: jax.Array, w: jax.Array,
                   x: jax.Array, *,
                   edge_valid: Optional[jax.Array] = None,
                   use_spmv_kernel: bool = False) -> jax.Array:
    """y = L x for x: (n, P) — one gather + two scatter-adds (default),
    or the Pallas one-hot kernel (`kernels/spmv.py`) when selected.
    Padding edges are zero-weight self loops either way, so no mask
    arithmetic survives into the inner loop."""
    wm = w if edge_valid is None else jnp.where(edge_valid, w, 0.0)
    wm = wm.astype(jnp.float32)
    if use_spmv_kernel:
        from repro.kernels.ops import laplacian_spmv_edges

        return laplacian_spmv_edges(u, v, wm, x)
    d = x[u] - x[v]
    c = wm[:, None] * d
    return jnp.zeros_like(x).at[u].add(c).at[v].add(-c)


def _solve_jacobi(spmv, dinv, y, n_iters: int, omega) -> jax.Array:
    """x ← x + ω D⁻¹ (y − L x), x₀ = 0: residual filter (1 − ωλ̃)^k."""
    om = jnp.float32(omega)

    def step(_, x):
        return x + om * dinv[:, None] * (y - spmv(x))

    return jax.lax.fori_loop(0, n_iters, step, jnp.zeros_like(y))


def _solve_cheby(spmv, dinv, y, n_iters: int, lam_min) -> jax.Array:
    """Chebyshev iteration on D⁻¹L x = D⁻¹y over [lam_min, LAM_MAX]
    (Saad, Alg. 12.1). Scalars ride the carry as float32 so the x64 CI
    leg cannot silently promote the recurrence."""
    lam_min = jnp.float32(lam_min)
    theta = jnp.float32(0.5) * (jnp.float32(LAM_MAX) + lam_min)
    delta = jnp.float32(0.5) * (jnp.float32(LAM_MAX) - lam_min)
    sigma1 = theta / delta
    c = dinv[:, None] * y

    def m_apply(x):
        return dinv[:, None] * spmv(x)

    def step(_, state):
        x, r, d, rho = state
        x = x + d
        r = r - m_apply(d)
        rho_new = jnp.float32(1.0) / (jnp.float32(2.0) * sigma1 - rho)
        d = rho_new * rho * d + (jnp.float32(2.0) * rho_new / delta) * r
        return x, r, d, rho_new

    state = (jnp.zeros_like(c), c, c / theta, jnp.float32(1.0) / sigma1)
    x, _, _, _ = jax.lax.fori_loop(0, n_iters, step, state)
    return x


@functools.partial(
    jax.jit,
    static_argnames=("n", "n_probes", "n_iters", "method",
                     "use_spmv_kernel"))
@jax.named_scope("PROBE")  # every op of the estimator, for the trace
def _probe_er_program(
    u: jax.Array,
    v: jax.Array,
    w: jax.Array,
    edge_valid: Optional[jax.Array],
    qu: jax.Array,
    qv: jax.Array,
    key: jax.Array,
    omega: jax.Array,
    lam_min: jax.Array,
    n: int,
    n_probes: int,
    n_iters: int,
    method: str,
    use_spmv_kernel: bool,
) -> jax.Array:
    """The device program: probes → lift → k spmv rounds → R̂ gathers."""
    m = u.shape[0]
    wm = w if edge_valid is None else jnp.where(edge_valid, w, 0.0)
    wm = wm.astype(jnp.float32)

    xi = jax.random.rademacher(key, (m, n_probes), jnp.float32)
    sw = jnp.sqrt(wm)[:, None] * xi                    # W^{1/2} ξ
    y = (jnp.zeros((n, n_probes), jnp.float32)
         .at[u].add(sw).at[v].add(-sw))                # Bᵀ W^{1/2} ξ

    deg = weighted_degree(u, v, wm, n)
    dinv = jnp.where(deg > 0.0, 1.0 / deg, 0.0).astype(jnp.float32)

    def spmv(x):
        return laplacian_spmv(u, v, wm, x,
                              use_spmv_kernel=use_spmv_kernel)

    if method == "jacobi":
        x = _solve_jacobi(spmv, dinv, y, n_iters, omega)
    elif method == "cheby":
        x = _solve_cheby(spmv, dinv, y, n_iters, lam_min)
    else:
        raise ValueError(f"unknown probe method {method!r}")

    d = x[qu] - x[qv]                                  # (Lq, P)
    return jnp.sum(d * d, axis=1, dtype=jnp.float32) / jnp.float32(
        n_probes)


def probe_edge_resistance(
    u: jax.Array,
    v: jax.Array,
    w: jax.Array,
    n: int,
    qu: Optional[jax.Array] = None,
    qv: Optional[jax.Array] = None,
    *,
    n_probes: int = 64,
    n_iters: int = 64,
    method: str = "cheby",
    omega: float = 2.0 / 3.0,
    lam_min: Optional[float] = None,
    seed: int = 0,
    key: Optional[jax.Array] = None,
    edge_valid: Optional[jax.Array] = None,
    use_spmv_kernel: bool = False,
) -> jax.Array:
    """Solver-free approximate effective resistances R̂(qu_i, qv_i).

    Queries default to the graph's own edge list — the shape the
    quality tiers need (per-edge R̂ feeds both the criticality ordering
    and the trace-similarity score). `method` picks the filter:
    "cheby" (default — sharper 1/λ resolution per spmv) or "jacobi"
    (the plainest smoother; `omega` is its damping). `lam_min` bounds
    the Chebyshev interval from below (None → `auto_lam_min(n_iters)`).
    With `edge_valid`, padding slots carry zero weight everywhere —
    they never touch degrees, probes' lift, or the spmv — and R̂ is
    returned for every query slot, padded queries included (node 0
    against itself → 0.0). Padding does reshape the Rademacher draw
    ((L_pad, P) vs (L, P)), so a padded run is a different
    same-distribution sketch than an unpadded one, with the same
    calibration contract.

    Endpoints in the same component get calibrated estimates
    (tests/test_spectral_probe.py). Cross-component queries — where the
    true R is infinite — return finite filter-saturated values:
    bounded garbage by design, pinned in the degenerate tests.
    """
    if qu is None:
        qu = u
    if qv is None:
        qv = v
    if key is None:
        key = jax.random.PRNGKey(seed)
    if lam_min is None:
        lam_min = auto_lam_min(n_iters)
    return _probe_er_program(
        jnp.asarray(u, jnp.int32), jnp.asarray(v, jnp.int32),
        jnp.asarray(w, jnp.float32),
        None if edge_valid is None else jnp.asarray(edge_valid, bool),
        jnp.asarray(qu, jnp.int32), jnp.asarray(qv, jnp.int32),
        key, jnp.float32(omega), jnp.float32(lam_min),
        n=int(n), n_probes=int(n_probes), n_iters=int(n_iters),
        method=method, use_spmv_kernel=bool(use_spmv_kernel))


@functools.partial(
    jax.jit,
    static_argnames=("n", "n_probes", "n_iters", "method",
                     "use_spmv_kernel"))
def _probe_er_batched_program(u, v, w, edge_valid, keys, omega, lam_min,
                              n, n_probes, n_iters, method,
                              use_spmv_kernel):
    return jax.vmap(
        lambda bu, bv, bw, bev, bk: _probe_er_program(
            bu, bv, bw, bev, bu, bv, bk, omega, lam_min, n=n,
            n_probes=n_probes, n_iters=n_iters, method=method,
            use_spmv_kernel=use_spmv_kernel)
    )(u, v, w, edge_valid, keys)


def probe_edge_resistance_batched(
    u: jax.Array,
    v: jax.Array,
    w: jax.Array,
    edge_valid: jax.Array,
    n: int,
    *,
    n_probes: int = 64,
    n_iters: int = 64,
    method: str = "cheby",
    omega: float = 2.0 / 3.0,
    lam_min: Optional[float] = None,
    seed: int = 0,
) -> jax.Array:
    """`probe_edge_resistance` vmapped over a padded `GraphBatch`:
    (B, L_max) edge arrays in, (B, L_max) per-edge R̂ out, one dispatch.
    Each lane draws its own probe key: lane i is bit-identical to a
    single-graph `probe_edge_resistance` call on the same padded arrays
    with seed `seed + i` (asserted in tests/test_spectral_probe.py).
    Against an UNpadded run the estimates differ only through the probe
    sample — the Rademacher draw is shaped (L_max, P), so padding
    changes which same-distribution sketch is drawn, not its quality;
    the calibration contract holds for both."""
    if lam_min is None:
        lam_min = auto_lam_min(n_iters)
    b = u.shape[0]
    keys = jax.vmap(lambda s: jax.random.PRNGKey(s))(
        jnp.arange(seed, seed + b, dtype=jnp.uint32))
    return _probe_er_batched_program(
        jnp.asarray(u, jnp.int32), jnp.asarray(v, jnp.int32),
        jnp.asarray(w, jnp.float32), jnp.asarray(edge_valid, bool),
        keys, jnp.float32(omega), jnp.float32(lam_min),
        n=int(n), n_probes=int(n_probes), n_iters=int(n_iters),
        method=method, use_spmv_kernel=False)


def probe_criticality(w: jax.Array, r_hat: jax.Array) -> jax.Array:
    """Solver-free criticality proxy w(e) · R̂(u, v) — the estimator's
    stand-in for `resistance.criticality`'s w(e) · R_T(u, v) sort key,
    with the *graph* (not tree) resistance under the hood."""
    return w.astype(jnp.float32) * r_hat


def trace_similarity(w: jax.Array, r_hat: jax.Array,
                     mask: Optional[jax.Array] = None) -> jax.Array:
    """Approximate tr(L_G⁺ L_H) = Σ_{e ∈ H} w_e R_G(u_e, v_e), with H
    the `mask`-selected subgraph and R̂ estimated once on G for every
    edge. Scalar in [0, n − #components]; equality at H = G; larger is
    spectrally closer. The truncated filter underestimates each term,
    so in expectation this is a LOWER bound on the true trace."""
    terms = w.astype(jnp.float32) * r_hat
    if mask is not None:
        terms = jnp.where(mask, terms, 0.0)
    return jnp.sum(terms, dtype=jnp.float32)
