"""LGRASS end-to-end pipeline (Fig. 1b/1c): the public sparsifier API.

    EFF  -> graph BFS + depth-scaled effective weights      (bfs.py)
    MST  -> Borůvka maximum spanning tree                   (mst.py)
    LCA  -> binary lifting + root-subtree shortcut          (lca.py)
    RES  -> root-path resistance sums -> criticality        (resistance.py)
    SORT -> 4-pass radix sort on IEEE-754 keys              (sort.py)
    MARK -> per-group greedy, basic or lockstep-parallel    (marking.py)
    REC  -> greedy replay of non-crossing edges             (recovery.py)

All stages are jit-compiled device programs. `lgrass_device` fuses the
whole pipeline — phase 1 *and* the Algorithm-6 recovery replay — into a
single dispatch, and `lgrass_device_batched` vmaps it over a padded
graph batch, so the serving path never syncs to host between phases.
The host recovery tail (`recovery.recover_host`) is retained as the
fidelity oracle behind `recovery="host"`.

Measurement lives inside the program. Every operation of the device
programs sits in one `jax.named_scope` per stage (`STAGES`), so the
compiled HLO's `op_name` metadata, and a profiler trace through it,
name the stage of each device op. Each while loop counts its own rounds
in its carry; the programs return the counts as one int32 vector in
`LOOPS` order (`SparsifyResult.loop_rounds`). `lgrass_sparsify` marks
its host phases with `jax.profiler.TraceAnnotation` spans (`lgrass.*`),
which a profiler records on the device planes' clock.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import _host as H
from repro.core.baseline import default_budget
from repro.core.bfs import (
    bfs,
    bfs_counted,
    effective_weights,
    finite_depth,
    root_tree_euler,
    select_root,
)
from repro.core.graph import Graph
from repro.core.lca import (
    LiftingTables,
    build_euler,
    build_lifting,
    lca_euler,
    lca_with_shortcut,
)
from repro.core.marking import (
    GroupLayout,
    Phase1Result,
    build_group_layout,
    group_keys,
    phase1_basic,
    phase1_edge_views,
    phase1_parallel,
    run_phase1,
)
from repro.core.mst import boruvka_mst_counted
from repro.core.pow2 import log2_ceil, next_pow2
from repro.core.recovery import _recover_scan, recover_host
from repro.core.resistance import (
    criticality,
    node_parent_inv_w,
    root_path_sums,
)
from repro.core.sort import sort_f32_desc_stable

# Device recovery holds accepted edges in a (b_cap,) buffer; b_cap is a
# compiled constant, so small budgets share one bucketed program.
B_CAP_FLOOR = 8

# The paper's stages, one `jax.named_scope` each, in pipeline order.
STAGES = ("EFF", "MST", "LCA", "RES", "SORT", "MARK", "REC")

# The device programs' while loops, in the order of their `loop_rounds`
# vector: the graph BFS (EFF); the tree BFS (LCA, "levels" engine only,
# 0 under "doubling"); the Borůvka rounds and the pointer-jumping rounds
# of their contractions, summed (MST); the MARK schedule's steps; the
# recovery replay's blocks (REC, 0 where the host replays). Each loop
# but "mst_jump", which runs inside "mst", is called under a named
# scope of its own name, so its `while` instruction's op_name ends in
# "<loop>/while" (jit(...) components may come between).
LOOPS = ("bfs", "tree", "mst", "mst_jump", "mark", "rec")


def _loop_vector(rounds: dict) -> jax.Array:
    """(len(LOOPS),) int32 round counts; loops absent from `rounds` did
    not run."""
    return jnp.stack([jnp.asarray(rounds.get(k, 0), jnp.int32)
                      for k in LOOPS])


def _bucket_b_cap(budgets) -> int:
    """Static accept-buffer size covering every budget in `budgets`."""
    need = max([int(b) for b in budgets] + [1])
    return max(next_pow2(need), B_CAP_FLOOR)


@dataclasses.dataclass
class SparsifyResult:
    edge_mask: np.ndarray       # (L,) bool — tree + accepted off-tree edges
    tree_mask: np.ndarray       # (L,) bool
    accepted_mask: np.ndarray   # (L,) bool — accepted off-tree edges
    n_accepted: int
    n_groups: int
    n_overflow_groups: int
    n_dirty: int
    # rounds each while loop of the device program ran, by `LOOPS` name
    loop_rounds: Dict[str, int] = dataclasses.field(default_factory=dict)
    # columns (buffer width + block) of the recovery replay's cover
    # tables, summed over its blocks (0 where the host replays)
    rec_table_cols: int = 0


def _phase1_program(
    u: jax.Array,
    v: jax.Array,
    w: jax.Array,
    n: int,
    k_cap: int,
    parallel: bool,
    lift_levels: int | None,
    edge_valid: jax.Array | None,
    schedule: str = "chunked",
    p1_chunk: int | None = None,
    use_euler_lca: bool = True,
    use_tree_kernel: bool = False,
    bfs_engine: str = "doubling",
):
    """EFF→MST→LCA→RES→SORT→MARK(phase 1), optionally padding-masked.

    With edge_valid=None this is exactly the single-graph device program.
    With a padding mask (batched pipeline, see `GraphBatch`) every stage
    is threaded so padding edges can never enter the tree or a crossing
    group, and all real-slot outputs are bit-identical to an unpadded run
    of the same graph (binary-lifting depth only grows with n, and extra
    levels are provable no-ops for both LCA climbs and root-path sums).

    schedule/p1_chunk select the MARK engine (marking.run_phase1):
    "chunked" (default, block size p1_chunk or auto-pow2 ~sqrt(L)) or
    "scan" (the legacy engines; `parallel` picks lockstep vs basic). All
    schedules are bit-identical. use_euler_lca additionally builds the
    Euler-tour O(1)-LCA tables once and backs the chunked cover tables
    with them; use_tree_kernel routes those tables through the Pallas
    tree-distance kernel instead.

    bfs_engine picks the two traversal passes' implementation
    (bfs.py): "doubling" (default) runs the graph pass as the
    O(log n)-round hop-doubling engine and replaces the tree pass with
    the Euler-tour rooting (`root_tree_euler` — no BFS at all);
    "levels" keeps both passes level-synchronous. On the pipeline's
    legal inputs (connected graphs, graph.py's contract) outputs are
    bit-identical and this is purely a performance knob
    (tests/test_bfs_doubling.py; diameter-bound feeder chains are
    where "doubling" wins). The BFS engines themselves agree on ANY
    input including disconnected forests, but downstream LCA values
    for *unreachable* endpoints are backend-dependent garbage under
    every backend, so full-pipeline parity is only promised where the
    pipeline is defined.
    """
    with jax.named_scope("EFF"):
        root = select_root(u, v, n, edge_valid)
        with jax.named_scope("bfs"):
            depth_g, _, bfs_rounds = bfs_counted(u, v, n, root,
                                                 edge_valid, bfs_engine)
        eff = effective_weights(u, v, w, depth_g, n, edge_valid)

    with jax.named_scope("MST"):
        perm_eff = sort_f32_desc_stable(eff, valid=edge_valid)
        rank_eff = (
            jnp.zeros_like(perm_eff)
            .at[perm_eff]
            .set(jnp.arange(perm_eff.shape[0], dtype=jnp.int32))
        )
        with jax.named_scope("mst"):
            tree_mask, mst_rounds, jump_rounds = boruvka_mst_counted(
                u, v, rank_eff, n, edge_valid)
    rounds = dict(bfs=bfs_rounds, mst=mst_rounds, mst_jump=jump_rounds)

    # the Pallas kernel path takes precedence inside ball_pair_table, so
    # skip the (then-unused) Euler build when it is selected. Built for
    # ANY schedule: the fused recovery replay consumes it too.
    want_euler = use_euler_lca and not use_tree_kernel
    euler = None
    with jax.named_scope("LCA"):
        if bfs_engine == "doubling":
            # exact O(log n) tree rooting via the Euler tour — the tree's
            # depth/parent are unique, so no fixpoint iteration is
            # needed; the rooted tour doubles as the O(1)-LCA tables (no
            # second tour construction via build_euler)
            depth_t, parent_t, euler = root_tree_euler(
                u, v, n, root, tree_mask, with_euler=want_euler)
        else:
            with jax.named_scope("tree"):
                depth_t, parent_t, rounds["tree"] = bfs_counted(
                    u, v, n, root, tree_mask, bfs_engine)
            if want_euler:
                euler = build_euler(parent_t, depth_t, root, n)
        t = build_lifting(parent_t, depth_t, n, levels=lift_levels)
        if euler is not None:
            # O(1) gathers per edge instead of L-wide lifting climbs; the
            # LCA of two reachable nodes is backend-independent, so every
            # downstream value is bit-identical
            elca = lca_euler(euler, u, v)
        else:
            elca = lca_with_shortcut(t, root, u, v)

    with jax.named_scope("RES"):
        inv_w = node_parent_inv_w(u, v, w, tree_mask, parent_t, n)
        r = root_path_sums(t, inv_w)
        crit = criticality(t, r, u, v, w, elca)
        beta = jnp.maximum(
            jnp.minimum(depth_t[u], depth_t[v]) - depth_t[elca], 1
        ).astype(jnp.int32)

    with jax.named_scope("SORT"):
        is_offtree = (~tree_mask if edge_valid is None
                      else (~tree_mask) & edge_valid)
        hi, lo, crossing = group_keys(t, root, u, v, elca, is_offtree)
        layout = build_group_layout(crit, hi, lo, crossing, edge_valid)
        su, sv, sbeta = u[layout.perm], v[layout.perm], beta[layout.perm]

    with jax.named_scope("MARK"), jax.named_scope("mark"):
        p1 = run_phase1(t, su, sv, sbeta, layout, k_cap=k_cap,
                        schedule=schedule, parallel=parallel,
                        chunk=p1_chunk, use_tree_kernel=use_tree_kernel,
                        euler=euler if schedule == "chunked" else None)
    rounds["mark"] = p1.rounds
    d = dict(
        tree_mask=tree_mask,
        parent_t=parent_t,
        depth_t=depth_t,
        up=t.up,
        beta=beta,
        crit=crit,
        crossing=crossing,
        perm=layout.perm,
        gidx=layout.gidx,
        accept_sorted=p1.accept,
        group_overflow=p1.group_overflow,
        n_groups=layout.n_groups,
    )
    return d, euler, rounds


@functools.partial(jax.jit,
                   static_argnames=("n", "k_cap", "parallel", "lift_levels",
                                    "schedule", "p1_chunk", "use_euler_lca",
                                    "use_tree_kernel", "bfs_engine"))
def phase1_device(
    u: jax.Array,
    v: jax.Array,
    w: jax.Array,
    n: int,
    k_cap: int = 32,
    parallel: bool = True,
    lift_levels: int | None = None,
    schedule: str = "chunked",
    p1_chunk: int | None = None,
    use_euler_lca: bool = True,
    use_tree_kernel: bool = False,
    bfs_engine: str = "doubling",
):
    """The phase-1 device program: EFF→MST→LCA→RES→SORT→MARK.

    Returns everything the host recovery tail needs, and the phase-1
    loops' `loop_rounds`. This function is the unit the multi-pod dry-run
    lowers and compiles.
    """
    return _phase1_outputs(u, v, w, n, k_cap, parallel, lift_levels, None,
                           schedule, p1_chunk, use_euler_lca,
                           use_tree_kernel, bfs_engine)


def _phase1_outputs(*args):
    """`_phase1_program`'s outputs with the round counts attached."""
    d, _, rounds = _phase1_program(*args)
    with jax.named_scope("MARK"):
        d["loop_rounds"] = _loop_vector(rounds)
    return d


@functools.partial(jax.jit,
                   static_argnames=("n", "k_cap", "parallel", "lift_levels",
                                    "schedule", "p1_chunk", "use_euler_lca",
                                    "use_tree_kernel", "bfs_engine"))
def phase1_device_batched(
    u: jax.Array,
    v: jax.Array,
    w: jax.Array,
    edge_valid: jax.Array,
    n: int,
    k_cap: int = 32,
    parallel: bool = True,
    lift_levels: int | None = None,
    schedule: str = "chunked",
    p1_chunk: int | None = None,
    use_euler_lca: bool = True,
    use_tree_kernel: bool = False,
    bfs_engine: str = "doubling",
):
    """`phase1_device` vmapped over a leading batch axis.

    Args are (B, L_max) padded edge lists plus the (B, L_max) padding
    mask; `n` is the shared node pad n_max. One compile + one dispatch
    covers the whole batch — the amortisation the serving path needs.
    """
    return jax.vmap(
        lambda bu, bv, bw, bev: _phase1_outputs(
            bu, bv, bw, n, k_cap, parallel, lift_levels, bev,
            schedule, p1_chunk, use_euler_lca, use_tree_kernel,
            bfs_engine
        )
    )(u, v, w, edge_valid)


def _lgrass_program(
    u: jax.Array,
    v: jax.Array,
    w: jax.Array,
    budget: jax.Array,
    n: int,
    k_cap: int,
    parallel: bool,
    lift_levels: int | None,
    b_cap: int,
    edge_valid: jax.Array | None,
    use_tree_kernel: bool,
    chunk: int = 32,
    schedule: str = "chunked",
    p1_chunk: int | None = None,
    use_euler_lca: bool = True,
    bfs_engine: str = "doubling",
    batched: bool = False,
):
    """Phase 1 + device recovery fused into one program (Fig. 1b end-to-end).

    The MARK outputs are scattered back to edge-id order on device
    (`phase1_edge_views`), the global criticality order is taken over all
    off-tree edges, and the Algorithm-6 replay runs as a lax.scan — no
    host round-trip anywhere. Only scalars and the final masks leave the
    device. `batched` marks the trace under vmap (`_recover_scan`).
    """
    d, euler, rounds = _phase1_program(
        u, v, w, n, k_cap, parallel, lift_levels, edge_valid, schedule,
        p1_chunk, use_euler_lca, use_tree_kernel, bfs_engine)
    t = LiftingTables(up=d["up"], depth=d["depth_t"])
    tree_mask = d["tree_mask"]
    crossing = d["crossing"]
    with jax.named_scope("MARK"):
        accept_by_edge, group_of_edge, dirty0 = phase1_edge_views(
            d["perm"], d["gidx"], d["accept_sorted"], d["group_overflow"],
            crossing,
        )
    with jax.named_scope("SORT"):
        offtree = (~tree_mask if edge_valid is None
                   else (~tree_mask) & edge_valid)
        keys = jnp.where(offtree, d["crit"], -jnp.inf)
        order = sort_f32_desc_stable(keys)
    with jax.named_scope("REC"):
        with jax.named_scope("rec"):
            accepted, n_accepted, rounds["rec"], table_cols = _recover_scan(
                t, u, v, d["beta"], offtree, crossing, order,
                accept_by_edge, group_of_edge, dirty0,
                jnp.asarray(budget, jnp.int32), b_cap, use_tree_kernel,
                chunk, euler, batched,
            )
        depth_fin = finite_depth(d["depth_t"])
        return dict(
            tree_mask=tree_mask,
            accepted=accepted,
            n_accepted=n_accepted,
            n_groups=d["n_groups"],
            n_overflow_groups=jnp.sum(
                d["group_overflow"].astype(jnp.int32)),
            n_dirty=jnp.sum(dirty0.astype(jnp.int32)),
            tree_depth_max=jnp.max(depth_fin),
            loop_rounds=_loop_vector(rounds),
            rec_table_cols=table_cols,
        )


@functools.partial(jax.jit,
                   static_argnames=("n", "k_cap", "parallel", "lift_levels",
                                    "b_cap", "use_tree_kernel", "chunk",
                                    "schedule", "p1_chunk", "use_euler_lca",
                                    "bfs_engine"))
def lgrass_device(
    u: jax.Array,
    v: jax.Array,
    w: jax.Array,
    budget: jax.Array,
    n: int,
    k_cap: int = 32,
    parallel: bool = True,
    lift_levels: int | None = None,
    b_cap: int = B_CAP_FLOOR,
    use_tree_kernel: bool = False,
    chunk: int = 32,
    schedule: str = "chunked",
    p1_chunk: int | None = None,
    use_euler_lca: bool = True,
    bfs_engine: str = "doubling",
):
    """The full device program: phase 1 fused with the recovery replay.

    `budget` is a traced int32 scalar (one compile serves any budget up
    to the static buffer bound `b_cap`). Returns final masks, scalar
    stats and the `loop_rounds` vector only — the first point data
    leaves the device.
    """
    return _lgrass_program(u, v, w, budget, n, k_cap, parallel,
                           lift_levels, b_cap, None, use_tree_kernel, chunk,
                           schedule, p1_chunk, use_euler_lca, bfs_engine)


def _lgrass_batched_impl(
    u: jax.Array,
    v: jax.Array,
    w: jax.Array,
    edge_valid: jax.Array,
    budget: jax.Array,
    n: int,
    k_cap: int = 32,
    parallel: bool = True,
    lift_levels: int | None = None,
    b_cap: int = B_CAP_FLOOR,
    use_tree_kernel: bool = False,
    chunk: int = 32,
    schedule: str = "chunked",
    p1_chunk: int | None = None,
    use_euler_lca: bool = True,
    bfs_engine: str = "doubling",
):
    return jax.vmap(
        lambda bu, bv, bw, bev, bb: _lgrass_program(
            bu, bv, bw, bb, n, k_cap, parallel, lift_levels, b_cap, bev,
            use_tree_kernel, chunk, schedule, p1_chunk, use_euler_lca,
            bfs_engine, batched=True,
        )
    )(u, v, w, edge_valid, budget)


_BATCHED_STATICS = ("n", "k_cap", "parallel", "lift_levels", "b_cap",
                    "use_tree_kernel", "chunk", "schedule", "p1_chunk",
                    "use_euler_lca", "bfs_engine")

lgrass_device_batched = jax.jit(
    _lgrass_batched_impl, static_argnames=_BATCHED_STATICS)
lgrass_device_batched.__doc__ = (
    """`lgrass_device` vmapped over a padded batch: ONE dispatch runs
    phase 1 *and* recovery for every graph — no host round-trip between
    phases. `budget` is a (B,) int32 vector (per-graph budgets).
    `loop_rounds` is (B, len(LOOPS)): under vmap each lane's loop carry
    stops at its own condition, so every row counts its own graph."""
)

# The serving plane's steady-state variant: the padded edge arrays and
# the budget vector are donated, so XLA reuses their device buffers for
# the outputs instead of allocating fresh ones every request. Callers
# must hand over arrays they will never touch again (the service builds
# them fresh from its host staging pool each chunk; see
# serve/sparsify_service.py). Same program, bit-identical outputs —
# donation only changes buffer lifetime.
lgrass_device_batched_donated = jax.jit(
    _lgrass_batched_impl, static_argnames=_BATCHED_STATICS,
    donate_argnums=(0, 1, 2, 3, 4))


def _result_from_device(d: dict, i: Optional[int], L: int) -> SparsifyResult:
    """Slice one graph's `SparsifyResult` out of (batched) device outputs."""
    pick = (lambda x: x[i]) if i is not None else (lambda x: x)
    tree_mask = np.asarray(pick(d["tree_mask"])).astype(bool)[:L]
    accepted = np.asarray(pick(d["accepted"])).astype(bool)[:L]
    return SparsifyResult(
        edge_mask=tree_mask | accepted,
        tree_mask=tree_mask,
        accepted_mask=accepted,
        n_accepted=int(pick(d["n_accepted"])),
        n_groups=int(pick(d["n_groups"])),
        n_overflow_groups=int(pick(d["n_overflow_groups"])),
        n_dirty=int(pick(d["n_dirty"])),
        loop_rounds=_rounds_by_loop(pick(d["loop_rounds"])),
        rec_table_cols=int(pick(d["rec_table_cols"])),
    )


def _rounds_by_loop(row) -> Dict[str, int]:
    """One graph's `loop_rounds` vector as {LOOPS name: rounds}."""
    return dict(zip(LOOPS, np.asarray(row).astype(int).tolist()))


def lgrass_sparsify(
    g: Graph,
    budget: Optional[int] = None,
    k_cap: int = 32,
    parallel: bool = True,
    auto_lift_bound: bool = False,
    recovery: str = "device",
    b_cap: Optional[int] = None,
    use_tree_kernel: bool = False,
    chunk: int = 32,
    schedule: str = "chunked",
    p1_chunk: Optional[int] = None,
    use_euler_lca: bool = True,
    bfs_engine: str = "doubling",
) -> SparsifyResult:
    """Run LGRASS on a host graph; returns the sparsifier edge mask.

    recovery: "device" (default) runs the fused `lgrass_device` program —
    one dispatch end-to-end; "host" runs phase 1 on device and replays
    Algorithm 6 with the numpy oracle (`recover_host`). Both are
    bit-identical (tests/test_recovery_device.py).

    schedule/p1_chunk: the phase-1 marking engine — "chunked" (default;
    block size p1_chunk, or an auto pow2 ~sqrt(L)) or "scan" (legacy
    per-slot engines, `parallel` picking lockstep vs basic). All
    schedules are bit-identical (tests/test_marking_chunked.py);
    use_euler_lca (default on) backs the chunked cover tables with the
    Euler-tour O(1) LCA built once per graph — measured faster than the
    lifting climbs at every size on CPU, including the build.

    bfs_engine: the traversal engine for both BFS passes — "doubling"
    (default: hop-doubling graph BFS + Euler-tour tree rooting,
    O(log n) rounds on chain-like inputs) or "levels" (the legacy
    level-synchronous passes). Bit-identical outputs
    (tests/test_bfs_doubling.py); benchmarks/bench_bfs.py measures the
    difference on the diameter-bound feeder family.

    auto_lift_bound: measure the tree depth first (one extra BFS) and
    build depth-bounded lifting tables — identical output, ~log(N)/log(D)
    less LCA gather traffic (§Perf 'lift_bound').

    b_cap: static accept-buffer bound for device recovery; defaults to a
    pow2 bucket of `budget` so nearby budgets share compiled programs.
    """
    n, L = g.n, g.m
    budget = default_budget(n) if budget is None else int(budget)
    if recovery == "device":
        fn, args, kwargs = lgrass_program(
            g, budget, b_cap=b_cap, k_cap=k_cap, parallel=parallel,
            use_tree_kernel=use_tree_kernel, chunk=chunk,
            schedule=schedule, p1_chunk=p1_chunk,
            use_euler_lca=use_euler_lca, bfs_engine=bfs_engine)
    elif recovery == "host":
        with jax.profiler.TraceAnnotation("lgrass.upload"):
            args = (jnp.asarray(g.u, jnp.int32), jnp.asarray(g.v, jnp.int32),
                    jnp.asarray(g.w, jnp.float32))
        fn, kwargs = phase1_device, dict(
            n=n, k_cap=k_cap, parallel=parallel, schedule=schedule,
            p1_chunk=p1_chunk, use_euler_lca=use_euler_lca,
            use_tree_kernel=use_tree_kernel, bfs_engine=bfs_engine)
    else:
        raise ValueError(f"unknown recovery mode {recovery!r}")

    lift_levels = None
    if auto_lift_bound:
        # estimate from graph BFS depth ×4 (tree paths stretch); the
        # post-hoc check below guarantees correctness regardless.
        u, v = args[0], args[1]
        root = select_root(u, v, n)
        depth_g, _ = bfs(u, v, n, root, engine=bfs_engine)
        # finite_depth: unreachable (INF) depths must not inflate the
        # estimate — the shared bfs.py guard, not an ad-hoc mask
        dmax = int(jax.device_get(jnp.max(finite_depth(depth_g))))
        safe = 1
        while (1 << safe) <= 4 * max(dmax, 1):
            safe += 1
        lift_levels = min(safe, log2_ceil(n + 1))

    kwargs["lift_levels"] = lift_levels
    while True:
        with jax.profiler.TraceAnnotation("lgrass.dispatch"):
            out = fn(*args, **kwargs)
        with jax.profiler.TraceAnnotation("lgrass.fetch"):
            d = jax.device_get(out)
        lift = kwargs["lift_levels"]
        depth_max = (d["tree_depth_max"] if recovery == "device"
                     else d["depth_t"].max())
        if lift is None or int(depth_max) < (1 << lift):
            break
        kwargs["lift_levels"] = None  # bound violated: redo safely
    if recovery == "host":
        return _recovery_tail(g, d, budget)
    with jax.profiler.TraceAnnotation("lgrass.unpack"):
        return _result_from_device(d, None, L)


def lgrass_program(
    g: Graph,
    budget: Optional[int] = None,
    b_cap: Optional[int] = None,
    lift_levels: Optional[int] = None,
    k_cap: int = 32,
    parallel: bool = True,
    use_tree_kernel: bool = False,
    chunk: int = 32,
    schedule: str = "chunked",
    p1_chunk: Optional[int] = None,
    use_euler_lca: bool = True,
    bfs_engine: str = "doubling",
):
    """The fused dispatch `lgrass_sparsify(g, budget, ...)` makes with
    recovery="device": (jitted program, device arguments, static keyword
    arguments), the arguments uploaded under the `lgrass.upload` span.
    `fn(*args, **kwargs)` runs the call's program, and
    `fn.lower(*args, **kwargs).compile()` is that program compiled."""
    budget = default_budget(g.n) if budget is None else int(budget)
    if b_cap is None:
        b_cap = _bucket_b_cap([budget])
    if b_cap < budget:
        raise ValueError(f"b_cap {b_cap} < budget {budget}")
    with jax.profiler.TraceAnnotation("lgrass.upload"):
        args = (jnp.asarray(g.u, jnp.int32), jnp.asarray(g.v, jnp.int32),
                jnp.asarray(g.w, jnp.float32), jnp.int32(budget))
    kwargs = dict(n=g.n, k_cap=k_cap, parallel=parallel,
                  lift_levels=lift_levels, b_cap=b_cap,
                  use_tree_kernel=use_tree_kernel, chunk=chunk,
                  schedule=schedule, p1_chunk=p1_chunk,
                  use_euler_lca=use_euler_lca, bfs_engine=bfs_engine)
    return lgrass_device, args, kwargs


def phase1_views_np(d: dict, L: int):
    """Numpy mirror of `marking.phase1_edge_views` + the global
    criticality order — the glue between MARK and a host-side replay.

    `d` holds one graph's phase-1 outputs as numpy arrays of padded
    length L_pad >= L (slicing to the leading L real slots is exact:
    padding edges were kept out of the tree and every crossing group on
    device, see graph.py's padding conventions). Returns (tree_mask,
    crossing, accept_by_edge, group_of_edge, dirty0, order) with `order`
    the full (L,) (crit desc, id asc) permutation, off-tree edges first.

    Shared by `_recovery_tail`, bench_recovery.py and the recovery parity
    tests so there is exactly ONE host formulation to drift-check against
    the device glue.
    """
    L_pad = int(d["tree_mask"].shape[0])
    crossing_p = d["crossing"].astype(bool)
    perm = d["perm"].astype(np.int64)
    gidx = d["gidx"].astype(np.int64)

    # per-edge phase-1 decision / dense group / overflow dirtiness
    accept_by_edge = np.zeros(L_pad, bool)
    accept_by_edge[perm] = d["accept_sorted"]
    group_of_edge = np.full(L_pad, -1, np.int64)
    group_of_edge[perm] = gidx
    group_of_edge[~crossing_p] = -1
    dirty0 = np.zeros(L_pad, bool)
    dirty0[perm] = d["group_overflow"].astype(bool)[gidx] & crossing_p[perm]

    tree_mask = d["tree_mask"].astype(bool)[:L]
    # global criticality order over all off-tree edges (incl. non-crossing)
    keys = np.where(~tree_mask, d["crit"][:L],
                    np.float32(-np.inf)).astype(np.float32)
    order = H.desc_stable_order_np(keys)
    return (tree_mask, crossing_p[:L], accept_by_edge[:L],
            group_of_edge[:L], dirty0[:L], order)


def _recovery_tail(g: Graph, d: dict, budget: int) -> SparsifyResult:
    """Host recovery from one graph's phase-1 outputs (the oracle tail)."""
    n, L = g.n, g.m
    (tree_mask, crossing, accept_by_edge, group_of_edge, dirty0,
     order) = phase1_views_np(d, L)
    ovf_groups = d["group_overflow"].astype(bool)
    crit_order = order[: int((~tree_mask).sum())]

    accepted = recover_host(
        n=n,
        u=g.u.astype(np.int64),
        v=g.v.astype(np.int64),
        tree_mask=tree_mask,
        parent_t=d["parent_t"][:n],
        depth_t=d["depth_t"][:n],
        up=d["up"][:, :n],
        beta=d["beta"][:L],
        crossing=crossing,
        crit_order=crit_order,
        phase1_accept=accept_by_edge,
        group_of_edge=group_of_edge,
        dirty0=dirty0,
        budget=budget,
    )
    return SparsifyResult(
        edge_mask=tree_mask | accepted,
        tree_mask=tree_mask,
        accepted_mask=accepted,
        n_accepted=int(accepted.sum()),
        n_groups=int(d["n_groups"]),
        n_overflow_groups=int(ovf_groups.sum()),
        n_dirty=int(dirty0.sum()),
        loop_rounds=_rounds_by_loop(d["loop_rounds"]),
    )


def lgrass_sparsify_batch(
    graphs,
    budget: Optional[int] = None,
    k_cap: int = 32,
    parallel: bool = True,
    recovery: str = "device",
    b_cap: Optional[int] = None,
    use_tree_kernel: bool = False,
    chunk: int = 32,
    schedule: str = "chunked",
    p1_chunk: Optional[int] = None,
    use_euler_lca: bool = True,
    bfs_engine: str = "doubling",
) -> list:
    """Run LGRASS on many graphs with ONE device compile + dispatch.

    graphs: a `GraphBatch`, or a sequence of `Graph`s (padded here).
    budget: None -> per-graph `default_budget(g.n)`; a scalar applies to
    every graph; a sequence gives one budget per graph (None entries
    fall back to that graph's default).

    recovery="device" (default) runs `lgrass_device_batched`: phase 1
    AND the Algorithm-6 replay execute in the one vmapped dispatch, with
    per-graph budgets as a traced vector — only final masks and scalar
    stats come back to host. recovery="host" keeps the oracle path:
    batched phase 1, then a per-graph numpy replay. Results are
    bit-identical either way, and to per-graph `lgrass_sparsify(g)`
    (asserted in tests/test_batch.py and tests/test_recovery_device.py).
    """
    from repro.core.graph import GraphBatch

    batch = (graphs if isinstance(graphs, GraphBatch)
             else GraphBatch.from_graphs(list(graphs)))
    if budget is None or np.ndim(budget) == 0:
        budget = [budget] * len(batch.graphs)
    elif len(budget) != len(batch.graphs):
        raise ValueError("one budget per graph required")
    budgets = [default_budget(g.n) if b is None else int(b)
               for g, b in zip(batch.graphs, budget)]

    if recovery == "device":
        if b_cap is None:
            b_cap = _bucket_b_cap(budgets)
        if b_cap < max(budgets):
            raise ValueError(f"b_cap {b_cap} < max budget {max(budgets)}")
        d = jax.device_get(lgrass_device_batched(
            jnp.asarray(batch.u, jnp.int32),
            jnp.asarray(batch.v, jnp.int32),
            jnp.asarray(batch.w, jnp.float32),
            jnp.asarray(batch.edge_valid, bool),
            jnp.asarray(np.asarray(budgets, np.int32)),
            batch.n_max,
            k_cap,
            parallel,
            None,
            b_cap,
            use_tree_kernel,
            chunk,
            schedule,
            p1_chunk,
            use_euler_lca,
            bfs_engine,
        ))
        return [_result_from_device(d, i, g.m)
                for i, g in enumerate(batch.graphs)]
    if recovery != "host":
        raise ValueError(f"unknown recovery mode {recovery!r}")

    d = jax.device_get(phase1_device_batched(
        jnp.asarray(batch.u, jnp.int32),
        jnp.asarray(batch.v, jnp.int32),
        jnp.asarray(batch.w, jnp.float32),
        jnp.asarray(batch.edge_valid, bool),
        batch.n_max,
        k_cap,
        parallel,
        None,
        schedule,
        p1_chunk,
        use_euler_lca,
        use_tree_kernel,
        bfs_engine,
    ))
    results = []
    for i, (g, b) in enumerate(zip(batch.graphs, budgets)):
        di = {k: np.asarray(val[i]) for k, val in d.items()}
        results.append(_recovery_tail(g, di, b))
    return results
