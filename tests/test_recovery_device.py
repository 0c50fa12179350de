"""Device recovery parity: the fused `lgrass_device` replay must be
BIT-IDENTICAL to the host `recover_host` oracle and to `baseline.py`
across graph families — including the overflow-dirty (k_cap=1) and
budget-exhaustion paths — and the standalone `recover_device` must agree
when driven directly from phase-1 outputs.

Shapes are deliberately reused across cases so the sweep costs a handful
of XLA compiles, not one per case (budgets are drawn from pow2-bucketed
values for the same reason).

At b_cap > 32 a replay block's cover table is as wide as the filled part
of the accept buffer (`recovery.rec_widths`); the width cases pin those
masks, and the column count `rec_table_cols`, against a host count.
"""
import functools
import re
from collections import Counter

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _prop import cases, integers, sampled_from
from repro.analysis.jaxpr_audit import collect_eqns
from repro.core import (baseline_sparsify, lgrass_sparsify,
                        lgrass_sparsify_batch, recover_device,
                        recover_device_batched)
from repro.core.graph import (GraphBatch, feeder_like_graph,
                              powergrid_like_graph, random_connected_graph)
from repro.core.sparsify import (lgrass_device_batched, lgrass_program,
                                 phase1_device, phase1_device_batched,
                                 phase1_views_np)


def _assert_triple(g, budget, **kw):
    """device ≡ host ≡ baseline, masks and stats."""
    base = baseline_sparsify(g, budget=budget)
    host = lgrass_sparsify(g, budget=budget, recovery="host", **kw)
    dev = lgrass_sparsify(g, budget=budget, recovery="device", **kw)
    assert np.array_equal(base.edge_mask, host.edge_mask)
    assert np.array_equal(base.edge_mask, dev.edge_mask)
    assert np.array_equal(host.tree_mask, dev.tree_mask)
    assert np.array_equal(host.accepted_mask, dev.accepted_mask)
    assert dev.n_accepted == host.n_accepted
    assert dev.n_groups == host.n_groups
    assert dev.n_overflow_groups == host.n_overflow_groups
    assert dev.n_dirty == host.n_dirty
    return dev


@pytest.mark.parametrize(
    "seed,weight,budget",
    cases(integers(0, 100_000), sampled_from(["lognormal", "ties"]),
          sampled_from([3, 7, 12]), n_cases=12, seed=31),
)
def test_device_recovery_parity_sweep(seed, weight, budget):
    g = random_connected_graph(36, 80, seed=seed, weight=weight)
    _assert_triple(g, budget)


@pytest.mark.parametrize("parallel", [True, False])
def test_device_recovery_both_schedules(parallel):
    g = random_connected_graph(45, 90, seed=1, weight="ties")
    _assert_triple(g, 8, parallel=parallel)


def test_device_recovery_powergrid():
    _assert_triple(powergrid_like_graph(6, 0.4, seed=2), 10)


@pytest.mark.parametrize("seed", [0, 3])
def test_device_recovery_feeder_noncross_heavy(seed):
    """Chain-heavy feeder graphs accept NON-crossing edges, so the
    replay's after-effects machinery does real work: the host oracle
    propagates ball dirt eagerly, the device scan derives it lazily
    (covered-by-accepted-noncross) — both must land bit-identically."""
    g = feeder_like_graph(96, 48, span=6, seed=seed)
    base = baseline_sparsify(g, budget=6)
    # the family does what it claims: non-crossing edges get accepted
    assert (~base.crossing[base.accepted]).sum() >= 1
    _assert_triple(g, 6)


def test_device_recovery_overflow_dirty():
    """k_cap=1 overflows nearly every group: device recovery must replay
    the fully-dirty groups exactly."""
    g = random_connected_graph(40, 110, seed=9)
    dev = _assert_triple(g, 20, k_cap=1)
    assert dev.n_overflow_groups > 0
    assert dev.n_dirty > 0


def test_device_recovery_budget_exhaustion():
    """Both budget cut (count hits budget) and budget excess (greedy runs
    dry before the cut) must match."""
    g = random_connected_graph(36, 80, seed=4)
    cut = _assert_triple(g, 3)
    assert cut.n_accepted == 3  # the scan's budget gate actually fired
    g2 = random_connected_graph(24, 12, seed=4)  # 12 off-tree edges
    excess = _assert_triple(g2, 20)  # budget > off-tree count
    assert excess.n_accepted < 20  # greedy ran dry below the budget


def test_device_recovery_batched_matches_host_tail():
    graphs = [
        random_connected_graph(30, 60, seed=0, weight="lognormal"),
        powergrid_like_graph(6, 0.4, seed=3),
        random_connected_graph(45, 110, seed=1, weight="ties"),
    ]
    dev = lgrass_sparsify_batch(graphs, budget=6, recovery="device")
    host = lgrass_sparsify_batch(graphs, budget=6, recovery="host")
    for g, rd, rh in zip(graphs, dev, host):
        assert np.array_equal(rd.edge_mask, rh.edge_mask)
        assert np.array_equal(
            rd.edge_mask, baseline_sparsify(g, budget=6).edge_mask
        )
        assert (rd.n_accepted, rd.n_groups, rd.n_overflow_groups,
                rd.n_dirty) == (rh.n_accepted, rh.n_groups,
                                rh.n_overflow_groups, rh.n_dirty)


def test_recover_device_standalone_from_phase1():
    """Drive `recover_device` directly from phase-1 outputs (the unit
    bench_recovery.py times) and compare against the host oracle — on
    both distance backends: the default Euler path (tables rebuilt on
    device from up[0]) and the legacy lifting climbs."""
    g = random_connected_graph(36, 80, seed=7)
    budget = 7
    u = jnp.asarray(g.u, jnp.int32)
    v = jnp.asarray(g.v, jnp.int32)
    w = jnp.asarray(g.w, jnp.float32)
    d = {k: np.asarray(val)
         for k, val in phase1_device(u, v, w, g.n).items()}
    tree, crossing, accept, group, dirty0, full_order = phase1_views_np(
        d, g.m)
    want = lgrass_sparsify(g, budget=budget, recovery="host").accepted_mask

    for use_euler in (True, False):
        got, n_acc = recover_device(
            jnp.asarray(d["up"]), jnp.asarray(d["depth_t"]), u, v,
            jnp.asarray(d["beta"]), jnp.asarray(tree),
            jnp.asarray(crossing),
            jnp.asarray(full_order.astype(np.int32)), jnp.asarray(accept),
            jnp.asarray(group.astype(np.int32)), jnp.asarray(dirty0),
            jnp.int32(budget), b_cap=8, use_euler_lca=use_euler,
        )
        assert np.array_equal(np.asarray(got), want), use_euler
        assert int(n_acc) == int(want.sum())


def test_recover_device_batched_standalone_euler_parity():
    """`recover_device_batched` driven from batched phase-1 outputs:
    each lane rebuilds its own Euler tables from up[0] (the ROADMAP
    'standalone recovery still climbs the lifting tables' fix) and must
    agree with the per-graph host oracle on every lane — padded shapes
    and all — and with the lifting backend bit for bit."""
    graphs = [
        feeder_like_graph(80, 40, span=6, seed=11),
        random_connected_graph(45, 110, seed=12, weight="ties"),
        powergrid_like_graph(6, 0.4, seed=13),
    ]
    batch = GraphBatch.from_graphs(graphs)
    budgets = [6, 9, 5]
    d = {k: np.asarray(val) for k, val in phase1_device_batched(
        jnp.asarray(batch.u, jnp.int32), jnp.asarray(batch.v, jnp.int32),
        jnp.asarray(batch.w, jnp.float32),
        jnp.asarray(batch.edge_valid), batch.n_max).items()}
    L_pad = batch.L_max
    tree = np.zeros((len(graphs), L_pad), bool)
    crossing = np.zeros((len(graphs), L_pad), bool)
    accept = np.zeros((len(graphs), L_pad), bool)
    group = np.full((len(graphs), L_pad), -1, np.int32)
    dirty0 = np.zeros((len(graphs), L_pad), bool)
    order = np.zeros((len(graphs), L_pad), np.int32)
    for i in range(len(graphs)):
        di = {k: val[i] for k, val in d.items()}
        # phase1_views_np over the PADDED length: the padded tail sorts
        # after every real slot, exactly what the device glue sees
        t_, c_, a_, g_, dd_, o_ = phase1_views_np(di, L_pad)
        tree[i], crossing[i], accept[i] = t_, c_, a_
        group[i], dirty0[i], order[i] = g_, dd_, o_.astype(np.int32)

    outs = {}
    for use_euler in (True, False):
        got, cnt = recover_device_batched(
            jnp.asarray(d["up"]), jnp.asarray(d["depth_t"]),
            jnp.asarray(batch.u, jnp.int32),
            jnp.asarray(batch.v, jnp.int32),
            jnp.asarray(d["beta"]), jnp.asarray(tree),
            jnp.asarray(crossing), jnp.asarray(order),
            jnp.asarray(accept), jnp.asarray(group), jnp.asarray(dirty0),
            jnp.asarray(np.asarray(budgets, np.int32)), b_cap=16,
            edge_valid=jnp.asarray(batch.edge_valid),
            use_euler_lca=use_euler,
        )
        outs[use_euler] = (np.asarray(got), np.asarray(cnt))
    assert np.array_equal(outs[True][0], outs[False][0])
    assert np.array_equal(outs[True][1], outs[False][1])
    for i, (g, b) in enumerate(zip(graphs, budgets)):
        want = lgrass_sparsify(g, budget=b, recovery="host").accepted_mask
        assert np.array_equal(outs[True][0][i][: g.m], want), i
        assert int(outs[True][1][i]) == int(want.sum())
        assert not outs[True][0][i][g.m:].any()  # padding never accepted


def test_feeder_like_graph_clamps_unreachable_chords():
    """Chord requests beyond the span-reachable pair count must clamp,
    not spin the rejection loop forever."""
    g = feeder_like_graph(50, 10_000, span=5, seed=0)
    g.validate()
    assert g.m - (g.n - 1) == sum(50 - d for d in range(2, 6))


def test_recover_device_budget_clamped_to_b_cap():
    """The traced-budget precondition (b_cap >= budget) cannot raise in
    jit; the scan clamps instead, yielding the exact b_cap-budget replay
    rather than a corrupted buffer."""
    g = random_connected_graph(36, 80, seed=2)
    over = lgrass_sparsify(g, budget=4, recovery="host")
    d = {k: np.asarray(val) for k, val in phase1_device(
        jnp.asarray(g.u, jnp.int32), jnp.asarray(g.v, jnp.int32),
        jnp.asarray(g.w, jnp.float32), g.n).items()}
    tree, crossing, accept, group, dirty0, order = phase1_views_np(d, g.m)
    got, n_acc = recover_device(
        jnp.asarray(d["up"]), jnp.asarray(d["depth_t"]),
        jnp.asarray(g.u, jnp.int32), jnp.asarray(g.v, jnp.int32),
        jnp.asarray(d["beta"]), jnp.asarray(tree), jnp.asarray(crossing),
        jnp.asarray(order.astype(np.int32)), jnp.asarray(accept),
        jnp.asarray(group.astype(np.int32)), jnp.asarray(dirty0),
        jnp.int32(9), b_cap=4,  # budget 9 > b_cap 4 -> clamped to 4
    )
    assert np.array_equal(np.asarray(got), over.accepted_mask)
    assert int(n_acc) == over.n_accepted


def test_device_recovery_tree_kernel_parity():
    """The Pallas tree-distance kernel path (interpret mode on CPU) is
    bit-identical inside the fused program."""
    g = random_connected_graph(24, 40, seed=5)
    host = lgrass_sparsify(g, budget=5, recovery="host")
    dev = lgrass_sparsify(g, budget=5, recovery="device",
                          use_tree_kernel=True)
    assert np.array_equal(host.edge_mask, dev.edge_mask)


# --- cover tables only as wide as the filled accept buffer -------------

@pytest.fixture
def release_programs():
    """Drop the test's compiled programs when it ends. Programs with eight
    table widths are large, XLA:CPU maps memory for each compiled
    program, and a test process that keeps them all can reach the
    kernel's per-process limit on memory maps in a later test file."""
    yield
    jax.clear_caches()

def _graph(family):
    if family == "grid":
        return powergrid_like_graph(24, 0.3, seed=5)
    return random_connected_graph(300, 900, seed=8, weight="lognormal")


def _phase1_views(g):
    d = {k: np.asarray(val) for k, val in phase1_device(
        jnp.asarray(g.u, jnp.int32), jnp.asarray(g.v, jnp.int32),
        jnp.asarray(g.w, jnp.float32), g.n).items()}
    return d, phase1_views_np(d, g.m)


def _host_table_cols(accepted, order, n_off, budget, b_cap, c=32):
    """(columns summed over blocks, blocks, `cnt` at each block's start):
    each block's table holds W + c columns, W the smallest of g, 2g, ...,
    b_cap (g = max(b_cap // 8, c)) that is at least the accepts before
    the block, counted from the accepted mask in sort order."""
    g = max(b_cap // 8, c)
    widths = list(range(g, b_cap, g)) + [b_cap]
    acc = accepted[order].astype(int)
    cols, starts = 0, []
    for blk in range(-(-n_off // c)):
        cnt = int(acc[: blk * c].sum())
        if cnt >= min(budget, b_cap):
            break
        starts.append(cnt)
        cols += min(w for w in widths if w >= cnt) + c
    return cols, len(starts), starts


@pytest.mark.parametrize("family,b_cap,budget", [
    ("grid", 64, 64), ("grid", 64, 1), ("grid", 64, 20),
    ("grid", 256, 256), ("grid", 256, 150), ("grid", 256, 30),
    ("rand", 64, 64), ("rand", 256, 256), ("rand", 256, 1),
])
@pytest.mark.usefixtures("release_programs")
def test_recovery_parity_across_widths(family, b_cap, budget):
    """The fused program and `recover_device` at b_cap 64 and 256, where
    the table width follows `cnt` through 2 and 8 widths: masks equal to
    the host replay and the baseline, and the summed table columns equal
    to the host count."""
    g = _graph(family)
    base = baseline_sparsify(g, budget=budget)
    host = lgrass_sparsify(g, budget=budget, recovery="host")
    dev = lgrass_sparsify(g, budget=budget, b_cap=b_cap)
    assert np.array_equal(base.edge_mask, host.edge_mask)
    assert np.array_equal(base.edge_mask, dev.edge_mask)
    assert np.array_equal(host.accepted_mask, dev.accepted_mask)
    assert dev.n_accepted == host.n_accepted == min(budget, b_cap)

    d, (tree, crossing, accept, group, dirty0, order) = _phase1_views(g)
    cols, rounds, starts = _host_table_cols(
        dev.accepted_mask, order, int((~tree).sum()), budget, b_cap)
    assert dev.loop_rounds["rec"] == rounds
    assert dev.rec_table_cols == cols
    g_w = max(b_cap // 8, 32)
    if budget > g_w:
        # some block's accepts take `cnt` past a width boundary
        ends = starts[1:] + [dev.n_accepted]
        assert any(-(-a // g_w) < -(-b // g_w)
                   for a, b in zip(starts, ends)), (starts, ends)
    if budget <= g_w:
        assert cols == rounds * (g_w + 32)  # the first width alone

    got, n_acc = recover_device(
        jnp.asarray(d["up"]), jnp.asarray(d["depth_t"]),
        jnp.asarray(g.u, jnp.int32), jnp.asarray(g.v, jnp.int32),
        jnp.asarray(d["beta"]), jnp.asarray(tree), jnp.asarray(crossing),
        jnp.asarray(order.astype(np.int32)), jnp.asarray(accept),
        jnp.asarray(group.astype(np.int32)), jnp.asarray(dirty0),
        jnp.int32(budget), b_cap=b_cap)
    assert np.array_equal(np.asarray(got), host.accepted_mask)
    assert int(n_acc) == host.n_accepted


_CONDITIONAL = re.compile(r"\sconditional\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _rec_conditionals(hlo, scope):
    """op_names of the compiled program's conditionals within `scope`
    ("REC" in the fused program; "" for a standalone recovery, which is
    all replay)."""
    return [m.group(1) for line in hlo.splitlines()
            if _CONDITIONAL.search(line)
            for m in [_OP_NAME.search(line)] if m and scope in m.group(1)]


def _primitives(fn, args, kw):
    """Primitive counts of a program's jaxpr, through every sub-jaxpr."""
    closed = jax.make_jaxpr(functools.partial(fn, **kw))(*args)
    return Counter(e.primitive.name for e in collect_eqns(closed))


def _recover_args(g, budget, batch=None):
    d, (tree, crossing, accept, group, dirty0, order) = _phase1_views(g)
    args = [d["up"], d["depth_t"], g.u.astype(np.int32),
            g.v.astype(np.int32), d["beta"], tree, crossing,
            order.astype(np.int32), accept, group.astype(np.int32), dirty0,
            np.int32(budget)]
    if batch is None:
        return [jnp.asarray(a) for a in args]
    return [jnp.asarray(np.stack([a] * batch)) for a in args]


@pytest.mark.parametrize("program", ["lgrass_device_batched",
                                     "recover_device_batched"])
@pytest.mark.usefixtures("release_programs")
def test_batched_programs_keep_one_table_width(program):
    """Under vmap a switch on the lanes' `cnt` would run every width: the
    batched programs build the full-width table with no conditional in
    REC, where the single-graph program at the same b_cap has one, and
    trace the same primitives at b_cap 256 as at b_cap 32, where there is
    one width (vmap would turn the switch into a select over every
    width's table, which leaves no conditional behind); and their masks
    equal the per-graph ones."""
    g, b_cap, budget = _graph("grid"), 256, 150
    single = lgrass_sparsify(g, budget=budget, b_cap=b_cap)
    if program == "lgrass_device_batched":
        fn, args, kw = lgrass_program(g, budget, b_cap=b_cap)
        batch = GraphBatch.from_graphs([g, _graph("rand")])
        bargs = (jnp.asarray(batch.u, jnp.int32),
                 jnp.asarray(batch.v, jnp.int32),
                 jnp.asarray(batch.w, jnp.float32),
                 jnp.asarray(batch.edge_valid),
                 jnp.asarray([budget, budget], jnp.int32))
        bfn, bkw = lgrass_device_batched, dict(n=batch.n_max, b_cap=b_cap)
        scope = "REC"
        rs = lgrass_sparsify_batch(batch, budget=budget, b_cap=b_cap)
        for gi, r in zip(batch.graphs, rs):
            one = lgrass_sparsify(gi, budget=budget, b_cap=b_cap)
            assert np.array_equal(r.edge_mask, one.edge_mask)
            assert r.loop_rounds == one.loop_rounds
            assert r.rec_table_cols == r.loop_rounds["rec"] * (b_cap + 32)
    else:
        fn, args, kw = recover_device, _recover_args(g, budget), dict(
            b_cap=b_cap)
        bfn, bargs, bkw = recover_device_batched, _recover_args(
            g, budget, batch=2), dict(b_cap=b_cap)
        scope = ""
        got, cnt = bfn(*bargs, **bkw)
        for lane in range(2):
            assert np.array_equal(np.asarray(got[lane]),
                                  single.accepted_mask)
            assert int(cnt[lane]) == single.n_accepted
    assert len(_rec_conditionals(
        fn.lower(*args, **kw).compile().as_text(), scope)) == 1
    assert _rec_conditionals(
        bfn.lower(*bargs, **bkw).compile().as_text(), scope) == []
    one_width = dict(kw, b_cap=32)
    assert "cond" in _primitives(fn, args, kw)
    assert "cond" not in _primitives(fn, args, one_width)
    assert (_primitives(bfn, bargs, bkw)
            == _primitives(bfn, bargs, dict(bkw, b_cap=32)))
