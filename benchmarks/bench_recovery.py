"""Host vs device recovery latency (the tail PR 2 moved on-device).

Workload: `feeder_like_graph` — the chain-heavy radial topology where
almost every off-tree edge is non-crossing, so phase 1 decides nothing
and Algorithm 6 does all the work. This is the recovery-dominated
serving regime the refactor targets.

Three comparisons:

  * isolated tail — one graph's phase-1 outputs prepared up front, then
    `recover_host` (numpy replay) vs the jitted `recover_device`
    chunked scan on identical inputs.
  * batched tail — phase-1 outputs for 8 mixed-size graphs already
    device-resident; the host path then pays what serving actually
    pays: the device→host sync of the full per-edge dict, per-graph
    numpy glue, and 8 sequential interpreted replays. The device path
    is ONE `recover_device_batched` dispatch (glue + order sort + scan
    all on device) returning only masks.
  * end-to-end batch — `lgrass_sparsify_batch` with recovery="host" vs
    the fused recovery="device" program, one dispatch for everything.

Context for reading the numbers: the device replay is built from
batched LCA gathers — the TPU-native shape. On the CPU CI backend,
XLA's scalarised gathers pace the device path, while the host path
rides numpy's cache-friendly kernels; the device wins here come from
removing the sync + per-graph python, and grow with batch size. On an
accelerator the gap widens further because the host path's sync cost
is a real transfer, not a memcpy.

Since the phase-1 chunking PR the e2e rows compare two paths that both
run the chunked+Euler marking schedule, and the fused device path
additionally backs its recovery cover tables with the same Euler
tables — that flip is what moved e2e past parity (~1.33x at smoke
sizes). The full-size rows were then BFS-bound (diameter ~n feeder
chains pinned the ratio at ~1.0-1.1x) until the hop-doubling engine
(benchmarks/bench_bfs.py) collapsed the two traversal passes; both
paths share that win, so the absolute e2e dropped ~2.7x while the
host-vs-device ratio moved to the ~1.2x the remaining shared stages
(MST, marking) allow — bench_bfs records the engine before/after.

    PYTHONPATH=src python benchmarks/bench_recovery.py [--smoke]
"""
import argparse
import functools
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import lgrass_sparsify_batch
from repro.core.graph import GraphBatch, feeder_like_graph
from repro.core.lca import LiftingTables
from repro.core.marking import phase1_edge_views
from repro.core.recovery import (_recover_scan, recover_device,
                                 recover_host)
from repro.core.sort import sort_f32_desc_stable
from repro.core.sparsify import (_recovery_tail, phase1_device,
                                 phase1_device_batched, phase1_views_np)

BATCH = 8


def _time(fn, reps):
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _mixed_graphs(quick):
    base = 96 if quick else 256
    step = 16 if quick else 64
    return [
        feeder_like_graph(base + step * i, base + step * i,
                          span=16 + 4 * (i % 3), seed=500 + i)
        for i in range(BATCH)
    ]


@functools.partial(jax.jit, static_argnames=("b_cap",))
def _device_tail_batched(d, u, v, edge_valid, budgets, b_cap):
    """On-device glue + order sort + chunked replay, vmapped — what the
    fused program runs after phase 1, as a standalone timed unit.
    b_cap is the tight per-batch bound (a pow2 bucket only matters for
    compile sharing across batches, which a benchmark doesn't need)."""
    def one(dd, bu, bv, bev, bb):
        t = LiftingTables(up=dd["up"], depth=dd["depth_t"])
        tree, crossing = dd["tree_mask"], dd["crossing"]
        acc, grp, dirty0 = phase1_edge_views(
            dd["perm"], dd["gidx"], dd["accept_sorted"],
            dd["group_overflow"], crossing)
        offtree = (~tree) & bev
        order = sort_f32_desc_stable(jnp.where(offtree, dd["crit"],
                                               -jnp.inf))
        return _recover_scan(t, bu, bv, dd["beta"], offtree, crossing,
                             order, acc, grp, dirty0, bb, b_cap,
                             chunk=16, batched=True)[:2]
    return jax.vmap(one)(d, u, v, edge_valid, budgets)


def run(quick: bool = False):
    reps = 2 if quick else 5
    rows = []

    # --- isolated tail: recover_host vs recover_device, same inputs ---
    g = feeder_like_graph(192 if quick else 512, 192 if quick else 512,
                          span=24, seed=42)
    budget = max(4, g.n // 20)
    b_cap = max(budget, 8)  # tight static bound (no bucket sharing needed)
    u = jnp.asarray(g.u, jnp.int32)
    v = jnp.asarray(g.v, jnp.int32)
    d1 = {k: np.asarray(x) for k, x in
          phase1_device(u, v, jnp.asarray(g.w, jnp.float32), g.n).items()}
    tree, crossing, accept, group, dirty0, order = phase1_views_np(d1, g.m)
    n_off = int((~tree).sum())

    def host_tail():
        return recover_host(
            g.n, g.u.astype(np.int64), g.v.astype(np.int64), tree,
            d1["parent_t"], d1["depth_t"], d1["up"], d1["beta"], crossing,
            order[:n_off], accept, group, dirty0, budget)

    dev_args = (
        jnp.asarray(d1["up"]), jnp.asarray(d1["depth_t"]), u, v,
        jnp.asarray(d1["beta"]), jnp.asarray(tree), jnp.asarray(crossing),
        jnp.asarray(order.astype(np.int32)), jnp.asarray(accept),
        jnp.asarray(group.astype(np.int32)), jnp.asarray(dirty0),
        jnp.int32(budget),
    )

    def device_tail():
        out, _ = recover_device(*dev_args, b_cap=b_cap, chunk=16)
        return out.block_until_ready()

    ref = host_tail()
    assert np.array_equal(np.asarray(device_tail()), ref)  # and warm jit
    t_host = _time(host_tail, reps)
    t_dev = _time(device_tail, reps)
    rows += [
        ("recovery.tail.host_us", t_host * 1e6, f"L={g.m}"),
        ("recovery.tail.device_us", t_dev * 1e6, f"b_cap={b_cap}"),
        ("recovery.tail.speedup", 0.0, round(t_host / t_dev, 2)),
    ]

    # --- batched tail: sync + 8 host replays vs ONE device dispatch ---
    graphs = _mixed_graphs(quick)
    batch = GraphBatch.from_graphs(graphs)
    ub = jnp.asarray(batch.u, jnp.int32)
    vb = jnp.asarray(batch.v, jnp.int32)
    evb = jnp.asarray(batch.edge_valid, bool)
    budgets = [max(1, round(0.05 * gg.n)) for gg in graphs]
    bcap_b = max(max(budgets), 8)  # tight static bound
    d = phase1_device_batched(ub, vb, jnp.asarray(batch.w, jnp.float32),
                              evb, batch.n_max, 32, False, None)
    jax.block_until_ready(d)
    bv = jnp.asarray(np.asarray(budgets, np.int32))

    def batched_host_tail():
        dd = {k: np.asarray(val) for k, val in d.items()}  # the sync
        return [
            _recovery_tail(gg, {k: val[i] for k, val in dd.items()}, b)
            for i, (gg, b) in enumerate(zip(graphs, budgets))
        ]

    def batched_device_tail():
        out, cnt = _device_tail_batched(d, ub, vb, evb, bv, bcap_b)
        return np.asarray(out), np.asarray(cnt)

    ref_b = batched_host_tail()
    got, _ = batched_device_tail()  # warms the jit too
    for i, (gg, r) in enumerate(zip(graphs, ref_b)):
        assert np.array_equal(got[i][: gg.m], r.accepted_mask), i
    t_bh = _time(batched_host_tail, reps)
    t_bd = _time(batched_device_tail, reps)
    rows += [
        (f"recovery.batch{BATCH}_tail.host_us", t_bh * 1e6,
         "sync + 8 replays"),
        (f"recovery.batch{BATCH}_tail.device_us", t_bd * 1e6, "1 dispatch"),
        (f"recovery.batch{BATCH}_tail.speedup", 0.0, round(t_bh / t_bd, 2)),
    ]

    # --- end-to-end: host-tail path vs fused device path ---
    def e2e_host():
        return lgrass_sparsify_batch(batch, parallel=False,
                                     recovery="host")

    def e2e_device():
        return lgrass_sparsify_batch(batch, parallel=False,
                                     recovery="device")

    for a, b in zip(e2e_host(), e2e_device()):  # warm both + equivalence
        assert np.array_equal(a.edge_mask, b.edge_mask)
    t_h = _time(e2e_host, reps)
    t_d = _time(e2e_device, reps)
    rows += [
        (f"recovery.e2e_batch{BATCH}.host_tail_us", t_h * 1e6, ""),
        (f"recovery.e2e_batch{BATCH}.device_us", t_d * 1e6, "1 dispatch"),
        (f"recovery.e2e_batch{BATCH}.speedup", 0.0, round(t_h / t_d, 2)),
    ]
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes / few reps (CI smoke job)")
    args = ap.parse_args()
    rows = run(quick=args.smoke)
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    tail = rows[5][2]
    e2e = rows[-1][2]
    print(f"batched tail: device is {tail}x the sync+host path; "
          f"end-to-end: {e2e}x "
          f"({'WIN' if min(tail, e2e) > 1 else 'MIXED'})")
