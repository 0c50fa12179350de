"""rec_width_share.single: read from a CPU run of `ipcc_case1` at its
full size, where the replay's cover tables take eight widths, and
checked against a count made on the host from the reference's answers;
and the stage map of a program whose REC holds the width switch."""
import io
import time
import types

import numpy as np

from chipbench import generate, harness, stages
from chipbench.test_chipbench_rehearsal import (  # noqa: F401 (fixture)
    BENCH, CPU_TRACE, SEED, _own_compile_cache_settings)

CELL = "ipcc_case1"
C = 32


def _host_share(pool, traced):
    """(share %, mean blocks a graph) of the traced graphs, counted on the
    host: a block's table holds W + C columns, W the smallest of g, 2g,
    ..., b_cap (g = max(b_cap // 8, C)) that is at least the accepts
    before the block, read from the reference's answer in sort order."""
    import jax.numpy as jnp

    from repro.core import baseline_sparsify
    from repro.core.sparsify import phase1_device, phase1_views_np

    held = full = blocks = graphs = 0
    for i in traced:
        for g, budget in zip(*pool[i]):
            d = {k: np.asarray(x) for k, x in phase1_device(
                jnp.asarray(g.u), jnp.asarray(g.v), jnp.asarray(g.w),
                g.n).items()}
            tree, *_, order = phase1_views_np(d, g.m)
            acc = (baseline_sparsify(g, budget=budget).edge_mask
                   & ~tree)[order].astype(int)
            b_cap = max(1 << (budget - 1).bit_length(), 8)
            step = max(b_cap // 8, C)
            widths = list(range(step, b_cap, step)) + [b_cap]
            assert len(widths) == 8
            for blk in range(-(-int((~tree).sum()) // C)):
                cnt = int(acc[: blk * C].sum())
                if cnt >= budget:
                    break
                held += min(w for w in widths if w >= cnt) + C
                full += b_cap + C
                blocks += 1
            graphs += 1
    return 100.0 * held / full, blocks / graphs


def test_rec_width_share_of_a_cpu_run_is_the_host_count():
    config = harness.load_json("configs", "ipcc_powergrid.json")
    traffic = dict(harness.load_json("traffic", "case1_pool4.json"),
                   pool_calls=2, trace_calls=2)
    line = harness.run_cell(
        BENCH, CELL, SEED, 0.3, True, time.perf_counter(),
        require_chip=False, traffics={"case1_pool4": traffic},
        trace_ops=CPU_TRACE, out=io.StringIO(), err=io.StringIO())
    assert line["correct"] is True
    got = {k: m["value"] for k, m in line["metrics"].items()}
    pool = generate.make_calls(config, traffic, SEED)
    share, blocks = _host_share(pool, range(traffic["trace_calls"]))
    assert got["rec_width_share.single"] == share
    assert got["rec_rounds.single"] == blocks
    assert 0 < share < 100
    # the stage map still covers MIN_COVER of the device's operations:
    # below it every stage reading falls out
    assert "rec_ms.single" in got


def test_stage_map_of_the_width_switch():
    """At b_cap 256 the single program's REC loop holds one conditional
    over eight table widths: it maps to REC, and the loop keeps its
    one `while`."""
    from repro.core.graph import powergrid_like_graph
    from repro.core.sparsify import LOOPS, lgrass_program

    fn, args, kw = lgrass_program(powergrid_like_graph(8, 0.25, seed=3), 3,
                                  b_cap=256)
    m = stages.StageMap(fn.lower(*args, **kw).compile().as_text(), LOOPS)
    conds = [n for n in m.executed() if m.opcode[n] == "conditional"]
    assert len(conds) == 1 and m.stage[conds[0]] == "REC"
    assert len(m.called[conds[0]]) == 8
    ran = m.executed()
    assert sum(m.stage[n] is None for n in ran) <= 0.02 * len(ran)
    assert {lp: len(ws) for lp, ws in m.loops.items()}["rec"] == 1


def test_a_program_without_the_count_reads_nothing(monkeypatch):
    import repro.core

    reader = harness.load_module("metrics", "rec_width_share.single.py")
    g = generate.powergrid_like_graph(6, 0.25, seed=1)
    run = types.SimpleNamespace(pool=[([g], [3])], traced=[
        harness.Call(0, 1, 0, 1, None)])
    monkeypatch.setattr(repro.core, "lgrass_sparsify",
                        lambda g, budget: types.SimpleNamespace(
                            loop_rounds={"rec": 2}))
    reader.after_window(run)
    assert reader.read(run) is None
    monkeypatch.setattr(repro.core, "lgrass_sparsify",
                        lambda g, budget: types.SimpleNamespace(
                            loop_rounds={"rec": 2}, rec_table_cols=60))
    reader.after_window(run)
    assert reader.read(run) == 100.0 * 60 / (2 * (8 + 32))
