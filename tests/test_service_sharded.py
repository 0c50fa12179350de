"""The batch-sharded service (`SparsifyService(mesh=batch_mesh(4))`) on
four devices, the path of a four-chip TPU v5e host.

The test process sees one device, so the four-device cases run in one
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=4 (as
tests/test_distributed.py does); it runs every case, reports each one's
outcome, and each test below asserts its own. The cases:

- the service, sync and async+donate, on small power grids and on a
  mixed stream, bit for bit against `baseline_sparsify`, with every
  dispatched output spanning the four devices;
- the compiled sharded program trades no data between devices: its
  only collectives are `pred[]` all-reduces, one per batch-dependent
  while loop of the program (`EXPECTED_WHILE`);
- `program_specs` compiles the program the dispatch runs: the same
  collectives as a compile from the dispatch's own sharded arguments;
- at a b_cap where one graph's replay switches among table widths, the
  sharded program keeps the one full width: no conditional in REC;
- the benchmark cell `svc_case1_b8_x4` rehearsed at a tiny size, and
  refused with its answers broken four ways.

`ServiceStats.loop_chip_rounds` is hand-counted in this process.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core.distributed import batch_mesh
from repro.core.sparsify import LOOPS
from repro.serve.sparsify_service import ServiceStats, SparsifyService

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEVICES = 4

_CASES = r'''
import contextlib, io, json, re, time, traceback

import jax
import numpy as np

from repro.analysis.jaxpr_audit import EXPECTED_WHILE
from repro.core import baseline_sparsify
from repro.core.distributed import batch_mesh
from repro.core.graph import (powergrid_like_graph, random_connected_graph,
                              trivial_graph)
from repro.serve import sparsify_service as service
from repro.serve.sparsify_service import SparsifyService

DEVICES = 4
assert len(jax.devices()) == DEVICES, jax.devices()
COLLECTIVE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s+=\s+(\S+|\(.*?\))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|ragged-all-to-all|"
    r"collective-permute|collective-broadcast)(?:-start|-done)?\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
out = {}


def case(name):
    def wrap(fn):
        try:
            fn()
            out[name] = "ok"
        except Exception:
            out[name] = traceback.format_exc()
        return fn
    return wrap


class Recorded:
    """A jitted program that keeps the arguments of every call."""

    def __init__(self, fn):
        self.fn, self.calls = fn, []

    def __call__(self, *args, **kw):
        self.calls.append((args, kw))
        return self.fn(*args, **kw)

    def lower(self, *args, **kw):
        return self.fn.lower(*args, **kw)


for _name in ("lgrass_device_batched", "lgrass_device_batched_donated"):
    setattr(service, _name, Recorded(getattr(service, _name)))


class Recording(SparsifyService):
    """Keeps every dispatch's outputs."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.outputs = []

    def _dispatch(self, *args, **kw):
        d = super()._dispatch(*args, **kw)
        self.outputs.append(d)
        return d


def graphs(family):
    if family == "powergrid":
        return ([powergrid_like_graph(8, 0.25, seed=s) for s in range(1, 8)],
                None)
    return ([random_connected_graph(30, 60, seed=0, weight="lognormal"),
             random_connected_graph(45, 110, seed=1, weight="ties"),
             powergrid_like_graph(6, 0.4, seed=3),
             trivial_graph(),
             random_connected_graph(24, 40, seed=2),
             random_connected_graph(40, 95, seed=5, weight="ties")],
            [8, None, 5, None, 3, 7])


def parity(family, mode):
    gs, budgets = graphs(family)
    want = [baseline_sparsify(g, budget=b).edge_mask if g.m else None
            for g, b in zip(gs, budgets or [None] * len(gs))]
    svc = Recording(mesh=batch_mesh(DEVICES),
                    async_dispatch=(mode != "sync"),
                    donate=(mode == "async_donate"))
    for _ in range(2):   # the second call reuses the staging pool
        got = svc.sparsify(gs, budget=budgets)
        for k, (g, r) in enumerate(zip(gs, got)):
            if g.m == 0:
                assert r.edge_mask.shape == (0,), k
            else:
                assert np.array_equal(r.edge_mask, want[k]), (family, k)
    devs = {d.id for d in jax.devices()}
    for d in svc.outputs:
        for key, arr in d.items():
            assert arr.shape[0] % DEVICES == 0, (key, arr.shape)
            on = {s.device.id for s in arr.addressable_shards}
            assert on == devs and len(arr.sharding.device_set) == DEVICES, key
    s = svc.stats
    for lp in s.loop_rounds:
        assert (s.loop_rounds[lp] <= s.loop_chip_rounds[lp]
                <= s.loop_lane_rounds[lp]), (lp, s)


for family in ("powergrid", "mixed"):
    for mode in ("sync", "async_donate"):
        case(f"parity/{family}/{mode}")(
            lambda f=family, m=mode: parity(f, m))


def collectives(hlo):
    """(opcode, result type, op_name) of every collective in the HLO."""
    found = []
    for line in hlo.splitlines():
        m = COLLECTIVE.match(line)
        if m:
            op = OP_NAME.search(line)
            found.append((m.group(3), m.group(2), op.group(1) if op else ""))
    return sorted(found)


SIZE = (64, 128)      # a power grid of side 8
svc = Recording(mesh=batch_mesh(DEVICES))
spec = svc.program_specs([SIZE], batch_sizes=(8,))[0]
spec_hlo = spec.fn.lower(*spec.args, **spec.static_kwargs).compile().as_text()


@case("contract")
def _():
    got = collectives(spec_hlo)
    assert len(got) == EXPECTED_WHILE["lgrass"], got
    for opcode, rtype, op_name in got:
        assert (opcode, rtype) == ("all-reduce", "pred[]"), got
        assert op_name.endswith("/while"), got
    loops = sorted(re.search(r"/(bfs|mst|mark|rec)/", o).group(1)
                   for _, _, o in got)
    assert loops == ["bfs", "mark", "mst", "mst", "rec"], loops
    from chipbench import harness
    reader = harness.load_module("metrics", "xchip_ms.x4.py")
    names = {line.split("=")[0].split()[-1].lstrip("%")
             for line in spec_hlo.splitlines() if COLLECTIVE.match(line)}
    assert reader.collective_ops(spec_hlo) == names, names


@case("spec_is_dispatch")
def _():
    svc.dispatch_fn.calls.clear()
    svc.sparsify([powergrid_like_graph(8, 0.25, seed=s) for s in range(8)])
    (args, kw), = svc.dispatch_fn.calls
    assert all(a.sharding.spec == s.sharding.spec
               for a, s in zip(args, spec.args))
    hlo = svc.dispatch_fn.lower(*args, **kw).compile().as_text()
    assert collectives(hlo) == collectives(spec_hlo)
    # the spec without its sharding compiles another program
    bare = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in spec.args]
    alone = spec.fn.lower(*bare, **spec.static_kwargs).compile().as_text()
    assert collectives(alone) == [] != collectives(spec_hlo)


@case("no_rec_conditional")
def _():
    # a b_cap of 256, where the one-graph program switches among eight
    # table widths: the sharded batch keeps one width, no conditional in
    # REC, and the same loop predicates
    wide = svc.program_specs([SIZE], batch_sizes=(8,), budgets=(150,))[0]
    assert wide.static_kwargs["b_cap"] == 256, wide.static_kwargs
    hlo = wide.fn.lower(*wide.args, **wide.static_kwargs).compile().as_text()
    conds = [op.group(1) for line in hlo.splitlines()
             if re.search(r"\sconditional\(", line)
             for op in [OP_NAME.search(line)] if op and "REC" in op.group(1)]
    assert conds == [], conds
    assert ([c[:2] for c in collectives(hlo)]
            == [c[:2] for c in collectives(spec_hlo)]), collectives(hlo)


FAULTS = {
    "all_kept": lambda drv, masks: [np.ones_like(m) for m in masks],
    "one_edge_flipped": lambda drv, masks: masks[:-1] + [
        np.concatenate([~masks[-1][:1], masks[-1][1:]])],
    "stale": lambda drv, masks: (
        getattr(drv, "prev", None) or masks,
        setattr(drv, "prev", masks))[0],
    "half_left_out": lambda drv, masks: masks[: len(masks) // 2],
}


def rehearse(trace, fault=None):
    """One run of the cell at a tiny size; `fault` breaks every answer
    of the entry, and the check must then refuse the run."""
    from chipbench import harness
    bench = json.load(open("BENCHMARK.json"))
    cell = "svc_case1_b8_x4"
    work, e2e, layers = harness.cell_spec(bench, cell)
    cfg = dict(harness.load_json("configs", work["config"] + ".json"))
    cfg["cases"] = {k: {"n_side": 6, "chord_frac": 0.25}
                    for k in cfg["cases"]}
    tr = dict(harness.load_json("traffic", work["traffic"] + ".json"))
    tr.update(pool_calls=3, trace_calls=2, graphs_per_call=8)
    load = harness.load_module
    if fault:
        def broken(*parts):
            mod = load(*parts)
            if parts[0] != "entries":
                return mod

            class Broken(mod.Entry):
                def call(self, i):
                    return FAULTS[fault](self, super().call(i))
            return type("Entries", (), {"Entry": Broken})
        harness.load_module = broken
    err, readers = io.StringIO(), io.StringIO()
    try:
        with contextlib.redirect_stderr(readers):
            line = harness.run_cell(
                bench, cell, 2**31 + 17, 0.3, trace, time.perf_counter(),
                require_chip=False, configs={work["config"]: cfg},
                traffics={work["traffic"]: tr},
                trace_ops=(("/host:CPU", "tf_XLAPjRtCpuClient"),
                           ("/host:CPU", "none")),
                out=io.StringIO(), err=err)
    finally:
        harness.load_module = load
    assert line["device"]["count"] == DEVICES, line
    if fault:
        assert line["correct"] is False and line["failed"] > 0, line
        worst = line["compared"]["edges_wrong_max"]["value"]
        missing = line["compared"]["answers_missing"]["value"]
        assert (missing if fault == "half_left_out" else worst) > 0, line
        return
    assert line["correct"] is True and line["failed"] == 0, line
    assert "in the window 0 (0 from the cache)" in err.getvalue()
    want = {m["name"] for m in (layers if trace else e2e)}
    if trace:
        # the CPU's trace holds no events of its collectives; the chip's
        # does (xchip_ms.x4 on a TPU v5e)
        want.discard("xchip_ms.x4")
        assert "5 collectives in the HLO" in readers.getvalue(), \
            readers.getvalue()
    assert set(line["metrics"]) == want, line["metrics"]
    assert all(m["value"] >= 0 for m in line["metrics"].values())


for trace in (False, True):
    case(f"cell/{'traced' if trace else 'untraced'}")(
        lambda t=trace: rehearse(t))
for fault in FAULTS:
    case(f"cell/{fault}")(lambda f=fault: rehearse(False, f))
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def four_devices():
    """Every four-device case's outcome: "ok" or its traceback."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={DEVICES}",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    r = subprocess.run([sys.executable, "-c", _CASES], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("mode", ["sync", "async_donate"])
@pytest.mark.parametrize("family", ["powergrid", "mixed"])
def test_sharded_service_matches_baseline(four_devices, family, mode):
    assert four_devices[f"parity/{family}/{mode}"] == "ok", \
        four_devices[f"parity/{family}/{mode}"]


def test_sharded_program_collectives_are_loop_predicates(four_devices):
    assert four_devices["contract"] == "ok", four_devices["contract"]


def test_program_specs_compile_the_dispatched_program(four_devices):
    assert four_devices["spec_is_dispatch"] == "ok", \
        four_devices["spec_is_dispatch"]


def test_sharded_program_keeps_one_rec_table_width(four_devices):
    assert four_devices["no_rec_conditional"] == "ok", \
        four_devices["no_rec_conditional"]


@pytest.mark.parametrize("kind", ["untraced", "traced", "all_kept",
                                  "one_edge_flipped", "stale",
                                  "half_left_out"])
def test_x4_cell_rehearsal_on_four_devices(four_devices, kind):
    """The cell at a tiny size: correct, on four devices, every metric
    read; and not correct with the entry's answers broken."""
    assert four_devices[f"cell/{kind}"] == "ok", four_devices[f"cell/{kind}"]


# Rows of one dispatch, B_pad 8, one column per loop of LOOPS; rows 6 and
# 7 are placeholders. With 4 shards of 2 rows: slowest rows per shard
# bfs (5, 7, 3, 1), tree 0, mst (4, 2, 6, 1), mst_jump (3, 9, 2, 0),
# mark (10, 12, 20, 1), rec (8, 30, 12, 1); real rows per shard 2, 2, 2, 0.
ROWS = np.array([
    # bfs tree mst jump mark rec
    [5, 0, 4, 3, 10, 8],
    [4, 0, 3, 1, 9, 6],
    [7, 0, 2, 9, 12, 30],
    [6, 0, 1, 2, 11, 29],
    [3, 0, 6, 2, 20, 12],
    [2, 0, 5, 1, 18, 11],
    [1, 0, 1, 0, 1, 1],
    [1, 0, 1, 0, 1, 1],
])


@pytest.mark.parametrize("shards,chip", [
    # sum over shards of real rows x the shard's slowest row
    (4, {"bfs": 2 * (5 + 7 + 3), "tree": 0, "mst": 2 * (4 + 2 + 6),
         "mst_jump": 2 * (3 + 9 + 2), "mark": 2 * (10 + 12 + 20),
         "rec": 2 * (8 + 30 + 12)}),
    # one shard: 6 real rows x the slowest of all 8
    (1, {"bfs": 6 * 7, "tree": 0, "mst": 6 * 6, "mst_jump": 6 * 9,
         "mark": 6 * 20, "rec": 6 * 30}),
])
def test_loop_chip_rounds_hand_counted(shards, chip):
    assert tuple(LOOPS) == ("bfs", "tree", "mst", "mst_jump", "mark", "rec")
    s = ServiceStats()
    s.count_loops(ROWS, 6, shards)
    assert s.loop_chip_rounds == chip
    assert s.loop_rounds == {"bfs": 27, "tree": 0, "mst": 21,
                             "mst_jump": 18, "mark": 80, "rec": 96}
    assert s.loop_lane_rounds == {"bfs": 42, "tree": 0, "mst": 36,
                                  "mst_jump": 54, "mark": 120, "rec": 180}
    if shards == 1:
        assert s.loop_chip_rounds == s.loop_lane_rounds


def test_program_specs_carry_the_batch_sharding():
    """In this one-device process: a mesh service's specs carry the
    mesh's batch sharding, a plain service's none."""
    mesh = batch_mesh(1)
    [spec] = SparsifyService(mesh=mesh).program_specs(
        [(64, 128)], batch_sizes=(3,))
    assert [a.shape[0] for a in spec.args] == [4] * 5
    for a in spec.args:
        assert a.sharding.mesh == mesh
        assert a.sharding.spec == jax.sharding.PartitionSpec(("batch",))
    [plain] = SparsifyService().program_specs([(64, 128)], batch_sizes=(3,))
    assert all(a.sharding is None for a in plain.args)
