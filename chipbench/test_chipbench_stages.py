"""The stage map (stages.py): parsed from hand-written HLO, taken over
the whole compiled program, and read on hand-made trace intervals the
way `test_chipbench_trace.py` builds them."""
import types

import pytest

from chipbench import devtrace, harness, stages
from chipbench.test_chipbench_trace import _Ev, _Line, _Plane, _Profile

HLO = """HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %tanh.0 = f32[8]{0} tanh(%param_0), metadata={op_name="jit(f)/vmap(EFF)/tanh"}
}

%fused_mixed (param_1: f32[8]) -> f32[8] {
  %param_1 = f32[8]{0} parameter(0)
  %exp.1 = f32[8]{0} exponential(%param_1), metadata={op_name="jit(f)/LCA/exp"}
  %exp.2 = f32[8]{0} exponential(%exp.1), metadata={op_name="jit(f)/LCA/exp"}
  ROOT %neg.3 = f32[8]{0} negate(%exp.2), metadata={op_name="jit(f)/RES/neg"}
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%arg), index=1
  %gte.0 = s32[] get-tuple-element(%arg), index=0
  %copy.9 = s32[] copy(%gte.0)
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%copy.9, %gte.1)
}

%cond (arg.1: (s32[], f32[8])) -> pred[] {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.3 = pred[] compare(%arg.1, %arg.1), direction=LT
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %constant.1 = s32[] constant(0)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/vmap(EFF)/tanh"}
  %copy.2 = f32[8]{0} copy(%fusion.1)
  %fusion.3 = f32[8]{0} fusion(%copy.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/MST/mst/sort"}
  %both.4 = f32[8]{0} add(%fusion.1, %fusion.3)
  %fusion.10 = f32[8]{0} fusion(%both.4), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/MARK/tanh"}
  %fusion.11 = f32[8]{0} fusion(%fusion.10), kind=kLoop, calls=%fused_mixed
  %tuple.5 = (s32[], f32[8]{0}) tuple(%constant.1, %both.4)
  %while.6 = (s32[], f32[8]{0}) while(%tuple.5), condition=%cond, body=%body, metadata={op_name="jit(f)/vmap(REC)/rec/jit(g)/while"}
  %while.7 = (s32[], f32[8]{0}) while(%tuple.5), condition=%cond, body=%body, metadata={op_name="jit(f)/REC/rec/while/body/while"}
  ROOT %gte.8 = f32[8]{0} get-tuple-element(%while.6), index=1
}
"""


def test_stage_map_of_hand_written_hlo():
    m = stages.StageMap(HLO, loops=("rec", "mst"))
    assert m.entry == "main"
    assert m.opcode["tuple.5"] == "tuple" and m.opcode["while.6"] == "while"
    assert m.operands["both.4"] == ["fusion.1", "fusion.3"]
    assert m.called["while.6"] == ["cond", "body"]
    # scopes, under vmap too
    assert m.stage["fusion.1"] == "EFF" and m.stage["fusion.3"] == "MST"
    assert m.stage["while.6"] == "REC"
    # no scope: the operand's stage, the caller's, the users'
    assert m.stage["copy.2"] == "EFF"
    assert m.stage["copy.9"] == "REC" and m.stage["lt.3"] == "REC"
    assert m.stage["gte.8"] == "REC"
    assert m.stage["tuple.5"] == "REC" and m.stage["constant.1"] == "REC"
    assert m.stage["x"] == "EFF"
    # two stages in, two stages out: no one stage holds its work
    assert m.stage["both.4"] is None
    # a fusion XLA made across stages: the stage of most of its work
    assert m.stage["fusion.11"] == "LCA"
    # a loop's while; the nested scan of its body is not the loop
    assert m.loops == {"rec": {"while.6"}, "mst": set()}
    assert set(m.executed()) == set(m.body["main"] + m.body["body"]
                                    + m.body["cond"])


def test_merge_drops_names_the_modules_disagree_on():
    a = stages.StageMap(HLO, loops=("rec",))
    b = stages.StageMap(HLO.replace("vmap(EFF)/tanh", "SORT/tanh"),
                        loops=("rec",))
    stage, loops = stages.merge([a, b])
    assert stage["fusion.3"] == "MST" and stage["fusion.1"] is None
    assert loops == {"rec": {"while.6"}}


# instructions that hold no work of a stage: arguments, the output
# tuple, and constants with the copies and broadcasts made of them
ALLOWED = {"parameter", "tuple", "constant"}


def _constant_plumbing(m, name):
    op = m.opcode[name]
    return op == "constant" or (bool(m.operands[name]) and all(
        _constant_plumbing(m, o) for o in m.operands[name]))


@pytest.mark.parametrize("batched", [False, True])
def test_every_executed_instruction_has_a_stage(batched):
    """Compile the fused program (single, and vmapped as the service
    runs it) and map it: every instruction that runs as a device op
    falls under one of the seven stages, but for the allowlist."""
    from repro.core.graph import powergrid_like_graph
    from repro.core.sparsify import LOOPS, STAGES, lgrass_program
    from repro.serve.sparsify_service import SparsifyService

    assert STAGES == stages.STAGES
    g = powergrid_like_graph(8, 0.25, seed=3)
    if batched:
        spec = SparsifyService().program_specs([(g.n, g.m)],
                                               batch_sizes=(4,))[0]
        fn, args, kw = spec.fn, spec.args, spec.static_kwargs
    else:
        fn, args, kw = lgrass_program(g)
    m = stages.StageMap(fn.lower(*args, **kw).compile().as_text(), LOOPS)
    ran = m.executed()
    loose = [n for n in ran if m.stage[n] is None]
    assert not [n for n in loose if m.opcode[n] not in ALLOWED
                and not _constant_plumbing(m, n)], loose
    assert len(loose) <= 0.02 * len(ran)
    assert set(m.stage[n] for n in ran) - {None} == set(STAGES)
    # one while per counted loop; "tree" runs under "levels" only and
    # "mst_jump" inside "mst", without a scope of its own
    assert {lp: len(ws) for lp, ws in m.loops.items()} == {
        "bfs": 1, "tree": 0, "mst": 1, "mst_jump": 0, "mark": 1, "rec": 1}
    assert all(m.stage[w] for ws in m.loops.values() for w in ws)


def _run(ops, host, stage, loops, traced=2, stats=None):
    pd = _Profile([
        _Plane("/device:TPU:0", [_Line("XLA Ops", ops)]),
        _Plane("/host:CPU", [_Line("python", host)])])
    trace = devtrace.Trace(pd, ("/device:TPU:", "XLA Ops"),
                           ("/device:TPU:", "XLA Modules"))
    calls = [harness.Call(0, 1, i, 1, None) for i in range(traced)]
    return types.SimpleNamespace(
        trace=trace, traced=calls, stage_map=(stage, loops),
        entry=types.SimpleNamespace(svc=types.SimpleNamespace(stats=stats)))


STAGE = {"fusion.1": "EFF", "while.2": "REC", "fusion.3": "REC",
         "fusion.5": "MST", "copy.4": None}
OPS = [_Ev("%fusion.1 = f32[8] fusion(x)", 100, 50),
       _Ev("while.2", 150, 150), _Ev("fusion.3", 160, 40),
       _Ev("copy.4", 300, 2), _Ev("fusion.5", 310, 90),
       # a runtime event of the CPU's line: not an operation
       _Ev("ThunkExecutor::Execute", 100, 300)]
HOST = [_Ev(devtrace.WINDOW, 100, 300), _Ev("lgrass.upload", 295, 20),
        _Ev("lgrass.fetch", 380, 20), _Ev("other", 302, 8)]


def test_stage_times_on_hand_made_intervals():
    run = _run(OPS, HOST, STAGE, {"rec": {"while.2"}, "mst": set()})
    # window [100, 400); mapped 50 + 150 + 90 of 292 ns of operations
    ms = {s: stages.stage_ms(run, s, per_graph=True) for s in stages.STAGES}
    assert ms["EFF"] == pytest.approx(1e3 * 50e-9 / 2)
    assert ms["REC"] == pytest.approx(1e3 * 150e-9 / 2)  # while holds body
    assert ms["MST"] == pytest.approx(1e3 * 90e-9 / 2)
    assert ms["LCA"] == 0
    assert stages.stage_ms(run, "REC", per_graph=False) == ms["REC"]
    assert stages.loop_ms(run) == {"rec": pytest.approx(1e3 * 150e-9 / 2),
                                   "mst": 0}


def test_a_map_that_covers_too_little_reads_nothing():
    ops = OPS[:3] + [_Ev("copy.4", 300, 10)] + OPS[4:]
    run = _run(ops, HOST, STAGE, {})   # 290 of 300 ns: under 98%
    assert stages.stage_ms(run, "EFF", per_graph=True) is None
    assert stages.loop_ms(run) is None
    bare = types.SimpleNamespace(trace=None, traced=[], stage_map=None)
    assert stages.stage_ms(bare, "EFF", per_graph=True) is None


def _metric(name):
    return harness.load_module("metrics", name + ".py")


def test_lockstep_xfer_and_rec_rounds_readers():
    stats = types.SimpleNamespace(loop_rounds={"rec": 6, "mst": 0},
                                  loop_lane_rounds={"rec": 8, "mst": 0})
    # a device line of operations only: busy and idle as idle_share
    # reads them
    run = _run(OPS[:-1], HOST, STAGE, {"rec": {"while.2"}, "mst": set()},
               stats=stats)
    assert _metric("lockstep_ms.batch").read(run) == pytest.approx(
        1e3 * 150e-9 / 2 * (1 - 6 / 8))
    # idle gaps [302, 310) under the upload span; none under the fetch
    assert _metric("xfer_ms.single").read(run) == pytest.approx(
        1e3 * 8e-9 / 2)
    no_spans = _run(OPS, HOST[:1], STAGE, {})
    assert _metric("xfer_ms.single").read(no_spans) is None
    old = types.SimpleNamespace(svc=types.SimpleNamespace(
        stats=types.SimpleNamespace()))
    run.entry = old   # a service without the loop counters
    assert _metric("lockstep_ms.batch").read(run) is None
    rec = _metric("rec_rounds.single")
    assert rec.read(run) is None
    run.rec_rounds = [3, 5, 4, 4]
    assert rec.read(run) == 4
