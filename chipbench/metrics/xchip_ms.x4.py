"""xchip_ms.x4: device ms per batch call, the mean over the chips, in the
collective operations of the program the service dispatched, in the
traced window. In the set-up of a traced run `prepare` compiles that
program from the service's `program_specs` (which carry the mesh's
batch sharding, so the spec compiles what the dispatch runs) and takes
from its HLO, by opcode, every executed collective (and every fusion
that holds one). In the batch-sharded program these are the loop
predicates' `pred[]` all-reduces, one a round of each batch-dependent
while loop: the ICI round trip plus the wait for the slowest chip. A
program whose specs carry no sharding reads nothing."""
import sys

from chipbench import devtrace, readers, stages

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "ragged-all-to-all", "collective-permute",
               "collective-broadcast")
OPCODES = {c + end for c in COLLECTIVES for end in ("", "-start", "-done")}


def collective_ops(hlo_text: str) -> set:
    """Names of the instructions of one compiled module that run as
    device operations and are collectives, or fusions holding one."""
    m = stages.StageMap(hlo_text)
    return {n for n in m.executed()
            if m.opcode[n] in OPCODES or (m.opcode[n] == "fusion" and any(
                m.opcode[i] in OPCODES for c in m.called[n]
                for i in m.body[c]))}


def prepare(run):
    if hasattr(run, "xchip_ops"):
        return
    run.xchip_ops = None
    svc = getattr(run.entry, "svc", None)
    specs = svc.program_specs() if svc is not None else []
    if not specs or any(getattr(a, "sharding", None) is None
                        for s in specs for a in s.args):
        return
    try:
        run.xchip_ops = set().union(*(
            collective_ops(stages._hlo_text(s.fn, s.args, s.static_kwargs))
            for s in specs))
    except Exception as e:  # the run goes on; the metric falls out
        print(f"chipbench: no collective map ({type(e).__name__}: {e})",
              file=sys.stderr)


def read(run):
    names = getattr(run, "xchip_ops", None)
    if names is None or readers.traced_busy_s(run) is None:
        return None
    lo, hi = run.trace.window
    busy, found = 0.0, set()
    for evs in run.trace.chips:
        iv = []
        for name, s, e in evs:
            op = devtrace.op_name(name)
            if op in names and e > lo and s < hi:
                iv.append((s, e))
                found.add(op)
        busy += devtrace.covered(iv, lo, hi)
    print(f"chipbench: xchip_ms.x4: {len(names)} collectives in the HLO "
          f"{sorted(names)}, {len(found)} of them in the trace "
          f"{sorted(found)}", file=sys.stderr)
    if names and not found:
        return None
    return 1e3 * busy * 1e-9 / len(run.trace.chips) / len(run.traced)
