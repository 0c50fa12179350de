"""Device time per LGRASS stage and per while loop, read through the
program's own scopes.

The program puts every operation of its device programs in one
`jax.named_scope` per paper stage (`STAGES`), and calls each counted
while loop under a scope of the loop's name (`repro.core.sparsify.LOOPS`);
XLA keeps both in each instruction's `op_name`. In the set-up of a
traced run `prepare` compiles, from the persistent cache, the programs
the cell runs, and maps each instruction name to its stage. A reader
then takes, in the traced window, the union of the device intervals of
each stage's operations, and of each loop's `while` operation.

Instructions XLA adds (copies, tuple plumbing, constants, fusions it
made across stages) carry no scope. A fusion takes the stage most of
its fused instructions carry; others take the stage of their
computation's caller, else the one stage of their mapped operands,
else, once all their users are mapped, the one stage of those. Where the
mapped operations cover less than `MIN_COVER` of the device's operation
time, every reading is None: a broken map shows as a missing metric,
never as a wrong one.
"""
from __future__ import annotations

import re
import sys
from collections import Counter
from typing import Dict, List, Optional

from chipbench import devtrace, readers

STAGES = ("EFF", "MST", "LCA", "RES", "SORT", "MARK", "REC")
MIN_COVER = 0.98

_STAGE = re.compile(r"(?:^|[/(])(" + "|".join(STAGES) + r")(?=[/)]|$)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.-]+)\s+\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s+=\s+(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.-]+)")
_OPCODE = re.compile(r"\s*([\w-]+)")
# an event of the device's operations line that names an instruction
# (the CPU's line also holds runtime events such as "Foo::Execute")
_OP_LIKE = re.compile(r"^[A-Za-z_][\w.-]*$")
# opcodes whose called computations run as operations of their own;
# the rest (fusion, reduce, sort, ...) run inside their caller
_CONTROL = {"while", "call", "conditional"}


def stage_of(op_name: str) -> Optional[str]:
    """The outermost stage scope in an `op_name`, or None."""
    m = _STAGE.search(op_name or "")
    return m.group(1) if m else None


def _opcode(rest: str) -> str:
    """The opcode of an instruction's text after its `=`: skip the
    result type (one token, or a parenthesised tuple)."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[-1]
    m = _OPCODE.match(rest)
    return m.group(1) if m else ""


class StageMap:
    """{instruction name: stage} of one compiled HLO module's text, and
    the `while` instructions of each counted loop."""

    def __init__(self, hlo_text: str, loops=()):
        self.opcode: Dict[str, str] = {}
        self.op_name: Dict[str, str] = {}
        self.operands: Dict[str, List[str]] = {}
        self.called: Dict[str, List[str]] = {}
        self.body: Dict[str, List[str]] = {}   # computation -> instrs
        self.entry = None
        comp = None
        for line in hlo_text.splitlines():
            mc = _COMPUTATION.match(line)
            if mc:
                comp = mc.group(1)
                self.body[comp] = []
                if line.startswith("ENTRY"):
                    self.entry = comp
                continue
            mi = _INSTRUCTION.match(line)
            if comp is None or not mi:
                continue
            name, rest = mi.groups()
            self.body[comp].append(name)
            self.opcode[name] = _opcode(rest)
            m = _OP_NAME.search(rest)
            self.op_name[name] = m.group(1) if m else ""
            self.operands[name] = _REF.findall(rest.split(" metadata=")[0])
        for name, refs in self.operands.items():
            self.called[name] = [r for r in refs if r in self.body]
            self.operands[name] = [r for r in refs if r in self.opcode]
        self.stage = {n: stage_of(o) for n, o in self.op_name.items()}
        for n, comps in self.called.items():
            if self.stage[n] is None and self.opcode[n] == "fusion":
                votes = Counter(stage_of(self.op_name[i])
                                for c in comps for i in self.body[c])
                votes.pop(None, None)
                top = votes.most_common(2)
                if top and (len(top) == 1 or top[0][1] > top[1][1]):
                    self.stage[n] = top[0][0]
        self._propagate()
        pats = {lp: re.compile(r"(?:^|/)" + re.escape(lp)
                               + r"(?:/jit\([^/]*\))*/while$")
                for lp in loops}
        self.loops = {lp: {n for n, o in self.op_name.items()
                           if self.opcode[n] == "while" and p.search(o)}
                      for lp, p in pats.items()}

    def _propagate(self):
        users: Dict[str, List[str]] = {n: [] for n in self.opcode}
        for n, ops in self.operands.items():
            for o in ops:
                users[o].append(n)
        changed = True
        while changed:
            changed = False
            for n, comps in self.called.items():
                s = self.stage[n]
                if s is None:
                    continue
                for c in comps:
                    for i in self.body[c]:
                        if self.stage[i] is None:
                            self.stage[i] = s
                            changed = True
            for n in self.opcode:
                if self.stage[n] is None:
                    self.stage[n] = self._one(self.operands[n])
                    changed |= self.stage[n] is not None
            if changed:  # callers and operands first, to a fixpoint
                continue
            for n in self.opcode:
                if self.stage[n] is None and all(
                        self.stage[u] is not None for u in users[n]):
                    self.stage[n] = self._one(users[n])
                    changed |= self.stage[n] is not None

    def _one(self, names) -> Optional[str]:
        """The stage of `names`, where the mapped ones share one."""
        got = {self.stage[m] for m in names} - {None}
        return got.pop() if len(got) == 1 else None

    def executed(self) -> List[str]:
        """Instructions that run as device operations of their own: those
        of the entry computation and of the computations that `while`,
        `call` and `conditional` run, not those inside a fusion or an
        applied reducer."""
        seen, todo, out = set(), [self.entry], []
        while todo:
            c = todo.pop()
            if c in seen or c not in self.body:
                continue
            seen.add(c)
            for n in self.body[c]:
                out.append(n)
                if self.opcode[n] in _CONTROL:
                    todo.extend(self.called[n])
        return out


def merge(maps: List[StageMap]):
    """({instruction: stage}, {loop: {while instructions}}) over several
    modules; a name the modules disagree on maps to nothing."""
    stage: Dict[str, Optional[str]] = {}
    loops: Dict[str, set] = {}
    for m in maps:
        for n, s in m.stage.items():
            stage[n] = s if stage.get(n, s) == s else None
        for lp, ws in m.loops.items():
            loops.setdefault(lp, set()).update(ws)
    for lp in loops:
        loops[lp] = {w for w in loops[lp] if stage.get(w) is not None}
    return stage, loops


def _programs(run):
    """(jitted fn, args, static kwargs) of every program the cell runs."""
    if run.traffic["entry"] == "service":
        return [(s.fn, s.args, s.static_kwargs)
                for s in run.entry.svc.program_specs()]
    from repro.core.sparsify import lgrass_program

    seen, out = set(), []
    for graphs, budgets in run.pool:
        for g, b in zip(graphs, budgets):
            if (g.n, g.m, b) not in seen:
                seen.add((g.n, g.m, b))
                out.append(lgrass_program(g, b))
    return out


def _hlo_text(fn, args, kw) -> str:
    """The compiled program's HLO text. JAX documents that `as_text` may
    raise NotImplementedError where the runtime keeps no text view, as
    an executable loaded from the persistent cache may: then compile the
    same program afresh with the cache off (same HLO, same names)."""
    import jax

    lowered = fn.lower(*args, **kw)
    try:
        return lowered.compile().as_text()
    except NotImplementedError:
        pass
    on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", on)


def prepare(run):
    """Build the run's stage map once (`run.stage_map`), or None where
    the program has no such scopes or helpers."""
    if hasattr(run, "stage_map"):
        return
    run.stage_map = None
    try:
        from repro.core.sparsify import LOOPS

        maps = [StageMap(_hlo_text(fn, args, kw), LOOPS)
                for fn, args, kw in _programs(run)]
    except Exception as e:  # the run goes on; the stage metrics fall out
        print(f"chipbench: no stage map ({type(e).__name__}: {e})",
              file=sys.stderr)
        return
    run.stage_map = merge(maps)


def _reduce(run):
    """{"stage": {stage: s}, "loop": {loop: s}} of device time in the
    traced window, the mean over the chips, or None."""
    if hasattr(run, "stage_times"):
        return run.stage_times
    run.stage_times = None
    smap = getattr(run, "stage_map", None)
    if smap is None or readers.traced_busy_s(run) is None:
        return None
    stage, loops = smap
    loop_of = {w: lp for lp, ws in loops.items() for w in ws}
    lo, hi = run.trace.window
    sums = {"stage": dict.fromkeys(STAGES, 0.0),
            "loop": dict.fromkeys(loops, 0.0)}
    mapped = total = 0.0
    for evs in run.trace.chips:
        by = {"stage": {s: [] for s in STAGES},
              "loop": {lp: [] for lp in loops}}
        ops, hit = [], []
        for name, s, e in evs:
            op = devtrace.op_name(name)
            if e <= lo or s >= hi or not _OP_LIKE.match(op):
                continue
            ops.append((s, e))
            st = stage.get(op)
            if st is not None:
                hit.append((s, e))
                by["stage"][st].append((s, e))
            if op in loop_of:
                by["loop"][loop_of[op]].append((s, e))
        total += devtrace.covered(ops, lo, hi)
        mapped += devtrace.covered(hit, lo, hi)
        for kind, groups in by.items():
            for k, iv in groups.items():
                sums[kind][k] += devtrace.covered(iv, lo, hi) * 1e-9
    if total <= 0 or mapped < MIN_COVER * total:
        return None
    n = len(run.trace.chips)
    run.stage_times = {kind: {k: v / n for k, v in d.items()}
                       for kind, d in sums.items()}
    return run.stage_times


def stage_ms(run, stage: str, per_graph: bool):
    """Device ms of `stage`'s operations per graph (or per call) of the
    traced window, or None."""
    t = _reduce(run)
    if t is None:
        return None
    n = readers.graphs(run.traced) if per_graph else len(run.traced)
    return 1e3 * t["stage"][stage] / n


def loop_ms(run) -> Optional[Dict[str, float]]:
    """Device ms of each counted loop's `while` operation per call of the
    traced window, or None."""
    t = _reduce(run)
    if t is None:
        return None
    return {lp: 1e3 * s / len(run.traced) for lp, s in t["loop"].items()}
