"""Interval-arithmetic range propagation over jaxprs.

The device pipeline packs integers aggressively — dist·(n+1)+id
relaxation keys, (tail,head) u32 radix pairs, pow2 bucket math — and
every pack carries an implicit "fits int32" proof in a comment. This
module makes those proofs machine-checked:

  * `Interval` — integer/float interval arithmetic with an optional
    out-of-band *sentinel* value (the INT32_MAX "unreachable" marker
    BFS depths carry). Sentinels model the pipeline's ∪ {INF} value
    sets exactly: `[0, n] ∪ {INF}` is `Interval(0, n, sentinel=INF)`,
    and arithmetic distinguishes "the finite range overflows" from
    "the sentinel escaped into arithmetic".
  * `propagate` / `check_ranges` — seed a traced program's inputs with
    intervals and walk its jaxpr, flagging each op whose result
    provably exceeds its dtype (`int-overflow`), casts a sentinel into
    float arithmetic (`sentinel-escape` — the PR 5 unclamped-INF-depth
    bug, caught statically), or narrows past its input range
    (`cast-overflow`). Unmodelled primitives yield TOP (unknown)
    intervals which never flag: the propagator under-approximates, so
    every finding is real.
  * symbolic bound derivation — `packed_key_interval(n)` is the
    checker-side model of `bfs.bfs_doubling`'s packed relaxation key;
    `derive_packed_key_max_n()` computes the largest int32-safe n from
    it, and the auditor asserts it equals the constant the runtime
    actually switches on (`bfs.PACKED_KEY_MAX_N`).

The select-refinement rule is what lets clean code pass: the guard
idiom ``jnp.where(x == SENTINEL, repl, x)`` (bfs.finite_depth) strips
the sentinel from the false branch, so downstream float casts are
provably sentinel-free — while the same cast *without* the guard is
flagged. Only explicitly seeded values and their derivations are
checked; loop carries are TOP (audit loop bodies via witness programs).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.extend.core as jcore
import jax.numpy as jnp
import numpy as np

INT32_MAX = 2 ** 31 - 1
INT32_MIN = -(2 ** 31)

_INT_BOUNDS = {
    "int8": (-(2 ** 7), 2 ** 7 - 1),
    "int16": (-(2 ** 15), 2 ** 15 - 1),
    "int32": (INT32_MIN, INT32_MAX),
    "int64": (-(2 ** 63), 2 ** 63 - 1),
    "uint8": (0, 2 ** 8 - 1),
    "uint16": (0, 2 ** 16 - 1),
    "uint32": (0, 2 ** 32 - 1),
    "uint64": (0, 2 ** 64 - 1),
}


def dtype_bounds(dtype) -> Optional[Tuple[int, int]]:
    return _INT_BOUNDS.get(np.dtype(dtype).name)


@dataclasses.dataclass(frozen=True)
class Interval:
    """[lo, hi] plus an optional out-of-band sentinel the value may
    also take (e.g. BFS depth ∈ [0, n-1] ∪ {INT32_MAX}). `unknown`
    marks TOP: nothing is known, and nothing derived from it flags."""

    lo: float = 0
    hi: float = 0
    sentinel: Optional[int] = None
    unknown: bool = False

    # -------------------------------------------------------- builders
    @staticmethod
    def top() -> "Interval":
        return Interval(unknown=True)

    @staticmethod
    def const(c) -> "Interval":
        c = float(c) if isinstance(c, float) else c
        return Interval(lo=c, hi=c)

    @staticmethod
    def of(lo, hi, sentinel: Optional[int] = None) -> "Interval":
        return Interval(lo=lo, hi=hi, sentinel=sentinel)

    # ---------------------------------------------------------- views
    def hull_with_sentinel(self) -> "Interval":
        """Fold the sentinel into the range (what arithmetic on the raw
        values actually sees)."""
        if self.unknown or self.sentinel is None:
            return self
        return Interval(min(self.lo, self.sentinel),
                        max(self.hi, self.sentinel))

    def fits(self, dtype) -> bool:
        b = dtype_bounds(dtype)
        if b is None or self.unknown:
            return True
        eff = self.hull_with_sentinel()
        return b[0] <= eff.lo and eff.hi <= b[1]

    def union(self, other: "Interval") -> "Interval":
        if self.unknown or other.unknown:
            return Interval.top()
        s = self.sentinel if self.sentinel is not None else other.sentinel
        if (self.sentinel is not None and other.sentinel is not None
                and self.sentinel != other.sentinel):
            # two distinct sentinels: fold both into the range
            return self.hull_with_sentinel().union(
                other.hull_with_sentinel())
        return Interval(min(self.lo, other.lo), max(self.hi, other.hi),
                        sentinel=s)

    # ------------------------------------------------------ arithmetic
    def _binop(self, other: "Interval",
               f: Callable[[float, float], float]) -> "Interval":
        if self.unknown or other.unknown:
            return Interval.top()
        a, b = self.hull_with_sentinel(), other.hull_with_sentinel()
        vals = [f(a.lo, b.lo), f(a.lo, b.hi), f(a.hi, b.lo), f(a.hi, b.hi)]
        return Interval(min(vals), max(vals))

    def __add__(self, other):
        return self._binop(_coerce(other), lambda x, y: x + y)

    def __sub__(self, other):
        return self._binop(_coerce(other), lambda x, y: x - y)

    def __mul__(self, other):
        return self._binop(_coerce(other), lambda x, y: x * y)

    def min_(self, other):
        return self._binop(_coerce(other), min)

    def max_(self, other):
        return self._binop(_coerce(other), max)

    def neg(self):
        if self.unknown:
            return self
        h = self.hull_with_sentinel()
        return Interval(-h.hi, -h.lo)

    def taints_float(self) -> bool:
        """True when casting this value to float would launder the
        sentinel into arithmetic (the PR 5 poisoning)."""
        return (not self.unknown) and self.sentinel is not None


def _coerce(x) -> Interval:
    if isinstance(x, Interval):
        return x
    return Interval.const(x)


# ---------------------------------------------------------------------
# symbolic bound models (the checker side of the runtime constants)
# ---------------------------------------------------------------------

def packed_key_interval(n: int) -> Interval:
    """Model of `bfs.bfs_doubling`'s fused relaxation key at node count
    n: dist·(n+1) + id with dist clamped to [0, n] and id ∈ [0, n].
    Mirrors `bfs.packed_key_bound(n)` — the audit asserts both agree."""
    dist = Interval.of(0, n)
    node = Interval.of(0, n)
    return dist * Interval.const(n + 1) + node


def derive_packed_key_max_n() -> int:
    """Largest n for which the packed relaxation key provably fits
    int32, derived from the interval model (not from the runtime's own
    constant — that is the point: two independent derivations)."""
    # key_max = (n+1)^2 - 1 is monotone in n: binary search the switch.
    lo, hi = 1, 1 << 20
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if packed_key_interval(mid).fits(jnp.int32):
            lo = mid
        else:
            hi = mid - 1
    return lo


def euler_pack_interval(n: int) -> Interval:
    """Model of `bfs.root_tree_euler`'s u32 (tail << 16 | head) arc
    key: exact for tail, head ∈ [0, n]."""
    return Interval.of(0, n) * Interval.const(1 << 16) + Interval.of(0, n)


def derive_euler_pack_max_n() -> int:
    """Largest n whose (tail, head) pair packs into u32 with 16-bit
    fields — fields must not collide, so n itself is bounded by the
    field width, not just the u32 range."""
    n = (1 << 16) - 1
    assert euler_pack_interval(n).fits(jnp.uint32)
    return n


# ---------------------------------------------------------------------
# jaxpr propagation
# ---------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RangeFinding:
    kind: str          # "int-overflow" | "sentinel-escape" | "cast-overflow"
    primitive: str
    eqn_index: int     # index into the walked equation list
    detail: str

    def __str__(self):
        return (f"[{self.kind}] eqn {self.eqn_index} ({self.primitive}): "
                f"{self.detail}")


def _const_interval(val) -> Interval:
    arr = np.asarray(val)
    if arr.size == 0:
        return Interval.top()
    if arr.dtype == bool:
        return Interval.of(0, 1)
    if np.issubdtype(arr.dtype, np.floating):
        finite = arr[np.isfinite(arr)]
        if finite.size == 0:
            return Interval.top()
        return Interval.of(float(finite.min()), float(finite.max()))
    return Interval.of(int(arr.min()), int(arr.max()))


class _Env:
    """Var -> Interval map over one jaxpr, plus predicate provenance
    (`eq(x, K)` facts) for the select-refinement rule."""

    def __init__(self):
        self.vals: Dict[Any, Interval] = {}
        # pred var -> (operand var, const K) for eq-against-constant
        self.eq_facts: Dict[Any, Tuple[Any, int]] = {}

    def read(self, atom) -> Interval:
        if isinstance(atom, jcore.Literal):
            return _const_interval(atom.val)
        return self.vals.get(atom, Interval.top())

    def write(self, var, iv: Interval):
        self.vals[var] = iv


_PASSTHROUGH = {
    "broadcast_in_dim", "reshape", "squeeze", "expand_dims", "slice",
    "transpose", "copy", "stop_gradient", "rev", "gather",
    "dynamic_slice",
}

_BOOL_OUT = {"eq", "ne", "lt", "le", "gt", "ge", "and", "or", "not",
             "xor", "is_finite", "reduce_and", "reduce_or"}


def _refine_select(env: _Env, eqn) -> Optional[Interval]:
    """select_n(pred, case_false, case_true) with pred == eq(x, K):
    the false branch is x with the sentinel K stripped (x != K there),
    the true branch is taken as-is. Returns the refined union, or None
    when the pattern doesn't apply."""
    pred = eqn.invars[0]
    fact = env.eq_facts.get(pred)
    if fact is None or len(eqn.invars) != 3:
        return None
    x_var, k = fact
    branches: List[Interval] = []
    for case_atom, taken_when_eq in ((eqn.invars[1], False),
                                     (eqn.invars[2], True)):
        iv = env.read(case_atom)
        if (not taken_when_eq) and case_atom is x_var and not iv.unknown:
            if iv.sentinel == k:
                iv = Interval(iv.lo, iv.hi)          # sentinel stripped
            elif iv.hi == k:
                iv = Interval(iv.lo, k - 1, iv.sentinel)
        branches.append(iv)
    return branches[0].union(branches[1])


def propagate(closed_jaxpr: jcore.ClosedJaxpr,
              seeds: Sequence[Interval]) -> List[RangeFinding]:
    """Walk `closed_jaxpr` with input intervals `seeds` (one per invar,
    Interval.top() for "unknown"); return every provable range finding.

    Sub-jaxprs of inlined jits (the `jit` primitive) and custom_jvp wrappers are
    recursed into with their operand intervals; loop bodies (while /
    scan / cond) are NOT — their carries are TOP by construction, so
    in-loop invariants need dedicated witness programs.
    """
    findings: List[RangeFinding] = []
    counter = [0]
    _propagate_open(closed_jaxpr.jaxpr,
                    [_const_interval(c) for c in closed_jaxpr.consts],
                    list(seeds), findings, counter, {})
    return findings


def _inner_eq_facts(env: _Env, outer_atoms, inner_vars) -> Dict:
    """Translate eq-against-constant facts across a call boundary:
    when both the predicate and its operand are passed into the
    sub-jaxpr, rebind the fact onto the callee's invars (jnp.where
    lowers its select_n inside a jit, so refinement must follow)."""
    pos = {id(a): i for i, a in enumerate(outer_atoms)}
    facts = {}
    for i, atom in enumerate(outer_atoms):
        if isinstance(atom, jcore.Literal):
            continue
        fact = env.eq_facts.get(atom)
        if fact is None:
            continue
        x_outer, k = fact
        j = pos.get(id(x_outer))
        if j is not None and i < len(inner_vars) and j < len(inner_vars):
            facts[inner_vars[i]] = (inner_vars[j], k)
    return facts


def _propagate_open(jaxpr, const_ivs, seed_ivs, findings, counter,
                    in_facts):
    env = _Env()
    env.eq_facts.update(in_facts)
    for var, iv in zip(jaxpr.constvars, const_ivs):
        env.write(var, iv)
    for var, iv in zip(jaxpr.invars, seed_ivs):
        env.write(var, iv)
    for eqn in jaxpr.eqns:
        idx = counter[0]
        counter[0] += 1
        name = eqn.primitive.name
        ins = [env.read(a) for a in eqn.invars]
        out_iv = Interval.top()

        if name in ("add", "sub", "mul"):
            a, b = ins[0], ins[1]
            if a.taints_float() or b.taints_float():
                pass  # int arithmetic on a sentinel: folded below
            op = {"add": lambda x, y: x + y,
                  "sub": lambda x, y: x - y,
                  "mul": lambda x, y: x * y}[name]
            out_iv = op(a, b)
            dt = eqn.outvars[0].aval.dtype
            if not out_iv.unknown and dtype_bounds(dt) is not None \
                    and not out_iv.fits(dt):
                findings.append(RangeFinding(
                    "int-overflow", name, idx,
                    f"result range [{out_iv.lo}, {out_iv.hi}] exceeds "
                    f"{np.dtype(dt).name}"))
                out_iv = Interval.top()
        elif name == "neg":
            out_iv = ins[0].neg()
        elif name == "max":
            out_iv = ins[0].max_(ins[1])
        elif name == "min":
            out_iv = ins[0].min_(ins[1])
        elif name == "clamp":
            lo_iv, x_iv, hi_iv = ins
            if not any(i.unknown for i in (lo_iv, x_iv, hi_iv)):
                out_iv = x_iv.max_(lo_iv).min_(hi_iv)
        elif name == "select_n":
            refined = _refine_select(env, eqn)
            if refined is not None:
                out_iv = refined
            elif len(ins) == 3:
                out_iv = ins[1].union(ins[2])
        elif name == "convert_element_type":
            src = ins[0]
            dt = eqn.outvars[0].aval.dtype
            if np.issubdtype(dt, np.floating) and src.taints_float():
                findings.append(RangeFinding(
                    "sentinel-escape", name, idx,
                    f"integer sentinel {src.sentinel} cast into "
                    f"{np.dtype(dt).name} arithmetic"))
                out_iv = Interval.top()
            elif not src.fits(dt):
                findings.append(RangeFinding(
                    "cast-overflow", name, idx,
                    f"range [{src.lo}, {src.hi}]"
                    + (f" ∪ {{{src.sentinel}}}" if src.sentinel is not None
                       else "")
                    + f" does not fit {np.dtype(dt).name}"))
                out_iv = Interval.top()
            else:
                out_iv = src
        elif name == "iota":
            size = int(np.prod(eqn.outvars[0].aval.shape)) or 1
            out_iv = Interval.of(0, size - 1)
        elif name in ("reduce_min", "reduce_max", "argmin", "argmax"):
            if name in ("argmin", "argmax"):
                sz = int(np.prod(eqn.invars[0].aval.shape)) or 1
                out_iv = Interval.of(0, sz - 1)
            else:
                out_iv = ins[0]
        elif name == "reduce_sum":
            src = ins[0]
            if not src.unknown:
                cnt = max(int(np.prod(eqn.invars[0].aval.shape)), 1)
                h = src.hull_with_sentinel()
                out_iv = Interval(min(h.lo * cnt, h.lo),
                                  max(h.hi * cnt, h.hi))
                dt = eqn.outvars[0].aval.dtype
                if dtype_bounds(dt) is not None and not out_iv.fits(dt):
                    findings.append(RangeFinding(
                        "int-overflow", name, idx,
                        f"sum bound [{out_iv.lo}, {out_iv.hi}] exceeds "
                        f"{np.dtype(dt).name}"))
                    out_iv = Interval.top()
        elif name in ("scatter_min", "scatter_max"):
            out_iv = ins[0].union(ins[-1])
        elif name in _PASSTHROUGH:
            out_iv = ins[0]
        elif name in _BOOL_OUT:
            out_iv = Interval.of(0, 1)
            if name == "eq":
                # record eq-against-constant facts for select refinement
                for x_atom, k_atom in ((eqn.invars[0], eqn.invars[1]),
                                       (eqn.invars[1], eqn.invars[0])):
                    kiv = env.read(k_atom)
                    if not kiv.unknown and kiv.lo == kiv.hi \
                            and not isinstance(x_atom, jcore.Literal):
                        env.eq_facts[eqn.outvars[0]] = (x_atom, kiv.lo)
                        break
        elif name in ("jit", "closed_call", "custom_jvp_call",
                      "custom_vjp_call", "remat", "checkpoint"):
            sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if sub is not None:
                if isinstance(sub, jcore.ClosedJaxpr):
                    inner = sub.jaxpr
                    facts = _inner_eq_facts(env, eqn.invars, inner.invars)
                    outs = _propagate_open(
                        inner, [_const_interval(c) for c in sub.consts],
                        ins, findings, counter, facts)
                else:
                    facts = _inner_eq_facts(env, eqn.invars, sub.invars)
                    outs = _propagate_open(sub, [], ins, findings, counter,
                                           facts)
                for var, iv in zip(eqn.outvars, outs):
                    env.write(var, iv)
                continue
        # anything else: outputs stay TOP (under-approximation)

        for var in eqn.outvars:
            env.write(var, out_iv)
    return [env.read(v) for v in jaxpr.outvars]


def check_ranges(fn: Callable, seeds: Sequence[Interval], *args,
                 static_kwargs: Optional[dict] = None) -> List[RangeFinding]:
    """Trace `fn` over `args` (arrays or jax.ShapeDtypeStruct) and
    propagate `seeds` (one Interval per positional arg)."""
    static_kwargs = static_kwargs or {}
    closed = jax.make_jaxpr(lambda *a: fn(*a, **static_kwargs))(*args)
    flat_seeds: List[Interval] = []
    for s, a in zip(seeds, args):
        leaves = jax.tree_util.tree_leaves(a)
        flat_seeds.extend([s] * len(leaves))
    n_in = len(closed.jaxpr.invars)
    flat_seeds += [Interval.top()] * (n_in - len(flat_seeds))
    return propagate(closed, flat_seeds[:n_in])
