"""Jaxpr-level audit of the public device programs.

Traces each public jit program (`jax.make_jaxpr` — no compile, no
device) over the bucket signatures `SparsifyService` actually serves,
then walks the closed jaxpr (recursively through jit / while / scan /
cond sub-jaxprs) and asserts the pipeline's contracts:

  * **one dispatch** — the program traces as a single closed jaxpr with
    zero host-callback primitives, so the dispatch the service issues
    is the only host↔device transition: no hidden `device_get`, no
    debug callback, no infeed. (`dispatch_count` is 1 + the number of
    callback primitives found.)
  * **no f64 / weak-type leaks** — on the non-x64 leg no variable
    anywhere in the program may carry a 64-bit dtype, and the top-level
    outputs must be strongly typed (a weak output means a Python
    literal's promotion escaped the program boundary).
  * **loop budgets** — the while-loop COUNT is pinned per
    (program, bfs_engine) — the O(log n)/O(diameter) round loops are
    data-bounded by construction, but an accidental extra while is a
    regression this catches — and every scan trip count must be a
    documented O(log n) or O(chunk) constant, never O(L)/O(n)
    (`allowed_scan_lengths`): the contract behind the
    "O(log n)-round / ceil(n_crossing/C)-step" claims.
  * **derived constants** — the runtime's pack-switch constants
    (`bfs.PACKED_KEY_MAX_N`, `bfs.EULER_PACK_MAX_N`) must equal the
    values independently derived from the interval models in
    `analysis.ranges`, and the packed-key witness program must range-
    check clean at the switch point and FLAG one past it.

Audited program set (`standard_program_audits`): `phase1_device
[_batched]`, `lgrass_device[_batched]` (the donated variant shares the
trace — donation is a compile-time property, checked via
`launch.hlo_analysis.analyze_jitted`'s output_alias report), the
standalone `recover_device[_batched]`, and the spectral-probe
estimator; `audit_service` covers a live `SparsifyService`'s warmed
signature set through `ProgramSpec`s.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.extend.core as jcore
import jax.numpy as jnp
import numpy as np

from repro.analysis.ranges import (
    Interval,
    check_ranges,
    derive_euler_pack_max_n,
    derive_packed_key_max_n,
    packed_key_interval,
)

# Host-transition primitives: any of these inside a "single dispatch"
# program means the dispatch is not actually single.
FORBIDDEN_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback",
    "infeed", "outfeed", "host_callback_call", "outside_call",
})

# 64-bit dtypes that may not appear outside the x64 leg.
_WIDE_DTYPES = ("float64", "int64", "uint64", "complex128")

# The documented while-loop budget per program family × BFS engine
# (schedule-independent; `parallel` uses a while in both engines):
#   phase-1 = graph BFS (1 while doubling / 2 while levels for the two
#   passes) + Borůvka rounds (1) + the pointer jumping of each round's
#   contraction (1, nested) + MARK scheduler (1); the fused program adds
#   the recovery outer loop (1). Each counts its rounds in its carry
#   (`core.sparsify.LOOPS`).
EXPECTED_WHILE: Dict[Tuple[str, str], int] = {
    ("phase1", "doubling"): 4,
    ("phase1", "levels"): 5,
    ("lgrass", "doubling"): 5,
    ("lgrass", "levels"): 6,
    ("recover", "-"): 1,
    ("probe", "-"): 0,
}


def _sub_jaxprs(eqn) -> Iterable[Any]:
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for sub in vs:
            if isinstance(sub, (jcore.ClosedJaxpr, jcore.Jaxpr)):
                yield sub


def collect_eqns(closed_or_jaxpr) -> List[Any]:
    """Every equation of the program, recursively through all
    sub-jaxprs (jit bodies, while cond/body, scan body, cond branches)."""
    jx = getattr(closed_or_jaxpr, "jaxpr", closed_or_jaxpr)
    out: List[Any] = []

    def walk(j):
        for eqn in j.eqns:
            out.append(eqn)
            for sub in _sub_jaxprs(eqn):
                walk(getattr(sub, "jaxpr", sub))

    walk(jx)
    return out


def _all_avals(closed) -> Iterable[Any]:
    jx = closed.jaxpr
    for v in list(jx.invars) + list(jx.outvars) + list(jx.constvars):
        if hasattr(v, "aval"):
            yield v.aval
    for eqn in collect_eqns(closed):
        for v in list(eqn.invars) + list(eqn.outvars):
            av = getattr(v, "aval", None)
            if av is not None:
                yield av


@dataclasses.dataclass
class AuditReport:
    name: str
    n_eqns: int = 0
    n_while: int = 0
    scan_lengths: Tuple[int, ...] = ()
    dispatch_count: int = 1
    findings: List[str] = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.findings

    def as_dict(self) -> dict:
        return dict(name=self.name, n_eqns=self.n_eqns,
                    n_while=self.n_while,
                    scan_lengths=list(self.scan_lengths),
                    dispatch_count=self.dispatch_count,
                    findings=list(self.findings), ok=self.ok)


def audit_program(
    name: str,
    fn: Callable,
    args: Sequence[Any],
    static_kwargs: Optional[dict] = None,
    *,
    expected_while: Optional[int] = None,
    allowed_scan_lengths: Optional[Iterable[int]] = None,
    allow_wide: Optional[bool] = None,
) -> AuditReport:
    """Trace `fn(*args, **static_kwargs)` and run every jaxpr check.

    args are arrays or `jax.ShapeDtypeStruct`s. allow_wide=None reads
    the live x64 flag (the x64 CI leg legitimately carries 64-bit
    dtypes). expected_while / allowed_scan_lengths=None skip the loop
    budget (used for ad-hoc programs without a documented budget).
    """
    static_kwargs = static_kwargs or {}
    if allow_wide is None:
        allow_wide = bool(jax.config.jax_enable_x64)
    rep = AuditReport(name=name)
    closed = jax.make_jaxpr(lambda *a: fn(*a, **static_kwargs))(*args)
    eqns = collect_eqns(closed)
    rep.n_eqns = len(eqns)

    # --- dispatch count / forbidden primitives -------------------------
    callbacks = [e.primitive.name for e in eqns
                 if e.primitive.name in FORBIDDEN_PRIMITIVES]
    rep.dispatch_count = 1 + len(callbacks)
    for cb in callbacks:
        rep.findings.append(
            f"host-callback primitive '{cb}' inside the device program "
            f"(dispatch is not single)")

    # --- dtype scan ----------------------------------------------------
    if not allow_wide:
        seen_wide = set()
        for av in _all_avals(closed):
            dt = str(getattr(av, "dtype", ""))
            if dt in _WIDE_DTYPES:
                seen_wide.add(dt)
        for dt in sorted(seen_wide):
            rep.findings.append(
                f"64-bit dtype {dt} leaked into the non-x64 program")
    for i, v in enumerate(closed.jaxpr.outvars):
        if getattr(getattr(v, "aval", None), "weak_type", False):
            rep.findings.append(
                f"output {i} is weakly typed (literal promotion escaped "
                f"the program)")

    # --- loop budget ---------------------------------------------------
    rep.n_while = sum(1 for e in eqns if e.primitive.name == "while")
    rep.scan_lengths = tuple(sorted(
        int(e.params["length"]) for e in eqns
        if e.primitive.name == "scan"))
    if expected_while is not None and rep.n_while != expected_while:
        rep.findings.append(
            f"while-loop count {rep.n_while} != documented budget "
            f"{expected_while}")
    if allowed_scan_lengths is not None:
        allowed = set(int(x) for x in allowed_scan_lengths)
        for ln in rep.scan_lengths:
            if ln not in allowed:
                rep.findings.append(
                    f"scan trip count {ln} outside the documented budget "
                    f"set {sorted(allowed)} (an O(L)/O(n) loop?)")
    return rep


# ---------------------------------------------------------------------
# derived constants
# ---------------------------------------------------------------------

def check_derived_constants() -> List[str]:
    """Assert the runtime pack-switch constants equal the values the
    interval models derive independently, and that the packed-key
    witness range-checks clean at the switch point and flags past it."""
    from repro.core import bfs

    findings: List[str] = []
    derived = derive_packed_key_max_n()
    if derived != bfs.PACKED_KEY_MAX_N:
        findings.append(
            f"bfs.PACKED_KEY_MAX_N={bfs.PACKED_KEY_MAX_N} != derived "
            f"int32-safe bound {derived}")
    if derive_euler_pack_max_n() != bfs.EULER_PACK_MAX_N:
        findings.append(
            f"bfs.EULER_PACK_MAX_N={bfs.EULER_PACK_MAX_N} != derived "
            f"u32 pack bound {derive_euler_pack_max_n()}")
    for n in (2, 1024, bfs.PACKED_KEY_MAX_N):
        model = packed_key_interval(n).hi
        if model != bfs.packed_key_bound(n):
            findings.append(
                f"packed_key_bound({n})={bfs.packed_key_bound(n)} "
                f"disagrees with interval model {model}")

    # the traced witness: key = dist * (n+1) + id on finite clamped dist
    def witness(dist, ids, base):
        return dist * base + ids

    def run(n: int) -> List:
        spec = jax.ShapeDtypeStruct((4,), jnp.int32)
        return check_ranges(
            witness,
            [Interval.of(0, n), Interval.of(0, n),
             Interval.const(n + 1)],
            spec, spec, jax.ShapeDtypeStruct((), jnp.int32))

    if run(bfs.PACKED_KEY_MAX_N):
        findings.append(
            f"packed-key witness flags at n=PACKED_KEY_MAX_N="
            f"{bfs.PACKED_KEY_MAX_N} (bound too loose)")
    if not run(bfs.PACKED_KEY_MAX_N + 1):
        findings.append(
            f"packed-key witness fails to flag at n=PACKED_KEY_MAX_N+1 "
            f"(bound not tight — the fallback switch is unverified)")
    return findings


# ---------------------------------------------------------------------
# standard program set + service audit
# ---------------------------------------------------------------------

def _lgrass_budget(n: int, L: int, schedule: str,
                   p1_chunk: Optional[int], chunk: int) -> set:
    """The documented scan-trip-count set for the fused pipeline:
    binary-lifting depth (log n), the MARK block size, the recovery
    replay block size — and nothing else."""
    from repro.core.pow2 import auto_chunk, log2_ceil

    allowed = {log2_ceil(n + 1), chunk}
    if schedule == "chunked":
        allowed.add(p1_chunk if p1_chunk is not None else auto_chunk(L))
    return allowed


def standard_program_audits(n: int = 64, L: int = 128, B: int = 2,
                            b_cap: int = 8) -> List[AuditReport]:
    """Audit the public jit programs at one representative signature.

    Covers both BFS engines for the fused and phase-1 programs (the
    serving default "doubling" plus the "levels" fallback), the
    standalone recovery units, and the spectral-probe estimator —
    every `@jax.jit` entry point a caller can dispatch.
    """
    from repro.core import spectral_probe as sp
    from repro.core.pow2 import log2_ceil
    from repro.core.recovery import recover_device, recover_device_batched
    from repro.core.sparsify import (
        lgrass_device,
        lgrass_device_batched,
        phase1_device,
        phase1_device_batched,
    )

    f = jax.ShapeDtypeStruct
    i32, f32, b8 = jnp.int32, jnp.float32, jnp.bool_
    e1 = (f((L,), i32), f((L,), i32), f((L,), f32))
    eB = (f((B, L), i32), f((B, L), i32), f((B, L), f32), f((B, L), b8))
    lev = log2_ceil(n + 1)
    reports: List[AuditReport] = []

    for eng in ("doubling", "levels"):
        reports.append(audit_program(
            f"phase1_device[{eng}]", phase1_device, e1,
            dict(n=n, bfs_engine=eng),
            expected_while=EXPECTED_WHILE[("phase1", eng)],
            allowed_scan_lengths=_lgrass_budget(n, L, "chunked", None, 32)))
        reports.append(audit_program(
            f"phase1_device_batched[{eng}]", phase1_device_batched, eB,
            dict(n=n, bfs_engine=eng),
            expected_while=EXPECTED_WHILE[("phase1", eng)],
            allowed_scan_lengths=_lgrass_budget(n, L, "chunked", None, 32)))
        reports.append(audit_program(
            f"lgrass_device[{eng}]", lgrass_device,
            e1 + (f((), i32),), dict(n=n, b_cap=b_cap, bfs_engine=eng),
            expected_while=EXPECTED_WHILE[("lgrass", eng)],
            allowed_scan_lengths=_lgrass_budget(n, L, "chunked", None, 32)))
        reports.append(audit_program(
            f"lgrass_device_batched[{eng}]", lgrass_device_batched,
            eB + (f((B,), i32),), dict(n=n, b_cap=b_cap, bfs_engine=eng),
            expected_while=EXPECTED_WHILE[("lgrass", eng)],
            allowed_scan_lengths=_lgrass_budget(n, L, "chunked", None, 32)))

    rec1 = (f((lev, n), i32), f((n,), i32), f((L,), i32), f((L,), i32),
            f((L,), i32), f((L,), b8), f((L,), b8), f((L,), i32),
            f((L,), b8), f((L,), i32), f((L,), b8), f((), i32))
    reports.append(audit_program(
        "recover_device", recover_device, rec1, dict(b_cap=b_cap),
        expected_while=EXPECTED_WHILE[("recover", "-")],
        allowed_scan_lengths={32}))
    recB = tuple(f((B,) + s.shape, s.dtype) for s in rec1[:-1]) \
        + (f((B,), i32),)
    reports.append(audit_program(
        "recover_device_batched", recover_device_batched, recB,
        dict(b_cap=b_cap),
        expected_while=EXPECTED_WHILE[("recover", "-")],
        allowed_scan_lengths={32}))

    n_iters = 16
    probe1 = (f((L,), i32), f((L,), i32), f((L,), f32), f((L,), b8),
              f((L,), i32), f((L,), i32), f((2,), jnp.uint32),
              f((), f32), f((), f32))
    reports.append(audit_program(
        "probe_edge_resistance", sp._probe_er_program, probe1,
        dict(n=n, n_probes=8, n_iters=n_iters, method="cheby",
             use_spmv_kernel=False),
        expected_while=EXPECTED_WHILE[("probe", "-")],
        allowed_scan_lengths={n_iters}))
    probeB = (f((B, L), i32), f((B, L), i32), f((B, L), f32),
              f((B, L), b8), f((B, 2), jnp.uint32), f((), f32),
              f((), f32))
    reports.append(audit_program(
        "probe_edge_resistance_batched", sp._probe_er_batched_program,
        probeB,
        dict(n=n, n_probes=8, n_iters=n_iters, method="cheby",
             use_spmv_kernel=False),
        expected_while=EXPECTED_WHILE[("probe", "-")],
        allowed_scan_lengths={n_iters}))
    return reports


def audit_service(svc, sizes=None, batch_sizes=(1,),
                  budgets=()) -> List[AuditReport]:
    """Audit every compiled-program signature of a `SparsifyService`.

    Each `ProgramSpec` (the service's own dispatch funnel, see
    `serve.sparsify_service.program_specs`) is traced and checked:
    exactly one dispatch per serving mode, no f64 on the non-x64 leg,
    loop budgets — for the EXACT static kwargs traffic runs.
    """
    reports = []
    for spec in svc.program_specs(sizes, batch_sizes=batch_sizes,
                                  budgets=budgets):
        kw = spec.static_kwargs
        reports.append(audit_program(
            spec.name, spec.fn, spec.args, kw,
            expected_while=EXPECTED_WHILE[("lgrass", kw["bfs_engine"])],
            allowed_scan_lengths=_lgrass_budget(
                kw["n"], spec.args[0].shape[-1], kw["schedule"],
                kw["p1_chunk"], kw["chunk"])))
    return reports
