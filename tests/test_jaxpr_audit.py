"""Jaxpr auditor (`analysis.jaxpr_audit`): the standard program set
traces clean, the loop/dtype/dispatch checks catch seeded violations,
`audit_service` covers a live service's signatures, and the
`python -m repro.analysis` CLI honours its exit-code contract."""
import json

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.jaxpr_audit import (
    EXPECTED_WHILE,
    audit_program,
    audit_service,
    check_derived_constants,
    collect_eqns,
    standard_program_audits,
)

F32 = jax.ShapeDtypeStruct((16,), jnp.float32)


# --------------------------------------------------- standard program set

PROGRAMS = ["phase1_device", "phase1_device_batched", "lgrass_device",
            "lgrass_device_batched", "recover_device",
            "recover_device_batched", "probe_edge_resistance",
            "probe_edge_resistance_batched"]


def test_standard_programs_all_clean():
    reports = standard_program_audits()
    assert [r.name for r in reports] == PROGRAMS
    bad = {r.name: r.findings for r in reports if not r.ok}
    assert bad == {}
    # every serving-path program is a single dispatch
    assert all(r.dispatch_count == 1 for r in reports)


def test_loop_budgets_are_engine_dependent():
    """One documented while budget a program family, the counts the
    programs trace: the fused program is phase 1 plus the replay."""
    byname = {r.name: r for r in standard_program_audits()}
    assert EXPECTED_WHILE == {"phase1": 4, "lgrass": 5, "recover": 1,
                              "probe": 0}
    for name in PROGRAMS:
        family = name.split("_")[0]
        assert byname[name].n_while == EXPECTED_WHILE[family], name


# The compile-cache key of each public program: its static arguments,
# every one a size or a block/buffer bound (the arrays come first).
STATICS = {
    "phase1_device": (3, ("n", "k_cap", "p1_chunk")),
    "lgrass_device": (4, ("n", "k_cap", "b_cap", "chunk", "p1_chunk")),
    "lgrass_device_batched": (5, ("n", "k_cap", "b_cap", "chunk",
                                  "p1_chunk")),
}


@pytest.mark.parametrize("name", sorted(STATICS))
def test_program_statics_are_the_documented_set(name):
    """The program takes its arrays and exactly the documented static
    arguments, and none of those reaches the traced program as an
    input: each is part of the compile-cache key."""
    import inspect

    from repro.core import sparsify

    fn = getattr(sparsify, name)
    n_arrays, statics = STATICS[name]
    params = list(inspect.signature(fn).parameters)
    assert tuple(params[n_arrays:]) == statics
    f = jax.ShapeDtypeStruct
    batch = (2,) if name.endswith("_batched") else ()
    arrays = [f(batch + (32,), jnp.int32), f(batch + (32,), jnp.int32),
              f(batch + (32,), jnp.float32)]
    if batch:
        arrays.append(f(batch + (32,), jnp.bool_))
    if name.startswith("lgrass"):
        arrays.append(f(batch, jnp.int32))
    kw = dict(n=16, k_cap=32, b_cap=8, chunk=32, p1_chunk=None)
    traced = fn.trace(*arrays, **{k: kw[k] for k in statics})
    assert len(traced.jaxpr.jaxpr.invars) == n_arrays


def test_derived_constants_agree():
    assert check_derived_constants() == []


# ------------------------------------------------------- seeded violations

def test_extra_while_loop_flags():
    def extra_loop(x):
        y = jax.lax.while_loop(lambda c: c[1] < 3,
                               lambda c: (c[0] * 2, c[1] + 1),
                               (x, jnp.int32(0)))[0]
        return y

    rep = audit_program("seeded", extra_loop, (F32,), expected_while=0)
    assert not rep.ok and "while-loop count 1" in rep.findings[0]


def test_undocumented_scan_length_flags():
    def long_scan(x):
        return jax.lax.scan(lambda c, _: (c + 1.0, None), x,
                            None, length=99)[0]

    rep = audit_program("seeded", long_scan, (F32,),
                        allowed_scan_lengths={7, 16, 32})
    assert not rep.ok and "99" in rep.findings[0]


def test_callback_primitive_flags_dispatch():
    def chatty(x):
        jax.debug.print("x={x}", x=x)
        return x * 2

    rep = audit_program("seeded", chatty, (F32,))
    assert rep.dispatch_count > 1
    assert any("callback" in f for f in rep.findings)


def test_weak_typed_output_flags():
    def weak(x):
        return 1.0  # bare Python literal escapes as a weak output

    rep = audit_program("seeded", weak, (F32,))
    assert any("weakly typed" in f for f in rep.findings)


def test_collect_eqns_recurses_into_loops():
    def nested(x):
        def body(c, _):
            return jax.lax.while_loop(lambda v: jnp.any(v < 0),
                                      lambda v: v + 1, c), None
        return jax.lax.scan(body, x, None, length=3)[0]

    names = [e.primitive.name for e in
             collect_eqns(jax.make_jaxpr(nested)(jnp.zeros(4)))]
    assert "scan" in names and "while" in names
    assert "add" in names  # from inside the while body, two levels down


# ------------------------------------------------------------ service audit

def test_audit_service_signatures():
    from repro.serve.sparsify_service import SparsifyService

    svc = SparsifyService()
    reports = audit_service(svc, sizes=[(64, 128)], batch_sizes=(1, 2))
    assert len(reports) == 2
    assert all(r.ok for r in reports), [r.findings for r in reports]
    assert all(r.dispatch_count == 1 for r in reports)


# ---------------------------------------------------------------- the CLI

def test_cli_seeded_bugs_exit_nonzero(capsys):
    from repro.analysis.__main__ import main

    assert main(["--seed-bug", "inf-depth"]) != 0
    assert "CAUGHT" in capsys.readouterr().out
    assert main(["--seed-bug", "pack-overflow"]) != 0


def test_cli_clean_tree_exits_zero(tmp_path, monkeypatch):
    import os

    from repro.analysis.__main__ import main

    monkeypatch.chdir(os.path.join(os.path.dirname(__file__), ".."))
    report = tmp_path / "report.json"
    rc = main(["--skip-jaxpr", "--json", str(report), "src/repro"])
    assert rc == 0
    data = json.loads(report.read_text())
    assert data["ok"] and data["lint"] == [] and data["suppressed"] > 0


def test_cli_flags_seeded_lint_finding(tmp_path):
    from repro.analysis.__main__ import main

    bad = tmp_path / "core" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        "import jax.numpy as jnp\n\n"
        "def f(n):\n    return jnp.zeros((n,))\n")
    rc = main(["--skip-jaxpr", str(bad)])
    assert rc == 1
