"""CPU rehearsal of every cell: its traffic, window, trace and result
line at a tiny size; the refusals of a real run; and the check, which
has to come out false with the timed path broken underneath."""
import io
import json
import os
import shutil
import subprocess
import sys
import time
import types

import numpy as np
import pytest

from chipbench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
CPU_TRACE = (("/host:CPU", "tf_XLAPjRtCpuClient"), ("/host:CPU", "none"))
SEED = 2**31 + 17


@pytest.fixture(autouse=True)
def _own_compile_cache_settings():
    """A run turns JAX's persistent cache on for its process; put the
    settings back so the worker's next test file runs as it would alone."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def _tiny():
    """Every configuration and traffic file of the benchmark, shrunk."""
    configs, traffics = {}, {}
    for w in BENCH["workloads"]:
        cfg = dict(harness.load_json("configs", w["config"] + ".json"))
        cfg["cases"] = {k: {"n_side": 6, "chord_frac": 0.25}
                        for k in cfg["cases"]}
        configs[w["config"]] = cfg
        tr = dict(harness.load_json("traffic", w["traffic"] + ".json"))
        tr.update(pool_calls=3, trace_calls=2,
                  graphs_per_call=min(tr["graphs_per_call"], 4))
        traffics[w["traffic"]] = tr
    return configs, traffics


def _run(cell, trace=False, seconds=0.3):
    configs, traffics = _tiny()
    out, err = io.StringIO(), io.StringIO()
    line = harness.run_cell(BENCH, cell, SEED, seconds, trace,
                            time.perf_counter(), require_chip=False,
                            configs=configs, traffics=traffics,
                            trace_ops=CPU_TRACE, out=out, err=err)
    return line, out.getvalue(), err.getvalue()


def _expected(cell, kind):
    return {m["name"] for m in BENCH[kind]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal_untraced(cell):
    line, out, err = _run(cell)
    assert json.loads(out.strip().splitlines()[-1]) == line
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == _expected(cell, "end_to_end")
    for name, m in line["metrics"].items():
        assert m["value"] > 0 and m["unit"]
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    assert "in the window 0 (0 from the cache)" in err
    assert err.strip().splitlines()[-2:] == [
        "compared: edges_wrong_max 0 limit 0",
        "compared: answers_missing 0 limit 0"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal_traced(cell):
    line, _, _ = _run(cell, trace=True)
    assert line["correct"] is True
    # phase1_ms.single reads the TPU's module line, which a CPU lacks
    want = _expected(cell, "per_layer") - {"phase1_ms.single"}
    assert set(line["metrics"]) == want
    dev = line["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    for m in line["metrics"].values():
        assert m["value"] >= 0
    bd = line["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_every_named_file_exists():
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        cfg = harness.load_json("configs", c["name"] + ".json")
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "references",
                                           cfg["reference"] + ".py"))
    for w in BENCH["workloads"]:
        tr = harness.load_json("traffic", w["traffic"] + ".json")
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "entries",
                                           tr["entry"] + ".py"))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"] + ".py")
                        .read)


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_a_real_run_refuses_the_cpu():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_a_run_without_the_program_refuses(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


def _broken(monkeypatch, make_call):
    """Run the harness with each entry's `call` replaced."""
    real = harness.load_module

    def load(*parts):
        mod = real(*parts)
        if parts[0] != "entries":
            return mod

        class Broken(mod.Entry):
            def call(self, i):
                return make_call(self, i, super().call)

        return types.SimpleNamespace(Entry=Broken)

    monkeypatch.setattr(harness, "load_module", load)


def _all_kept(drv, i, call):
    """The answer is the input unchanged: every edge kept."""
    return [np.ones_like(m) for m in call(i)]


def _stale(drv, i, call):
    """Each call returns the answer of the call before it."""
    prev = getattr(drv, "_prev", None)
    drv._prev = call(i)
    return prev if prev is not None else drv._prev


def _one_edge_flipped(drv, i, call):
    masks = call(i)
    masks[-1] = masks[-1].copy()
    masks[-1][0] = ~masks[-1][0]
    return masks


def _half_batch_left_out(drv, i, call):
    masks = call(i)
    return masks[: len(masks) // 2]


@pytest.mark.parametrize("fault", [_all_kept, _stale, _one_edge_flipped])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_answer_is_not_correct(monkeypatch, cell, fault):
    _broken(monkeypatch, fault)
    line, _, err = _run(cell)
    assert line["correct"] is False and line["failed"] > 0
    assert line["compared"]["edges_wrong_max"]["value"] > 0
    assert "compared: edges_wrong_max" in err.strip().splitlines()[-2]


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    cell = next(w["name"] for w in BENCH["workloads"]
                if harness.load_json("traffic", w["traffic"] + ".json")
                ["entry"] == "service")
    _broken(monkeypatch, _half_batch_left_out)
    line, _, _ = _run(cell)
    assert line["correct"] is False
    assert line["compared"]["answers_missing"]["value"] > 0


def test_a_call_that_raises_is_not_correct(monkeypatch):
    def _raises(drv, i, call):
        drv._n = getattr(drv, "_n", 0) + 1
        if drv._n > 1:          # warm-up passes, every timed call raises
            raise RuntimeError("device lost")
        return call(i)

    _broken(monkeypatch, _raises)
    line, _, err = _run(CELLS[0])
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    assert "RuntimeError: device lost" in err


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct_at_the_cells_size(cell):
    """The bfloat16 reference in the program's place, on the cell's own
    configuration and graph sizes (the first calls of its pool), fails."""
    from chipbench import control

    w = next(x for x in BENCH["workloads"] if x["name"] == cell)
    cfg = harness.load_json("configs", w["config"] + ".json")
    tr = harness.load_json("traffic", w["traffic"] + ".json")
    result = control.judge(cell, cfg, tr, SEED,
                           calls=2 if tr["graphs_per_call"] == 1 else 1)
    assert result["correct"] is False
    assert result["compared"]["edges_wrong_max"]["value"] > 0
