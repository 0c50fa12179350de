"""One run of one cell: set-up, the measured window, the trace, the check
against the plain reference, and the result line.

Everything that belongs to one configuration, traffic mix or metric is
found by name under this directory:

- `configs/<config>.json`: the deployment (graph family, case sizes,
  budget rule), naming its `reference`;
- `references/<reference>.py`: the plain reference, `sparsify(g, budget,
  precision)`;
- `traffic/<traffic>.json`: the mix that `generate.make_calls` reads,
  naming its `entry`;
- `entries/<entry>.py`: how a call enters the program;
- `metrics/<metric>.py`: a reader, `read(run)`, returning a number or
  None when it finds nothing to read; per-layer readers may also define
  `prepare(run)` (set-up of a traced run) and `after_window(run)` (called
  while the profiler still records, after the traced window).

The caller is one closed loop: it sends the pool's calls in order, the
next one when the last has returned, until `seconds` have passed, and
finishes the call in flight. A traced run records the first
`trace_calls` calls of the window, then runs what is left of the window
untraced. Stopping the profiler can take longer than the window has
left (case3's four traced calls), and the check then covers the traced
answers alone: each graph of the pool once.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import List, Optional

import numpy as np

from chipbench import devtrace, generate

HERE = os.path.dirname(os.path.abspath(__file__))
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Refused(SystemExit):
    """A run that must end before any result: wrong device, bad spec."""


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(*parts):
    path = os.path.join(HERE, *parts)
    name = "chipbench._" + "_".join(parts).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Call:
    t0: float
    t1: float
    index: int           # into the pool
    graphs: int
    masks: Optional[list]
    error: Optional[str] = None


@dataclasses.dataclass
class Run:
    """What the metric readers see."""
    cell: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace_on: bool
    pool: list                       # [(graphs, budgets)], benchmark side
    entry: object = None
    setup_s: float = 0.0
    calls: List[Call] = dataclasses.field(default_factory=list)
    window_s: float = 0.0
    traced: List[Call] = dataclasses.field(default_factory=list)
    trace: object = None             # devtrace.Trace in a traced run
    counters: dict = dataclasses.field(default_factory=dict)
    chips: int = 1


def cell_spec(bench: dict, cell: str):
    """(workload entry, end-to-end metrics, per-layer metrics) of a cell."""
    work = {w["name"]: w for w in bench["workloads"]}
    if cell not in work:
        raise Refused(f"chipbench: no workload {cell!r} in BENCHMARK.json")

    def mine(metrics):
        return [m for m in metrics if cell in m.get("workloads", [cell])]

    return work[cell], mine(bench["end_to_end"]), mine(bench["per_layer"])


def device_info(chips: int, require_chip: bool):
    """The devices the run uses; refuses anything but enough TPUs."""
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise Refused(f"chipbench: needs a TPU, found platform "
                      f"{devs[0].platform!r}; refusing to fall back")
    if len(devs) < chips:
        raise Refused(f"chipbench: the cell needs {chips} chip(s), "
                      f"found {len(devs)}")
    if require_chip:
        peaks = load_json("peaks.json")["kinds"]
        if devs[0].device_kind not in peaks:
            raise Refused(f"chipbench: device kind {devs[0].device_kind!r} "
                          f"is not in peaks.json")
    return devs[:chips]


class _CompileCounter:
    """Counts programs built (compiled, or loaded from the persistent
    cache: JAX times both as a backend compile) and cache loads."""

    def __init__(self):
        self.compiled = 0
        self.loaded = 0

    def _on_duration(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            self.compiled += 1

    def _on_event(self, event, **kw):
        if event == CACHE_HIT:
            self.loaded += 1

    def __enter__(self):
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)

    def snapshot(self) -> dict:
        return {"compiled": self.compiled, "loaded": self.loaded}


def _closed_loop(run: Run, first: int, until: float = None,
                 count: int = None) -> List[Call]:
    """Send calls from pool index `first` on: `count` of them, or until
    `until` (perf_counter time), finishing the call in flight."""
    import jax

    out: List[Call] = []
    i = first
    n_pool = len(run.pool)
    while True:
        if count is not None:
            if len(out) == count:
                break
        elif time.perf_counter() >= until:
            break
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("chipbench.call"):
                masks = run.entry.call(i % n_pool)
            err = None
        except Exception as e:  # an answer that never comes, counted
            masks, err = None, f"{type(e).__name__}: {e}"
        t1 = time.perf_counter()
        out.append(Call(t0, t1, i % n_pool, len(run.pool[i % n_pool][0]),
                        masks, err))
        i += 1
    return out


def _window(run: Run, metrics: list, trace_ops=None):
    """The measured window, traced in its first part when asked."""
    import jax

    run.entry.start_window()
    t_start = time.perf_counter()
    end = t_start + run.seconds
    if run.trace_on:
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(devtrace.WINDOW):
                run.traced = _closed_loop(
                    run, 0, count=run.traffic["trace_calls"])
            for m in metrics:
                hook = getattr(m, "after_window", None)
                if hook:
                    with jax.profiler.TraceAnnotation("chipbench.extra"):
                        hook(run)
        finally:
            jax.profiler.stop_trace()
        run.calls = run.traced + _closed_loop(
            run, len(run.traced), until=end)
        ops, modules = trace_ops or (None, None)
        run.trace = devtrace.load(tdir, jax.devices()[0].platform,
                                  run.chips, ops, modules)
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        run.calls = _closed_loop(run, 0, until=end)
    run.window_s = run.calls[-1].t1 - t_start
    run.counters = run.entry.counters()


def check(run: Run, ref) -> dict:
    """Every answer of the window against the reference, which runs once
    per pool call that was answered, after the window."""
    want = {}
    missing = wrong = worst = attempted = 0
    for c in run.calls:
        attempted += c.graphs
        graphs, budgets = run.pool[c.index]
        if c.masks is None or len(c.masks) != len(graphs):
            missing += c.graphs
            continue
        if c.index not in want:
            want[c.index] = [ref.sparsify(g, b)
                             for g, b in zip(graphs, budgets)]
        for got, exp in zip(c.masks, want[c.index]):
            got = np.asarray(got)
            diff = (int(np.count_nonzero(got != exp))
                    if got.shape == exp.shape else int(exp.shape[0]))
            worst = max(worst, diff)
            wrong += diff > 0
    compared = {"edges_wrong_max": {"value": worst, "limit": 0},
                "answers_missing": {"value": missing, "limit": 0}}
    correct = attempted > 0 and all(
        v["value"] <= v["limit"] for v in compared.values())
    return dict(correct=correct, attempted=attempted,
                failed=missing + wrong, compared=compared)


def run_cell(bench: dict, cell: str, seed: int, seconds: float,
             trace: bool, t_process: float, require_chip: bool = True,
             configs: dict = None, traffics: dict = None, trace_ops=None,
             out=sys.stdout, err=sys.stderr) -> dict:
    """One run of `cell`; prints the result line and returns it.

    `configs`/`traffics` override the files by name (the CPU rehearsal
    shrinks them); `trace_ops` overrides where device operations are
    found in the trace (the CPU rehearsal points it at the CPU's ops).
    """
    import jax

    work, e2e, layers = cell_spec(bench, cell)
    config = (configs or {}).get(work["config"]) or load_json(
        "configs", work["config"] + ".json")
    traffic = (traffics or {}).get(work["traffic"]) or load_json(
        "traffic", work["traffic"] + ".json")
    devs = device_info(work["chips"], require_chip)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    specs = [(m, load_module("metrics", m["name"] + ".py"))
             for m in (layers if trace else e2e)]
    run = Run(cell=cell, config=config, traffic=traffic, seed=seed,
              seconds=seconds, trace_on=trace, chips=work["chips"],
              pool=generate.make_calls(config, traffic, seed))
    with _CompileCounter() as counter:
        module = load_module("entries", traffic["entry"] + ".py")
        run.entry = module.Entry(config, run.pool)
        run.entry.warm()
        if trace:
            for _, mod in specs:
                hook = getattr(mod, "prepare", None)
                if hook:
                    hook(run)
        before = counter.snapshot()
        run.setup_s = time.perf_counter() - t_process
        _window(run, [mod for _, mod in specs], trace_ops)
        in_window = {k: v - before[k] for k, v in counter.snapshot().items()}

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(
                  int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                  for d in devs)}
    metrics = {}
    for m, mod in specs:
        value = mod.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": False, "attempted": 0, "failed": 0,
            "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        line["breakdown"] = {"device_ops": run.trace.top_ops(),
                             "idle_gaps": run.trace.idle_gaps()}
    run.trace = None
    run.entry.close()
    run.entry = None

    result = check(run, load_module("references",
                                    config["reference"] + ".py"))
    line.update(correct=result["correct"], attempted=result["attempted"],
                failed=result["failed"])
    line["compared"] = result["compared"]
    errors = sorted({c.error for c in run.calls if c.error})
    for e in errors[:3]:
        print(f"chipbench: a call raised {e}", file=err)
    print(f"chipbench: {cell} seed {seed}: {len(run.calls)} calls, "
          f"{result['attempted']} graphs in {run.window_s:.3f} s; "
          f"set-up {run.setup_s:.3f} s; programs built in set-up "
          f"{before['compiled']} ({before['loaded']} from the cache), in "
          f"the window {in_window['compiled']} "
          f"({in_window['loaded']} from the cache)", file=err)
    for name, v in result["compared"].items():
        print(f"compared: {name} {v['value']} limit {v['limit']}", file=err)
    err.flush()
    print(json.dumps(line), file=out, flush=True)
    return line
