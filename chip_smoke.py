#!/usr/bin/env python3
"""Chip smoke test: drive the sparsifier's main paths once on a TPU and
check every result against the numpy oracle, bit for bit.

    python chip_smoke.py              # one chip: phases A-D
    python chip_smoke.py --scale      # only the 10^6-node grid, one chip
    python chip_smoke.py --chips 4    # only the batch-sharded service,
                                      # over four chips

Phases (one process, so one process holds the chip):
  A  the three official IPCC-sized cases through `lgrass_sparsify` at the
     default 5% budget;
  B  a mixed batch of 64 graphs through `SparsifyService`, plain and
     async+donate, warmed so that no request compiles;
  C  a 10^5-node power grid at the default budget (b_cap 8192). The
     10^6-node grid at budget 48 (b_cap 64) is its own run, --scale:
     with it the default run would not fit a 20-minute call;
  D  the two Pallas kernels on the chip: the tree-distance kernel inside
     the fused program, and the spmv kernel inside the probe estimator,
     each checked to be a compiled Mosaic call (`tpu_custom_call`).

It refuses to run anywhere but on a TPU and exits non-zero on any
mismatch or exception. Times it prints are smoke numbers from one cold
process, not a benchmark. The last line of its output is one JSON
object naming the device.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SMOKE = "[smoke number, not a benchmark]"


def device_check(want_count: int):
    """Fail before any work unless JAX sees `want_count` TPU devices."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but jax.devices()[0].platform "
                 f"is {devs[0].platform!r}; refusing to fall back")
    if len(devs) != want_count:
        sys.exit(f"chip_smoke: needs {want_count} TPU device(s), "
                 f"found {len(devs)}")
    print(f"device: {devs[0].device_kind} x{len(devs)}, "
          f"jax {jax.__version__}", flush=True)
    return devs


def check_identical(label: str, got: np.ndarray, want: np.ndarray):
    if got.shape != want.shape or not np.array_equal(got, want):
        n_diff = (int((got != want).sum()) if got.shape == want.shape
                  else "shape")
        raise AssertionError(f"{label}: edge_mask differs from the oracle "
                             f"({n_diff} of {want.shape[0]} edges)")


def first_differing_stage(g, budget: int) -> str:
    """Name the first stage whose device output differs from its numpy
    mirror (run only after a mismatch, to say where the bits split)."""
    import jax
    import jax.numpy as jnp

    from repro.core import _host as H
    from repro.core.baseline import baseline_sparsify
    from repro.core.sparsify import phase1_device

    w = g.w.astype(np.float32)
    inv = np.asarray(jax.jit(lambda x: 1.0 / x)(jnp.asarray(w)))
    if not np.array_equal(inv, np.float32(1.0) / w):
        return (f"f32 division: 1/w differs on "
                f"{int((inv != np.float32(1.0) / w).sum())} weights")
    ref = baseline_sparsify(g, budget=budget)
    d = jax.device_get(phase1_device(jnp.asarray(g.u), jnp.asarray(g.v),
                                     jnp.asarray(w), g.n))
    for name, got, want in (
            ("MST tree_mask", d["tree_mask"], ref.tree_mask),
            ("tree BFS depth_t", d["depth_t"], ref.depth_tree),
            ("tree BFS parent_t", d["parent_t"], ref.parent_tree),
            ("RES crit", np.where(ref.tree_mask, 0, d["crit"]),
             np.where(ref.tree_mask, 0, ref.crit)),
            ("beta", d["beta"], ref.beta),
            ("crossing", d["crossing"], ref.crossing)):
        if not np.array_equal(np.asarray(got), np.asarray(want)):
            return f"{name} ({int((np.asarray(got) != want).sum())} slots)"
    up = H.build_lifting_np(ref.parent_tree, ref.depth_tree, g.n)
    perm = d["perm"]
    acc, _ = H.phase1_np(up, ref.depth_tree, g.u[perm], g.v[perm],
                         ref.beta[perm], d["gidx"], ref.crossing[perm], 32)
    if not np.array_equal(d["accept_sorted"], acc):
        return "MARK accept_sorted"
    return "recovery tail (phase-1 outputs all agree)"


def timed_first_and_steady(fn, reps: int = 3):
    t0 = time.perf_counter()
    out = fn()
    first = time.perf_counter() - t0
    steady = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        steady.append(time.perf_counter() - t0)
    return out, first, float(np.median(steady)) if steady else float("nan")


def phase_a(oracles: dict):
    from repro.core import baseline_sparsify, default_budget, official_case
    from repro.core import lgrass_sparsify

    for name in ("case1", "case2", "case3"):
        g = official_case(name)
        r, first, steady = timed_first_and_steady(lambda: lgrass_sparsify(g))
        oracles[name] = baseline_sparsify(g).edge_mask
        if not np.array_equal(r.edge_mask, oracles[name]):
            print(f"A {name}: first differing stage: "
                  f"{first_differing_stage(g, default_budget(g.n))}")
        check_identical(f"A {name}", r.edge_mask, oracles[name])
        print(f"A {name}: n={g.n} m={g.m} budget={default_budget(g.n)} "
              f"kept={int(r.edge_mask.sum())}; compile+first call "
              f"{first:.1f} s, compile ~{first - steady:.1f} s, steady "
              f"{steady * 1e3:.2f} ms {SMOKE}; bit-identical", flush=True)


def service_mix(n_small: int = 61, grid_sides=(32, 64, 100)):
    """The examples/batch_sparsify.py mix (grids and random graphs of
    24-64 nodes) plus 10^3-10^4-node grids: 64 graphs by default."""
    from repro.core.graph import powergrid_like_graph, random_connected_graph

    rng = np.random.default_rng(0)
    graphs = []
    for i in range(n_small):
        if i % 3 == 0:
            graphs.append(powergrid_like_graph(int(rng.integers(5, 9)),
                                               0.3, seed=i))
        else:
            n = int(rng.integers(24, 64))
            graphs.append(random_connected_graph(n, 2 * n, seed=i))
    for side in grid_sides:
        graphs.append(powergrid_like_graph(side, 0.25, seed=side))
    return graphs


def serve_checked(svc, graphs, oracles, label: str):
    """Warm every bucket the batch needs, serve it, and check it: no
    compile on the request path, every result bit-identical."""
    counts = collections.Counter(svc.bucket_key(g) for g in graphs)
    t0 = time.perf_counter()
    for key, cnt in sorted(counts.items()):
        svc.warmup([key], batch_sizes=[cnt])
    warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    results = svc.sparsify(graphs)
    serve = time.perf_counter() - t0
    if svc.stats.n_on_path_compiles:
        raise AssertionError(f"{label}: {svc.stats.n_on_path_compiles} "
                             f"compile(s) on the request path")
    for i, (r, want) in enumerate(zip(results, oracles)):
        check_identical(f"{label} graph {i}", r.edge_mask, want)
    print(f"{label}: {len(graphs)} graphs in {len(counts)} buckets, "
          f"{svc.stats.n_dispatches} dispatches; warmup {warm:.1f} s, "
          f"served in {serve * 1e3:.1f} ms {SMOKE}; 0 on-path compiles; "
          f"all bit-identical", flush=True)


def phase_b():
    from repro.core import baseline_sparsify
    from repro.serve.sparsify_service import SparsifyService

    graphs = service_mix()
    oracles = [baseline_sparsify(g).edge_mask for g in graphs]
    serve_checked(SparsifyService(), graphs, oracles, "B sync")
    serve_checked(SparsifyService(async_dispatch=True, donate=True),
                  graphs, oracles, "B async+donate")


def phase_c(runs=((316, None),)):
    """Power grids of side**2 nodes at `budget` (None: the default)."""
    from repro.core import baseline_sparsify, default_budget
    from repro.core import lgrass_sparsify
    from repro.core.graph import powergrid_like_graph

    for side, budget in runs:
        g = powergrid_like_graph(side, 0.25, seed=1)
        b = default_budget(g.n) if budget is None else budget
        # one call: at this size a second one costs minutes of chip time
        r, first, _ = timed_first_and_steady(
            lambda: lgrass_sparsify(g, budget=b), reps=0)
        t0 = time.perf_counter()
        want = baseline_sparsify(g, budget=b).edge_mask
        t_oracle = time.perf_counter() - t0
        if not np.array_equal(r.edge_mask, want):
            print(f"C n={g.n}: first differing stage: "
                  f"{first_differing_stage(g, b)}")
        check_identical(f"C n={g.n}", r.edge_mask, want)
        print(f"C grid n={g.n} m={g.m} budget={b}: compile+first call "
              f"{first:.1f} s {SMOKE}; oracle {t_oracle:.1f} s on the "
              f"host; bit-identical", flush=True)


def phase_d(oracles: dict):
    import jax
    import jax.numpy as jnp

    from repro.core import default_budget, lgrass_sparsify, official_case
    from repro.core import lgrass_device, probe_edge_resistance
    from repro.core.sparsify import _bucket_b_cap

    g = official_case("case1")
    budget = default_budget(g.n)
    r = lgrass_sparsify(g, use_tree_kernel=True)
    check_identical("D tree_dist kernel case1", r.edge_mask, oracles["case1"])
    u, v, w = (jnp.asarray(g.u), jnp.asarray(g.v), jnp.asarray(g.w))
    text = lgrass_device.lower(
        u, v, w, jnp.int32(budget), n=g.n, b_cap=_bucket_b_cap([budget]),
        use_tree_kernel=True).compile().as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError("D: the tree_dist kernel did not compile to "
                             "a Mosaic call (interpret mode?)")
    print("D tree_dist kernel: case1 bit-identical; compiled Mosaic call",
          flush=True)

    def probe(use_kernel):
        return probe_edge_resistance(u, v, w, g.n, seed=1,
                                     use_spmv_kernel=use_kernel)

    ref = np.asarray(probe(False))
    got = np.asarray(probe(True))
    np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4,
                               err_msg="D spmv kernel vs segment-sum")
    text = jax.jit(lambda a, b, c: probe_edge_resistance(
        a, b, c, g.n, seed=1, use_spmv_kernel=True)).lower(
            u, v, w).compile().as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError("D: the spmv kernel did not compile to a "
                             "Mosaic call (interpret mode?)")
    print(f"D spmv kernel: case1 probe R^ allclose to segment-sum (max rel "
          f"{float(np.max(np.abs(got - ref) / np.abs(ref))):.2e}); compiled "
          f"Mosaic call", flush=True)


def phase_sharded(devs):
    """The batch-sharded service over every chip, checked against the
    oracle and for outputs that really span all devices."""
    from repro.core import baseline_sparsify
    from repro.core.distributed import batch_mesh
    from repro.serve.sparsify_service import SparsifyService

    class RecordingService(SparsifyService):
        def _dispatch(self, *args, **kwargs):
            out = super()._dispatch(*args, **kwargs)
            self.outputs.append(out)
            return out

    graphs = service_mix(n_small=60, grid_sides=())
    oracles = [baseline_sparsify(g).edge_mask for g in graphs]
    svc = RecordingService(mesh=batch_mesh())
    svc.outputs = []
    serve_checked(svc, graphs, oracles, f"sharded x{len(devs)}")
    want = {d.id for d in devs}
    for out in svc.outputs:
        for key, arr in out.items():
            on = {s.device.id for s in arr.addressable_shards}
            if len(arr.sharding.device_set) != len(devs) or on != want:
                raise AssertionError(
                    f"sharded output {key!r} spans devices {sorted(on)}, "
                    f"not all {sorted(want)}")
    print(f"sharded: every output of {len(svc.outputs)} dispatches spans "
          f"all {len(devs)} devices", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the batch-sharded service path")
    ap.add_argument("--scale", action="store_true",
                    help="run only the 10^6-node grid (phase C at scale)")
    args = ap.parse_args()

    devs = device_check(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(devs)
    elif args.scale:
        phase_c(runs=((1024, 48),))
    else:
        oracles: dict = {}
        for label, fn in (("A", lambda: phase_a(oracles)), ("B", phase_b),
                          ("C", phase_c), ("D", lambda: phase_d(oracles))):
            t = time.perf_counter()
            fn()
            print(f"phase {label} passed in {time.perf_counter() - t:.1f} s",
                  flush=True)
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
