# One function per paper table. Print ``name,us_per_call,derived`` CSV;
# ``--json`` additionally writes a BENCH_*.json document that embeds the
# pipeline configuration (backend, phase-1 schedule, chunk sizes), so
# benchmark trajectories across PRs compare like with like — a number
# measured under schedule="scan" must never be read against one measured
# under schedule="chunked" without the config saying so.
import argparse
import json
import sys
import traceback


def _bench_config(quick: bool):
    """The knobs that determine what the numbers mean.

    `pipeline_defaults` describes what a row gets when its suite does
    NOT pin an engine — the configuration every default-path row (e.g.
    the e2e recovery rows) ran under. Rows that deliberately pin a
    different engine (bench_phase1's scan_basic/scan_parallel/lifting
    rows, fig5's scan schedule) say so in their
    name or `derived` field; those annotations, not this block, are
    authoritative for such rows.
    """
    import jax

    from repro.core.pow2 import auto_chunk

    return {
        "backend": jax.default_backend(),
        "quick": bool(quick),
        "jax": jax.__version__,
        "pipeline_defaults": {
            "phase1_schedule": "chunked",
            "phase1_chunk_policy": "auto_pow2_sqrt",
            "phase1_chunk_at_4k_edges": auto_chunk(4096),
            "use_euler_lca": True,
            "recovery_chunk": 32,
            "k_cap": 32,
            "bfs_engine": "doubling",
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small sizes (CI)")
    ap.add_argument("--only", default=None,
                    help="comma list: table3,fig5,kernels,roofline,"
                         "batch,recovery,phase1,bfs,service,spectral")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows + config as JSON "
                         "(e.g. BENCH_pr4.json)")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (bench_batch, bench_bfs, bench_kernels,
                            bench_phase1, bench_recovery, bench_service,
                            bench_spectral, fig5_linearity, roofline,
                            table3_execution_time)

    suites = {
        "table3": table3_execution_time.run,
        "fig5": fig5_linearity.run,
        "kernels": bench_kernels.run,
        "roofline": roofline.run,
        "batch": bench_batch.run,
        "recovery": bench_recovery.run,
        "phase1": bench_phase1.run,
        "bfs": bench_bfs.run,
        "service": bench_service.run,
        "spectral": bench_spectral.run,
    }
    chosen = (args.only.split(",") if args.only else list(suites))
    all_rows = []
    failed = []
    print("name,us_per_call,derived")
    for name in chosen:
        try:
            rows = suites[name](quick=args.quick)
        except Exception as e:  # report, run the other suites, fail at exit
            traceback.print_exc()
            print(f"{name}.ERROR,0,{e!r}", file=sys.stdout)
            all_rows.append({"name": f"{name}.ERROR", "us_per_call": 0.0,
                             "derived": repr(e)})
            failed.append(name)
            continue
        for row in rows:
            n, us, derived = row
            print(f"{n},{us:.1f},{derived}")
            all_rows.append({"name": n, "us_per_call": round(float(us), 1),
                             "derived": derived})
    if args.json:
        doc = {"config": _bench_config(args.quick), "rows": all_rows}
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {args.json}", file=sys.stderr)
    if failed:
        sys.exit(f"failed suites: {', '.join(failed)}")


if __name__ == "__main__":
    main()
