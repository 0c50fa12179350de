#!/usr/bin/env python3
"""The control of a cell's check: the plain reference one precision
lower (bfloat16 for the configuration's float32), put in the program's
place on the cell's own pool, judged by the same check as a run.

    python chipbench/control.py --workload ipcc_case3 --seeds 1,2,3

For each seed it prints one JSON line with the numbers the check
compares and their limits; the check has to refuse every seed. The
benchmark's own runs never run this. The control needs no device: it is
the reference, computed on the host.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def judge(cell: str, config: dict, traffic: dict, seed: int,
          calls: int = None) -> dict:
    """The check's result with the control answering the first `calls`
    calls of the cell's pool for `seed` (default: the whole pool)."""
    from chipbench import generate, harness

    ref = harness.load_module("references", config["reference"] + ".py")
    pool = generate.make_calls(config, traffic, seed)[:calls]
    run = harness.Run(cell=cell, config=config, traffic=traffic, seed=seed,
                      seconds=0, trace_on=False, pool=pool)
    run.calls = [harness.Call(0, 0, i, len(gs), [
        ref.sparsify(g, b, "bfloat16") for g, b in zip(gs, bs)])
        for i, (gs, bs) in enumerate(pool)]
    return harness.check(run, ref)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--calls", type=int, default=None,
                    help="pool calls to judge (default: the whole pool)")
    args = ap.parse_args()
    sys.path[0] = ROOT
    from chipbench import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        work, _, _ = harness.cell_spec(json.load(f), args.workload)
    config = harness.load_json("configs", work["config"] + ".json")
    traffic = harness.load_json("traffic", work["traffic"] + ".json")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        result = judge(args.workload, config, traffic, seed, args.calls)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": "bfloat16 reference",
                          "correct": result["correct"],
                          "attempted": result["attempted"],
                          "failed": result["failed"],
                          "compared": result["compared"],
                          "seconds": time.perf_counter() - t0}), flush=True)


if __name__ == "__main__":
    main()
