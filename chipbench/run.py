#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python chipbench/run.py --workload ipcc_case3 --seed 7 --seconds 51 \
        --trace 0

The cells, their metrics and their bounds are in BENCHMARK.json at the
root of the checkout; everything a cell names is under chipbench/ (see
harness.py). The run refuses any device but a TPU, and any machine with
fewer chips than the cell asks for, before it prints a result. Its last
line on standard output is one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device`, with --trace 1 `breakdown`, and last
`compared`, each number the check compared with its limit. The same
numbers are the last lines on standard error.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0 or args.seconds <= 0:
        sys.exit("chipbench: --seed must be >= 0 and --seconds > 0")

    # libtpu logs to /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    try:
        import repro
    except ImportError as e:
        sys.exit(f"chipbench: the system under test is missing ({e})")
    if not os.path.abspath(repro.__file__).startswith(
            os.path.join(ROOT, "src")):
        sys.exit(f"chipbench: repro imported from {repro.__file__}, not "
                 f"from this checkout")
    from chipbench import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    harness.run_cell(bench, args.workload, args.seed, args.seconds,
                     bool(args.trace), T_PROCESS)


if __name__ == "__main__":
    main()
