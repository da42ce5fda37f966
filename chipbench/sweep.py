#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest offered rate
with no growing backlog.  Runs the cell in one process at each rate of
``--rates`` (the cell's own mix and window, the arrival rate replaced) and
prints one JSON line per rate with its end-to-end metrics and the
requests waiting for a slot at half the window and at its close.

    python chipbench/sweep.py --workload granite-chat --seconds 45 \\
        --seed 5 --rates 0.6 0.8 1.0 1.2

The cell's file then holds the rate chosen from it as a number; the
benchmark's runs never sweep.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import run as bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    cell, device, _ = harness.open_cell(args.workload)
    if cell.traffic["arrival"]["kind"] != "poisson":
        raise SystemExit("sweep: the cell's arrivals are not open-loop")
    for rate in args.rates:
        cell.traffic["arrival"]["rate_per_s"] = rate
        res = bench.execute(cell, args.seed, args.seconds, False, device,
                            time.perf_counter())
        print(json.dumps({"rate_per_s": rate, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
