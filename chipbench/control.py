#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python chipbench/control.py --workload granite-chat --seconds 25 \\
        --seeds 11 12 13 ... --control-seeds 11 12 13

Runs the cell once per seed in one process (the compiled programs are
shared), each with a window of ``--seconds`` at the cell's own load, and
prints one JSON line per seed: the numbers the run compares
(``checks``) and whether it came out ``correct``.  For the
``--control-seeds`` the control stands in the program's place: the plain
reference computed in the precision below the configuration's (float8
for bfloat16), whose numbers go through the same comparison and must
come out not correct.  The lower reading of a limit is the largest sound
reading over the seeds; the upper is the smallest control reading.  The
benchmark's own runs never run the control.
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    cell, device, _ = harness.open_cell(args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        res = bench.execute(cell, seed, args.seconds, False, device, t0,
                            control=seed in args.control_seeds)
        print(json.dumps({"seed": seed,
                          "control": seed in args.control_seeds,
                          "correct": res["correct"],
                          "checks": res["checks"],
                          "metrics": res["metrics"],
                          "memory_peak_bytes":
                              res["device"]["memory_peak_bytes"],
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
