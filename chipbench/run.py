#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python chipbench/run.py --workload granite-chat --seed 7 --seconds 45 --trace 0

Set-up (weights or data from ``--seed``, every shape of the cell's traffic
warmed), then ``--seconds`` of measured work, then the comparison that
decides ``correct``.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown`` of the trace, and last the
``checks``: each number compared beside its limit.  The same numbers close
standard error.

It runs only on the TPU: with no TPU, fewer chips than the cell asks for,
a device kind missing from ``peaks.json``, or no program (``src/``) in the
checkout, it exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, Optional  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import trace_reduce  # noqa: E402


class Run:
    """What a driver is handed: the cell, the seed, the window's length, the
    chips, and the means to open the measured window."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 device: Dict, t_start: float, control: bool = False):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace = trace
        # the control in the program's place (``control.py``), never in a
        # benchmark run: what it gives is compared and comes out not correct
        self.control = control
        self.device = device
        self.peaks = device["peaks"]
        self.t_start = t_start
        self.spans = harness.Spans(trace)
        self.compiles = harness.CompileCounter()
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.trace_dir = harness.trace_dir(cell.name)
        self.extra_traces: list = []

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends where it opens; no compile may
        happen inside it; with ``--trace 1`` the profiler records it."""
        if self.trace:
            import jax.profiler

            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.trace_dir / "window"))
        t0 = time.perf_counter()
        self.setup_s = t0 - self.t_start
        self.compiles.armed = True
        try:
            with self.spans.span("window"):
                yield t0
        finally:
            self.window_s = time.perf_counter() - t0
            self.compiles.armed = False
            if self.trace:
                import jax.profiler

                jax.profiler.stop_trace()

    def mark(self, phase: str) -> None:
        """Log how far set-up has come when ``phase`` ends."""
        harness.log(f"set-up to the end of {phase}: "
                    f"{time.perf_counter() - self.t_start:.3f} s")

    @contextlib.contextmanager
    def side_trace(self, name: str):
        """A traced stretch outside the window (a comparison the per-layer
        metrics need); only with ``--trace 1``."""
        import jax.profiler

        jax.profiler.start_trace(str(self.trace_dir / name))
        try:
            yield
        finally:
            jax.profiler.stop_trace()
            self.extra_traces.append(name)

    def memory_peak_bytes(self) -> Optional[int]:
        return harness.memory_peak_bytes(self.device["devices"])


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def judge(checks: Dict) -> bool:
    """``correct``: there is a number to compare, and each is finite and
    within its limit."""
    return bool(checks) and all(
        _finite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())


def execute(cell, seed: int, seconds: float, trace: bool, device: Dict,
            t_start: float, control: bool = False) -> Dict:
    """Drive one run of ``cell`` and return its result object."""
    run = Run(cell, seed, seconds, trace, device, t_start, control)
    out = cell.driver().run(run)
    harness.log(f"compiles inside the window: {run.compiles.count}; "
                f"set-up {run.setup_s:.3f} s; window {run.window_s:.3f} s")
    for line in out.get("notes", []):
        harness.log(line)
    checks = out["checks"]
    correct = judge(checks)
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": out.get("memory_peak_bytes")}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    if trace:
        summary = trace_reduce.reduce_dir(run.trace_dir / "window",
                                          span_prefix="chipbench.")
        sides = {name: trace_reduce.reduce_dir(run.trace_dir / name)
                 for name in run.extra_traces}
        ctx = {"trace": summary, "side_traces": sides,
               "counts": out.get("counts", {}), "model": cell.config,
               "peaks": run.peaks, "chips": device["count"],
               "window_s": run.window_s}
        metrics = {}
        for m in cell.per_layer:
            value = harness.metric_reader(m["name"]).read(m["name"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev["busy_s"] = summary["busy_s"]
        dev["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["top_ops"],
                               "idle_gaps": summary["idle_gaps"]}
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    else:
        values = dict(out["e2e"], setup_s=run.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result.update(metrics=metrics, device=dev, checks=checks)
    if run.compiles.count:
        harness.log(f"{run.compiles.count} compiles inside the window: the "
                    f"warm-up missed a shape")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell, device, cache = harness.open_cell(args.workload)
    harness.log(f"cell {cell.name}: {device['count']} x {device['kind']}, "
                f"seed {args.seed}, {args.seconds} s, trace {args.trace}, "
                f"compile cache {cache}; set-up to the chips and the cache: "
                f"{time.perf_counter() - T_START:.3f} s")
    result = execute(cell, args.seed, args.seconds, bool(args.trace), device,
                     T_START)
    gc.collect()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
