"""Reduce a ``jax.profiler`` trace to what the per-layer metrics read.

A trace directory holds one ``*.xplane.pb``.  Its device planes
(``/device:TPU:<n>``) have a line of XLA programs (``XLA Modules``) and a
line of the operations inside them (``XLA Ops``); host planes carry the
benchmark's own spans (``TraceAnnotation`` names starting with
``chipbench.``).  The reduction is plain arithmetic on intervals:

* busy time of a device: the union of its op intervals; ``busy_s`` is the
  mean over the devices, ``window_s`` the length of the traced window (the
  ``chipbench.window`` span);
* per program (module): how many times it ran and its device seconds, as a
  mean over the devices;
* per op name: device seconds, as a mean over the devices (``top_ops``);
* idle gaps: the stretches of the window in which device 0 runs nothing,
  charged to the benchmark's host spans that overlap them (``idle_gaps``).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

__all__ = ["union", "gaps", "program_name", "op_name", "reduce_planes",
           "reduce_dir", "find_xplane"]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "chipbench.window"


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: List[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] that the disjoint sorted ``busy`` leaves
    free."""
    out, t = [], lo
    for s, e in busy:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def program_name(event_name: str) -> str:
    """``jit_decode(123)`` -> ``jit_decode``."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def op_name(event_name: str) -> str:
    """An op event is named by its HLO text, ``%name.3 = type op(...)``:
    keep the instruction's name, ``name.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def _clip_len(intervals, lo, hi) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in intervals)


def _charge_gaps(free: List[Tuple[float, float]],
                 spans: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Charge each idle stretch to the host spans that overlap it, by the
    length of the overlap; what no span covers is charged to
    ``between benchmark spans``.  ``spans`` do not nest."""
    spans = sorted(spans, key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    out: Dict[str, float] = defaultdict(float)
    for gs, ge in free:
        covered = 0.0
        k = max(0, bisect.bisect_right(starts, gs) - 1)
        while k < len(spans) and spans[k][1] < ge:
            n, s, e = spans[k]
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                out[n] += ov
                covered += ov
            k += 1
        if ge - gs - covered > 0:
            out["between benchmark spans"] += ge - gs - covered
    return out


def reduce_planes(devices: Dict[str, Dict[str, list]],
                  spans: List[Tuple[str, float, float]],
                  window: Optional[Tuple[float, float]] = None,
                  top: int = 10) -> Dict:
    """The reduction, on plain data.

    ``devices``: per device, ``{"ops": [(name, start_s, end_s)],
    "modules": [(name, start_s, end_s)]}``.  ``spans``: host spans
    ``(name, start_s, end_s)`` on the same clock.  ``window``: the traced
    window; by default the ``chipbench.window`` span, else the extent of
    every event."""
    if window is None:
        w = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
        if w:
            window = w[0]
        else:
            ends = [t for d in devices.values() for o in d["ops"] for t in o[1:]]
            window = (min(ends), max(ends)) if ends else (0.0, 0.0)
    lo, hi = window
    n_dev = max(1, len(devices))
    busy_total = 0.0
    op_time: Dict[str, float] = defaultdict(float)
    mod_time: Dict[str, float] = defaultdict(float)
    mod_count: Dict[str, float] = defaultdict(float)
    first_busy = None
    for name in sorted(devices):
        d = devices[name]
        busy = union((s, e) for _, s, e in d["ops"])
        busy_total += _clip_len(busy, lo, hi)
        if first_busy is None:
            first_busy = busy
        for op, s, e in d["ops"]:
            op_time[op] += max(0.0, min(e, hi) - max(s, lo))
        for mod, s, e in d["modules"]:
            if s >= lo and s < hi:
                mod_time[mod] += e - s
                mod_count[mod] += 1
    idle = _charge_gaps(gaps(first_busy or [], lo, hi),
                        [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN])
    ranked = lambda d: [[k, v / n_dev] for k, v in
                        sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "busy_s": busy_total / n_dev,
        "window_s": hi - lo,
        "devices": len(devices),
        "modules": {k: {"count": mod_count[k] / n_dev,
                        "seconds": mod_time[k] / n_dev} for k in mod_time},
        "ops": {k: v / n_dev for k, v in op_time.items()},
        "top_ops": ranked(op_time),
        "idle_gaps": [[k, v] for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
    }


def find_xplane(path: Path) -> Path:
    found = sorted(Path(path).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return found[-1]


def _load(xplane: Path, span_prefix: str):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane))
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            d = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    name = (program_name(ev.name) if key == "modules"
                            else op_name(ev.name))
                    d[key].append((name, s, s + ev.duration_ns * 1e-9))
            devices[plane.name] = d
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
    spans.sort(key=lambda x: (x[1], -x[2]))
    return devices, spans


def reduce_dir(path: Path, span_prefix: str = "chipbench.") -> Dict:
    devices, spans = _load(find_xplane(path), span_prefix)
    return reduce_planes(devices, spans)
