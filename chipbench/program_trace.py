"""Read the program's own trace points (``src/repro/obs.py``) from a traced
window: what the benchmark's own spans, around whole calls, cannot split.

* Serving (``repro.server.*`` host spans, nested: ``engine_step`` holds
  ``prefill`` and ``decode``, each holds one ``fetch``).  Each stretch in
  which device 0 runs nothing is charged to the innermost program span
  that covers it, so each span is charged for its self time only:
  ``fetch`` for the token ids' trip to the host, ``prefill``/``decode``
  outside ``fetch`` for the dispatch (arrays built and uploaded, the jit
  called), ``engine_step`` outside its children for the bookkeeping.
* The planned sync (``stage<k>.<axis>.<mode>`` scopes).  Each TPU op's
  metadata in the trace carries its HLO ``op_name`` (the ``tf_op`` stat)
  and its program's id: the ops of the ``jit_planned_sync`` program are
  split into those under a stage scope (the collectives) and the rest (the
  local layout work around them), which the ``plan.<collective>`` scope
  and the scope under it split further in the log
  (``plan.ag/transpose``).

The serving spans and device 0's ops are loaded by ``trace_reduce``'s own
loader.  ``ProfileData`` gives an op event its own stats only
(``device_offset_ps``, ``device_duration_ps``), not those of its metadata
(``tf_op``, ``program_id``), so the stage split decodes the device planes
of the ``.xplane.pb`` itself.

A reader is handed the reduction of the window (``ctx["trace"]``) but not
its directory: the window's trace is the newest under ``.traces/*/window``
(``harness.trace_dir``), and it is used only if it matches the reduction
(the window's length, the runs of the sync program); a trace that holds
the program's spans or scopes and does not match is logged.  A trace
without them (a program that does not record them) reads as nothing.
"""
from __future__ import annotations

import bisect
import functools
import re
import struct
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent))

import trace_reduce  # noqa: E402
from trace_reduce import gaps, union  # noqa: E402

__all__ = ["window_xplane", "self_intervals", "charge", "serving_split",
           "serving", "log_serving", "device_planes", "stage_split", "sync",
           "log_sync"]

HERE = Path(__file__).resolve().parent
SPAN_PREFIX = "repro."
STEP, PREFILL, DECODE, FETCH = (SPAN_PREFIX + "server." + n for n in
                                ("engine_step", "prefill", "decode", "fetch"))
STAGE = re.compile(r"(?:^|/)stage\d+\.[^/]+\.(?:oneshot|ring|exchange)(?:/|:|$)")
PLAN = re.compile(r"(?:^|/)(plan\.[^/:;]+)(/[^/:;]+)?")
COLLECTIVE = re.compile(r"^(?:reduce[-_]scatter|all[-_]gather|all[-_]reduce|"
                        r"all[-_]to[-_]all|collective[-_]permute)", re.I)

Span = Tuple[str, float, float]


def _log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def _mismatch(path: Path, what: str) -> None:
    _log(f"program trace {path} is not the window reduced ({what}): its "
         f"spans and scopes are not read")


def window_xplane() -> Optional[Path]:
    """The newest window trace under ``.traces``, or None.  Its callers
    check it against the reduction they were handed."""
    found = sorted(HERE.glob(".traces/*/window/**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns)
    return found[-1] if found else None


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def self_intervals(spans: List[Span]) -> List[Span]:
    """Each span's self time as ``(name, start, end)`` pieces: its interval
    less the intervals of the spans it holds.  Spans nest (one thread's
    annotations); one that runs past its holder's end is cut there."""
    out: List[Span] = []
    stack: List[list] = []  # [name, cursor, end]

    def close(upto: float) -> None:
        while stack and stack[-1][2] <= upto:
            name, cur, end = stack.pop()
            if end > cur:
                out.append((name, cur, end))
            if stack:
                stack[-1][1] = max(stack[-1][1], end)

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        close(s)
        if stack:
            top = stack[-1]
            if s > top[1]:
                out.append((top[0], top[1], s))
            top[1] = max(top[1], s)
            e = min(e, top[2])
        stack.append([name, s, e])
    close(float("inf"))
    return sorted(out, key=lambda x: x[1])


def charge(free: List[Tuple[float, float]],
           pieces: List[Span]) -> Dict[str, float]:
    """Seconds of the disjoint sorted ``free`` stretches that each name's
    disjoint ``pieces`` cover."""
    out: Dict[str, float] = defaultdict(float)
    starts = [s for s, _ in free]
    for name, s, e in pieces:
        k = max(0, bisect.bisect_right(starts, s) - 1)
        while k < len(free) and free[k][0] < e:
            ov = min(e, free[k][1]) - max(s, free[k][0])
            if ov > 0:
                out[name] += ov
            k += 1
    return dict(out)


# --------------------------------------------------------------------------
# serving spans
# --------------------------------------------------------------------------

def _load_serving(xplane: Path):
    """Device 0's op intervals, the window span and the program's spans, by
    ``trace_reduce``'s own loader (host spans by a tuple of prefixes)."""
    devices, spans = trace_reduce._load(
        xplane, (SPAN_PREFIX, trace_reduce.WINDOW_SPAN))
    window = next(((s, e) for n, s, e in spans
                   if n == trace_reduce.WINDOW_SPAN), None)
    spans = [x for x in spans if x[0] != trace_reduce.WINDOW_SPAN]
    busy = (union((s, e) for _, s, e in devices[sorted(devices)[0]]["ops"])
            if devices else [])
    return busy, window, spans


def serving_split(busy: List[Tuple[float, float]], window: Tuple[float, float],
                  spans: List[Span]) -> Dict[str, float]:
    """Device 0's idle seconds in the window charged to the serving spans'
    self time; ``engine_step_idle_s`` is all of it inside
    ``server.engine_step`` (children included)."""
    lo, hi = window
    free = gaps(busy, lo, hi)
    inside = [(n, max(s, lo), min(e, hi)) for n, s, e in spans
              if e > lo and s < hi]
    idle = charge(free, self_intervals(inside))
    g = lambda n: idle.get(n, 0.0)  # noqa: E731
    return {"window_s": hi - lo,
            "fetch_idle_s": g(FETCH),
            "dispatch_idle_s": g(PREFILL) + g(DECODE),
            "bookkeeping_idle_s": g(STEP),
            "engine_step_idle_s": g(STEP) + g(PREFILL) + g(DECODE) + g(FETCH),
            "decode_spans": sum(1 for n, _, _ in inside if n == DECODE),
            "prefill_spans": sum(1 for n, _, _ in inside if n == PREFILL)}


@functools.lru_cache(maxsize=2)
def _serving_of(path: str, mtime_ns: int) -> Optional[Dict[str, float]]:
    busy, window, spans = _load_serving(Path(path))
    if window is None or not any(n == STEP for n, _, _ in spans):
        return None
    return serving_split(busy, window, spans)


def serving(ctx: Dict) -> Optional[Dict[str, float]]:
    """The serving split of the window ``ctx`` reduces, or None where the
    program recorded no serving spans."""
    path = window_xplane()
    if path is None:
        return None
    split = _serving_of(str(path), path.stat().st_mtime_ns)
    if split is None:
        return None  # no serving spans
    if abs(split["window_s"] - ctx["trace"]["window_s"]) > 1e-6:
        _mismatch(path, f"window {split['window_s']!r} s against "
                        f"{ctx['trace']['window_s']!r} s")
        return None
    return split


def log_serving(split: Dict[str, float], ctx: Dict) -> None:
    """One line on the serving split beside what the benchmark's own
    ``chipbench.engine_step`` span is charged on the same trace."""
    bench = dict(ctx["trace"]["idle_gaps"]).get("chipbench.engine_step")
    runs = sum(v["count"] for k, v in ctx["trace"]["modules"].items()
               if "decode" in k)
    _log(f"program spans: device idle inside repro.server.engine_step "
         f"{split['engine_step_idle_s']!r} s (chipbench.engine_step "
         f"{bench!r} s): fetch {split['fetch_idle_s']!r}, dispatch "
         f"{split['dispatch_idle_s']!r}, bookkeeping "
         f"{split['bookkeeping_idle_s']!r}; decode spans "
         f"{split['decode_spans']} against {runs:g} jit_decode runs; "
         f"prefill spans {split['prefill_spans']}")


# --------------------------------------------------------------------------
# stage scopes: the device planes' op metadata, read from the raw XSpace
# --------------------------------------------------------------------------

def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for varints,
    bytes for length-delimited fields and fixed-width ones."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} in an xplane")
        yield num, v


def _stat(fields: Dict[int, object], stat_names: Dict[int, str]):
    """An XStat's value (str for strings and references)."""
    if 5 in fields:
        return fields[5].decode(errors="replace")
    if 7 in fields:
        return stat_names.get(fields[7], "")
    if 2 in fields:
        return struct.unpack("<d", fields[2])[0]
    return fields.get(4, fields.get(3))


def _device_plane(raw: bytes):
    """One device plane: its name, its ops as ``(program id, op_name
    metadata, instruction name, seconds)`` and its program runs as
    ``(name, program id, seconds)``."""
    name, lines, events_md, stat_md = "", [], {}, {}
    for num, v in _fields(raw):
        if num == 2:
            name = v.decode()
        elif num == 3:
            lines.append(v)
        elif num in (4, 5):  # map<int64, X{Event,Stat}Metadata> entries
            for k, entry in _fields(v):
                if k != 2:
                    continue
                md, stats = {}, []
                for f, x in _fields(entry):
                    if f == 5 and num == 4:
                        stats.append(x)
                    else:
                        md[f] = x
                (events_md if num == 4 else stat_md)[md.get(1, 0)] = \
                    (md, stats)
    stat_names = {k: md.get(2, b"").decode() for k, (md, _) in stat_md.items()}
    meta = {}
    for mid, (md, stats) in events_md.items():
        st = {}
        for x in stats:
            f = dict(_fields(x))
            st[stat_names.get(f.get(1), "")] = _stat(f, stat_names)
        meta[mid] = (md.get(2, b"").decode(errors="replace"), st)
    ops, modules = [], []
    for line in lines:
        lname, evs = "", []
        for f, x in _fields(line):
            if f == 2:
                lname = x.decode()
            elif f == 4:
                evs.append(x)
        if lname not in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE):
            continue
        for ev in evs:
            ef = dict(_fields(ev))
            text, st = meta.get(ef.get(1, 0), ("", {}))
            secs = ef.get(3, 0) * 1e-12
            if lname == trace_reduce.MODULES_LINE:
                pid = re.search(r"\((-?\d+)\)$", text.strip())
                modules.append((trace_reduce.program_name(text),
                                _id(pid.group(1)) if pid else None, secs))
            else:
                pid = st.get("program_id")
                ops.append((None if pid is None else _id(pid),
                            str(st.get("tf_op", "")),
                            trace_reduce.op_name(text), secs))
    return name, ops, modules


def _id(v) -> int:
    return int(v) & 0xFFFFFFFFFFFFFFFF


def device_planes(xplane: Path):
    """``(name, ops, programs)`` of each TPU device plane of a trace file
    (``_device_plane``); host planes are skipped unread."""
    for num, plane in _fields(xplane.read_bytes()):
        if num != 1 or b"/device:TPU:" not in plane[:64]:
            continue
        name, ops, modules = _device_plane(plane)
        if name.startswith("/device:TPU:") and "Core" not in name:
            yield name, ops, modules


def stage_split(devices, word: str) -> Optional[Dict[str, float]]:
    """Of the programs whose name holds ``word``, as a mean over the devices
    that ran them: ``runs``, their device seconds (``program_s``), their
    ops' seconds under a stage scope (``stage_s``) and outside every stage
    scope (``layout_s``), and their collective ops' seconds (``collective_s``)
    with the part under a stage scope (``collective_stage_s``);
    ``layout_by_scope``: the layout seconds by the ``plan.<collective>``
    scope and the scope under it (``plan.ag/transpose``; ``no plan scope``
    for ops outside every plan).  None where no op of theirs is under a
    stage scope."""
    acc: Dict[str, float] = defaultdict(float)
    layout: Dict[str, float] = defaultdict(float)
    n_dev = 0
    for _, ops, modules in devices:
        ids = {pid for n, pid, _ in modules if word in n}
        if not ids:
            continue
        n_dev += 1
        for n, _, secs in modules:
            if word in n:
                acc["runs"] += 1
                acc["program_s"] += secs
        for pid, tf_op, op, secs in ops:
            if pid not in ids:
                continue
            staged = bool(STAGE.search(tf_op))
            acc["stage_s" if staged else "layout_s"] += secs
            if not staged:
                m = PLAN.search(tf_op)
                layout["".join(m.groups(""))
                       if m else "no plan scope"] += secs
            if COLLECTIVE.match(op):
                acc["collective_s"] += secs
                acc["collective_stage_s"] += secs if staged else 0.0
    if not n_dev or not acc["stage_s"]:
        return None
    out: Dict = {k: v / n_dev for k, v in acc.items()}
    out["layout_by_scope"] = {k: v / n_dev for k, v in layout.items()}
    return out


@functools.lru_cache(maxsize=2)
def _sync_of(path: str, mtime_ns: int, word: str):
    return stage_split(list(device_planes(Path(path))), word)


def sync(ctx: Dict, word: str = "planned_sync") -> Optional[Dict[str, float]]:
    """The stage split of the ``word`` programs in the window ``ctx``
    reduces, or None where their ops carry no stage scope."""
    path = window_xplane()
    if path is None:
        return None
    split = _sync_of(str(path), path.stat().st_mtime_ns, word)
    runs = sum(v["count"] for k, v in ctx["trace"]["modules"].items()
               if word in k)
    if split is None:
        return None  # no stage scopes
    if split["runs"] != runs:
        _mismatch(path, f"{split['runs']:g} {word} runs against {runs:g}")
        return None
    return split


def log_sync(split: Dict[str, float], word: str = "planned_sync") -> None:
    per = 1e3 / split["runs"]
    cover = (100 * split["collective_stage_s"] / split["collective_s"]
             if split["collective_s"] else float("nan"))
    _log(f"stage scopes, per {word} run: program "
         f"{split['program_s'] * per!r} ms, under stage scopes "
         f"{split['stage_s'] * per!r} ms, outside them "
         f"{split['layout_s'] * per!r} ms; collective ops "
         f"{split['collective_s'] * per!r} ms, {cover!r}% of it under a "
         f"stage scope")
    by_plan: Dict[str, Dict[str, float]] = defaultdict(dict)
    for key, secs in split["layout_by_scope"].items():
        plan, _, part = key.partition("/")
        by_plan[plan][part] = secs * per

    def one(plan: str, parts: Dict[str, float]) -> str:
        inner = ", ".join(f"{k} {v!r}" for k, v in
                          sorted(parts.items(), key=lambda kv: -kv[1]) if k)
        return f"{plan} {sum(parts.values())!r} ms" + \
            (f" ({inner})" if inner else "")

    _log("layout outside the stage scopes, per run: " + "; ".join(
        one(plan, parts) for plan, parts in sorted(by_plan.items())))
