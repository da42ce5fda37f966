"""What every cell's run shares: finding its files by name, holding the chips,
the compile cache and counter, host spans, the profiler window, and the
result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its files:

* ``configs/<config>.json`` (the ``file`` of its configuration): sizes,
  the deployment, and the name of its plain reference under
  ``references/``;
* ``traffic/<traffic>.json``: the driver under ``drivers/`` and every
  parameter of the traffic;
* ``workloads/<cell>.json``: the limits of the comparison that decides
  ``correct``, with the readings each was set from.

Per-layer metrics are read by ``metrics/<name>.py``, or, for a name with a
dot such as ``decode_roofline.chat``, by ``metrics/<part before the
dot>.py``.  Adding a cell, a driver or a metric adds files; nothing here
names one.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

__all__ = ["HERE", "ROOT", "Cell", "load_cell", "load_module",
           "metric_reader", "Spans", "CompileCounter", "open_cell",
           "require_chips", "hold_program", "memory_peak_bytes", "trace_dir",
           "log"]


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: Optional[str] = None) -> ModuleType:
    """Import a file of the benchmark by its path (names may hold '-' or
    '.', which ``import`` cannot spell)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        name or f"chipbench_{path.stem.replace('-', '_').replace('.', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, bench: Dict, name: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; known: "
                             f"{sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        conf = next(c for c in bench["configs"]
                    if c["name"] == self.entry["config"])
        self.config = _json(ROOT / conf["file"])
        self.traffic = _json(HERE / "traffic" / f"{self.entry['traffic']}.json")
        self.limits = _json(HERE / "workloads" / f"{name}.json")["limits"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def driver(self) -> ModuleType:
        return load_module(HERE / "drivers" / f"{self.traffic['driver']}.py")

    def reference(self) -> ModuleType:
        return load_module(HERE / "references" /
                           f"{self.config['reference']}.py")


def load_cell(name: str) -> Cell:
    return Cell(_json(ROOT / "BENCHMARK.json"), name)


def metric_reader(name: str) -> ModuleType:
    exact = HERE / "metrics" / f"{name}.py"
    return load_module(exact if exact.is_file() else
                       HERE / "metrics" / f"{name.split('.')[0]}.py")


def hold_program() -> None:
    """Put the program under test (``src/`` of the checkout) on the path,
    or stop: the benchmark measures it and nothing else.  Before JAX loads,
    keep the TPU runtime's logs off the disk (by default they go to a fixed
    directory under /tmp)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"chipbench: the program ({src}/repro) is not in "
                         f"this checkout")
    sys.path.insert(0, str(src))


def open_cell(name: str):
    """Everything a chip process does before it drives a cell: load the
    cell's files, hold the program, find the chips and turn on JAX's
    persistent compile cache (``launch/device.place_compile_cache``: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else the checkout's ``.jax_cache``).
    Returns the cell, the chips' facts and the cache directory."""
    cell = load_cell(name)
    hold_program()
    device = require_chips(cell.chips, _json(HERE / "peaks.json"))

    import jax
    from repro.launch.device import place_compile_cache

    cache = place_compile_cache()
    # every program, however quick to compile, is loaded from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cell, device, cache


def require_chips(count: int, peaks: Dict) -> Dict:
    """The first ``count`` TPU chips' facts, or stop with no result."""
    import jax

    devices = jax.devices()
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: no TPU (JAX found "
                         f"{devices[0].platform!r}); the benchmark runs "
                         f"only on the chip")
    if len(devices) < count:
        raise SystemExit(f"chipbench: the cell needs {count} chips, JAX "
                         f"found {len(devices)}")
    if kind not in peaks["devices"]:
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r} in "
                         f"peaks.json")
    return {"platform": devices[0].platform, "kind": kind, "count": count,
            "devices": devices[:count], "peaks": peaks["devices"][kind]}


class CompileCounter:
    """Counts XLA compilations (backend compiles, cache hits excluded) from
    the moment it is armed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if self.armed and event == self.EVENT:
            self.count += 1


class Spans:
    """Host spans the benchmark records around its calls into the program,
    written into the profiler's trace (``chipbench.<name>``) when the run
    is traced, and nothing otherwise."""

    def __init__(self, tracing: bool):
        self.tracing = tracing

    def span(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax.profiler

        return jax.profiler.TraceAnnotation(f"chipbench.{name}")


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def trace_dir(cell: str) -> Path:
    """A fixed directory in the checkout for this cell's trace; emptied
    before and after each traced run."""
    return HERE / ".traces" / cell
