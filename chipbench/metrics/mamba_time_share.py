"""Share of the serving programs' device time (``jit_prefill`` and
``jit_decode``) spent in ops under the program's ``mamba2`` scope: the
Mamba2 mixers with their pre-norms, against the ``attention`` and ``mlp``
scopes and the rest.  The ops' HLO ``op_name`` (the ``tf_op`` stat) is read
from the window's device planes with ``program_trace.device_planes``, on
device 0.  Nothing where no op carries the scope (a program that does not
record it), or where the trace found is not the window reduced."""
import functools
import re
import sys
from pathlib import Path
from typing import Dict, Optional

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import program_trace  # noqa: E402

SCOPE = re.compile(r"(?:^|/)mamba2(?:/|:|$)")
PROGRAMS = ("prefill", "decode")


def split(devices, scope=SCOPE) -> Optional[Dict[str, float]]:
    """Of the first device's serving programs: their runs, their device
    seconds (``program_s``) and their ops' seconds under ``scope``
    (``scope_s``); None where no op of theirs is under it."""
    for _, ops, modules in devices:
        ids = {pid for n, pid, _ in modules if any(w in n for w in PROGRAMS)}
        if not ids:
            continue
        runs = [s for n, pid, s in modules if pid in ids]
        scoped = sum(s for pid, tf_op, _, s in ops
                     if pid in ids and scope.search(tf_op))
        if not scoped:
            return None
        return {"runs": len(runs), "program_s": sum(runs), "scope_s": scoped}
    return None


@functools.lru_cache(maxsize=2)
def _split_of(path: str, mtime_ns: int):
    return split(program_trace.device_planes(Path(path)))


def read(name, ctx):
    path = program_trace.window_xplane()
    if path is None:
        return None
    got = _split_of(str(path), path.stat().st_mtime_ns)
    if got is None or not got["program_s"]:
        return None
    runs = sum(v["count"] for k, v in ctx["trace"]["modules"].items()
               if any(w in k for w in PROGRAMS))
    if got["runs"] != runs:
        program_trace._log(f"program trace {path} is not the window reduced "
                           f"({got['runs']} serving runs against {runs:g}): "
                           f"its scopes are not read")
        return None
    return 100.0 * got["scope_s"] / got["program_s"]
