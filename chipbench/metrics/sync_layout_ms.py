"""Device milliseconds per planned sync in the ``jit_planned_sync`` ops
outside every ``stage<k>`` scope: the local layout work (block permutes,
the finalize transpose, copies) around the stage collectives
(``program_trace.sync``).  Logs the whole split."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import program_trace  # noqa: E402


def read(name, ctx):
    split = program_trace.sync(ctx)
    if split is None or not split["runs"]:
        return None
    program_trace.log_sync(split)
    return 1e3 * split["layout_s"] / split["runs"]
