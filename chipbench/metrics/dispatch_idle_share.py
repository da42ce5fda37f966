"""Share of the traced window in which device 0 is idle inside the
program's ``repro.server.prefill`` and ``repro.server.decode`` spans but
outside their ``fetch``: the host building and uploading a call's arrays
and dispatching it (``program_trace.serving``)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import program_trace  # noqa: E402


def read(name, ctx):
    split = program_trace.serving(ctx)
    if split is None or not split["window_s"]:
        return None
    return 100.0 * split["dispatch_idle_s"] / split["window_s"]
