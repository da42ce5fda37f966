"""Share of the traced window in which device 0 is idle inside the
program's ``repro.server.fetch`` spans: the host waiting for each call's
token ids (``program_trace.serving``).  Logs the whole split of the idle
inside ``repro.server.engine_step``."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import program_trace  # noqa: E402


def read(name, ctx):
    split = program_trace.serving(ctx)
    if split is None or not split["window_s"]:
        return None
    program_trace.log_serving(split, ctx)
    return 100.0 * split["fetch_idle_s"] / split["window_s"]
