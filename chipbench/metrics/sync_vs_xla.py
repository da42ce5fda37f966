"""The planned sync's mean device time over that of XLA's own
``psum_scatter`` + ``all_gather`` of the same leaves, both from the traced
stretch that runs them alternately after the window."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _programs import program  # noqa: E402


def read(name, ctx):
    side = ctx["side_traces"].get("vs_xla")
    if side is None:
        return None
    planned, xla = program(side, "planned_sync"), program(side, "xla_sync")
    if planned is None or xla is None or not xla["seconds"]:
        return None
    return ((planned["seconds"] / planned["count"])
            / (xla["seconds"] / xla["count"]))
