"""Share of the chip's interconnect peak reached by the planned sync: the
bytes each chip must receive for the reduce-scatter and the all-gather
(``flops.sync_recv_bytes``) over the sync program's mean device time."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _programs import program  # noqa: E402


def read(name, ctx):
    sync = program(ctx["trace"], "planned_sync")
    need = ctx["counts"].get("recv_bytes_per_sync")
    if sync is None or not sync["seconds"] or not need:
        return None
    mean = sync["seconds"] / sync["count"]
    return 100.0 * need / ctx["peaks"]["ici_bytes_per_s"] / mean
