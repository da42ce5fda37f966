"""Share of the chip's interconnect peak reached by the planned sync's
stage collectives alone: the bytes each chip must receive for the
reduce-scatter and the all-gather (``flops.sync_recv_bytes``) over the
device time per sync of the ``jit_planned_sync`` ops under a ``stage<k>``
scope (``program_trace.sync``)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import program_trace  # noqa: E402


def read(name, ctx):
    split = program_trace.sync(ctx)
    need = ctx["counts"].get("recv_bytes_per_sync")
    if split is None or not split["stage_s"] or not need:
        return None
    per_sync = split["stage_s"] / split["runs"]
    return 100.0 * need / ctx["peaks"]["ici_bytes_per_s"] / per_sync
