"""Share of the HBM roofline reached by decoding: the least time for the
bytes the window's decode steps need (every weight once per engine step,
each decoded sequence's K and V up to its position; ``flops.
decode_step_bytes``) over the device time of the decode programs."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _programs import program  # noqa: E402


def read(name, ctx):
    dec = program(ctx["trace"], "decode")
    need = ctx["counts"].get("decode_bytes", 0)
    if dec is None or not dec["seconds"] or not need:
        return None
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / dec["seconds"]
