"""Decode programs run on the device per token emitted in the window."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _programs import program  # noqa: E402


def read(name, ctx):
    dec = program(ctx["trace"], "decode")
    tokens = ctx["counts"].get("tokens", 0)
    if dec is None or not tokens:
        return None
    return dec["count"] / tokens
