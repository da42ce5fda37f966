"""The whole serving step's share of the chip's peak: model FLOPs of every
prompt prefilled and token decoded in the window over the window at the
chip's bf16 peak."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import flops  # noqa: E402


def read(name, ctx):
    c = ctx["counts"]
    work = sum(flops.prefill_flops(ctx["model"]["model"], s)
               for s in c.get("prefill_lens", [])) + c.get("decode_flops", 0)
    window = ctx["trace"]["window_s"]
    if not work or not window:
        return None
    return 100.0 * work / (window * ctx["peaks"]["bf16_flops_per_s"])
