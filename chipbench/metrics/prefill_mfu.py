"""Model FLOPs of the prompts prefilled in the window (one lane each, with
causal attention; ``flops.prefill_flops``) over the prefill programs'
device time at the chip's bf16 peak."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))
import flops  # noqa: E402
from _programs import program  # noqa: E402


def read(name, ctx):
    pre = program(ctx["trace"], "prefill")
    lens = ctx["counts"].get("prefill_lens", [])
    if pre is None or not pre["seconds"] or not lens:
        return None
    work = sum(flops.prefill_flops(ctx["model"]["model"], s) for s in lens)
    return 100.0 * work / (pre["seconds"] * ctx["peaks"]["bf16_flops_per_s"])
