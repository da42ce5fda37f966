"""Share of its roofline reached by the Pallas Mamba2 scan kernel in the
window's prefills: for each prefill, every Mamba layer's scan over the one
lane that holds the prompt (``flops_hybrid.mamba2_scan_cost``; the server
runs it over every lane, and the idle lanes are not needed work, as in
``flash_attention_roofline``), the larger of operations over the bf16 peak
and bytes over HBM bandwidth, summed, over the device time of the ops
named ``mamba2_scan`` (the kernel's ``pallas_call`` name) in the trace.
Nothing where no such op ran."""
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import flops_hybrid  # noqa: E402

KERNEL = re.compile(r"mamba2_scan")


def read(name, ctx):
    ops = ctx["trace"]["ops"]
    secs = sum(v for k, v in ops.items() if KERNEL.search(k))
    lens = ctx["counts"].get("prefill_lens", [])
    if not secs or not lens:
        return None
    m, p = ctx["model"]["model"], ctx["peaks"]
    least = 0.0
    for s in lens:
        cost = flops_hybrid.mamba2_scan_cost(m, 1, s)
        least += flops_hybrid.mamba_layers(m) * max(
            cost["flops"] / p["bf16_flops_per_s"],
            cost["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / secs
