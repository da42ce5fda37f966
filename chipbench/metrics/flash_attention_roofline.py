"""Share of its roofline reached by the Pallas flash-attention kernel in
the window's prefills: for each prefill, every layer's kernel call over the
one lane that holds the prompt (``flops.flash_attention_cost``; the server
runs it over every lane, and the idle lanes are not needed work, as in
``prefill_mfu``), the larger of operations over the bf16 peak and bytes
over HBM bandwidth, summed, over the kernel's device time in the trace."""
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import flops  # noqa: E402

KERNEL = re.compile(r"flash", re.I)


def read(name, ctx):
    ops = ctx["trace"]["ops"]
    secs = sum(v for k, v in ops.items() if KERNEL.search(k))
    lens = ctx["counts"].get("prefill_lens", [])
    if not secs or not lens:
        return None
    m, p = ctx["model"]["model"], ctx["peaks"]
    least = 0.0
    for s in lens:
        cost = flops.flash_attention_cost(m, 1, s)
        least += m["num_layers"] * max(cost["flops"] / p["bf16_flops_per_s"],
                                       cost["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / secs
