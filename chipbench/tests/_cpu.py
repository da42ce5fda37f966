"""Run a cell here on the CPU at a size a test can hold: the harness's look
for a chip is skipped, everything after it is the benchmark's own path."""
import time

import jax

import harness
import run as bench

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
         "ici_bytes_per_s": 1e10}
SMALL = {"num_layers": 4, "d_model": 256, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 64, "d_ff": 512, "vocab_size": 1000, "padded_vocab": 1024}


def small_cell(name: str, **model):
    cell = harness.load_cell(name)
    cell.config["model"].update(SMALL, **model)
    tr = cell.traffic
    if tr["driver"] == "serve":
        tr.update(prompt_tokens={"values": [16, 32], "weights": [0.5, 0.5]},
                  output_tokens={"values": [6, 12], "weights": [0.5, 0.5]},
                  max_seq=64)
        if tr["arrival"]["kind"] == "poisson":
            tr["arrival"]["rate_per_s"] = 5.0
    return cell


def execute(cell, seed=3_000_000_021, seconds=2.0, trace=False, control=False):
    device = {"platform": "cpu", "kind": "cpu", "count": cell.chips,
              "devices": jax.devices()[: cell.chips], "peaks": PEAKS}
    return bench.execute(cell, seed, seconds, trace, device,
                         time.perf_counter(), control=control)
