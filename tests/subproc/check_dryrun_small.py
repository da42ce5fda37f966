"""Small-mesh dry-run machinery check (subprocess, 8 fake devices).

Exercises build_cell/lower/compile + the HLO collective parser for one cell
of every model family on a (data=2, model=4) mesh with reduced configs —
the same code path the production 16x16 / 2x16x16 dry-run uses.
"""
import os

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")

import dataclasses

import jax

from repro.compat import make_mesh
from repro.configs import get_config, reduced
from repro.launch.dryrun import build_cell, collective_bytes_from_hlo

mesh = make_mesh((2, 4), ("data", "model"))

CELLS = [
    ("qwen3-32b", "train_4k"),        # dense + qk_norm
    ("arctic-480b", "train_4k"),      # moe top-2 + dense residual
    ("llama4-scout-17b-a16e", "prefill_32k"),  # moe top-1 prefill
    ("rwkv6-7b", "decode_32k"),       # ssm decode
    ("zamba2-2.7b", "long_500k"),     # hybrid long-context decode
    ("hubert-xlarge", "train_4k"),    # encoder-only audio
    ("phi-3-vision-4.2b", "prefill_32k"),  # vlm prefix embeds
]

REDUCE_FIELDS = (
    "num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
    "d_ff", "vocab_size", "moe", "ssm", "hybrid_attn_every",
    "num_prefix_embeds", "dtype", "remat",
)

for arch, shape in CELLS:
    r = reduced(get_config(arch))
    overrides = {k: getattr(r, k) for k in REDUCE_FIELDS}
    fn, args, ins, outs, meta = build_cell(arch, shape, mesh, overrides=overrides)
    with mesh:
        compiled = jax.jit(fn, in_shardings=ins, out_shardings=outs).lower(*args).compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes_from_hlo(compiled.as_text())
    assert cost.get("flops", 0) > 0, (arch, shape, "no flops")
    print(f"ok {arch} x {shape}: flops={cost.get('flops'):.3e} "
          f"coll_ops={sum(coll['counts'].values())}")

print("DRYRUN-SMALL-OK")
