"""Serving driver for a Mamba2 / attention hybrid (``layer_types``, such as
granite-4.0-h-micro): the serving driver ``serve.py`` itself, imported and
not copied, with two things in place of the dense decoder's.

* The counts: ``flops_hybrid`` (the weights once per decode step, each
  decoded sequence's recurrent state read and written, the attention
  layers' K/V to its position) stands where ``serve.py`` reads ``flops``.
* The program's configuration: besides the sizes ``serve.py`` sets, the
  layer pattern, the Mamba2 sizes, the Granite multipliers and the
  positional encoding come from the configuration file.

Everything else, the set-up, the window, the end-to-end metrics and the
comparison that decides ``correct``, is ``serve.py``'s.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path
from typing import Dict

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import flops_hybrid  # noqa: E402
import harness  # noqa: E402

serve = harness.load_module(Path(__file__).with_name("serve.py"),
                            "chipbench_drivers_serve_for_hybrid")
_sizes = serve.program_config


def program_config(model: Dict):
    """The program's ModelConfig with every size, the layer pattern, the
    Mamba2 sizes, the multipliers and the positional encoding taken from
    the benchmark's configuration file."""
    if model["mamba_n_groups"] != 1:
        raise SystemExit("the program runs one group of Mamba2 B/C, the "
                         f"configuration says {model['mamba_n_groups']}")
    cfg = _sizes(model)
    ssm = dataclasses.replace(
        cfg.ssm, head_dim=model["mamba_d_head"],
        state_dim=model["mamba_d_state"], expand=model["mamba_expand"],
        conv_dim=model["mamba_d_conv"], scan_chunk=model["mamba_chunk_size"])
    cfg = dataclasses.replace(
        cfg, ssm=ssm, layer_types=tuple(model["layer_types"]),
        position_embedding=model["position_embedding"],
        embedding_multiplier=model["embedding_multiplier"],
        attention_multiplier=model["attention_multiplier"],
        residual_multiplier=model["residual_multiplier"],
        logits_scaling=model["logits_scaling"])
    heads = cfg.ssm.expand * cfg.d_model // cfg.ssm.head_dim
    if heads != model["mamba_n_heads"]:
        raise SystemExit(f"the program makes {heads} Mamba2 heads, the "
                         f"configuration says {model['mamba_n_heads']}")
    return cfg


serve.flops = flops_hybrid
serve.program_config = program_config
run = serve.run
