"""The granite-4.0-h-micro cell (``granite4h-docs-offline``): its
configuration file against the program's and the published config, the
cell run end to end on the CPU at a small size through the serving driver
for hybrids, and the fp8 control of its reference at published widths."""
from pathlib import Path

import numpy as np

import _cpu
import harness
import run as bench

HERE = Path(__file__).resolve().parent
CELL = "granite4h-docs-offline"

# one whole period of the pattern, Mamba2 heads of 16 with d_state 16
SMALL_HYBRID = dict(_cpu.SMALL, num_layers=10, mamba_d_head=16,
                    mamba_d_state=16, mamba_n_heads=32, mamba_chunk_size=16)


def _driver():
    return harness.load_module(HERE.parent / "drivers" / "serve_hybrid.py")


def small_cell(**model):
    cell = harness.load_cell(CELL)
    m = cell.config["model"]
    m.update(SMALL_HYBRID, layer_types=m["layer_types"][:10], **model)
    cell.traffic.update(
        prompt_tokens={"values": [16, 32], "weights": [0.5, 0.5]},
        output_tokens={"values": [6, 12], "weights": [0.5, 0.5]},
        max_seq=48)
    return cell


def test_config_file_is_the_program_config():
    """Every size of the configuration's ``model`` table is the program's
    registered granite-4.0-h-micro, as the driver builds it: 40 layers in
    the published pattern, the widths, the vocabulary, the multipliers,
    NoPE; and the table agrees with the published keys beside it."""
    from repro.configs import get_config

    conf = harness.load_cell(CELL).config
    m = conf["model"]
    assert _driver().program_config(m) == get_config(m["program_config"])
    assert conf["reduced"] == []
    pub = {"num_layers": "num_hidden_layers", "d_model": "hidden_size",
           "num_heads": "num_attention_heads",
           "num_kv_heads": "num_key_value_heads",
           "d_ff": "shared_intermediate_size", "vocab_size": "vocab_size",
           "norm_eps": "rms_norm_eps", "tie_embeddings": "tie_word_embeddings",
           "layer_types": "layer_types",
           "position_embedding": "position_embedding_type",
           "embedding_multiplier": "embedding_multiplier",
           "attention_multiplier": "attention_multiplier",
           "residual_multiplier": "residual_multiplier",
           "logits_scaling": "logits_scaling"}
    for ours, theirs in pub.items():
        assert m[ours] == conf[theirs], ours
    for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state",
              "mamba_n_groups", "mamba_expand", "mamba_d_conv",
              "mamba_chunk_size"):
        assert m[k] == conf[k], k


def test_reference_follows_the_program():
    """The benchmark's reference and the program's forward pass, both in
    float32 on the reference's weights, agree to float32 rounding."""
    import jax.numpy as jnp
    from repro.models import forward

    cell = small_cell(dtype="float32")
    m = cell.config["model"]
    ref = cell.reference()
    w = ref.init_weights(m, 3_000_000_077)
    cfg = _driver().program_config(m)
    toks = np.random.default_rng(0).integers(0, m["vocab_size"], 48)
    got, _, _ = forward(cfg, w, {"tokens": jnp.asarray(toks)[None]})
    want = np.asarray(ref.logits(m, w, toks))
    scale = np.abs(want).max()
    assert np.abs(np.asarray(got[0]) - want).max() < 1e-5 * scale, scale


def test_cell_runs_correct_on_cpu():
    """The whole benchmark path (set-up, window, comparison) at a small
    size: correct, with decode bytes counted by the hybrid's accounting."""
    res = _cpu.execute(small_cell())
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["output_tokens_per_s"]["value"] > 0


def test_cell_control_is_not_correct():
    """The fp8 control at published widths (one attention layer among
    three Mamba layers, the whole vocabulary): its first token at every
    position of random sequences lies below the float32 reference's best
    by more than the cell's limit."""
    cell = harness.load_cell(CELL)
    ref = cell.reference()
    m = dict(cell.config["model"], num_layers=4,
             layer_types=["mamba", "mamba", "attention", "mamba"])
    weights = ref.init_weights(m, 3_000_000_041)
    rng = np.random.default_rng(6)
    items = [(rng.integers(0, m["vocab_size"], 128).astype(np.int32),
              rng.integers(0, m["vocab_size"], 256).astype(np.int32))
             for _ in range(3)]
    serve = harness.load_module(HERE.parent / "drivers" / "serve.py")
    got = serve.compare(ref, m, weights, items, 384, quant="fp8")
    checks = {"logit_gap": {"value": got["gap_control"],
                            "limit": cell.limits["logit_gap"]}}
    print("fp8 control gap", got["gap_control"])
    assert bench.judge(checks) is False, got
