"""The counts of ``flops_hybrid.py`` against granite-4.0-h-micro numbers
worked out by hand from its published sizes."""
import json
from pathlib import Path

import flops_hybrid as fh

M = json.loads((Path(__file__).resolve().parents[1] / "configs" /
                "granite-4.0-h-micro.json").read_text())["model"]


def test_layers_of_each_kind():
    assert (fh.mamba_layers(M), fh.attention_layers(M)) == (36, 4)


def test_weights():
    # Mamba mixer: in_proj 2048*(2*4096 + 2*128 + 64) = 17,432,576, out_proj
    # 4096*2048 = 8,388,608, conv 4*4352 + 4352 = 21,760, A_log/D/dt_bias
    # 3*64, norm 4096: 25,847,232
    # attention: q, o 2*2048*2048 + k, v 2*2048*512 = 10,485,760
    # every layer: MLP 3*2048*8192 = 50,331,648 and two norms 4096
    # 100352*2048 + 40*50,335,744 + 36*25,847,232 + 4*10,485,760 + 2048
    assert fh.weight_params(M) == 3_191_396_096
    # A_log, D and dt_bias (36*192 = 6,912) in float32, the rest bf16
    assert fh.weight_bytes(M) == (3_191_396_096 - 6_912) * 2 + 6_912 * 4 \
        == 6_382_806_016


def test_state_and_kv():
    # per Mamba layer: SSM 64*64*128*4 = 2,097,152 + conv 3*4352*2 = 26,112
    assert fh.state_bytes(M) == 36 * 2_123_264 == 76_437_504
    # K and V of 4 layers, 8 heads of 64, bf16: 8,192 bytes a position
    assert fh.kv_bytes(M, 1000) == 8_192_000


def test_decode_step_bytes_at_4_lanes():
    # weights once, each lane's state read and written, K/V to its position
    # and the new row
    pos = [1023, 2047, 4095, 4607]
    want = (6_382_806_016 + 4 * 2 * 76_437_504
            + 8_192 * (1024 + 2048 + 4096 + 4608) + 4 * 8_192)
    assert fh.decode_step_bytes(M, pos) == want == 7_090_807_808
    assert fh.decode_step_bytes(M, []) == 0


def test_prefill_flops_at_1024():
    mlp = 2 * 40 * 50_331_648
    # Mamba: in_proj + out_proj 25,821,184 multiply-adds, the conv 4 taps
    # over 4352 channels, the scan 64 * (5*64*128 + 64)
    mamba = 36 * (2 * 25_821_184 + 2 * 4 * 4352 + 64 * (5 * 64 * 128 + 64))
    attn_proj = 2 * 4 * 10_485_760
    per_token = mlp + mamba + attn_proj
    assert per_token == 4_026_531_840 + 1_954_897_920 + 83_886_080
    scores = 4 * 4 * 32 * 64 * (1024 * 1025 // 2)
    head = 2 * 2048 * 100352
    assert fh.prefill_flops(M, 1024) == per_token * 1024 + scores + head
    assert fh.decode_token_flops(M, 1023) == (per_token + 4 * 4 * 32 * 64
                                              * 1024 + head)


def test_mamba2_scan_cost():
    c = fh.mamba2_scan_cost(M, 1, 4096)
    # 64 heads * (5*64*128 + 64) operations a step
    assert c["flops"] == 4096 * 64 * 41_024 == 10_754_195_456
    # a step reads x (64*64) and B, C (2*128) in bf16, dt and the log
    # decay (2*64) in f32, writes y (64*64) in f32; the state in and out
    step = (4096 + 256) * 2 + 128 * 4 + 4096 * 4
    assert c["bytes"] == 4096 * step + 2 * 64 * 64 * 128 * 4 == 109_051_904
    assert fh.mamba2_scan_cost(M, 4, 4096)["flops"] == 4 * c["flops"]
