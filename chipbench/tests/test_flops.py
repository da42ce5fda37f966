"""The counts of ``flops.py`` against granite-3-2b numbers worked out by
hand from its published sizes."""
import json
from pathlib import Path

import flops

GRANITE = json.loads((Path(__file__).resolve().parents[1] / "configs" /
                      "granite-3-2b.json").read_text())["model"]


def test_weights():
    # per layer: q 2048*2048 + k,v 2*2048*512 + o 2048*2048
    #            + gate, up, down 3*2048*8192 + two norms 2*2048
    assert flops.layer_params(GRANITE) == 60_821_504
    # tied embedding 49408*2048 + 40 layers + final norm 2048
    assert flops.weight_params(GRANITE) == 2_534_049_792
    assert flops.weight_bytes(GRANITE) == 5_068_099_584


def test_decode_bytes_at_8_slots_1280():
    # K and V of one sequence at 1280 positions: 2*40*8*64*1280*2 bytes
    assert flops.kv_bytes(GRANITE, 1280) == 104_857_600
    # eight sequences at the last position: weights, eight caches, and
    # eight new K/V rows of 2*40*8*64*2 bytes
    assert (flops.decode_step_bytes(GRANITE, [1279] * 8)
            == 5_068_099_584 + 8 * 104_857_600 + 8 * 81_920)


def test_prefill_flops_at_1024():
    matmul = 2 * 40 * (2048 * 2048 * 2 + 2 * 2048 * 512 + 3 * 2048 * 8192)
    assert matmul == 4_865_392_640
    attention = 4 * 40 * 32 * 64 * (1024 * 1025 // 2)
    assert attention == 171_966_464_000
    head = 2 * 2048 * 49155
    assert (flops.prefill_flops(GRANITE, 1024)
            == matmul * 1024 + attention + head == 5_154_329_866_240)


def test_sync_bytes_each_chip_receives():
    tree = flops.weight_bytes(GRANITE)
    # reduce-scatter and all-gather each bring 3/4 of the tree to a chip
    assert flops.sync_recv_bytes(tree, 4) == 7_602_149_376


def test_flash_attention_cost():
    c = flops.flash_attention_cost(GRANITE, 8, 1024)
    assert c["flops"] == 4 * 8 * 32 * 64 * 524_800
    assert c["bytes"] == 8 * 1024 * 64 * (2 * 32 + 2 * 8) * 2
