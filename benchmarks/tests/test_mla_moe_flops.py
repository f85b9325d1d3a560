"""The latent-attention MoE FLOP and byte functions against hand counts
for ``moonlight-16b-a3b`` (``harness/mla_moe_flops.py``)."""

import json
import os

from benchmarks.harness import flops, mla_moe_flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(**changes):
    with open(os.path.join(BENCH, "configs", "moonlight-16b-a3b.json")) as f:
        return dict(json.load(f), **changes)


def test_moonlight_weights_by_hand():
    cfg = config()
    w = mla_moe_flops.matmul_weights(cfg)
    # W_q 2048 x 16 x 192, W_kv_a 2048 x (512 + 64), W_kv_b 512 x 16 x 256, W_o 2048 x 2048
    assert w["attn_per_layer"] == 6_291_456 + 1_179_648 + 2_097_152 + 4_194_304 == 13_762_560
    assert w["dense_mlp_per_layer"] == 3 * 2048 * 11264 == 69_206_016
    assert w["router_per_layer"] == 2048 * 64 == 131_072
    assert w["expert"] == 3 * 2048 * 1408 == 8_650_752
    assert w["shared_per_layer"] == 2 * 8_650_752 == 17_301_504
    assert w["experts_stored_per_layer"] == 64 * 8_650_752 == 553_648_128
    assert w["experts_active_per_layer"] == 6 * 8_650_752 == 51_904_512
    assert w["head"] == 2048 * 163840 == 335_544_320
    assert cfg["num_hidden_layers"] == 2 and cfg["first_k_dense_replace"] == 1
    dense = 13_762_560 + 69_206_016
    active = 13_762_560 + 131_072 + 17_301_504 + 51_904_512
    assert w["active_total"] == dense + active + 335_544_320 == 501_612_544
    assert w["stored_total"] == dense + (active - 51_904_512 + 553_648_128) + 335_544_320


def test_moonlight_parameters_by_hand():
    cfg = config()
    norms = 2 * 2048 + 512                        # attn and mlp norms, the latent norm
    dense = 13_762_560 + 69_206_016 + norms
    sparse = 13_762_560 + 131_072 + 17_301_504 + 553_648_128 + norms + 64   # + the correction bias
    assert dense == 82_973_184 and sparse == 584_847_936
    assert mla_moe_flops.parameters(cfg) == dense + sparse + 2 * 335_544_320 + 2048 == 1_338_911_808
    # at the published depth: Moonlight's 16 B stored, about 3 B active a token with the embedding
    full = config(num_hidden_layers=27)
    assert mla_moe_flops.parameters(full) == dense + 26 * sparse + 671_088_640 + 2048 == 15_960_110_208
    active = mla_moe_flops.matmul_weights(full)["active_total"] + 335_544_320
    assert 2.9e9 < active < 3.0e9


def test_step_flops_by_hand():
    cfg = config()
    batch, seq = 1, 8192
    # causal: half of QK^T over 192 dims and half of PV over 128, 16 heads, 2 layers
    attention_forward = 2 * 16 * seq * seq * (192 + 128)
    by_hand = 6 * 501_612_544 * seq + 3 * attention_forward
    assert mla_moe_flops.step_flops(cfg, batch, seq) == by_hand == 26_716_844_064_768
    # by part, TFLOP: what PERF.md's prediction was built from
    assert round(6 * 335_544_320 * seq / 1e12, 2) == 16.49          # head: 62 % of the step
    assert round(6 * 69_206_016 * seq / 1e12, 2) == 3.40            # the dense layer's MLP
    assert round(6 * 51_904_512 * seq / 1e12, 2) == 2.55            # six routed experts
    assert round(6 * 17_301_504 * seq / 1e12, 2) == 0.85            # the shared experts
    assert round(6 * 2 * 13_762_560 * seq / 1e12, 2) == 1.35        # both layers' projections
    assert round(3 * attention_forward / 1e12, 2) == 2.06


def test_flash_needed_by_hand():
    cfg = config()
    needed = mla_moe_flops.flash_needed(cfg, 1, 8192, itemsize=2)
    heads = 16 * 2
    # forward QK^T (192) and PV (128); backward scores again, dQ, dK (192), dV, dP (128); causal halves
    assert needed["flops"] == (4 * 192 + 3 * 128) * 8192 * 8192 * heads == 1152 * 8192 * 8192 * heads
    assert needed["flops"] == 2_473_901_162_496
    wide, narrow, row = 8192 * 192 * 2, 8192 * 128 * 2, 8192 * 4
    per_head = (2 * wide + 2 * narrow + row) + (3 * wide + 2 * narrow + 2 * row) + (3 * wide + 3 * narrow + 2 * row)
    assert needed["bytes"] == per_head * heads
    # with equal dims the count is the dense family's 7 s^2 d
    equal = dict(cfg, qk_nope_head_dim=64, qk_rope_head_dim=64, head_dim=128, num_key_value_heads=16)
    assert mla_moe_flops.flash_needed(equal, 1, 8192) == flops.flash_needed(equal, 1, 8192)
    least = flops.roofline_seconds(needed["flops"], needed["bytes"], flops.peaks("TPU v5 lite"), 1)
    assert least["bound"] == "compute" and 0.0125 < least["seconds"] < 0.0126


def test_experts_needed_by_hand():
    cfg = config()
    needed = mla_moe_flops.experts_needed(cfg, 1, 8192, itemsize=2)
    rows = 8192 * 6
    assert rows == 49_152
    # ONE expert layer x 3 matrices x 3 passes, each 2 x rows x 2048 x 1408
    assert needed["flops"] == 9 * 2 * rows * 2048 * 1408 == 2_551_210_573_824
    assert needed["flops"] == 6 * 51_904_512 * 8192          # what the model count gives six experts
    per_call = (rows * 2048 + rows * 1408 + 64 * 2048 * 1408) * 2
    assert needed["bytes"] == 9 * per_call
    least = flops.roofline_seconds(needed["flops"], needed["bytes"], flops.peaks("TPU v5 lite"), 1)
    assert least["bound"] == "compute" and 0.0129 < least["seconds"] < 0.0130
    # no expert layer at depth 1: nothing needed
    assert mla_moe_flops.experts_needed(config(num_hidden_layers=1), 1, 8192)["flops"] == 0
