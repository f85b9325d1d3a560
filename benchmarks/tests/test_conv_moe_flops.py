"""``harness/conv_moe_flops.py`` against hand counts at LFM2-8B-A1B's
published widths: the yardstick's arithmetic is tested without the program."""

import json
import os

from benchmarks.harness import conv_moe_flops as F

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmarks", "configs", "lfm2-8b-a1b.json")) as f:
    CFG = json.load(f)
TOKENS = 16384


def test_layers_and_parts_by_hand():
    assert F.layer_counts(CFG) == {"conv": 4, "full": 1, "dense": 1, "expert": 4}
    assert F.head_dim(CFG) == 64
    w = F.matmul_weights(CFG)
    assert w["conv_mixer_per_layer"] == 2048 * 6144 + 2048 * 2048 + 3 * 2048 == 16_783_360
    assert w["attention_mixer_per_layer"] == 2 * 2048 * 2048 + 2 * 2048 * 512 == 10_485_760
    assert w["expert"] == 3 * 2048 * 1792 == 11_010_048
    assert w["dense_mlp_per_layer"] == 3 * 2048 * 7168 == 44_040_192
    assert w["router_per_layer"] == 2048 * 32            # ALL the published experts are scored
    assert w["experts_held_per_layer"] == 16 * 11_010_048
    assert w["head"] == 2048 * 16384


def test_parameters_are_issue_39s_count_and_the_whole_model_is_8_3_b():
    # conv layers 4 x 16,783,360; the attention mixer + its two norm weights of 64;
    # the dense MLP; 4 x (router + bias + 16 experts); the tied table ONCE; norms
    by_hand = (
        4 * 16_783_360 + 10_485_760 + 128 + 44_040_192
        + 4 * (65_536 + 32 + 16 * 11_010_048) + 33_554_432 + 5 * 2 * 2048 + 2048
    )
    assert F.parameters(CFG) == by_hand == 860_141_824
    whole = dict(
        CFG, **CFG["published"], first_expert_held=0, published={}, layer_offset=0,
    )
    assert F.layer_counts(whole) == {"conv": 18, "full": 6, "dense": 2, "expert": 22}
    assert round(F.parameters(whole) / 1e9, 2) == 8.34   # the published 8.3 B: one table, not two


def test_step_flops_and_their_shares():
    w = F.matmul_weights(CFG)
    held = F.held_rows(CFG, 1, TOKENS)
    assert held == TOKENS * 4 * 16 / 32 == 32768         # half of a layer's 65,536 pairs
    attention = F.causal_attention_flops(CFG, 1, TOKENS)
    assert attention["forward"] == 2 * TOKENS * TOKENS * 64 * 32
    assert attention["backward"] == 2 * attention["forward"]
    every_token = w["mixers"] + w["dense_mlp_per_layer"] + 4 * w["router_per_layer"] + w["head"]
    total = F.step_flops(CFG, 1, TOKENS)
    assert total == (
        6 * every_token * TOKENS + 6 * w["expert"] * held * 4
        + attention["forward"] + attention["backward"]
    )
    assert 27.2e12 < total < 27.3e12                     # ISSUE 39: 27 TFLOP of model work
    share = lambda flops: round(100 * flops / total)
    assert share(6 * w["expert"] * held * 4) == 32       # the expert layers' matmuls
    assert share(6 * w["mixers"] * TOKENS) == 28         # the five mixers' projections
    assert share(6 * w["dense_mlp_per_layer"] * TOKENS) == 16
    assert share(attention["forward"] + attention["backward"]) == 12
    assert share(6 * w["head"] * TOKENS) == 12


def test_what_the_kernels_need():
    flash = F.flash_needed(CFG, 1, TOKENS)
    assert flash["flops"] == 7 * TOKENS * TOKENS * 64 * 32
    tile, row = TOKENS * 64 * 2, TOKENS * 4
    assert flash["bytes"] == 32 * ((4 + 5 + 6) * tile + 5 * row)
    conv = F.short_conv_needed(CFG, 1, TOKENS)
    cells = 4 * TOKENS * 2048
    assert conv == {"flops": 6 * 3 * cells, "bytes": 5 * cells * 2}
    # memory-bound by three orders: 1.64 ms of bytes a step at 819 GB/s
    assert conv["bytes"] / 819e9 > 100 * conv["flops"] / 197e12
    assert round(conv["bytes"] / 819e9 * 1e3, 2) == 1.64
    experts = F.experts_needed(CFG, 1, TOKENS)
    assert experts["flops"] == 36 * 2 * 32768 * 2048 * 1792
    assert experts["bytes"] == 36 * (32768 * 2048 + 32768 * 1792 + 16 * 2048 * 1792) * 2
    counted = F.experts_needed(CFG, 1, TOKENS, rows=30000.5)
    assert counted["flops"] == int(36 * 2 * 30000.5 * 2048 * 1792) < experts["flops"]
