"""``harness/kda_gqa_moe_flops.py`` against counts by hand: the parameters
of Solar-Open2-250B whole and cut, the step's operations, and what its four
kernels are granted."""

import json
import os

from benchmarks.harness import flops, kda_gqa_moe_flops as F

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmarks", "configs", "solar-open2-250b.json")) as f:
    CFG = json.load(f)


def test_parameters_whole_and_cut():
    kda = 4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64 + 4 * 3 * 8192
    gqa = 3 * 4096 * 8192 + 2 * 4096 * 1024
    expert = 3 * 4096 * 1280
    w = F.matmul_weights(CFG)
    assert (w["linear_mixer_per_layer"], w["full_mixer_per_layer"]) == (kda, gqa) == (137_723_904, 109_051_904)
    assert (w["expert"], w["router_per_layer"], w["gates_per_linear_layer"]) == (expert, 4096 * 320, 3_145_728)
    per_layer = 4096 * 320 + 320 + 9 * expert + 2 * 4096            # router, bias, 8 + 1 experts, norms
    cut = (
        3 * (kda + 64 + 8192 + 128) + gqa + 4 * per_layer + 2 * 24576 * 4096 + 4096
    )
    assert F.parameters(CFG) == cut == 1_295_087_424
    whole = F.published(CFG)
    assert (whole["num_hidden_layers"], whole["n_routed_experts"], whole["vocab_size"]) == (48, 320, 196608)
    assert F.layer_counts(whole) == {"linear": 36, "full": 12, "expert": 48}
    per_layer = 4096 * 320 + 320 + 321 * expert + 2 * 4096
    assert F.parameters(whole) == (
        36 * (kda + 64 + 8192 + 128) + 12 * gqa + 48 * per_layer + 2 * 196608 * 4096 + 4096
    )
    assert round(F.parameters(whole) / 1e9, 1) == 250.3             # the published name
    assert F.layer_counts(CFG) == {"linear": 3, "full": 1, "expert": 4}


def test_step_flops_by_hand():
    batch, seq = 1, 4096
    w = F.matmul_weights(CFG)
    assert F.held_rows(CFG, batch, seq) == 4096 * 8 * 8 / 320 == 819.2
    every = w["mixers"] + 4 * (w["router_per_layer"] + w["shared_per_layer"]) + w["head"]
    attention = 6 * seq * seq * 128 * 64                            # one layer, causal: 2 + 4
    recurrence = 18 * 128 * 128 * 64 * 3 * seq
    want = 6 * every * seq + 6 * w["expert"] * 819.2 * 4 + attention + recurrence
    assert F.step_flops(CFG, batch, seq) == int(want)


def test_what_the_kernels_are_granted():
    batch, seq = 1, 4096
    flash = F.flash_needed(CFG, batch, seq)
    assert flash["flops"] == 7 * seq * seq * 128 * 64
    tile, row = seq * 128 * 2, seq * 4
    by_q = 64 * ((2 * tile + row) + (3 * tile + 2 * row) + (2 * tile + 2 * row))
    assert flash["bytes"] == by_q + 8 * 8 * tile                   # k v three times, dk dv once
    repeated = flops.flash_needed(dict(CFG, num_hidden_layers=1), batch, seq)
    assert flash["bytes"] < repeated["bytes"] and flash["flops"] == repeated["flops"]
    rule = F.delta_rule_needed(CFG, batch, seq)
    cells = 64 * 3 * seq
    assert rule == {"flops": 18 * 128 * 128 * cells, "bytes": (11 * 128 * 2 + 12 * 128 + 12) * cells}
    experts = F.experts_needed(CFG, batch, seq, rows=1000)
    assert experts["flops"] == 36 * 2 * 1000 * 4096 * 1280
    assert experts["bytes"] == 36 * (1000 * 4096 + 1000 * 1280 + 8 * 4096 * 1280) * 2
    # an expert nobody chose is not read by the forward and the input gradient
    # (24 of the 36 calls); the weight gradient writes all eight
    fewer = F.experts_needed(CFG, batch, seq, rows=1000, with_rows=6)
    assert experts["bytes"] - fewer["bytes"] == 24 * 2 * 4096 * 1280 * 2
    assert fewer["flops"] == experts["flops"]


def test_the_preparation_is_granted_the_tiles_it_multiplies():
    tiles = F.decay_prepare_tiles(128, 128)
    tile = 2 * 128 ** 3                                             # one [128, 128] x [128, 128]
    # forward: six levels of a 256-row left operand, the inverse's ten, W and U0
    assert tiles["forward"] == 6 * 2 * tile + 10 * tile + 2 * tile == 24 * tile
    # backward: dT 2, T^T dW and T^T dU0 2, dA 2, six levels of dX (2) and dC (2)
    assert tiles["backward"] == 6 * tile + 6 * 4 * tile == 30 * tile
    needed = F.decay_prepare_needed(CFG, 1, 4096)
    products = 64 * 3 * 4096 // 128
    assert needed["flops"] == 6 * (2 * 24 + 30) * tile * products
    # float32's six passes at the v5e's bf16 rate: 61 ms a step, above the bytes' 15 ms
    peak = flops.peaks("TPU v5 lite")
    least = flops.roofline_seconds(needed["flops"], needed["bytes"], peak, 1)
    assert least["bound"] == "compute" and 0.055 < least["seconds"] < 0.065
    assert 0.010 < least["memory_s"] < 0.020
