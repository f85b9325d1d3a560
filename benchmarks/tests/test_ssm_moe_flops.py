"""``harness/ssm_moe_flops.py`` against counts by hand: one layer of each kind
of NVIDIA-Nemotron-3-Super-120B-A12B, its parameters whole and cut, the step's
operations, and what its four kernels are granted."""

import json
import os

from benchmarks.harness import flops, ssm_moe_flops as F

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmarks", "configs", "nemotron-3-super-120b-a12b.json")) as f:
    CFG = json.load(f)

# one layer of each kind, by hand
MAMBA = 4096 * (8192 + (8192 + 2 * 8 * 128) + 128) + 8192 * 4096 + 4 * 10240
MAMBA_OTHER = 10240 + 3 * 128 + 8192                    # conv bias; dt_bias, A_log, D; the gated norm
ATTENTION = 2 * 4096 * (32 * 128) + 2 * 4096 * (2 * 128)
ROUTER, LATENT, SHARED, EXPERT = 4096 * 512, 2 * 4096 * 1024, 2 * 4096 * 5376, 2 * 1024 * 2688
HEAD = 4096 * 16384


def test_one_layer_of_each_kind_and_the_parameters_whole_and_cut():
    w = F.matmul_weights(CFG)
    assert (w["mamba_per_layer"], w["attention_per_layer"]) == (MAMBA, ATTENTION) == (109_617_152, 35_651_584)
    assert (w["router_per_layer"], w["latent_per_layer"]) == (ROUTER, LATENT)
    assert (w["shared_per_layer"], w["expert"]) == (SHARED, EXPERT) == (44_040_192, 5_505_024)
    assert F.layer_counts(CFG) == {"mamba": 5, "attention": 1, "expert": 5}
    expert_layer = ROUTER + 512 + LATENT + SHARED + 16 * EXPERT
    cut = (
        5 * (MAMBA + MAMBA_OTHER) + ATTENTION + 5 * expert_layer + 11 * 4096 + 2 * HEAD + 4096
    )
    assert F.parameters(CFG) == cut == 1_431_132_544
    whole = F.published(CFG)
    assert F.layer_counts(whole) == {"mamba": 40, "attention": 8, "expert": 40}
    assert (whole["n_routed_experts"], whole["vocab_size"]) == (512, 131072)
    expert_layer = ROUTER + 512 + LATENT + SHARED + 512 * EXPERT
    assert F.parameters(whole) == (
        40 * (MAMBA + MAMBA_OTHER) + 8 * ATTENTION + 40 * expert_layer + 88 * 4096
        + 2 * 4096 * 131072 + 4096
    )
    assert round(F.parameters(whole) / 1e9) == 121           # "120B" without its MTP block


def test_step_flops_by_hand():
    batch, seq = 1, 8192
    assert F.held_rows(CFG, batch, seq) == 8192 * 22 * 16 / 512 == 5632
    every = 5 * MAMBA + ATTENTION + 5 * (ROUTER + LATENT + SHARED) + HEAD
    attention = 6 * seq * seq * 128 * 32                              # one layer, causal: 2 + 4
    # the scan, a token and layer: 128 heads' chunk part (L P forward, 2 L P back), the state's
    # write and read (4 P N, 8 P N) and step; 8 groups' C B^T and dB, dC (L N, 2 L N)
    step = 2 * 64 * 128 // 128
    forward = 128 * (128 * 64 + 4 * 64 * 128 + step) + 8 * 128 * 128
    backward = 128 * (2 * 128 * 64 + 8 * 64 * 128 + step) + 8 * 2 * 128 * 128
    assert F.ssd_flops(CFG, batch, seq) == {
        "forward": forward * 5 * seq, "backward": backward * 5 * seq,
    }
    want = 6 * every * seq + 6 * EXPERT * 5632 * 5 + attention + (forward + backward) * 5 * seq
    assert F.step_flops(CFG, batch, seq) == want
    # the new mechanisms are most of the step: Mamba-2 and expert layers
    mamba = (6 * MAMBA + forward + backward) * 5 * seq
    experts = 6 * (ROUTER + LATENT + SHARED) * 5 * seq + 6 * EXPERT * 5632 * 5
    assert 0.80 < (mamba + experts) / want < 0.90 and 0.5 < mamba / want < 0.6


def test_what_the_kernels_are_granted():
    batch, seq = 1, 8192
    flash = F.flash_needed(CFG, batch, seq)
    assert flash["flops"] == 7 * seq * seq * 128 * 32
    tile, row = seq * 128 * 2, seq * 4
    by_q = 32 * ((2 * tile + row) + (3 * tile + 2 * row) + (2 * tile + 2 * row))
    assert flash["bytes"] == by_q + 2 * 8 * tile                   # k v three times, dk dv once
    repeated = flops.flash_needed(dict(CFG, num_hidden_layers=1), batch, seq)
    assert flash["bytes"] < repeated["bytes"] and flash["flops"] == repeated["flops"]
    scan = F.ssd_needed(CFG, batch, seq)
    counted = F.ssd_flops(CFG, batch, seq)
    assert scan["flops"] == counted["forward"] + counted["backward"]
    # x, y, dy, dx in bf16; dt and its gradient float32; B, C, dB, dC at the 8 groups
    assert scan["bytes"] == (4 * 8192 * 2 + 2 * 4 * 128 + 4 * 8 * 128 * 2) * 5 * seq
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_seconds(scan["flops"], scan["bytes"], peaks, 1)["bound"] == "memory"
    conv = F.short_conv_needed(CFG, batch, seq)
    assert conv == {"flops": 6 * 5 * 10240 * 5 * seq, "bytes": 5 * 10240 * 5 * seq * 2}
    experts = F.experts_needed(CFG, batch, seq, rows=5000)
    assert experts["flops"] == 3 * 10 * 2 * 5000 * 1024 * 2688      # TWO matrices an expert
    assert experts["bytes"] == 30 * (5000 * 1024 + 5000 * 2688 + 16 * 1024 * 2688) * 2
    fewer = F.experts_needed(CFG, batch, seq, rows=5000, with_rows=12)
    assert experts["bytes"] - fewer["bytes"] == 20 * 4 * 1024 * 2688 * 2
