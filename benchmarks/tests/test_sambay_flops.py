"""Hand counts of ``harness/sambay_flops.py`` at the cell's configuration."""

import json
import os

from benchmarks.harness import sambay_flops as F
from benchmarks.harness.window_moe_flops import band_pairs

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmarks", "configs", "phi-4-mini-flash-reasoning.json")) as f:
    CFG = json.load(f)
SEQ = 16384


def test_layer_sizes_are_the_issues():
    per = F.layer_parameters(CFG)
    assert per["mlp"] == 78_643_200 and per["norms"] == 10_240
    assert per["mamba"] == 41_241_600 and per["gmu"] == 26_214_400
    assert per["attention"] == 19_661_184 + 7_680 and per["cross"] == 13_107_584 + 5_120
    assert F.layer_counts(CFG) == {"mamba": 4, "window": 3, "full": 1, "gmu": 2, "cross": 2}
    assert F.layer_counts(F.published(CFG)) == {"mamba": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7}


def test_parameters_published_and_cut():
    # ISSUE 65's totals leave the attention projections' biases out ...
    assert F.parameters(F.published(CFG), biases=False) == 3_852_457_984
    assert F.parameters(CFG, biases=False) == 1_330_121_984
    # ... which the file states (attention_bias) and the program holds
    assert F.parameters(F.published(CFG)) == 3_852_457_984 + 9 * 7_680 + 7 * 5_120
    assert F.parameters(CFG) == 1_330_121_984 + 4 * 7_680 + 2 * 5_120 == 1_330_162_944


def test_parameters_are_the_programs():
    from benchmarks.families import sambay_decoder
    from benchmarks.harness.manifest import Manifest
    from ray_tpu.models import transformer as T

    family = sambay_decoder.build(CFG, Manifest(ROOT).traffic("seq16k-fixed"))
    assert T.config_num_params(family.model) == family.parameters() == F.parameters(CFG)


def test_step_flops_by_hand():
    d, inner, vocab = 2560, 5120, 25008
    mlp = 3 * d * 10240
    mamba = d * 2 * inner + inner * 192 + 160 * inner + inner * d + 4 * inner
    every_token = 12 * mlp + 4 * mamba + 4 * (2 * d * d + 2 * d * 1280) + 2 * 2 * d * d + 2 * 2 * d * inner + d * vocab
    causal = 3 * 20 * SEQ * SEQ // 2
    band = 3 * 20 * band_pairs(SEQ, 512)
    scan = 4 * SEQ * inner * ((7 * 16 + 3) + (15 * 16 + 4))
    assert F.step_flops(CFG, 1, SEQ) == 6 * every_token * SEQ + 2 * 18 * 64 * (causal + band) + scan
    # the head is about a twentieth of a step's matmul FLOP at an eighth of the vocabulary
    assert 0.04 < d * vocab / every_token < 0.06


def test_the_scans_need():
    needed = F.selective_scan_needed(CFG, 1, SEQ)
    cells = 4 * SEQ * 5120
    assert needed["flops"] == cells * (22 * 16 + 7)
    starts = 2 * 4 * (SEQ // 128) * 5120 * 16 * 4
    assert needed["bytes"] == 4 * SEQ * (5120 * (4 * 2 + 8) + 4 * 16 * 2) + starts
    # no array a token, channel and state is in the need: 5.4 GB a layer would be
    assert needed["bytes"] < 4 * SEQ * 5120 * 16 * 4
    assert F.scan_kept_bytes(CFG, 1, SEQ) == 4 * 5120 * (SEQ * 2 + (SEQ // 128) * 16 * 4)


def test_the_flash_calls_need():
    flash, window = F.flash_needed(CFG, 1, SEQ), F.window_flash_needed(CFG, 1, SEQ)
    assert window["flops"] == 2 * 20 * 64 * 3 * 20 * band_pairs(SEQ, 512)
    assert flash["flops"] == window["flops"] + 2 * 20 * 64 * 3 * 20 * SEQ * SEQ // 2
    # the window layers are half of the attention layers and 3 % of a full layer's pairs
    assert 0 < window["flops"] / flash["flops"] < 0.07
    assert flash["bytes"] == 2 * window["bytes"]
    conv = F.short_conv_needed(CFG, 1, SEQ)
    assert conv == {"flops": 6 * 5 * 4 * SEQ * 5120, "bytes": 5 * 4 * SEQ * 5120 * 2}
