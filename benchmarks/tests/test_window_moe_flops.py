"""``harness/window_moe_flops.py`` against hand counts and a count from the
mask itself, at the published sizes of ``smallthinker-21b-a3b``."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import flops
from benchmarks.harness import window_moe_flops as F

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmarks/configs/smallthinker-21b-a3b.json")) as _f:
    CFG = json.load(_f)


def test_the_bands_pairs_are_the_masks():
    for seq, window in ((64, 8), (64, 64), (64, 100), (96, 1), (1024, 256)):
        i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
        assert F.band_pairs(seq, window) == int(((j <= i) & (j > i - window)).sum()), (seq, window)
    assert F.band_pairs(16384, 4096) == 58_722_304                     # of the causal half's
    assert F.band_pairs(16384, 4096) / (16384 * 16384 // 2) == pytest.approx(0.4375, abs=2e-5)  # of 134,217,728
    assert F.band_pairs(4096, 4096) == 4096 * 4097 // 2                # at 4k the window is the context


def test_the_parameters_are_the_deployments():
    w = F.matmul_weights(CFG)
    assert w["attention_per_layer"] == 2 * 2560 * 3584 + 2 * 2560 * 512 == 20_971_520
    assert w["router_per_layer"] == 2560 * 64 == 163_840           # ALL 64 are scored
    assert w["expert"] == 3 * 2560 * 768 == 5_898_240
    assert w["experts_held_per_layer"] == 32 * 5_898_240            # 32 held
    assert F.layer_counts(CFG) == {"window": 3, "full": 1, "expert": 4}
    layer = 20_971_520 + 163_840 + 5_120 + 188_743_680
    assert F.parameters(CFG) == 4 * layer + 2 * 18_992 * 2560 + 2560 == 936_778_240
    # the whole model: 52 layers of 64 experts and the whole vocabulary
    whole = dict(CFG, num_hidden_layers=52, moe_num_primary_experts=64, vocab_size=151_936,
                 sliding_window_layout=[0, 1, 1, 1] * 13, rope_layout=[0, 1, 1, 1] * 13,
                 published={})
    assert 21.4e9 < F.parameters(whole) < 21.6e9                    # the published 21 B


def test_step_flops_count_the_band_for_a_window_layer():
    batch, seq = 1, 16384
    pairs = F.attention_pairs(CFG, batch, seq)
    assert pairs == {"full": 28 * 134_217_728, "window": 3 * 28 * 58_722_304}
    every_token = 4 * (20_971_520 + 163_840) + 18_992 * 2560
    held_rows = 16384 * 6              # every pair: the absent experts' router columns are zero
    assert F.held_rows(CFG, batch, seq) == held_rows == 98_304
    want = (
        6 * every_token * seq + 6 * 5_898_240 * held_rows * 4
        + 12 * (pairs["full"] + pairs["window"]) * 128
    )
    assert F.step_flops(CFG, batch, seq) == want
    # a window layer counted as a global one would overstate attention by 3 x (1 - 0.4375) layers
    causal = 12 * 4 * 28 * 134_217_728 * 128
    assert causal / (12 * (pairs["full"] + pairs["window"]) * 128) > 1.7


def test_the_flash_need_is_the_global_layers_half_and_the_window_layers_band():
    batch, seq = 1, 16384
    one_global = flops.flash_needed(dict(CFG, num_hidden_layers=1), batch, seq)
    band = F.window_flash_needed(CFG, batch, seq)
    assert band["flops"] == 3 * 28 * 14 * 58_722_304 * 128
    assert band["bytes"] == 3 * one_global["bytes"]                 # every operand moved once
    assert one_global["flops"] == 28 * 7 * seq * seq * 128 == 28 * 14 * 134_217_728 * 128
    both = F.flash_needed(CFG, batch, seq)
    assert both["flops"] == one_global["flops"] + band["flops"]
    assert both["bytes"] == 4 * one_global["bytes"]
    assert band["flops"] / (3 * one_global["flops"]) == pytest.approx(0.4375, abs=2e-5)
    # compute-bound on a v5e: the band's least time a layer is 15.0 ms of compute
    peak = flops.peaks("TPU v5 lite")
    least = flops.roofline_seconds(band["flops"], band["bytes"], peak, 1)
    assert least["bound"] == "compute" and 0.044 < least["seconds"] < 0.046


def test_the_experts_need_follows_the_held_rows():
    every = F.experts_needed(CFG, 1, 16384)
    assert every["flops"] == 36 * 2 * 98_304 * 2560 * 768
    counted = F.experts_needed(CFG, 1, 16384, rows=60_000)
    assert counted["flops"] == 36 * 2 * 60_000 * 2560 * 768 and counted["bytes"] < every["bytes"]
