"""``harness/sparse_gqa_moe_flops.py`` against hand counts and a count from
the mask itself, at the published sizes of ``keye-vl-2.0-30b-a3b``."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import sparse_gqa_moe_flops as F
from benchmarks.reference.sparse_gqa_moe_decoder import chosen_pairs

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmarks/configs/keye-vl-2.0-30b-a3b.json")) as _f:
    CFG = json.load(_f)
LAYERS = CFG["num_hidden_layers"]


def test_the_chosen_pairs_are_the_masks():
    for seq, topk in ((64, 8), (64, 64), (64, 100), (96, 1), (1024, 256)):
        t, s = np.arange(seq)[:, None], np.arange(seq)[None, :]
        rank_room = np.minimum(t + 1, topk)                     # keys a query keeps
        assert chosen_pairs(seq, topk) == int(rank_room.sum()), (seq, topk)
        assert chosen_pairs(seq, topk) <= int((s <= t).sum()) == F.causal_pairs(seq)
    assert chosen_pairs(16384, 2048) == 31_458_304
    assert F.causal_pairs(16384) == 134_225_920
    assert chosen_pairs(16384, 2048) / F.causal_pairs(16384) == pytest.approx(0.2344, abs=1e-4)


def test_the_parameters_are_the_deployments():
    w = F.matmul_weights(CFG)
    assert w["attention_per_layer"] == 2 * 2048 * 4096 + 2 * 2048 * 512 == 18_874_368
    assert w["indexer_per_layer"] == 2048 * (1024 + 64 + 16) == 2_260_992
    assert w["router_per_layer"] == 2048 * 128 == 262_144          # ALL 128 are scored
    assert w["expert"] == 3 * 2048 * 768 == 4_718_592
    assert w["experts_held_per_layer"] == 16 * 4_718_592 == 75_497_472
    layer = 18_874_368 + 2_260_992 + 262_144 + 75_497_472 + (2 * 2048 + 2 * 128 + 64)
    assert F.parameters(CFG) == LAYERS * layer + 2 * 18_992 * 2048 + 2048
    assert F.parameters(dict(CFG, num_hidden_layers=6)) == 659_189_632
    # the whole model: 48 layers of 128 experts and the whole vocabulary
    whole = dict(CFG, num_hidden_layers=48, num_experts=128, vocab_size=151_936, published={})
    assert 30.0e9 < F.parameters(whole) < 31.2e9                    # the published 30 B


def test_step_flops_count_the_chosen_pairs_and_the_scorer_s_causal_ones():
    batch, seq = 1, 16384
    w = F.matmul_weights(CFG)
    every_token = LAYERS * (18_874_368 + 2_260_992 + 262_144) + w["head"]
    rows = 118_000
    want = (
        6 * every_token * seq + 6 * 4_718_592 * rows * LAYERS
        + LAYERS * (12 * 31_458_304 * 128 * 32 + 6 * 134_225_920 * 16 * 64)
    )
    assert F.step_flops(CFG, batch, seq, rows=rows) == want
    # every causal key attended would be 4.27 times the attention's operations
    dense = dict(CFG, sa_config=dict(CFG["sa_config"], topk=seq))
    more = F.step_flops(dense, batch, seq, rows=rows) - want
    assert more == LAYERS * 12 * (134_225_920 - 31_458_304) * 128 * 32
    # the family's expected load before any check: half of the 16 held are positive
    assert F.held_rows(CFG, batch, seq) == seq * 8


def test_the_flash_need_is_the_chosen_pairs_and_every_operand_once():
    need = F.flash_needed(CFG, 1, 16384)
    assert need["flops"] == LAYERS * 14 * 31_458_304 * 128 * 32
    tile, row, selection = 16384 * 128 * 2, 16384 * 4, 16384 * 16384
    fwd = 32 * (2 * tile + row) + 4 * 2 * tile + selection
    dq = 32 * (3 * tile + 2 * row) + 4 * 2 * tile + selection
    dkv = 32 * (2 * tile + 2 * row) + 4 * 4 * tile + selection
    assert need["bytes"] == LAYERS * (fwd + dq + dkv)
    assert selection * 3 / (fwd + dq + dkv) > 0.4                 # the mask is the most of what moves


def test_the_experts_need_follows_the_held_rows():
    some, more = (F.experts_needed(CFG, 1, 16384, rows=rows) for rows in (65_536, 131_072))
    assert more["flops"] == 2 * some["flops"] == 9 * LAYERS * 2 * 131_072 * 2048 * 768
    assert some["bytes"] < more["bytes"] < 2 * some["bytes"]       # the weights move once either way
