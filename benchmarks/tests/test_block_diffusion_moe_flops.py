"""``harness/block_diffusion_moe_flops.py`` against hand counts and a count
from the mask itself, at the published sizes of ``sdar-30b-a3b-chat``."""

import json
import os

import jax.numpy as jnp
import numpy as np

from benchmarks.harness import block_diffusion_moe_flops as F
from benchmarks.harness import sparse_gqa_moe_flops as keye
from benchmarks.reference.block_diffusion_moe_decoder import visible

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmarks/configs/sdar-30b-a3b-chat.json")) as _f:
    CFG = json.load(_f)
LAYERS = CFG["num_hidden_layers"]


def test_the_allowed_pairs_are_the_mask_s():
    for seq, block in ((8, 2), (24, 4), (48, 16), (64, 64), (30, 5)):
        mask = np.asarray(visible(jnp.arange(2 * seq), seq, block))
        assert F.allowed_pairs(seq, block) == int(mask.sum()), (seq, block)
    assert F.allowed_pairs(8192, 4) == 67_141_632
    # a causal sequence of the same 16,384 rows has twice the pairs
    assert keye.causal_pairs(16384) == 134_225_920


def test_the_parameters_are_the_deployment_s():
    w = F.matmul_weights(CFG)
    assert w["attention_per_layer"] == 2 * 2048 * 4096 + 2 * 2048 * 512 == 18_874_368
    assert w["router_per_layer"] == 2048 * 128 == 262_144          # ALL 128 are scored
    assert w["expert"] == 3 * 2048 * 768 == 4_718_592
    assert w["experts_held_per_layer"] == 16 * 4_718_592 == 75_497_472
    layer = 18_874_368 + 262_144 + 75_497_472 + (2 * 2048 + 2 * 128)
    assert layer == 94_638_336
    assert F.parameters(CFG) == LAYERS * layer + 2 * 18_992 * 2048 + 2048
    assert F.parameters(dict(CFG, num_hidden_layers=6)) == 645_623_296
    # the whole model: 48 layers of 128 experts and the whole vocabulary
    whole = dict(CFG, num_hidden_layers=48, num_experts=128, vocab_size=151_936, published={})
    assert 30.0e9 < F.parameters(whole) < 31.0e9                    # the published 30 B


def test_step_flops_count_two_rows_a_trained_token_in_the_layers_and_one_in_the_head():
    batch, seq = 1, 8192
    w = F.matmul_weights(CFG)
    rows = 118_000
    want = (
        6 * LAYERS * (18_874_368 + 262_144) * 2 * seq        # every block over the 16,384 rows
        + 6 * w["head"] * seq                                # the head over the noised half alone
        + 6 * 4_718_592 * rows * LAYERS
        + LAYERS * 12 * 67_141_632 * 128 * 32
    )
    assert F.step_flops(CFG, batch, seq, rows=rows) == want
    # the family's expected load before any check: every pair of the 16,384 rows
    assert F.held_rows(CFG, batch, seq) == 2 * seq * 8 == 131_072
    assert F.step_flops(CFG, batch, seq) == want + 6 * 4_718_592 * (131_072 - rows) * LAYERS
    # Keye's 16,384 causal positions over the same backbone: the same rows through the blocks
    assert F.experts_needed(CFG, 1, 8192) == keye.experts_needed(
        dict(CFG, sa_config={"topk": 1}), 1, 16384)


def test_the_flash_need_is_over_the_allowed_pairs_and_grouped_query_bytes():
    need = F.flash_needed(CFG, 1, 8192)
    assert need["flops"] == 14 * 67_141_632 * 128 * 32 * LAYERS
    tile, row = 16384 * 128 * 2, 16384 * 4
    fwd = 32 * (2 * tile + row) + 4 * 2 * tile
    dq = 32 * (3 * tile + 2 * row) + 4 * 2 * tile
    dkv = 32 * (2 * tile + 2 * row) + 4 * 4 * tile
    assert need["bytes"] == (fwd + dq + dkv) * LAYERS
    # the executed tiles hold 1.25 times the allowed pairs: what the roofline share does not grant
    assert 80 * 1024 ** 2 / F.allowed_pairs(8192, 4) > 1.249
    assert F.flash_needed(CFG, 2, 8192)["flops"] == 2 * need["flops"]
