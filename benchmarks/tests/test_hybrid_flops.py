"""``harness/hybrid_flops.py`` against hand counts at Olmo-Hybrid-7B's
published widths, one period (three linear layers, one full) and an eighth
of the vocabulary, 1 x 16,384 tokens."""

import json
import os

import pytest

from benchmarks.harness import flops, hybrid_flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmarks", "configs", "olmo-hybrid-7b.json")) as f:
    CFG = json.load(f)
SEQ = 16384
PEAK = flops.peaks("TPU v5 lite")


def test_layer_counts_follow_the_published_pattern():
    assert hybrid_flops.layer_counts(CFG) == {"linear_attention": 3, "full_attention": 1}
    assert hybrid_flops.layer_counts(dict(CFG, num_hidden_layers=32)) == {
        "linear_attention": 24, "full_attention": 8,
    }


def test_parameters_by_hand():
    d, inter = 3840, 11008
    mixer = (
        2 * d * 2880 + 3 * d * 5760        # q k; v, gate, o
        + 2 * d * 30                       # the two gate projections
        + 4 * (2880 + 2880 + 5760)         # conv filters
        + 30 + 30 + 192                    # A_log, dt_bias, the gated norm
    )
    assert mixer == 88_750_332
    linear_layer = mixer + 3 * d * inter + 2 * d
    full_layer = 4 * d * d + 2 * d + 3 * d * inter + 2 * d
    assert (linear_layer, full_layer) == (215_570_172, 185_809_920)
    total = 3 * linear_layer + full_layer + 2 * 12544 * d + d
    assert hybrid_flops.parameters(CFG) == total == 928_862_196
    # the whole model: 8 periods and the published vocabulary
    whole = hybrid_flops.parameters(dict(CFG, num_hidden_layers=32, vocab_size=100352))
    assert whole == 8 * (3 * linear_layer + full_layer) + 2 * 100352 * d + d == 7_430_870_688


def test_step_flops_by_hand():
    d, inter = 3840, 11008
    weights = hybrid_flops.matmul_weights(CFG)
    assert weights["linear_mixer_per_layer"] == 88_750_332 - 30 - 30 - 192
    assert weights["full_mixer_per_layer"] == 4 * d * d
    assert weights["head"] == d * 12544
    matmuls = 6 * weights["total"] * SEQ
    attention = 6 * SEQ * SEQ * 128 * 30          # causal, one full layer: 2 forward + 4 backward
    recurrence = 18 * 96 * 192 * 30 * 3 * SEQ     # 6 d_k d_v forward, twice that backward
    assert hybrid_flops.step_flops(CFG, 1, SEQ) == matmuls + attention + recurrence
    assert hybrid_flops.step_flops(CFG, 1, SEQ) == 93_245_417_717_760
    # the head's share of the model FLOPs per token, cut and whole: the deployment's claim
    head = lambda cfg: 6 * hybrid_flops.matmul_weights(cfg)["head"] * SEQ / hybrid_flops.step_flops(cfg, 1, SEQ)
    assert head(CFG) == pytest.approx(0.0508, abs=5e-4)
    assert head(dict(CFG, num_hidden_layers=32, vocab_size=100352)) == pytest.approx(0.0508, abs=5e-4)


def test_the_delta_rules_need_is_bound_by_memory():
    needed = hybrid_flops.delta_rule_needed(CFG, 1, SEQ, itemsize=2)
    cells = 30 * 3 * SEQ
    assert needed["flops"] == 18 * 96 * 192 * cells == 489_223_618_560
    # q k (96) and v o (192) forward; q k v dO read, dq dk dv written backward; two float32 gates each way thrice
    assert needed["bytes"] == ((6 * 96 + 5 * 192) * 2 + 24) * cells == 3096 * cells
    least = flops.roofline_seconds(needed["flops"], needed["bytes"], PEAK, 1)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(5.574e-3, rel=1e-3)
    # a layer's forward alone: ISSUE 32's "about 0.7 ms a layer forward by its bytes"
    forward_bytes = ((2 * 96 + 2 * 192) * 2 + 8) * 30 * SEQ
    assert forward_bytes / PEAK["hbm_bytes_per_s"] == pytest.approx(0.70e-3, rel=2e-2)


def test_flash_needs_the_full_layer_alone():
    needed = hybrid_flops.flash_needed(CFG, 1, SEQ, itemsize=2)
    as_dense = {"num_attention_heads": 30, "num_hidden_layers": 1, "head_dim": 128}
    assert needed == flops.flash_needed(as_dense, 1, SEQ, 2)
    assert needed["flops"] == 7 * SEQ * SEQ * 128 * 30
    least = flops.roofline_seconds(needed["flops"], needed["bytes"], PEAK, 1)
    assert least["bound"] == "compute" and least["seconds"] == pytest.approx(36.6e-3, rel=1e-2)
