"""``harness/hybrid_moe_flops.py`` against hand counts at the published
widths of ``configs/ling-3.0-flash-vl.json``: the parameter count of ISSUE 36
to the unit, the held experts alone among the routed ones, the channel
decay's bytes in the delta rule's need."""

import os

from benchmarks.harness import hybrid_flops, hybrid_moe_flops as F, mla_moe_flops
from benchmarks.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CFG = Manifest(ROOT).config("ling-3.0-flash-vl")


def test_the_parameter_count_of_the_issue_to_the_unit():
    w = F.matmul_weights(CFG)
    linear = w["linear_mixer_per_layer"] + 32 + 4096 + 128       # A_log, dt_bias, o_norm
    latent = w["latent_mixer_per_layer"] + 512                   # the latent norm
    assert (linear, latent) == (63_049_888, 31_965_696)
    assert (w["expert"], w["shared_per_layer"], w["dense_mlp_per_layer"]) == (
        5_898_240, 5_898_240, 47_185_920,
    )
    assert w["router_per_layer"] + 512 == 1_311_232              # with the correction bias
    assert 2 * w["head"] == 100_597_760
    assert F.layer_counts(CFG) == {"linear": 6, "latent": 1, "dense": 1, "expert": 6}
    assert F.parameters(CFG) == 1_167_574_976
    # ISSUE 36's table: 8 held (64 chips a layer) and 32 (16 chips)
    assert F.parameters(dict(CFG, num_experts=8)) == 884_459_456
    assert F.parameters(dict(CFG, num_experts=32)) == 1_733_806_016
    # the whole model: 42 layers, 2 dense, every expert, the whole vocabulary
    whole = dict(
        CFG, num_hidden_layers=42, first_k_dense_replace=2, num_experts=512, vocab_size=157184,
        layer_offset=0,
    )
    assert F.layer_counts(whole) == {"linear": 35, "latent": 7, "dense": 2, "expert": 40}
    assert 124.3e9 < F.parameters(whole) < 124.5e9


def test_model_flops_count_the_held_pairs_and_what_every_token_runs():
    batch, seq = 1, 16384
    assert F.router_width(CFG) == 512 and F.held_rows(CFG, batch, seq) == 4096.0
    w = F.matmul_weights(CFG)
    every = (
        w["mixers"] + w["dense_mlp_per_layer"] + 6 * (w["router_per_layer"] + w["shared_per_layer"])
        + w["head"]
    )
    attention = 3 * seq * seq * (192 + 128) * 32
    recurrence = 18 * 128 * 128 * 32 * 6 * seq
    assert F.step_flops(CFG, batch, seq) == (
        6 * every * seq + 6 * w["expert"] * 4096 * 6 + attention + recurrence
    )
    assert 60e12 < F.step_flops(CFG, batch, seq) < 70e12


def test_kernel_needs():
    batch, seq = 1, 16384
    # flash: Moonlight's two-dim count over the ONE latent layer
    as_moonlight = dict(CFG, num_hidden_layers=1)
    assert F.flash_needed(CFG, batch, seq) == mla_moe_flops.flash_needed(as_moonlight, batch, seq)
    # the delta rule: the scalar rule's operations, and g as d_k float32 where it was one
    need = F.delta_rule_needed(CFG, batch, seq)
    cells = 32 * 6 * seq
    assert need == {"flops": 18 * 128 * 128 * cells, "bytes": 4364 * cells}
    scalar = hybrid_flops.delta_rule_needed(
        {"linear_key_head_dim": 128, "linear_value_head_dim": 128, "linear_num_value_heads": 32,
         "layer_types": ["linear_attention"] * 6, "num_hidden_layers": 6}, batch, seq,
    )
    assert need["flops"] == scalar["flops"]
    assert need["bytes"] - scalar["bytes"] == (12 * 128 - 12) * cells
    assert need["flops"] / need["bytes"] < 240                   # memory-bound on a v5e
    # the experts: an even routing's rows unless the run's own count is handed in
    even = F.experts_needed(CFG, batch, seq)
    assert even["flops"] == 54 * 2 * 4096 * 2560 * 768
    assert even["bytes"] == 54 * (4096 * 2560 + 4096 * 768 + 16 * 2560 * 768) * 2
    fewer = F.experts_needed(CFG, batch, seq, rows=1000.5)
    assert fewer["flops"] < even["flops"] and fewer["bytes"] > 54 * 16 * 2560 * 768 * 2
