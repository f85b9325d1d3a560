"""``harness/gated_window_moe_flops.py`` held to counts made by hand at tiny
sizes and to the program's own parameter count at the cell's."""

import json
import os

from benchmarks.harness import gated_window_moe_flops as F
from benchmarks.tests.test_discovery_gated_window_moe import TINY

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def cell_config():
    with open(os.path.join(ROOT, "benchmarks", "configs", "trinity-mini.json")) as f:
        return json.load(f)


def test_layers_and_weights_by_hand():
    assert F.layer_counts(TINY) == {"window": 4, "full": 1, "dense": 1, "expert": 4}
    w = F.matmul_weights(TINY)
    # q, gate, o: 48 x 64 each; k, v: 48 x 32 each
    assert w["attention_per_layer"] == 3 * 48 * 64 + 2 * 48 * 32
    assert w["dense_mlp"] == 3 * 48 * 96 and w["expert"] == 3 * 48 * 24
    assert w["router_per_layer"] == 48 * 8 and w["experts_held_per_layer"] == 4 * w["expert"]
    assert w["shared_per_layer"] == w["expert"] and w["head"] == 48 * 256
    per_layer = w["attention_per_layer"] + 4 * 48 + 2 * 16
    expert_layer = 48 * 8 + 8 + 4 * w["expert"] + w["expert"]
    assert F.parameters(TINY) == 5 * per_layer + w["dense_mlp"] + 4 * expert_layer + 2 * 48 * 256 + 48


def test_parameters_are_the_programs_at_the_cells_sizes():
    from benchmarks.families import gated_window_moe_decoder
    from ray_tpu.models import transformer as T

    config = cell_config()
    family = gated_window_moe_decoder.build(config, {"seq_len": 16384, "remat": "full"})
    assert F.parameters(config) == T.config_num_params(family.model) == 705_474_304
    tiny = gated_window_moe_decoder.build(TINY, {"seq_len": 96})
    assert F.parameters(TINY) == T.config_num_params(tiny.model)


def test_step_flops_count_the_band_the_gate_and_every_held_pair():
    config = cell_config()
    batch, seq = 1, 16384
    w, counts = F.matmul_weights(config), F.layer_counts(config)
    pairs = F.attention_pairs(config, batch, seq)
    band = 2048 * 2049 // 2 + (seq - 2048) * 2048
    assert pairs == {"full": 32 * seq * seq // 2, "window": 4 * 32 * band}
    # the window's band is 23.4 % of the causal half at 16k
    assert 0.23 < band / (seq * seq / 2) < 0.24
    every_token = 5 * w["attention_per_layer"] + w["dense_mlp"] + 4 * (
        w["router_per_layer"] + w["shared_per_layer"]) + w["head"]
    assert F.step_flops(config, batch, seq) == (
        6 * every_token * seq + 6 * w["expert"] * seq * 8 * 4 + 12 * sum(pairs.values()) * 128)
    assert F.held_rows(config, batch, seq) == 131072
    # the gate is a fourth attention matmul: a family without it counts 2048 x 4096 less a layer
    assert w["attention_per_layer"] == 3 * 2048 * 4096 + 2 * 2048 * 512


def test_kernel_needs():
    config = cell_config()
    batch, seq = 1, 16384
    flash, window = F.flash_needed(config, batch, seq), F.window_flash_needed(config, batch, seq)
    pairs = F.attention_pairs(config, batch, seq)
    assert window["flops"] == 14 * pairs["window"] * 128
    assert flash["flops"] == window["flops"] + 14 * pairs["full"] * 128
    # bytes: a window layer's operands are a global layer's, four layers to one
    assert window["bytes"] * 5 == flash["bytes"] * 4
    tile, row = seq * 128 * 2, seq * 4
    one_layer = (32 * (2 * tile + row) + 4 * 2 * tile) + (32 * (3 * tile + 2 * row) + 4 * 2 * tile) + (
        32 * (2 * tile + 2 * row) + 4 * 4 * tile)
    assert flash["bytes"] == 5 * one_layer
    experts = F.experts_needed(config, batch, seq)
    assert experts["flops"] == 9 * 4 * 2 * 131072 * 2048 * 1024
    assert F.experts_needed(config, batch, seq, rows=65536)["flops"] * 2 == experts["flops"]
    assert experts["bytes"] == 9 * 4 * (131072 * 2048 + 131072 * 1024 + 16 * 2048 * 1024) * 2
