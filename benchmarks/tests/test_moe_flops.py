"""The MoE FLOP and byte functions against hand counts for
``olmoe-1b-7b-0125`` (``harness/moe_flops.py``)."""

import json
import os

from benchmarks.harness import flops, moe_flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(**changes):
    with open(os.path.join(BENCH, "configs", "olmoe-1b-7b-0125.json")) as f:
        return dict(json.load(f), **changes)


def test_olmoe_weights_by_hand():
    cfg = config()
    w = moe_flops.matmul_weights(cfg)
    # q, k, v, o: 2048 x 2048 each (16 heads and 16 KV heads of 128)
    assert w["attn_per_layer"] == 4 * 2048 * 2048 == 16_777_216
    assert w["router_per_layer"] == 2048 * 64 == 131_072
    assert w["expert"] == 3 * 2048 * 1024 == 6_291_456
    assert w["experts_stored_per_layer"] == 64 * 6_291_456 == 402_653_184
    assert w["experts_active_per_layer"] == 8 * 6_291_456 == 50_331_648
    assert w["head"] == 2048 * 50304 == 103_022_592
    assert cfg["num_hidden_layers"] == 2
    assert w["active_total"] == 2 * (16_777_216 + 131_072 + 50_331_648) + 103_022_592 == 237_502_464
    assert w["stored_total"] == 2 * (16_777_216 + 131_072 + 402_653_184) + 103_022_592


def test_olmoe_parameters_by_hand():
    cfg = config()
    # a layer: matmuls + attn and mlp norms (2 x 2048) + q and k norms (2 x 2048)
    layer = 16_777_216 + 131_072 + 402_653_184 + 4 * 2048
    assert layer == 419_569_664
    assert moe_flops.parameters(cfg) == 2 * layer + 2 * 50304 * 2048 + 2048 == 1_045_186_560
    # at the published depth: OLMoE-1B-7B's 6.9 B stored, 1.3 B active a token
    full = config(num_hidden_layers=16)
    assert moe_flops.parameters(full) == 16 * layer + 206_045_184 + 2048 == 6_919_161_856
    active = moe_flops.matmul_weights(full)["active_total"] + 50304 * 2048
    assert 1.27e9 < active < 1.29e9


def test_step_flops_by_hand():
    cfg = config()
    batch, seq = 2, 4096
    attention_forward = batch * 2 * 16 * (2 * (2 * seq * seq * 128)) // 2
    by_hand = 6 * 237_502_464 * batch * seq + 3 * attention_forward
    assert moe_flops.step_flops(cfg, batch, seq) == by_hand == 12_498_354_831_360
    # by part, TFLOP: what PERF.md's prediction was built from
    tokens = batch * seq
    assert round(6 * 2 * 50_331_648 * tokens / 1e12, 2) == 4.95      # active experts
    assert round(6 * 103_022_592 * tokens / 1e12, 2) == 5.06         # head
    assert round(3 * attention_forward / 1e12, 2) == 0.82


def test_experts_needed_by_hand():
    cfg = config()
    needed = moe_flops.experts_needed(cfg, 2, 4096, itemsize=2)
    rows = 2 * 4096 * 8
    assert rows == 65_536
    # 2 layers x 3 matrices x 3 passes, each 2 x rows x 2048 x 1024
    assert needed["flops"] == 18 * 2 * rows * 2048 * 1024 == 4_947_802_324_992
    # the same operations as the model count gives the active experts
    assert needed["flops"] == 6 * 2 * 50_331_648 * 2 * 4096
    # a call moves its rows in (x 2048), its rows out (x 1024) and 64 experts' matrix
    per_call = (rows * 2048 + rows * 1024 + 64 * 2048 * 1024) * 2
    assert needed["bytes"] == 18 * per_call == 12_079_595_520
    peak = flops.peaks("TPU v5 lite")
    least = flops.roofline_seconds(needed["flops"], needed["bytes"], peak, 1)
    assert least["bound"] == "compute" and 0.0251 < least["seconds"] < 0.0252
