"""The FLOP and byte functions against hand counts for both configurations."""

import json
import os

import pytest

from benchmarks.harness import flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_mistral_7b_weights_by_hand():
    cfg = config("mistral-7b-v0.3")
    w = flops.dense_decoder_matmul_weights(cfg)
    # q and o: 4096 x 4096 each; k and v: 4096 x (8 x 128) each.
    assert w["attn_per_layer"] == 2 * 4096 * 4096 + 2 * 4096 * 1024 == 41_943_040
    assert w["mlp_per_layer"] == 3 * 4096 * 14336 == 176_160_768
    assert w["head"] == 4096 * 32768 == 134_217_728
    layers = cfg["num_hidden_layers"]
    assert w["total"] == layers * 218_103_808 + 134_217_728
    # the embedding table, two norm vectors a layer and the final norm on top
    assert flops.dense_decoder_parameters(cfg) == (
        w["total"] + 32768 * 4096 + layers * 2 * 4096 + 4096
    )
    # at the published depth this is Mistral-7B's 7.25 B parameters
    assert flops.dense_decoder_parameters(dict(cfg, num_hidden_layers=32)) == 7_248_023_552


def test_mistral_large_weights_by_hand():
    cfg = config("mistral-large-2407")
    w = flops.dense_decoder_matmul_weights(cfg)
    assert w["attn_per_layer"] == 2 * 12288 * 12288 + 2 * 12288 * 1024 == 327_155_712
    assert w["mlp_per_layer"] == 3 * 12288 * 28672 == 1_056_964_608
    assert w["head"] == 12288 * 32768 == 402_653_184
    assert cfg["num_hidden_layers"] == 2
    assert w["total"] == 2 * 1_384_120_320 + 402_653_184 == 3_170_893_824
    assert flops.dense_decoder_parameters(cfg) == 3_170_893_824 + 402_653_184 + 5 * 12288
    # at the published depth: Mistral Large 2's 123 B
    full = flops.dense_decoder_parameters(dict(cfg, num_hidden_layers=88))
    assert 122.5e9 < full < 122.7e9


@pytest.mark.parametrize(
    "name, batch, seq",
    [("mistral-7b-v0.3", 2, 4096), ("mistral-7b-v0.3", 1, 16384), ("mistral-large-2407", 4, 4096)],
)
def test_step_flops_by_hand(name, batch, seq):
    cfg = config(name)
    heads, hd, layers = cfg["num_attention_heads"], cfg["head_dim"], cfg["num_hidden_layers"]
    weights = flops.dense_decoder_matmul_weights(cfg)["total"]
    # forward: QK^T and PV are 2 s s d each over a full square; causal needs half.
    attention_forward = batch * layers * heads * (2 * (2 * seq * seq * hd)) // 2
    by_hand = 6 * weights * batch * seq + 3 * attention_forward
    assert flops.dense_decoder_step_flops(cfg, batch, seq) == by_hand
    a = flops.causal_attention_flops(cfg, batch, seq)
    assert a["forward"] == attention_forward and a["backward"] == 2 * attention_forward


def test_step_flops_values():
    # 6 x 570,425,344 x 8192 + 6 x 4096^2 x 128 x 32 heads x 2 layers x 2 sequences
    assert flops.dense_decoder_step_flops(config("mistral-7b-v0.3"), 2, 4096) == (
        28_037_546_508_288 + 1_649_267_441_664
    )
    assert flops.dense_decoder_step_flops(config("mistral-large-2407"), 4, 4096) == (
        6 * 3_170_893_824 * 16384 + 6 * 4096 * 4096 * 128 * 96 * 2 * 4
    )


def test_flash_needed_by_hand():
    cfg = config("mistral-7b-v0.3")
    seq, hd = 16384, 128
    calls = 32 * cfg["num_hidden_layers"] * 1          # one per head, layer, sequence
    n = flops.flash_needed(cfg, 1, seq)
    # forward 2 matmuls, backward 5 (scores rebuilt once), each 2 s s d, causal half
    assert n["flops"] == calls * 7 * (2 * seq * seq * hd) // 2
    tile, row = seq * hd * 2, seq * 4
    fwd = 3 * tile + tile + row                 # q k v -> o lse
    dq = 4 * tile + 2 * row + tile              # q k v do lse delta -> dq
    dkv = 4 * tile + 2 * row + 2 * tile         # q k v do lse delta -> dk dv
    assert n["bytes"] == calls * (fwd + dq + dkv)
    # far on the compute side of a v5e's ridge (197e12 / 819e9 = 240 FLOP/B)
    assert n["flops"] / n["bytes"] > 1000


def test_roofline_and_peaks():
    peak = flops.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    r = flops.roofline_seconds(197e12, 819e9 / 2, peak, 1)
    assert r["bound"] == "compute" and r["seconds"] == pytest.approx(1.0)
    r = flops.roofline_seconds(197e12, 4 * 819e9, peak, 4)
    assert r["bound"] == "memory" and r["seconds"] == pytest.approx(1.0)
    with pytest.raises(KeyError):
        flops.peaks("TPU v9")
    with pytest.raises(KeyError):
        flops.peaks("_source")
