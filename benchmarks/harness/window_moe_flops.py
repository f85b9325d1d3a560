"""Operations and bytes of the window-attention mixture-of-experts decoder
(family ``window_moe_decoder``: SmallThinker's grouped-query attention at a
stated ``head_dim``, under a sliding window on the layers its
``sliding_window_layout`` marks, over softmax-routed experts of which this
chip HOLDS A BLOCK, every layer an expert layer, an untied head), from
shapes. ``harness/flops.py`` holds the conventions and the flash kernels'
count for a layer that sees the whole context, ``harness/conv_moe_flops.py``
the held experts', whose reasoning is followed here. What is new:

* q is ``num_attention_heads x head_dim`` wide (3584), not the stream's
  width: ``W_q`` ``[hidden, 3584]``, ``W_o`` ``[3584, hidden]``.
* A WINDOW layer's attention needs the BAND and not the causal half: query i
  sees ``min(i + 1, window)`` keys, ``band_pairs`` in all (58,722,304 at
  16,384 positions under a window of 4096, where the causal half of
  ``flops.py``'s convention is ``seq^2 / 2`` = 134,217,728: 43.75 %). Model
  FLOPs take ``12 x pairs x head_dim`` a head (forward two matmuls over the
  pairs, backward four), the flash kernels' need ``14 x pairs x head_dim``
  (``flops.flash_needed``'s ``7 x seq x seq x head_dim`` with the band's
  pairs in place of the causal half's). Counting the causal half for a
  window layer would overstate ``step_mfu_pct`` and ``flash_roofline_pct``
  by a third. The needed BYTES are a global layer's: every operand and
  result is a whole ``[seq, head_dim]`` array and is moved once.
* The router ``[hidden, router width]`` is a matmul every token runs and
  counts; the experts count the (token, choice) pairs whose expert is held:
  EVERY pair, ``tokens x k`` (98,304 a layer), since the family's weights
  send this chip's tokens to the experts it holds alone (the absent
  experts' router columns are zero: ``families/window_moe_decoder.py::init``),
  or the pairs a run counted where the caller has them.
"""

from __future__ import annotations

from benchmarks.harness import flops
from benchmarks.reference.window_moe_decoder import layouts, router_width


def layer_counts(cfg: dict) -> dict:
    """Layers by attention at the file's depth; every one is an expert layer."""
    layout = layouts(cfg)
    return {"window": sum(layout), "full": len(layout) - sum(layout), "expert": len(layout)}


def band_pairs(seq: int, window: int) -> int:
    """(query, key) pairs one head's window layer computes: query i sees
    ``min(i + 1, window)`` keys."""
    window = min(window, seq)
    return window * (window + 1) // 2 + (seq - window) * window


def held_rows(cfg: dict, batch: int, seq: int) -> int:
    """(token, choice) pairs a layer's held experts get: every pair (the
    family's router columns of the absent experts are zero)."""
    return batch * seq * cfg["moe_num_active_primary_experts"]


def matmul_weights(cfg: dict) -> dict:
    """Matmul weights by part."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q_out, kv_out = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    expert = 3 * d * cfg["moe_ffn_hidden_size"]
    return {
        "attention_per_layer": d * q_out + 2 * d * kv_out + q_out * d,
        "router_per_layer": d * router_width(cfg),
        "expert": expert,
        "experts_held_per_layer": cfg["moe_num_primary_experts"] * expert,
        "head": d * cfg["vocab_size"],
    }


def parameters(cfg: dict) -> int:
    """Every stored parameter: attention, the router over ALL experts, the
    HELD experts and two block norms a layer, the embedding table, the untied
    head and the final norm."""
    d, w = cfg["hidden_size"], matmul_weights(cfg)
    layer = w["attention_per_layer"] + w["router_per_layer"] + w["experts_held_per_layer"] + 2 * d
    return cfg["num_hidden_layers"] * layer + 2 * w["head"] + d


def attention_pairs(cfg: dict, batch: int, seq: int) -> dict:
    """(query, key) pairs of one step by kind of layer, all heads: the causal
    half (``flops.py``'s ``seq^2 / 2``) a global layer and head, the band a
    window layer and head."""
    counts, heads = layer_counts(cfg), cfg["num_attention_heads"] * batch
    return {
        "full": counts["full"] * heads * seq * seq // 2,
        "window": counts["window"] * heads * band_pairs(seq, cfg["sliding_window_size"]),
    }


def step_flops(cfg: dict, batch: int, seq: int) -> int:
    """Model FLOPs of one training step: ``6 x weights x tokens`` for what
    every token runs (attention's projections, the routers, the head), ``6 x
    expert x held rows`` for the routed experts held here, which get every pair,
    ``12 x pairs x head_dim`` for attention: the causal half in the global
    layers, the BAND in the window layers."""
    w, counts = matmul_weights(cfg), layer_counts(cfg)
    every_token = counts["expert"] * (w["attention_per_layer"] + w["router_per_layer"]) + w["head"]
    pairs = attention_pairs(cfg, batch, seq)
    return int(
        6 * every_token * batch * seq
        + 6 * w["expert"] * held_rows(cfg, batch, seq) * counts["expert"]
        + 12 * (pairs["full"] + pairs["window"]) * cfg["head_dim"]
    )


def window_flash_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the window layers' three flash calls of one step need: the
    BAND's operations, ``14 x band_pairs x head_dim`` a head and layer, and a
    global layer's bytes (each whole operand and result moved once)."""
    layers = dict(cfg, num_hidden_layers=layer_counts(cfg)["window"])
    pairs = attention_pairs(cfg, batch, seq)["window"]
    return {
        "flops": 14 * pairs * cfg["head_dim"],
        "bytes": flops.flash_needed(layers, batch, seq, itemsize)["bytes"],
    }


def flash_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What every flash call of one step needs: the global layers' causal
    half (``flops.flash_needed``) and the window layers' band."""
    layers = dict(cfg, num_hidden_layers=layer_counts(cfg)["full"])
    whole = flops.flash_needed(layers, batch, seq, itemsize)
    band = window_flash_needed(cfg, batch, seq, itemsize)
    return {"flops": whole["flops"] + band["flops"], "bytes": whole["bytes"] + band["bytes"]}


def experts_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2, rows=None) -> dict:
    """What the held experts' grouped matmuls of one step need, all layers:
    gate, up and down over ``rows`` (token, choice) pairs a layer (None:
    every pair), forward, input gradient and weight gradient, each
    operand and result moved once, the held experts' stack of one matrix
    among them."""
    d, m = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    rows = held_rows(cfg, batch, seq) if rows is None else rows
    calls = 3 * 3 * layer_counts(cfg)["expert"]
    per_call_bytes = (rows * d + rows * m + cfg["moe_num_primary_experts"] * d * m) * itemsize
    return {"flops": int(calls * 2 * rows * d * m), "bytes": int(calls * per_call_bytes)}
