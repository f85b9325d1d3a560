"""Operations and bytes of the gated-convolution mixture-of-experts decoder
(family ``conv_moe_decoder``: LFM2's ``conv`` layers and grouped-query
attention in the file's ``layer_types``, leading dense layers, then
sigmoid-routed experts of which this chip HOLDS A BLOCK, under a tied
head), from shapes. ``harness/flops.py`` holds the conventions and the
flash kernels' count, ``harness/hybrid_moe_flops.py`` the held experts',
whose reasoning is followed here. What is new:

* A conv layer's matmul weights are ``W_in`` ``[hidden, 3 hidden]`` and
  ``W_out`` ``[hidden, hidden]``; its ``conv_L_cache``-tap filter is counted
  with them (2 operations a tap, channel and position forward, 4 backward),
  as the linear mixers' filters are. The two gates, ``B * u`` and ``C * z``,
  are elementwise and count nothing.
* The convolution KERNELS' need (``short_conv_needed``): memory-bound.
  Forward reads the gated input and writes ``z``; the backward reads the
  input and ``dz`` and writes ``dx`` (``dfilters`` is ``taps x hidden``
  float32: nothing): five ``[tokens, hidden]`` arrays moved once a layer.
  Full remat's second forward is the step's cost, not a need.
* The flash calls are counted over the attention layers alone, at the head
  size ``hidden_size / num_attention_heads`` (64), the causal half.
* The head is tied: its matmul ``[hidden, vocab]`` counts (6 a weight and
  token, as an untied head's), the table is stored ONCE.
* The experts: the router scores ALL ``published.num_experts``; the file's
  ``num_experts`` are held. Model FLOPs and the grouped matmuls' need count
  the (token, choice) pairs whose expert is held: at an EVEN routing
  ``tokens x k x held / router width`` (32,768 of 65,536 a layer at 16 of
  32), or the pairs a run counted where the caller has them.
"""

from __future__ import annotations

from benchmarks.harness import flops
from benchmarks.reference.conv_moe_decoder import layer_kinds, router_width


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_counts(cfg: dict) -> dict:
    """Layers by mixer and by MLP at the file's depth."""
    kinds = layer_kinds(cfg)
    dense = min(cfg["num_dense_layers"], cfg["num_hidden_layers"])
    return {
        "conv": kinds.count("conv"), "full": kinds.count("full_attention"),
        "dense": dense, "expert": cfg["num_hidden_layers"] - dense,
    }


def held_rows(cfg: dict, batch: int, seq: int) -> float:
    """(token, choice) pairs a layer's held experts get at an even routing."""
    return batch * seq * cfg["num_experts_per_tok"] * cfg["num_experts"] / router_width(cfg)


def matmul_weights(cfg: dict) -> dict:
    """Matmul weights (and filter taps) by part."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    conv = 3 * d * d + d * d + cfg["conv_L_cache"] * d
    q_out, kv_out = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    full = d * q_out + 2 * d * kv_out + q_out * d
    counts = layer_counts(cfg)
    expert = 3 * d * cfg["moe_intermediate_size"]
    return {
        "conv_mixer_per_layer": conv, "attention_mixer_per_layer": full,
        "mixers": counts["conv"] * conv + counts["full"] * full,
        "dense_mlp_per_layer": 3 * d * cfg["intermediate_size"],
        "router_per_layer": d * router_width(cfg),
        "expert": expert,
        "experts_held_per_layer": cfg["num_experts"] * expert,
        "head": d * cfg["vocab_size"],
    }


def parameters(cfg: dict) -> int:
    """Every stored parameter: the matmul weights and filters above with the
    HELD experts, the embedding table ONCE (the head is its transpose), per
    attention layer the two per-head norm weights, per expert layer the
    expert bias (one an expert the router scores), two block norms a layer,
    the final norm."""
    d = cfg["hidden_size"]
    w, counts = matmul_weights(cfg), layer_counts(cfg)
    return (
        w["mixers"]
        + counts["dense"] * w["dense_mlp_per_layer"]
        + counts["expert"] * (w["router_per_layer"] + w["experts_held_per_layer"] + router_width(cfg))
        + w["head"]
        + counts["full"] * 2 * head_dim(cfg)
        + cfg["num_hidden_layers"] * 2 * d
        + d
    )


def _attention_layers(cfg: dict) -> dict:
    """The attention layers as ``harness/flops.py`` wants them."""
    return dict(cfg, num_hidden_layers=layer_counts(cfg)["full"], head_dim=head_dim(cfg))


def causal_attention_flops(cfg: dict, batch: int, seq: int) -> dict:
    """Score / value matmuls of one step, the attention layers, causal."""
    return flops.causal_attention_flops(_attention_layers(cfg), batch, seq)


def step_flops(cfg: dict, batch: int, seq: int) -> int:
    """Model FLOPs of one training step: ``6 x weights x tokens`` for what
    every token runs (mixers, dense MLPs, routers, the tied head), ``6 x
    expert x held rows`` for the routed experts held here at an even
    routing, causal attention in the attention layers."""
    w, counts = matmul_weights(cfg), layer_counts(cfg)
    every_token = (
        w["mixers"] + counts["dense"] * w["dense_mlp_per_layer"]
        + counts["expert"] * w["router_per_layer"] + w["head"]
    )
    attention = causal_attention_flops(cfg, batch, seq)
    return int(
        6 * every_token * batch * seq
        + 6 * w["expert"] * held_rows(cfg, batch, seq) * counts["expert"]
        + attention["forward"] + attention["backward"]
    )


def flash_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the three flash calls of one step need, the attention layers
    alone (``flops.flash_needed``'s count at the head size of 64)."""
    return flops.flash_needed(_attention_layers(cfg), batch, seq, itemsize)


def short_conv_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the convolution kernels of one step need, all conv layers: the
    taps' multiply-adds forward (2 a tap) and backward (``dx`` and
    ``dfilters``: 4 a tap), and five ``[tokens, hidden]`` arrays moved once
    (input and output forward; input, ``dz`` and ``dx`` backward)."""
    cells = layer_counts(cfg)["conv"] * batch * seq * cfg["hidden_size"]
    return {"flops": 6 * cfg["conv_L_cache"] * cells, "bytes": 5 * cells * itemsize}


def experts_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2, rows=None) -> dict:
    """What the held experts' grouped matmuls of one step need, all expert
    layers: gate, up and down over ``rows`` (token, choice) pairs a layer
    (None: an even routing's), forward, input gradient and weight gradient,
    each operand and result moved once, the held experts' stack of one
    matrix among them."""
    d, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = held_rows(cfg, batch, seq) if rows is None else rows
    calls = 3 * 3 * layer_counts(cfg)["expert"]
    per_call_bytes = (rows * d + rows * m + cfg["num_experts"] * d * m) * itemsize
    return {"flops": int(calls * 2 * rows * d * m), "bytes": int(calls * per_call_bytes)}
