"""Operations and bytes of the gated window / global mixture-of-experts
decoder (family ``gated_window_moe_decoder``: AFMoE's output-gated
grouped-query attention under a sliding window on the layers its
``layer_types`` call ``sliding_attention``, four norms a layer, leading dense
layers, then sigmoid-routed experts of which this chip HOLDS A BLOCK beside
one shared expert, an untied head), from shapes. ``harness/flops.py`` holds
the conventions and the flash kernels' count for a layer that sees the whole
context, ``harness/window_moe_flops.py`` the window's BAND (``band_pairs``,
used as it is) and the held experts' count, whose reasoning is followed
here. What is new:

* the output gate ``W_g`` ``[hidden, heads x head_dim]`` is a fourth matmul
  of the attention block that every token runs, beside q, k / v and o.
* the leading ``num_dense_layers`` layers carry a SwiGLU of
  ``intermediate_size``; every expert layer one shared SwiGLU of
  ``num_shared_experts x moe_intermediate_size`` that every token runs.
* K and V are moved at their own ``num_key_value_heads`` (the kernels take the
  grouped heads as they are: ``block_diffusion_moe_flops.flash_needed``'s
  byte count, at ``seq`` rows).
* the experts count the (token, choice) pairs whose expert is held: EVERY
  pair, ``tokens x k`` (131,072 a layer), since the family's weights put the
  absent experts' selection bias under every reachable held value
  (``families/gated_window_moe_decoder.py::init``), or the pairs a run
  counted where the caller has them.
* the bias rule and the norms are no matmuls: they are in the step's time and
  not in its model FLOPs.
"""

from __future__ import annotations

from benchmarks.harness.window_moe_flops import band_pairs
from benchmarks.reference.gated_window_moe_decoder import layer_kinds, router_width


def layer_counts(cfg: dict) -> dict:
    """Layers by attention and by MLP at the file's depth."""
    kinds = layer_kinds(cfg)
    window = kinds.count("sliding_attention")
    dense = cfg["num_dense_layers"]
    return {
        "window": window, "full": len(kinds) - window, "dense": dense,
        "expert": len(kinds) - dense,
    }


def held_rows(cfg: dict, batch: int, seq: int) -> int:
    """(token, choice) pairs a layer's held experts get: every pair."""
    return batch * seq * cfg["num_experts_per_tok"]


def matmul_weights(cfg: dict) -> dict:
    """Matmul weights by part."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q_out, kv_out = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    expert = 3 * d * cfg["moe_intermediate_size"]
    return {
        # q, the gate and o at the heads' width; k and v at the KV heads'
        "attention_per_layer": 3 * d * q_out + 2 * d * kv_out,
        "dense_mlp": 3 * d * cfg["intermediate_size"],
        "router_per_layer": d * router_width(cfg),
        "expert": expert,
        "shared_per_layer": cfg["num_shared_experts"] * expert,
        "experts_held_per_layer": cfg["num_experts"] * expert,
        "head": d * cfg["vocab_size"],
    }


def parameters(cfg: dict) -> int:
    """Every stored parameter: attention with its gate and two head norms and
    four block norms a layer; a dense layer's SwiGLU; an expert layer's
    router over ALL experts with its selection bias, the HELD experts and the
    shared one; the embedding table, the untied head and the final norm."""
    d, w, counts = cfg["hidden_size"], matmul_weights(cfg), layer_counts(cfg)
    every_layer = w["attention_per_layer"] + 4 * d + 2 * cfg["head_dim"]
    expert_layer = (
        w["router_per_layer"] + router_width(cfg) + w["experts_held_per_layer"]
        + w["shared_per_layer"]
    )
    return (
        cfg["num_hidden_layers"] * every_layer + counts["dense"] * w["dense_mlp"]
        + counts["expert"] * expert_layer + 2 * w["head"] + d
    )


def attention_pairs(cfg: dict, batch: int, seq: int) -> dict:
    """(query, key) pairs of one step by kind of layer, all heads: the causal
    half (``flops.py``'s ``seq^2 / 2``) a global layer and head, the band a
    window layer and head."""
    counts, heads = layer_counts(cfg), cfg["num_attention_heads"] * batch
    return {
        "full": counts["full"] * heads * seq * seq // 2,
        "window": counts["window"] * heads * band_pairs(seq, cfg["sliding_window"]),
    }


def step_flops(cfg: dict, batch: int, seq: int) -> int:
    """Model FLOPs of one training step: ``6 x weights x tokens`` for what
    every token runs (attention's projections and gate, the dense layers'
    SwiGLU, the routers, the shared experts, the head), ``6 x expert x held
    rows`` for the routed experts held here, ``12 x pairs x head_dim`` for
    attention: the causal half in the global layers, the BAND in the window
    layers."""
    w, counts = matmul_weights(cfg), layer_counts(cfg)
    every_token = (
        cfg["num_hidden_layers"] * w["attention_per_layer"] + counts["dense"] * w["dense_mlp"]
        + counts["expert"] * (w["router_per_layer"] + w["shared_per_layer"]) + w["head"]
    )
    pairs = attention_pairs(cfg, batch, seq)
    return int(
        6 * every_token * batch * seq
        + 6 * w["expert"] * held_rows(cfg, batch, seq) * counts["expert"]
        + 12 * (pairs["full"] + pairs["window"]) * cfg["head_dim"]
    )


def _flash_bytes(cfg: dict, layers: int, batch: int, seq: int, itemsize: int) -> int:
    """The three flash calls' bytes of ``layers`` layers: every operand and
    result a whole ``[seq, head_dim]`` array moved once, K and V at the KV
    heads (a window layer's are a global layer's)."""
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    tile = seq * cfg["head_dim"] * itemsize     # one [seq, head_dim] operand
    row = seq * 4                               # one float32 per query (lse, delta)
    fwd = heads * (2 * tile + row) + kv_heads * 2 * tile          # q -> o, lse; K V
    dq = heads * (3 * tile + 2 * row) + kv_heads * 2 * tile       # q dO -> dq
    dkv = heads * (2 * tile + 2 * row) + kv_heads * 4 * tile      # q dO; K V -> dK dV
    return (fwd + dq + dkv) * batch * layers


def window_flash_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the window layers' three flash calls of one step need: the
    BAND's operations, ``14 x band_pairs x head_dim`` a head and layer."""
    pairs = attention_pairs(cfg, batch, seq)["window"]
    return {
        "flops": 14 * pairs * cfg["head_dim"],
        "bytes": _flash_bytes(cfg, layer_counts(cfg)["window"], batch, seq, itemsize),
    }


def flash_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What every flash call of one step needs: the global layers' causal half
    (``14 x seq^2 / 2 x head_dim`` a head) and the window layers' band."""
    pairs = attention_pairs(cfg, batch, seq)["full"]
    band = window_flash_needed(cfg, batch, seq, itemsize)
    whole = _flash_bytes(cfg, layer_counts(cfg)["full"], batch, seq, itemsize)
    return {
        "flops": 14 * pairs * cfg["head_dim"] + band["flops"], "bytes": whole + band["bytes"],
    }


def experts_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2, rows=None) -> dict:
    """What the held experts' grouped matmuls of one step need, all expert
    layers (``window_moe_flops.experts_needed``'s count): gate, up and down
    over ``rows`` (token, choice) pairs a layer (None: every pair), forward,
    input gradient and weight gradient."""
    d, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = held_rows(cfg, batch, seq) if rows is None else rows
    calls = 3 * 3 * layer_counts(cfg)["expert"]
    per_call_bytes = (rows * d + rows * m + cfg["num_experts"] * d * m) * itemsize
    return {"flops": int(calls * 2 * rows * d * m), "bytes": int(calls * per_call_bytes)}
