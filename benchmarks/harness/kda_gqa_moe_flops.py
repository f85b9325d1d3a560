"""Operations and bytes of family ``kda_gqa_moe_decoder`` (channel-decay
delta-rule layers under an unbounded gate whose gates go through a rank,
element-gated grouped-query layers where ``gqa_layers`` says, sigmoid-routed
experts of which this chip HOLDS A BLOCK beside one shared expert in every
layer), from shapes. ``harness/flops.py`` holds the conventions and
``harness/hybrid_moe_flops.py`` the delta rule's and the held experts'
counts, whose reasoning is followed here. What is new:

* A linear layer's matmul weights are FOUR ``[hidden, heads x head_dim]``
  matrices (``W_q``, ``W_k``, ``W_v``, ``W_o``), the two gates' ``[hidden,
  rank]`` and ``[rank, heads x head_dim]`` pairs (``kda_use_full_proj``
  false; the rank is the linear heads' ``head_dim``) and ``W_b`` ``[hidden,
  heads]``; the three 4-tap filters are counted with them, as there.
* A grouped-query layer adds the element gate, a whole ``[hidden, heads x
  head_dim]`` matrix; its flash calls take K and V at ``num_key_value_heads``
  (the kernels' index maps pick a query head's group), so their BYTES count K,
  V and their gradients a KV head and everything else a query head.
* ``decay_prepare_needed``: the chunk preparation under a decay per channel
  with NO bound (``ops/gated_delta_rule.py``'s halving form) is bound by the
  MXU's float32 rate, so its need is the tiles its two kernels really
  multiply, each product of float32 operands six bfloat16 passes. Two chunks
  of 64 ride one 128-row product. A forward call multiplies, a product: a
  level's stacked ``[k; q] . rows`` ``[256, d_k]`` by ``k . columns`` ``[128,
  d_k]``, six levels; the inverse's five levels of two ``[128, 128]``
  products; ``W`` and ``U0``. A backward call: ``dT``'s two, ``T^T dW`` and
  ``T^T dU0``, ``dA``'s two, and a level's ``dX = dM C`` (``[256, 128]`` by
  ``[128, d_k]``) and ``dC = dM^T X`` (contraction 256). A layer and step
  run the forward kernel twice (the forward; the backward's own, which also
  hands over ``T``) and the backward once. The element work beside them
  (the ``exp`` of two ``[128, d_k]`` arrays a level, ``P``'s diagonal as a
  row sum) is the VPU's and EUP's, for which ``peaks.json`` has no rate: it
  is NOT counted, so the share reads low by what it takes.
"""

from __future__ import annotations

from benchmarks.harness import flops
from benchmarks.reference.kda_gqa_moe_decoder import held_block, layer_kinds, router_width

# ops/gated_delta_rule.py: tokens a chunk, rows a product, bf16 passes a float32 product
CHUNK, PRODUCT_ROWS, F32_PASSES = 64, 128, 6


def layer_counts(cfg: dict) -> dict:
    """Layers by mixer at the file's depth; every layer's MLP is an expert layer."""
    kinds = layer_kinds(cfg)
    return {
        "linear": kinds.count("linear_attention"), "full": kinds.count("full_attention"),
        "expert": cfg["num_hidden_layers"],
    }


def held_rows(cfg: dict, batch: int, seq: int) -> float:
    """(token, choice) pairs a layer's held experts get at an even routing."""
    return batch * seq * cfg["num_experts_per_tok"] * held_block(cfg)[1] / router_width(cfg)


def matmul_weights(cfg: dict) -> dict:
    """Matmul weights (and filter taps) by part."""
    d, linear = cfg["hidden_size"], cfg["linear_attn_config"]
    wide = linear["num_heads"] * linear["head_dim"]
    rank = linear["head_dim"]
    q_out = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_out = cfg["num_key_value_heads"] * cfg["head_dim"]
    gates = 2 * (d * rank + rank * wide)
    linear_mixer = (
        4 * d * wide + gates + d * linear["num_heads"]
        + linear["short_conv_kernel_size"] * 3 * wide
    )
    full_mixer = 3 * d * q_out + 2 * d * kv_out
    counts = layer_counts(cfg)
    expert = 3 * d * cfg["moe_intermediate_size"]
    return {
        "linear_mixer_per_layer": linear_mixer, "full_mixer_per_layer": full_mixer,
        "gates_per_linear_layer": gates,
        "mixers": counts["linear"] * linear_mixer + counts["full"] * full_mixer,
        "router_per_layer": d * router_width(cfg),
        "expert": expert,
        "shared_per_layer": cfg["n_shared_experts"] * expert,
        "experts_held_per_layer": held_block(cfg)[1] * expert,
        "head": d * cfg["vocab_size"],
    }


def parameters(cfg: dict) -> int:
    """Every stored parameter: the matmul weights and filters above with the
    HELD experts, the embedding table, per linear layer ``A_log`` (a head),
    ``dt_bias`` (a channel) and the gated norm's weight, per layer the
    correction bias (one an expert the router scores) and two block norms,
    the final norm."""
    d, linear = cfg["hidden_size"], cfg["linear_attn_config"]
    w, counts = matmul_weights(cfg), layer_counts(cfg)
    heads, head_dim = linear["num_heads"], linear["head_dim"]
    return (
        w["mixers"]
        + counts["expert"] * (
            w["router_per_layer"] + w["experts_held_per_layer"] + w["shared_per_layer"]
            + router_width(cfg) + 2 * d
        )
        + 2 * w["head"]
        + counts["linear"] * (heads + heads * head_dim + head_dim)
        + d
    )


def published(cfg: dict) -> dict:
    """The configuration with every cut taken back: what the source states."""
    return {**cfg, **cfg.get("published", {}), "published": {}}


def _full_as_dense(cfg: dict) -> dict:
    """The grouped-query layers as ``harness/flops.py`` wants them."""
    return dict(cfg, num_hidden_layers=layer_counts(cfg)["full"])


def delta_rule_flops(cfg: dict, batch: int, seq: int) -> dict:
    """The recurrence's own operations of one step, all linear layers."""
    linear = cfg["linear_attn_config"]
    per = linear["head_dim"] * linear["head_dim"]
    cells = linear["num_heads"] * layer_counts(cfg)["linear"] * batch * seq
    return {"forward": 6 * per * cells, "backward": 12 * per * cells}


def step_flops(cfg: dict, batch: int, seq: int) -> int:
    """Model FLOPs of one training step: ``6 x weights x tokens`` for what
    every token runs, ``6 x expert x held rows`` for the routed experts held
    here at an even routing, causal attention in the grouped-query layers,
    the recurrence in the linear ones."""
    w, counts = matmul_weights(cfg), layer_counts(cfg)
    every_token = (
        w["mixers"] + counts["expert"] * (w["router_per_layer"] + w["shared_per_layer"])
        + w["head"]
    )
    attention = flops.causal_attention_flops(_full_as_dense(cfg), batch, seq)
    recurrence = delta_rule_flops(cfg, batch, seq)
    return int(
        6 * every_token * batch * seq
        + 6 * w["expert"] * held_rows(cfg, batch, seq) * counts["expert"]
        + attention["forward"] + attention["backward"]
        + recurrence["forward"] + recurrence["backward"]
    )


def flash_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the three flash calls of one step need, the grouped-query layers
    alone: ``flops.flash_needed``'s operations (``7 s^2 head_dim`` a query
    head), and each operand and result moved once with K, V, dK and dV at
    ``num_key_value_heads``."""
    layers = layer_counts(cfg)["full"] * batch
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    tile = seq * cfg["head_dim"] * itemsize      # one [seq, head_dim] operand
    row = seq * 4                                # one float32 per query (lse, delta)
    by_query_head = (2 * tile + row) + (3 * tile + 2 * row) + (2 * tile + 2 * row)
    by_kv_head = 2 * tile + 2 * tile + 4 * tile  # k v | k v | k v dk dv
    return {
        "flops": flops.flash_needed(_full_as_dense(cfg), batch, seq, itemsize)["flops"],
        "bytes": layers * (heads * by_query_head + kv_heads * by_kv_head),
    }


def delta_rule_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the channel-decay delta rule of one step needs, all linear
    layers: ``hybrid_moe_flops.delta_rule_needed``'s count at this file's
    heads (the recurrence's operations; q, k, v, ``g`` (``d_k`` float32),
    ``beta``, ``o`` and their gradients moved once)."""
    linear = cfg["linear_attn_config"]
    d = linear["head_dim"]
    cells = linear["num_heads"] * layer_counts(cfg)["linear"] * batch * seq
    recurrence = delta_rule_flops(cfg, batch, seq)
    return {
        "flops": recurrence["forward"] + recurrence["backward"],
        "bytes": (11 * d * itemsize + 12 * d + 12) * cells,
    }


def decay_prepare_tiles(d_k: int, d_v: int) -> dict:
    """Multiply-adds x 2 of ONE product (``PRODUCT_ROWS`` tokens of a head) in
    the forward and in the backward preparation kernel, as the module
    docstring counts them, before the six passes."""
    rows = PRODUCT_ROWS
    levels = (CHUNK - 1).bit_length()
    square = 2 * rows * rows * rows
    forward = (
        levels * 2 * (2 * rows) * d_k * rows          # [k; q] . rows  x  (k . columns)^T
        + (levels - 1) * 2 * square                   # the inverse by doubling
        + 2 * rows * rows * (d_k + d_v)               # W, U0
    )
    backward = (
        2 * 2 * rows * rows * (d_k + d_v)             # dT's two; T^T dW, T^T dU0
        + 2 * square                                  # dA = -T^T dT T^T
        + levels * 2 * 2 * (2 * rows) * rows * d_k    # dX = dM C; dC = dM^T X
    )
    return {"forward": forward, "backward": backward}


def decay_prepare_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the channel-decay chunk preparation of one step needs, all
    linear layers: two forward calls and one backward a layer; ``flops`` are
    bfloat16 passes' (six a float32 product), ``bytes`` each operand and
    result of the three calls moved once (q, k, ``g``, the six chunk operands,
    ``T`` and every gradient float32; v and ``dv`` in the model's dtype)."""
    linear = cfg["linear_attn_config"]
    d = linear["head_dim"]
    cells = linear["num_heads"] * layer_counts(cfg)["linear"] * batch * seq
    tiles = decay_prepare_tiles(d, d)
    inputs = 3 * 4 * d + itemsize * d + 4                # q k g | v | beta
    operands = 4 * 4 * d + 4 * CHUNK + 4 * d / CHUNK     # w u0 qg kd | p | gamma
    inverse = 4 * PRODUCT_ROWS
    per_token = (
        (inputs + operands) + (inputs + operands + inverse)
        + (inputs + inverse + operands) + inputs
    )
    return {
        "flops": int(F32_PASSES * (2 * tiles["forward"] + tiles["backward"]) * cells / PRODUCT_ROWS),
        "bytes": int(per_token * cells),
    }


def experts_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2, rows=None,
                   with_rows=None) -> dict:
    """What the held experts' grouped matmuls of one step need, all layers:
    gate, up and down over ``rows`` (token, choice) pairs a layer (None: an
    even routing's), forward, input gradient and weight gradient, each
    operand and result moved once. The two calls that READ a matrix's stack
    read the experts that got rows (``with_rows`` of them a layer; None: every
    held one: at ~100 rows an expert the stack is most of the bytes, and a
    kernel reads no tile of an expert nobody chose); the weight gradient
    writes the whole held stack."""
    d, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = held_block(cfg)[1]
    rows = held_rows(cfg, batch, seq) if rows is None else rows
    with_rows = held if with_rows is None else with_rows
    matrices = 3 * layer_counts(cfg)["expert"]
    moved = lambda experts: (rows * d + rows * m + experts * d * m) * itemsize
    return {
        "flops": int(3 * matrices * 2 * rows * d * m),
        "bytes": int(matrices * (2 * moved(with_rows) + moved(held))),
    }
