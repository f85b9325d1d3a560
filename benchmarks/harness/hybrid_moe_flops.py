"""Operations and bytes of the hybrid mixture-of-experts decoder (family
``hybrid_moe_decoder``: channel-decay delta-rule layers and gated latent
attention in the published ``layer_group_size`` pattern, a leading dense
layer, then group-routed experts of which this chip HOLDS A BLOCK), from
shapes. ``harness/flops.py`` holds the conventions, ``harness/hybrid_flops.
py`` the delta rule's count and ``harness/mla_moe_flops.py`` the latent
attention's and the experts', whose reasoning is followed here. What is new:

* A linear layer's matmul weights are SIX ``[hidden, heads x head_dim]``
  matrices (``W_q``, ``W_k``, ``W_v``, the decay's ``W_f``, the gate's
  ``W_g``, ``W_o``: ``no_kda_lora``) and ``W_b`` ``[hidden, heads]``; the
  three 4-tap filters are counted with them, as there.
* The recurrence's operations are the scalar rule's (``18 d_k d_v`` a head
  and position a step: the decay's multiplications ride on the write). Its
  BYTES are not: the log-decay ``g`` is ``d_k`` float32 a head and position
  where the scalar rule's is one, read forward, read backward, its gradient
  written: ``(6 d_k + 5 d_v) itemsize + 12 d_k + 12`` (``beta`` the 12). At
  128 / 128 in bfloat16 that is 4,364 bytes for 294,912 operations: 68
  operations a byte against the v5e's 240: MEMORY-bound, more so than the
  scalar rule (107).
* A latent layer adds the head-wise gate ``[hidden, heads]``; the flash
  calls are counted over the latent layers alone, at ``qk_nope + qk_rope``
  against ``v_head_dim``.
* The experts: the router scores ALL ``published.num_experts``; the file's
  ``num_experts`` are held. Parameters count the held experts. Model FLOPs
  and the grouped matmuls' need count the (token, choice) pairs whose
  expert is held: at an EVEN routing ``tokens x k x held / router width``
  (``held_rows`` below: 4,096 of 131,072 a layer at 16 of 512), or the
  pairs a run counted (the check's ``held_pairs``) where the caller has
  them. The shared expert runs every token.
"""

from __future__ import annotations

from benchmarks.harness import mla_moe_flops
from benchmarks.reference.hybrid_moe_decoder import layer_kinds


def layer_counts(cfg: dict) -> dict:
    """Layers by mixer and by MLP at the file's depth."""
    kinds = layer_kinds(cfg)
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return {
        "linear": kinds.count("linear_attention"), "latent": kinds.count("full_attention"),
        "dense": dense, "expert": cfg["num_hidden_layers"] - dense,
    }


def router_width(cfg: dict) -> int:
    return (cfg.get("published") or {}).get("num_experts", cfg["num_experts"])


def held_rows(cfg: dict, batch: int, seq: int) -> float:
    """(token, choice) pairs a layer's held experts get at an even routing."""
    return batch * seq * cfg["num_experts_per_tok"] * cfg["num_experts"] / router_width(cfg)


def matmul_weights(cfg: dict) -> dict:
    """Matmul weights (and filter taps) by part."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    wide = heads * cfg["head_dim"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    rank = cfg["kv_lora_rank"]
    linear = 6 * d * wide + d * heads + cfg["short_conv_kernel_size"] * 3 * wide
    latent = (
        d * heads * qk + d * (rank + cfg["qk_rope_head_dim"])
        + rank * heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
        + heads * cfg["v_head_dim"] * d + d * heads
    )
    counts = layer_counts(cfg)
    expert = 3 * d * cfg["moe_intermediate_size"]
    return {
        "linear_mixer_per_layer": linear, "latent_mixer_per_layer": latent,
        "mixers": counts["linear"] * linear + counts["latent"] * latent,
        "dense_mlp_per_layer": 3 * d * cfg["intermediate_size"],
        "router_per_layer": d * router_width(cfg),
        "expert": expert,
        "shared_per_layer": 3 * d * cfg["moe_shared_expert_intermediate_size"],
        "experts_held_per_layer": cfg["num_experts"] * expert,
        "head": d * cfg["vocab_size"],
    }


def parameters(cfg: dict) -> int:
    """Every stored parameter: the matmul weights and filters above with
    the HELD experts, the embedding table, per linear layer ``A_log`` (a
    head), ``dt_bias`` (a channel) and the gated norm's weight, per latent
    layer the latent norm, per expert layer the correction bias (one an
    expert the router scores), two block norms a layer, the final norm."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    w, counts = matmul_weights(cfg), layer_counts(cfg)
    return (
        w["mixers"]
        + counts["dense"] * w["dense_mlp_per_layer"]
        + counts["expert"] * (
            w["router_per_layer"] + w["experts_held_per_layer"] + w["shared_per_layer"]
            + router_width(cfg)
        )
        + 2 * w["head"]
        + counts["linear"] * (heads + heads * cfg["head_dim"] + cfg["head_dim"])
        + counts["latent"] * cfg["kv_lora_rank"]
        + cfg["num_hidden_layers"] * 2 * d
        + d
    )


def _latent_as_mla(cfg: dict) -> dict:
    """The latent layers as ``harness/mla_moe_flops.py`` wants them."""
    return dict(cfg, num_hidden_layers=layer_counts(cfg)["latent"])


def causal_attention_flops(cfg: dict, batch: int, seq: int) -> dict:
    """Score / value matmuls of one step, the latent layers, causal."""
    return mla_moe_flops.causal_attention_flops(_latent_as_mla(cfg), batch, seq)


def delta_rule_flops(cfg: dict, batch: int, seq: int) -> dict:
    """The recurrence's own operations of one step, all linear layers."""
    per = cfg["head_dim"] * cfg["head_dim"]
    cells = cfg["num_attention_heads"] * layer_counts(cfg)["linear"] * batch * seq
    return {"forward": 6 * per * cells, "backward": 12 * per * cells}


def step_flops(cfg: dict, batch: int, seq: int) -> int:
    """Model FLOPs of one training step: ``6 x weights x tokens`` for what
    every token runs, ``6 x expert x held rows`` for the routed experts held
    here at an even routing, causal attention in the latent layers, the
    recurrence in the linear ones."""
    w, counts = matmul_weights(cfg), layer_counts(cfg)
    every_token = (
        w["mixers"] + counts["dense"] * w["dense_mlp_per_layer"]
        + counts["expert"] * (w["router_per_layer"] + w["shared_per_layer"]) + w["head"]
    )
    attention = causal_attention_flops(cfg, batch, seq)
    recurrence = delta_rule_flops(cfg, batch, seq)
    return int(
        6 * every_token * batch * seq
        + 6 * w["expert"] * held_rows(cfg, batch, seq) * counts["expert"]
        + attention["forward"] + attention["backward"]
        + recurrence["forward"] + recurrence["backward"]
    )


def flash_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the three flash calls of one step need, the latent layers alone
    (``mla_moe_flops.flash_needed``'s count: ``(4 qk + 3 v) s^2`` a head)."""
    return mla_moe_flops.flash_needed(_latent_as_mla(cfg), batch, seq, itemsize)


def delta_rule_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the channel-decay delta rule of one step needs, all linear
    layers: the recurrence's operations and each of q, k, v, ``g`` (``d_k``
    float32), ``beta``, ``o`` and their gradients moved once."""
    d = cfg["head_dim"]
    cells = cfg["num_attention_heads"] * layer_counts(cfg)["linear"] * batch * seq
    recurrence = delta_rule_flops(cfg, batch, seq)
    return {
        "flops": recurrence["forward"] + recurrence["backward"],
        "bytes": (11 * d * itemsize + 12 * d + 12) * cells,
    }


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
