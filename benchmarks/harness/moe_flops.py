"""Operations and bytes of the mixture-of-experts decoder, from shapes
(family ``moe_decoder``; ``harness/flops.py`` holds the conventions and
the dense block's counts, and is used here as it is).

What is new against the dense count:

* A token runs ``num_experts_per_tok`` of the ``num_experts`` experts:
  model FLOPs count the ACTIVE experts' matmul weights, the parameter
  count every stored expert.
* The router ``[hidden, num_experts]`` is a matmul and counts; the q/k
  norm vectors are not and count only as parameters.
* The expert matmuls' NEEDED operations are those of a grouped matmul
  that computes each (token, choice) row against its own expert only:
  ``tokens x k`` rows, three matrices of ``2 x hidden x expert_width``
  each, forward and the two backward matmuls (three passes). Needed bytes
  are each operand and result of the nine calls moved once: the rows in
  and out and the whole stack of one matrix's experts.
* ``balancing_loss`` is the Hugging Face ``load_balancing_loss_func`` in
  numpy, for tests and readers that hold the counts.
"""

from __future__ import annotations

from benchmarks.harness import flops


def matmul_weights(cfg: dict) -> dict:
    """Matmul weights by part; ``experts_active`` is what one token runs."""
    d = cfg["hidden_size"]
    hd = cfg["head_dim"]
    q_out = cfg["num_attention_heads"] * hd
    kv_out = cfg["num_key_value_heads"] * hd
    attn = d * q_out + 2 * d * kv_out + q_out * d
    router = d * cfg["num_experts"]
    expert = 3 * d * cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    head = d * cfg["vocab_size"]
    active = attn + router + cfg["num_experts_per_tok"] * expert
    return {
        "attn_per_layer": attn,
        "router_per_layer": router,
        "expert": expert,
        "experts_stored_per_layer": cfg["num_experts"] * expert,
        "experts_active_per_layer": cfg["num_experts_per_tok"] * expert,
        "head": head,
        "active_total": layers * active + head,
        "stored_total": layers * (attn + router + cfg["num_experts"] * expert) + head,
    }


def parameters(cfg: dict) -> int:
    """Every stored parameter: matmul weights (all experts), the embedding
    table, two norm vectors and the q/k norm vectors a layer, the final norm."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    norms = 2 * d + (cfg["num_attention_heads"] + cfg["num_key_value_heads"]) * hd
    return (
        matmul_weights(cfg)["stored_total"]
        + cfg["vocab_size"] * d
        + cfg["num_hidden_layers"] * norms
        + d
    )


def step_flops(cfg: dict, batch: int, seq: int) -> int:
    """Model FLOPs of one training step: ``6 x active matmul weights x
    tokens`` plus causal attention. The balancing loss is a few reductions
    over ``[tokens, experts]`` and counts nothing."""
    attention = flops.causal_attention_flops(cfg, batch, seq)
    return (
        6 * matmul_weights(cfg)["active_total"] * batch * seq
        + attention["forward"]
        + attention["backward"]
    )


def experts_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the expert matmuls of one step need, all layers: gate, up and
    down over ``tokens x k`` rows, forward, input gradient and weight
    gradient."""
    d, m = cfg["hidden_size"], cfg["intermediate_size"]
    rows = batch * seq * cfg["num_experts_per_tok"]
    calls = 3 * 3 * cfg["num_hidden_layers"]          # matrices x passes x layers
    per_call_bytes = (rows * d + rows * m + cfg["num_experts"] * d * m) * itemsize
    return {
        "flops": calls * 2 * rows * d * m,
        "bytes": calls * per_call_bytes,
    }
