"""Operations and bytes of the latent-attention mixture-of-experts decoder,
from shapes (family ``mla_moe_decoder``; ``harness/flops.py`` holds the
conventions, ``harness/moe_flops.py`` the dropless experts' count, whose
reasoning is followed here).

What is new against those counts:

* Attention's projections are ``W_q`` ``[hidden, heads x (qk_nope +
  qk_rope)]``, ``W_kv_a`` ``[hidden, kv_lora_rank + qk_rope]``, ``W_kv_b``
  ``[kv_lora_rank, heads x (qk_nope + v_head_dim)]`` and ``W_o`` ``[heads x
  v_head_dim, hidden]``.
* Scores run over ``qk = qk_nope + qk_rope`` dims and values over
  ``v_head_dim``: causal forward ``s^2 (qk + v)`` per head and sequence
  (half of two matmuls), backward twice that (dV and dP over ``v``, dQ and
  dK over ``qk``). The flash kernels NEED a fifth backward matmul to
  rebuild the scores, over ``qk``: in all ``(4 qk + 3 v) s^2`` = 1152
  ``s^2`` at 192 / 128, where one head dim of 128 needs 896. Bytes: q, k,
  dq, dk move ``qk``-wide rows; v, o, dO, dv ``v``-wide ones.
* The first ``first_k_dense_replace`` layers hold a dense SwiGLU of
  ``intermediate_size``; the others ``n_routed_experts`` experts of
  ``moe_intermediate_size`` of which a token runs ``num_experts_per_tok``,
  a router, and one shared SwiGLU of ``n_shared_experts x
  moe_intermediate_size`` that every token runs.
* ``e_score_correction_bias`` (one float an expert and layer) is stored and
  counted as a parameter; no gradient reaches it.
"""

from __future__ import annotations


def _layers(cfg: dict) -> tuple[int, int]:
    """(dense layers, expert layers) at the configuration's depth."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def matmul_weights(cfg: dict) -> dict:
    """Matmul weights by part; ``active`` is what one token runs."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    rank = cfg["kv_lora_rank"]
    attn = (
        d * heads * qk
        + d * (rank + cfg["qk_rope_head_dim"])
        + rank * heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
        + heads * cfg["v_head_dim"] * d
    )
    dense_mlp = 3 * d * cfg["intermediate_size"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    shared = cfg["n_shared_experts"] * expert
    router = d * cfg["n_routed_experts"]
    dense, sparse = _layers(cfg)
    head = d * cfg["vocab_size"]
    return {
        "attn_per_layer": attn,
        "dense_mlp_per_layer": dense_mlp,
        "router_per_layer": router,
        "expert": expert,
        "shared_per_layer": shared,
        "experts_stored_per_layer": cfg["n_routed_experts"] * expert,
        "experts_active_per_layer": cfg["num_experts_per_tok"] * expert,
        "head": head,
        "active_total": (
            dense * (attn + dense_mlp)
            + sparse * (attn + router + shared + cfg["num_experts_per_tok"] * expert)
            + head
        ),
        "stored_total": (
            dense * (attn + dense_mlp)
            + sparse * (attn + router + shared + cfg["n_routed_experts"] * expert)
            + head
        ),
    }


def parameters(cfg: dict) -> int:
    """Every stored parameter: matmul weights (all experts), the embedding
    table, a layer's two norm vectors and its latent norm, the expert
    layers' correction bias, the final norm."""
    d = cfg["hidden_size"]
    _dense, sparse = _layers(cfg)
    return (
        matmul_weights(cfg)["stored_total"]
        + cfg["vocab_size"] * d
        + cfg["num_hidden_layers"] * (2 * d + cfg["kv_lora_rank"])
        + sparse * cfg["n_routed_experts"]
        + d
    )


def causal_attention_flops(cfg: dict, batch: int, seq: int) -> dict:
    """Attention score/value matmuls of one step, all layers, causal."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    per_head = seq * seq * (qk + cfg["v_head_dim"])
    heads = cfg["num_attention_heads"] * cfg["num_hidden_layers"] * batch
    return {"forward": per_head * heads, "backward": 2 * per_head * heads}


def step_flops(cfg: dict, batch: int, seq: int) -> int:
    """Model FLOPs of one training step: ``6 x active matmul weights x
    tokens`` plus causal attention. The balance loss counts nothing."""
    attention = causal_attention_flops(cfg, batch, seq)
    return (
        6 * matmul_weights(cfg)["active_total"] * batch * seq
        + attention["forward"]
        + attention["backward"]
    )


def flash_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the three flash calls (fwd, dq, dkv) of one step need, all
    layers, with q / k of ``qk`` dims and v of ``v_head_dim``: two forward
    matmuls (``qk`` and ``v``) and five backward ones (scores rebuilt,
    dQ, dK over ``qk``; dV, dP over ``v``), causal; each operand and result
    moved once."""
    heads = cfg["num_attention_heads"] * cfg["num_hidden_layers"] * batch
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    v = cfg["v_head_dim"]
    wide, narrow = seq * qk * itemsize, seq * v * itemsize
    row = seq * 4                                     # one float32 per query (lse, delta)
    fwd_bytes = 2 * wide + 2 * narrow + row           # q k v -> o, lse
    dq_bytes = 3 * wide + 2 * narrow + 2 * row        # q k v do lse delta -> dq
    dkv_bytes = 3 * wide + 3 * narrow + 2 * row       # q k v do lse delta -> dk dv
    return {
        "flops": (4 * qk + 3 * v) * seq * seq * heads,
        "bytes": (fwd_bytes + dq_bytes + dkv_bytes) * heads,
    }


def experts_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the routed experts' grouped matmuls of one step need, all
    expert layers: gate, up and down over ``tokens x k`` rows, forward,
    input gradient and weight gradient (``moe_flops.experts_needed`` at
    this family's keys). The shared experts are plain matmuls and not in
    it."""
    d, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = batch * seq * cfg["num_experts_per_tok"]
    calls = 3 * 3 * _layers(cfg)[1]                   # matrices x passes x expert layers
    per_call_bytes = (rows * d + rows * m + cfg["n_routed_experts"] * d * m) * itemsize
    return {
        "flops": calls * 2 * rows * d * m,
        "bytes": calls * per_call_bytes,
    }
