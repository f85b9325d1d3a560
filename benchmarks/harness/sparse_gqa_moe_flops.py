"""Operations and bytes of the sparse-attention mixture-of-experts decoder
(family ``sparse_gqa_moe_decoder``: Keye-VL-2.0's grouped-query attention
over the keys a learned index scorer chose, every layer, over softmax-routed
experts of which this chip HOLDS A BLOCK, an untied head), from shapes.
``harness/flops.py`` holds the conventions, ``harness/window_moe_flops.py``
the held experts' count, whose reasoning is followed here. What is new:

* A layer's attention needs the CHOSEN pairs and not the causal half: query
  ``t`` attends to ``min(t + 1, topk)`` keys, ``chosen_pairs`` in all
  (31,458,304 at 16,384 positions and ``topk`` 2,048: 23.44 % of the
  134,225,920 causal pairs). Model FLOPs take ``12 x pairs x head_dim`` a
  head (forward two matmuls over the pairs, backward four), the flash
  kernels' need ``14 x pairs x head_dim``, whatever implements them: a
  kernel that walks every causal tile under a mask reads a low
  ``flash_roofline_pct``, which is the point. The needed BYTES are every
  operand and result of the three calls moved once: q, o, dO, dq a query
  head; K, V (read by each call) and dK, dV a KV HEAD (grouped-query: 4, not
  32); ``lse`` and ``delta``; and the selection, ``seq x seq`` bytes a batch
  row, read by each of the three calls.
* The index scorer: its three projections ``[hidden, 16 x 64 + 64 + 16]``
  are matmuls every token runs, trained by the scorer's own term (6 a weight
  and token); its scores take ``2 x index heads x index dim`` a CAUSAL pair
  a pass (``seq (seq + 1) / 2`` pairs: the scorer reads every key before a
  query to choose among them), forward once and backward twice (its two
  operands' gradients): 6 in all. The attention's probabilities that the
  scorer's term reads (``pbar``) are counted with the attention: a kernel
  could hand them out as it goes.
* The experts count the (token, choice) pairs whose expert is held: the
  pairs a run counted where the caller has them, else what the family's
  weights give (``held_rows``: every pair).
"""

from __future__ import annotations

from benchmarks.reference.sparse_gqa_moe_decoder import chosen_pairs, router_width


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def held_rows(cfg: dict, batch: int, seq: int) -> int:
    """(token, choice) pairs a layer's held experts get under the family's
    weights: every pair (the routers are zero, so a token's ``top_k`` equal
    scores choose the lowest-numbered experts, all held:
    ``families/sparse_gqa_moe_decoder.py::init``)."""
    return batch * seq * cfg["num_experts_per_tok"]


def matmul_weights(cfg: dict) -> dict:
    """Matmul weights by part."""
    d, hd, sa = cfg["hidden_size"], cfg["head_dim"], cfg["sa_config"]
    q_out, kv_out = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    index = sa["indexer_num_heads"] * sa["indexer_head_dim"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    return {
        "attention_per_layer": d * q_out + 2 * d * kv_out + q_out * d,
        "indexer_per_layer": d * (
            index + sa["indexer_num_kv_heads"] * sa["indexer_head_dim"] + sa["indexer_num_heads"]
        ),
        "router_per_layer": d * router_width(cfg),
        "expert": expert,
        "experts_held_per_layer": cfg["num_experts"] * expert,
        "head": d * cfg["vocab_size"],
    }


def parameters(cfg: dict) -> int:
    """Every stored parameter: attention with its two head norms, the
    scorer with its key's norm, the router over ALL experts, the HELD
    experts and two block norms a layer, the embedding table, the untied
    head and the final norm."""
    d, w, sa = cfg["hidden_size"], matmul_weights(cfg), cfg["sa_config"]
    norms = 2 * d + 2 * cfg["head_dim"] + sa["indexer_head_dim"]
    layer = (
        w["attention_per_layer"] + w["indexer_per_layer"] + w["router_per_layer"]
        + w["experts_held_per_layer"] + norms
    )
    return cfg["num_hidden_layers"] * layer + 2 * w["head"] + d


def step_flops(cfg: dict, batch: int, seq: int, rows=None) -> int:
    """Model FLOPs of one training step (module docstring)."""
    w, layers, sa = matmul_weights(cfg), cfg["num_hidden_layers"], cfg["sa_config"]
    every_token = layers * (
        w["attention_per_layer"] + w["indexer_per_layer"] + w["router_per_layer"]
    ) + w["head"]
    rows = held_rows(cfg, batch, seq) if rows is None else rows
    attention = 12 * chosen_pairs(seq, sa["topk"]) * cfg["head_dim"] * cfg["num_attention_heads"]
    scorer = 6 * causal_pairs(seq) * sa["indexer_num_heads"] * sa["indexer_head_dim"]
    return int(
        6 * every_token * batch * seq
        + 6 * w["expert"] * rows * layers
        + (attention + scorer) * batch * layers
    )


def flash_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the three flash calls of one step need, all layers: the CHOSEN
    pairs' operations and every operand and result moved once."""
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, layers = cfg["head_dim"], cfg["num_hidden_layers"]
    tile = seq * hd * itemsize        # one [seq, head_dim] operand
    row = seq * 4                     # one float32 per query (lse, delta)
    selection = seq * seq             # int8
    fwd = heads * (2 * tile + row) + kv_heads * 2 * tile + selection         # q -> o, lse; K V
    dq = heads * (3 * tile + 2 * row) + kv_heads * 2 * tile + selection      # q dO -> dq
    dkv = heads * (2 * tile + 2 * row) + kv_heads * 4 * tile + selection     # q dO; K V -> dK dV
    return {
        "flops": 14 * chosen_pairs(seq, cfg["sa_config"]["topk"]) * hd * heads * batch * layers,
        "bytes": (fwd + dq + dkv) * batch * layers,
    }


def experts_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2, rows=None) -> dict:
    """What the held experts' grouped matmuls of one step need, all layers
    (``window_moe_flops.experts_needed``'s count)."""
    d, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = held_rows(cfg, batch, seq) if rows is None else rows
    calls = 3 * 3 * cfg["num_hidden_layers"]
    per_call_bytes = (rows * d + rows * m + cfg["num_experts"] * d * m) * itemsize
    return {"flops": int(calls * 2 * rows * d * m), "bytes": int(calls * per_call_bytes)}
