"""Operations and bytes of the block-diffusion mixture-of-experts decoder
(family ``block_diffusion_moe_decoder``: SDAR's grouped-query attention
under the block-diffusion mask over a stream of clean and noised rows, every
layer, over softmax-routed experts of which this chip HOLDS A BLOCK, an
untied head), from shapes. ``harness/flops.py`` holds the conventions,
``harness/sparse_gqa_moe_flops.py`` the held experts' count and the
grouped-query bytes, whose reasoning is followed here. What is new:

* ``seq`` is the TRAINED tokens a sequence, ``L``; the layers run ``2 L``
  rows (the clean copy and the noised one), the head and the loss ``L`` (the
  noised half alone). So every block's matmul weights count ``6 x weights x
  2 L`` and the head's ``6 x weights x L``: the clean half is the objective's
  own requirement (a noised block reads the clean blocks before it), not a
  recomputation. ``tokens_per_s_per_chip`` counts ``L`` a step.
* A layer's attention needs the ALLOWED pairs of the mask: clean -> clean
  of the own and earlier blocks ``(L^2 + L B) / 2``, noised -> clean of
  earlier blocks ``(L^2 - L B) / 2``, noised -> noised of the own block ``L
  B``: ``allowed_pairs = L^2 + L B`` a head and sequence (67,141,632 at
  8,192 and blocks of 4; a causal 16,384 has 134,225,920). Model FLOPs take
  ``12 x pairs x head_dim`` a head, the flash kernels' need ``14 x pairs x
  head_dim``, whatever tiles implement them: the tiles the kernels execute
  hold 83,886,080 pairs (``flash_allowed_pairs_pct`` 80.0), and a kernel that
  walks them all reads a ``flash_roofline_pct`` that much lower, which is
  the point. The needed BYTES are every operand and result of the three
  calls moved once, at ``2 L`` rows: q, o, dO, dq a query head; K, V (read
  by each call) and dK, dV a KV HEAD; ``lse`` and ``delta``. The mask is no
  operand.
* The experts count the (row, choice) pairs whose expert is held, over
  ``2 L`` rows: the pairs a run counted where the caller has them, else what
  the family's weights give (``held_rows``: every pair).
"""

from __future__ import annotations

from benchmarks.reference.sparse_gqa_moe_decoder import router_width


def allowed_pairs(seq: int, block: int) -> int:
    """(query, key) pairs the block-diffusion mask lets through, a head and
    sequence of ``seq`` trained tokens in blocks of ``block``."""
    return seq * seq + seq * block


def held_rows(cfg: dict, batch: int, seq: int) -> int:
    """(row, choice) pairs a layer's held experts get under the family's
    weights: every pair of the ``2 x seq`` rows (the routers are zero, so a
    row's ``top_k`` equal scores choose the lowest-numbered experts, all
    held: ``families/block_diffusion_moe_decoder.py::init``)."""
    return batch * 2 * seq * cfg["num_experts_per_tok"]


def matmul_weights(cfg: dict) -> dict:
    """Matmul weights by part."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q_out, kv_out = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    expert = 3 * d * cfg["moe_intermediate_size"]
    return {
        "attention_per_layer": d * q_out + 2 * d * kv_out + q_out * d,
        "router_per_layer": d * router_width(cfg),
        "expert": expert,
        "experts_held_per_layer": cfg["num_experts"] * expert,
        "head": d * cfg["vocab_size"],
    }


def parameters(cfg: dict) -> int:
    """Every stored parameter: attention with its two head norms, the router
    over ALL experts, the HELD experts and two block norms a layer, the
    embedding table, the untied head and the final norm."""
    d, w = cfg["hidden_size"], matmul_weights(cfg)
    layer = (
        w["attention_per_layer"] + w["router_per_layer"] + w["experts_held_per_layer"]
        + 2 * d + 2 * cfg["head_dim"]
    )
    return cfg["num_hidden_layers"] * layer + 2 * w["head"] + d


def step_flops(cfg: dict, batch: int, seq: int, rows=None) -> int:
    """Model FLOPs of one training step of ``batch`` sequences of ``seq``
    trained tokens (module docstring)."""
    w, layers = matmul_weights(cfg), cfg["num_hidden_layers"]
    every_row = layers * (w["attention_per_layer"] + w["router_per_layer"])
    rows = held_rows(cfg, batch, seq) if rows is None else rows
    attention = (
        12 * allowed_pairs(seq, cfg["block_length"]) * cfg["head_dim"] * cfg["num_attention_heads"]
    )
    return int(
        6 * every_row * batch * 2 * seq
        + 6 * w["head"] * batch * seq
        + 6 * w["expert"] * rows * layers
        + attention * batch * layers
    )


def flash_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the three flash calls of one step need, all layers: the ALLOWED
    pairs' operations and every operand and result moved once, at ``2 x
    seq`` rows."""
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, layers = cfg["head_dim"], cfg["num_hidden_layers"]
    tile = 2 * seq * hd * itemsize    # one [2 L, head_dim] operand
    row = 2 * seq * 4                 # one float32 per row (lse, delta)
    fwd = heads * (2 * tile + row) + kv_heads * 2 * tile          # q -> o, lse; K V
    dq = heads * (3 * tile + 2 * row) + kv_heads * 2 * tile       # q dO -> dq
    dkv = heads * (2 * tile + 2 * row) + kv_heads * 4 * tile      # q dO; K V -> dK dV
    return {
        "flops": 14 * allowed_pairs(seq, cfg["block_length"]) * hd * heads * batch * layers,
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
