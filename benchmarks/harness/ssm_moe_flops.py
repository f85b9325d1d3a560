"""Operations and bytes of family ``ssm_moe_decoder`` (layers that are ONE
block each: a Mamba-2 mixer, grouped-query attention or a latent mixture of
un-gated experts of which this chip HOLDS A BLOCK beside one shared expert),
from shapes. ``harness/flops.py`` holds the conventions. What is new:

* A Mamba-2 layer's matmul weights are the in-projection ``[hidden, 2 inner +
  2 groups x state + heads]`` and ``W_out`` ``[inner, hidden]``; the
  convolution's ``conv_kernel`` taps a channel are counted with them (its bias
  is an addition and counts nothing).
* An expert layer's are the router over ALL the experts, the two latent
  projections ``[hidden, latent]``, ``[latent, hidden]``, the shared expert's
  TWO matrices of its own width on the stream, and TWO ``[latent, width]``
  matrices an expert (``mlp_hidden_act`` ``relu2``: no gate).
* ``ssd_flops`` / ``ssd_needed``: the chunked scan's own operations, whatever
  implements it (``ops/ssd.py`` has the formulas). A token and head, forward:
  the chunk's own part ``(C B^T o decay) (dt x)``, causal inside a chunk of
  ``chunk_size`` L so half of ``2 L P``, and once a GROUP ``C B^T`` (half of
  ``2 L N``); the token's write into the chunk's end state, ``2 P N``; its read
  of the chunk's start state, ``2 P N``; the state's step from chunk to chunk,
  ``2 P N / L``. Backward: the two products of the chunk's own part transposed
  (``2 L P``) and ``dB``, ``dC`` from ``dG`` a group (``2 L N``); the state's
  cotangent, ``dC`` through the start state, ``dt x``'s and ``dB``'s through
  the end state, ``2 P N`` each; the cotangent's step. What the backward makes
  AGAIN (the chunk-start states, ``C B^T``, the start state's read) is not a
  need. Bytes: ``x``, ``y`` and their gradients ``[tokens, heads, P]`` in the
  model's dtype, ``dt`` and its gradient float32, ``B``, ``C`` and their
  gradients AT THE GROUPS, each moved once. The gate ``z`` and the group norm
  are the mixer's, not the scan's. Memory-bound on a v5e.
* ``short_conv_needed``: ``conv_moe_flops``'s count at the convolved width
  (``inner + 2 groups x state``), a tap more for the bias's addition and its
  gradient's sum.
"""

from __future__ import annotations

from benchmarks.harness import flops
from benchmarks.reference.ssm_moe_decoder import held_block, layer_kinds, router_width


def layer_counts(cfg: dict) -> dict:
    kinds = layer_kinds(cfg)
    return {
        "mamba": kinds.count("mamba"), "attention": kinds.count("attention"),
        "expert": kinds.count("moe"),
    }


def held_rows(cfg: dict, batch: int, seq: int) -> float:
    """(token, choice) pairs a layer's held experts get at an even routing."""
    return batch * seq * cfg["num_experts_per_tok"] * held_block(cfg)[1] / router_width(cfg)


def ssm_sizes(cfg: dict) -> dict:
    heads, width = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, state = cfg["n_groups"], cfg["ssm_state_size"]
    inner = heads * width
    return {
        "heads": heads, "width": width, "groups": groups, "state": state, "inner": inner,
        "conv": inner + 2 * groups * state, "chunk": cfg["chunk_size"],
    }


def matmul_weights(cfg: dict) -> dict:
    """Matmul weights (and filter taps) by part."""
    d, ssm = cfg["hidden_size"], ssm_sizes(cfg)
    q_out = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_out = cfg["num_key_value_heads"] * cfg["head_dim"]
    mamba = (
        d * (ssm["inner"] + ssm["conv"] + ssm["heads"]) + ssm["inner"] * d
        + cfg["conv_kernel"] * ssm["conv"]
    )
    attention = 2 * d * q_out + 2 * d * kv_out
    counts = layer_counts(cfg)
    return {
        "mamba_per_layer": mamba, "attention_per_layer": attention,
        "mixers": counts["mamba"] * mamba + counts["attention"] * attention,
        "router_per_layer": d * router_width(cfg),
        "latent_per_layer": 2 * d * cfg["moe_latent_size"],
        "shared_per_layer": (
            cfg["n_shared_experts"] * 2 * d * cfg["moe_shared_expert_intermediate_size"]
        ),
        "expert": 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"],
        "head": d * cfg["vocab_size"],
    }


def parameters(cfg: dict) -> int:
    """Every stored parameter: the matmul weights and filters above with the
    HELD experts, the embedding table, per Mamba-2 layer the convolution's
    bias, ``dt_bias``, ``A_log`` and ``D`` (a head each) and the gated norm's
    weight, per expert layer the correction bias (one an expert the router
    scores), ONE block norm a layer, the final norm."""
    d, ssm = cfg["hidden_size"], ssm_sizes(cfg)
    w, counts = matmul_weights(cfg), layer_counts(cfg)
    return (
        w["mixers"]
        + counts["mamba"] * (ssm["conv"] + 3 * ssm["heads"] + ssm["inner"])
        + counts["expert"] * (
            w["router_per_layer"] + w["latent_per_layer"] + w["shared_per_layer"]
            + held_block(cfg)[1] * w["expert"] + router_width(cfg)
        )
        + cfg["num_hidden_layers"] * d
        + 2 * w["head"] + d
    )


def published(cfg: dict) -> dict:
    """The configuration with every cut taken back: what the source states."""
    return {**cfg, **cfg.get("published", {}), "published": {}}


def _attention_as_dense(cfg: dict) -> dict:
    """The grouped-query layers as ``harness/flops.py`` wants them."""
    return dict(cfg, num_hidden_layers=layer_counts(cfg)["attention"])


def ssd_flops(cfg: dict, batch: int, seq: int) -> dict:
    """The chunked scan's own operations of one step, all Mamba-2 layers (the
    module docstring counts them)."""
    ssm = ssm_sizes(cfg)
    chunk, width, state = ssm["chunk"], ssm["width"], ssm["state"]
    tokens = layer_counts(cfg)["mamba"] * batch * seq
    by_head = tokens * ssm["heads"]
    by_group = tokens * ssm["groups"]
    step = 2 * width * state / chunk
    return {
        "forward": int(by_head * (chunk * width + 4 * width * state + step) + by_group * chunk * state),
        "backward": int(
            by_head * (2 * chunk * width + 8 * width * state + step) + by_group * 2 * chunk * state
        ),
    }


def step_flops(cfg: dict, batch: int, seq: int) -> int:
    """Model FLOPs of one training step: ``6 x weights x tokens`` for what
    every token runs, ``6 x expert x held rows`` for the routed experts held
    here at an even routing, causal attention in the grouped-query layers,
    the scan in the Mamba-2 ones."""
    w, counts = matmul_weights(cfg), layer_counts(cfg)
    every_token = (
        w["mixers"]
        + counts["expert"] * (w["router_per_layer"] + w["latent_per_layer"] + w["shared_per_layer"])
        + w["head"]
    )
    attention = flops.causal_attention_flops(_attention_as_dense(cfg), batch, seq)
    scan = ssd_flops(cfg, batch, seq)
    return int(
        6 * every_token * batch * seq
        + 6 * w["expert"] * held_rows(cfg, batch, seq) * counts["expert"]
        + attention["forward"] + attention["backward"]
        + scan["forward"] + scan["backward"]
    )


def flash_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the three flash calls of one step need, the grouped-query layers
    alone: ``flops.flash_needed``'s operations (``7 s^2 head_dim`` a query
    head), and each operand and result moved once with K, V, dK and dV at
    ``num_key_value_heads`` (``kda_gqa_moe_flops.flash_needed``'s count)."""
    layers = layer_counts(cfg)["attention"] * batch
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    tile = seq * cfg["head_dim"] * itemsize      # one [seq, head_dim] operand
    row = seq * 4                                # one float32 per query (lse, delta)
    by_query_head = (2 * tile + row) + (3 * tile + 2 * row) + (2 * tile + 2 * row)
    by_kv_head = 2 * tile + 2 * tile + 4 * tile  # k v | k v | k v dk dv
    return {
        "flops": flops.flash_needed(_attention_as_dense(cfg), batch, seq, itemsize)["flops"],
        "bytes": layers * (heads * by_query_head + kv_heads * by_kv_head),
    }


def ssd_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the scan of one step needs, all Mamba-2 layers."""
    ssm = ssm_sizes(cfg)
    tokens = layer_counts(cfg)["mamba"] * batch * seq
    scan = ssd_flops(cfg, batch, seq)
    per_token = (
        4 * ssm["inner"] * itemsize                       # x, y, dy, dx
        + 2 * 4 * ssm["heads"]                            # dt, ddt (float32)
        + 4 * ssm["groups"] * ssm["state"] * itemsize     # B, C, dB, dC at the groups
    )
    return {"flops": scan["forward"] + scan["backward"], "bytes": int(per_token * tokens)}


def short_conv_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the convolution kernels of one step need, all Mamba-2 layers: the
    taps' multiply-adds forward (2 a tap) and backward (``dx`` and
    ``dfilters``: 4 a tap), the bias's as one tap more, and five ``[tokens,
    conv]`` arrays moved once (input and output forward; input, ``dy`` and
    ``dx`` backward)."""
    cells = layer_counts(cfg)["mamba"] * batch * seq * ssm_sizes(cfg)["conv"]
    return {"flops": 6 * (cfg["conv_kernel"] + 1) * cells, "bytes": 5 * cells * itemsize}


def experts_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2, rows=None,
                   with_rows=None) -> dict:
    """What the held experts' grouped matmuls of one step need, all expert
    layers: up and down (TWO matrices an expert, on the latent) over ``rows``
    (token, choice) pairs a layer (None: an even routing's), forward, input
    gradient and weight gradient, each operand and result moved once. The two
    calls that READ a matrix's stack read the experts that got rows
    (``with_rows`` of them a layer; None: every held one); the weight
    gradient writes the whole held stack (``kda_gqa_moe_flops``'s count)."""
    d, m = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    held = held_block(cfg)[1]
    rows = held_rows(cfg, batch, seq) if rows is None else rows
    with_rows = held if with_rows is None else with_rows
    matrices = 2 * layer_counts(cfg)["expert"]
    moved = lambda experts: (rows * d + rows * m + experts * d * m) * itemsize
    return {
        "flops": int(3 * matrices * 2 * rows * d * m),
        "bytes": int(matrices * (2 * moved(with_rows) + moved(held))),
    }
