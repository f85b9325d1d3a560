"""Operations and bytes of the hybrid decoder (family ``hybrid_decoder``:
gated-delta-rule linear-attention layers and full-attention layers in the
published ``layer_types`` pattern), from shapes. ``harness/flops.py`` holds
the conventions; what is new against its counts:

* A ``linear_attention`` layer's matmul weights are ``W_q``, ``W_k``
  ``[hidden, key heads x d_k]``, ``W_v``, ``W_g``, ``W_o`` ``[hidden, value
  heads x d_v]`` and the two gate projections ``[hidden, value heads]``.
  The three depthwise convolutions (``taps`` weights a channel, each used
  once a token) are counted with them: ``6 x weights x tokens`` holds for
  a filter tap as for a matrix entry.
* The recurrence itself, per head and position, forward: the state's read
  ``S k`` (``2 d_k d_v``), its rank-one write (``2 d_k d_v``) and the
  output ``S q`` (``2 d_k d_v``): ``6 d_k d_v`` (the decay's ``d_k d_v``
  multiplications ride on the write). The backward needs twice that, as a
  matmul's does: ``18 d_k d_v`` a head and position a step. What the
  chunked form spends beyond that (the ``[chunk, chunk]`` products, the
  triangular inverse) is the algorithm's cost, not a need.
* Its bytes: q, k (``d_k``), v, o (``d_v``) in the model dtype and the two
  gates in float32, a head and position, each moved once forward; backward
  q, k, v, the gates and ``dO`` read, ``dq, dk, dv`` and the gates'
  gradients written: ``(6 d_k + 5 d_v) itemsize + 24`` bytes. At d_k 96,
  d_v 192 in bfloat16 that is 3,096 bytes for 331,776 operations: 107
  operations a byte against the v5e's 240, so the bound is MEMORY, unlike
  the flash kernels' and the grouped matmuls'.
* A ``full_attention`` layer is the dense block's attention at
  ``hidden_size / num_attention_heads`` (``flops.flash_needed`` over the
  full layers alone).
"""

from __future__ import annotations

from benchmarks.harness import flops


def layer_counts(cfg: dict) -> dict:
    """{"linear_attention": n, "full_attention": n} at the file's depth."""
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    return {kind: kinds.count(kind) for kind in ("linear_attention", "full_attention")}


def _full_as_dense(cfg: dict) -> dict:
    """The full-attention layers as ``harness/flops.py`` wants them."""
    return {
        "num_attention_heads": cfg["num_attention_heads"],
        "num_hidden_layers": layer_counts(cfg)["full_attention"],
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
    }


def matmul_weights(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    key_dim = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    value_dim = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    kv_dim = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    linear = (
        2 * d * key_dim + 3 * d * value_dim + 2 * d * cfg["linear_num_value_heads"]
        + cfg["linear_conv_kernel_dim"] * (2 * key_dim + value_dim)
    )
    full = 2 * d * d + 2 * d * kv_dim
    mlp = 3 * d * cfg["intermediate_size"]
    counts = layer_counts(cfg)
    head = d * cfg["vocab_size"]
    layers = (
        counts["linear_attention"] * (linear + mlp) + counts["full_attention"] * (full + mlp)
    )
    return {
        "linear_mixer_per_layer": linear, "full_mixer_per_layer": full,
        "mlp_per_layer": mlp, "layers": layers, "head": head, "total": layers + head,
    }


def parameters(cfg: dict) -> int:
    """Every stored parameter: the matmul weights and filters above, the
    embedding table, per linear layer ``A_log``, ``dt_bias`` and the gated
    norm's weight, per full layer the q / k norms, two block norms a layer,
    the final norm."""
    d = cfg["hidden_size"]
    counts = layer_counts(cfg)
    kv_dim = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    return (
        matmul_weights(cfg)["total"]
        + cfg["vocab_size"] * d
        + counts["linear_attention"] * (
            2 * cfg["linear_num_value_heads"] + cfg["linear_value_head_dim"]
        )
        + counts["full_attention"] * (d + kv_dim)
        + cfg["num_hidden_layers"] * 2 * d
        + d
    )


def delta_rule_flops(cfg: dict, batch: int, seq: int) -> dict:
    """The recurrence's own operations of one step, all linear layers."""
    per = cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]
    cells = cfg["linear_num_value_heads"] * layer_counts(cfg)["linear_attention"] * batch * seq
    return {"forward": 6 * per * cells, "backward": 12 * per * cells}


def step_flops(cfg: dict, batch: int, seq: int) -> int:
    """Model FLOPs of one training step: ``6 x matmul weights x tokens``,
    causal attention in the full layers, the recurrence in the linear ones."""
    attention = flops.causal_attention_flops(_full_as_dense(cfg), batch, seq)
    recurrence = delta_rule_flops(cfg, batch, seq)
    return (
        6 * matmul_weights(cfg)["total"] * batch * seq
        + attention["forward"] + attention["backward"]
        + recurrence["forward"] + recurrence["backward"]
    )


def flash_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the three flash calls of one step need: the full layers alone."""
    return flops.flash_needed(_full_as_dense(cfg), batch, seq, itemsize)


def delta_rule_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the delta rule of one step needs, all linear layers: the
    recurrence's operations and each of q, k, v, the two gates, ``o`` and
    their gradients moved once (the module docstring has the count). The
    bound it reads on a v5e is memory."""
    d_k, d_v = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    cells = cfg["linear_num_value_heads"] * layer_counts(cfg)["linear_attention"] * batch * seq
    recurrence = delta_rule_flops(cfg, batch, seq)
    return {
        "flops": recurrence["forward"] + recurrence["backward"],
        "bytes": ((6 * d_k + 5 * d_v) * itemsize + 24) * cells,
    }
