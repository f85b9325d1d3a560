"""Operations and bytes from shapes, and the table of peaks.

The yardstick's arithmetic: every FLOP and byte a metric divides by comes
from here, computed from the configuration's sizes and the traffic's
shapes — never from the program, and never from the compiler's own count.

Conventions (each a choice; ``benchmarks/tests/test_flops.py`` holds the
hand counts):

* A matmul of ``[m, k] x [k, n]`` is ``2 m k n`` operations.
* Model FLOPs of a training step are what forward and backward REQUIRE:
  ``6 x matmul-weights x tokens`` (forward 2, backward 4) plus causal
  attention. The embedding TABLE is a lookup and counts nothing; the
  untied output head is a matmul and counts. Norm vectors are not
  matmuls and count nothing. Recomputation (remat, or a kernel's own)
  counts nothing: a step that recomputes is slower at the same model
  FLOPs, so its MFU falls.
* Causal attention needs half the score matrix. Forward is two matmuls
  over it (QK^T, PV): ``2 x (2 s s d) / 2 = 2 s^2 d`` per head and
  sequence. Backward needs four (dV, dP, dQ, dK): ``4 s^2 d``.
* The flash kernels' NEEDED operations are the algorithm's own: the
  backward of a flash kernel never stored the scores, so it needs a fifth
  matmul to rebuild them: forward ``2 s^2 d``, backward ``5 s^2 d``. That
  this repo's backward is two kernels (dq, dkv) which each rebuild scores
  and dP (7 matmuls) is the kernels' cost, not a need. Needed bytes are
  each operand and result of the three calls moved once.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmarks/harness/peaks.json; "
            "add it with its published source, do not default"
        )
    return table[device_kind]


def dense_decoder_matmul_weights(cfg: dict) -> dict:
    """Matmul weights of a pre-norm GQA + SwiGLU decoder with an untied
    head, by part. ``cfg`` uses the published (Hugging Face) key names."""
    d = cfg["hidden_size"]
    hd = cfg["head_dim"]
    q_out = cfg["num_attention_heads"] * hd
    kv_out = cfg["num_key_value_heads"] * hd
    attn = d * q_out + 2 * d * kv_out + q_out * d
    mlp = 3 * d * cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    head = d * cfg["vocab_size"]
    return {
        "attn_per_layer": attn,
        "mlp_per_layer": mlp,
        "layers": layers * (attn + mlp),
        "head": head,
        "total": layers * (attn + mlp) + head,
    }


def dense_decoder_parameters(cfg: dict) -> int:
    """Every stored parameter: matmul weights, the embedding table, norms."""
    d = cfg["hidden_size"]
    return (
        dense_decoder_matmul_weights(cfg)["total"]
        + cfg["vocab_size"] * d
        + cfg["num_hidden_layers"] * 2 * d
        + d
    )


def causal_attention_flops(cfg: dict, batch: int, seq: int) -> dict:
    """Attention score/value matmuls of one step, all layers, causal."""
    per_head = seq * seq * cfg["head_dim"]
    heads = cfg["num_attention_heads"] * cfg["num_hidden_layers"] * batch
    return {"forward": 2 * per_head * heads, "backward": 4 * per_head * heads}


def dense_decoder_step_flops(cfg: dict, batch: int, seq: int) -> int:
    """Model FLOPs of one training step of ``batch`` sequences of ``seq``."""
    attention = causal_attention_flops(cfg, batch, seq)
    return (
        6 * dense_decoder_matmul_weights(cfg)["total"] * batch * seq
        + attention["forward"]
        + attention["backward"]
    )


def flash_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the three flash calls (fwd, dq, dkv) of one step need, all
    layers: operations as the module docstring counts them, bytes as each
    operand and result moved once. The kernels are called with K and V
    already repeated to the query heads, so K and V count per query head."""
    heads = cfg["num_attention_heads"] * cfg["num_hidden_layers"] * batch
    hd = cfg["head_dim"]
    tile = seq * hd * itemsize      # one [seq, head_dim] operand
    row = seq * 4                   # one float32 per query (lse, delta)
    fwd_bytes = 4 * tile + row                      # q k v -> o, lse
    dq_bytes = 5 * tile + 2 * row                   # q k v do lse delta -> dq
    dkv_bytes = 6 * tile + 2 * row                  # q k v do lse delta -> dk dv
    return {
        "flops": 7 * seq * seq * hd * heads,
        "bytes": (fwd_bytes + dq_bytes + dkv_bytes) * heads,
    }


def roofline_seconds(flops: float, nbytes: float, peak: dict, chips: int) -> dict:
    """Least time ``chips`` chips could take, and which bound holds."""
    compute = flops / (chips * peak["bf16_flops_per_s"])
    memory = nbytes / (chips * peak["hbm_bytes_per_s"])
    return {
        "seconds": max(compute, memory),
        "bound": "compute" if compute >= memory else "memory",
        "compute_s": compute,
        "memory_s": memory,
    }
