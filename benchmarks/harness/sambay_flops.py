"""Operations and bytes of family ``sambay_decoder`` (SambaY under
differential attention: Mamba-1 / window layers, a bridge, gated memory units
/ cross attention, every layer with a dense SwiGLU, a tied head), from shapes.
``harness/flops.py`` holds the conventions. What is new:

* A Mamba-1 layer's matmul weights are ``W_in`` ``[hidden, 2 inner]``, ``W_x``
  ``[inner, dt_rank + 2 states]``, ``W_dt`` ``[dt_rank, inner]`` and ``W_out``
  ``[inner, hidden]``; the convolution's taps a channel are counted with them
  (its bias is an addition and counts nothing). A gated memory unit's are
  ``W_1`` and ``W_2``; a cross layer's ``W_q`` and ``W_o`` alone. The TIED head is
  a matmul and counts, once; the embedding's lookup counts nothing.
* Differential attention is TWO softmaxes a pair of query heads, q / k of
  ``head_dim`` against a V of twice that: a (query, key) pair of one call costs
  ``2 head_dim`` for its score and ``4 head_dim`` for ``P V`` forward, twice that
  backward (``dQ``, ``dK``; ``dP``, ``dV``): ``18 head_dim`` a pair and call of
  model FLOPs, and one rebuilt score more, ``20 head_dim``, of the flash
  kernels' need (``mla_moe_flops.flash_needed``'s count at unlike widths).
  The pairs a mask allows: the causal half in the full and the cross layers,
  the band in the window layers (``window_moe_flops.band_pairs``).
* ``selective_scan_flops`` / ``selective_scan_needed``: the recurrence's own
  operations, whatever implements it (``ops/selective_scan.py`` has the
  formulas). A token, channel and STATE, forward: ``dt A``, its exponential,
  the decayed state, ``B (dt u)``, their sum, ``C S`` and its sum over the
  states: 7. Backward: the state's whole cotangent (2), ``dC`` (2), ``dB`` (2),
  the decay's cotangent through the state before (2), ``dA`` (2), ``du`` and
  ``d(dt)`` through ``B`` and through ``A`` (2 each), the cotangent handed back
  (1): 15. What the backward makes AGAIN (a chunk's states, the decays) is not
  a need. Bytes: ``u'``, ``y`` and their gradients in the model's dtype, ``dt``
  and its gradient float32, each moved once; ``B``, ``C`` and their gradients
  ``[tokens, states]``; the chunk-start states the forward keeps, float32,
  written and read once. Bound by neither peak of ``peaks.json``: the
  operations are the vector unit's, the table's FLOP/s the MXU's, so its share
  of that roofline reads low by construction and says how far a scan is from
  costing what its bytes cost.
* ``short_conv_needed``: ``ssm_moe_flops``'s count at the convolved width
  (``inner`` alone: Mamba-1 convolves ``u``, not ``B`` and ``C``).
"""

from __future__ import annotations

from benchmarks.harness.window_moe_flops import band_pairs
from benchmarks.reference.sambay_decoder import layer_kinds, mamba_sizes
from ray_tpu.ops.selective_scan import CHUNK as SCAN_CHUNK   # tokens between two kept states


def layer_counts(cfg: dict) -> dict:
    kinds = layer_kinds(cfg)
    return {kind: kinds.count(kind) for kind in ("mamba", "window", "full", "gmu", "cross")}


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def matmul_weights(cfg: dict) -> dict:
    """Matmul weights (and filter taps) by part."""
    d, mb, hd = cfg["hidden_size"], mamba_sizes(cfg), head_dim(cfg)
    q_out, kv_out = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return {
        "mlp": 3 * d * cfg["intermediate_size"],
        "mamba": (
            d * 2 * mb["inner"] + mb["inner"] * (mb["rank"] + 2 * mb["state"])
            + mb["rank"] * mb["inner"] + mb["inner"] * d + mb["taps"] * mb["inner"]
        ),
        "attention": 2 * d * q_out + 2 * d * kv_out,
        "cross": 2 * d * q_out,
        "gmu": 2 * d * mb["inner"],
        "head": d * cfg["vocab_size"],
    }


def layer_parameters(cfg: dict, biases: bool = True) -> dict:
    """Every stored parameter of one layer's part, by part: the matmul weights
    and filters above, a Mamba-1 layer's convolution bias, ``dt_bias``, ``D`` (a
    channel each) and ``A_log`` (a channel and state); differential attention's
    four vectors of ``head_dim`` and its norm's weight of twice that; the
    biases of ``W_q``, ``W_k``, ``W_v`` and ``W_o`` (``attention_bias``); two
    LayerNorms' weight and bias. ``biases`` False: without the attention
    projections' biases."""
    d, mb, hd, w = cfg["hidden_size"], mamba_sizes(cfg), head_dim(cfg), matmul_weights(cfg)
    q_out, kv_out = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    biased = bool(cfg["attention_bias"]) and biases
    return {
        "mlp": w["mlp"], "norms": 4 * d,
        "mamba": w["mamba"] + 3 * mb["inner"] + mb["inner"] * mb["state"],
        "attention": w["attention"] + 6 * hd + biased * (q_out + 2 * kv_out + d),
        "cross": w["cross"] + 6 * hd + biased * (q_out + d),
        "gmu": w["gmu"],
    }


def parameters(cfg: dict, biases: bool = True) -> int:
    """Every stored parameter: each layer's mixer, SwiGLU and two LayerNorms,
    the embedding table (which is the head), the final LayerNorm. ``biases``
    False leaves the attention projections' biases out, the file's
    ``attention_bias`` (an ``assumed`` key): ISSUE 65's totals (3,852,457,984
    published, 1,330,121,984 cut) are that count; the program holds 7,680 more
    a window or full layer and 5,120 a cross layer."""
    per, counts = layer_parameters(cfg, biases), layer_counts(cfg)
    mixers = (
        counts["mamba"] * per["mamba"] + (counts["window"] + counts["full"]) * per["attention"]
        + counts["gmu"] * per["gmu"] + counts["cross"] * per["cross"]
    )
    d = cfg["hidden_size"]
    return (
        mixers + cfg["num_hidden_layers"] * (per["mlp"] + per["norms"])
        + cfg["vocab_size"] * d + 2 * d
    )


def published(cfg: dict) -> dict:
    """The configuration with every cut taken back: what the source states."""
    return {**cfg, **cfg.get("published", {}), "published": {}}


def attention_pairs(cfg: dict, batch: int, seq: int) -> dict:
    """(query, key) pairs of one step by kind of layer, all PAIRS of query heads
    and ONE of a pair's two calls: the causal half a full or cross layer, the
    band a window layer."""
    counts, pairs = layer_counts(cfg), cfg["num_attention_heads"] // 2 * batch
    return {
        "full": (counts["full"] + counts["cross"]) * pairs * seq * seq // 2,
        "window": counts["window"] * pairs * band_pairs(seq, cfg["sliding_window"]),
    }


def selective_scan_flops(cfg: dict, batch: int, seq: int) -> dict:
    """The recurrence's own operations of one step, all Mamba-1 layers (the
    module docstring counts them)."""
    mb = mamba_sizes(cfg)
    cells = layer_counts(cfg)["mamba"] * batch * seq * mb["inner"]
    return {
        "forward": cells * (7 * mb["state"] + 3), "backward": cells * (15 * mb["state"] + 4),
    }


def step_flops(cfg: dict, batch: int, seq: int) -> int:
    """Model FLOPs of one training step: ``6 x weights x tokens`` for what
    every token runs (every mixer's projections, every SwiGLU, the tied head),
    ``18 head_dim`` a (query, key) pair and call of differential attention, two
    calls a pair of heads, and the selective scans' recurrence."""
    w, counts = matmul_weights(cfg), layer_counts(cfg)
    every_token = (
        cfg["num_hidden_layers"] * w["mlp"] + counts["mamba"] * w["mamba"]
        + (counts["window"] + counts["full"]) * w["attention"] + counts["cross"] * w["cross"]
        + counts["gmu"] * w["gmu"] + w["head"]
    )
    pairs = attention_pairs(cfg, batch, seq)
    scan = selective_scan_flops(cfg, batch, seq)
    return int(
        6 * every_token * batch * seq + 2 * 18 * (pairs["full"] + pairs["window"]) * head_dim(cfg)
        + scan["forward"] + scan["backward"]
    )


def _flash_bytes(cfg: dict, layers: dict, batch: int, seq: int, itemsize: int) -> int:
    """The flash calls' bytes of ``layers`` (``{"self": n, "cross": m}``):
    TWO sets of three calls a layer, q / k ``[seq, head_dim]`` and V / o ``[seq,
    2 head_dim]`` each moved once, K and V at the key-value pairs. A cross layer
    reads the bridge's K and V and returns their gradients like any other."""
    pairs, kv_pairs = cfg["num_attention_heads"] // 2, cfg["num_key_value_heads"] // 2
    narrow = seq * head_dim(cfg) * itemsize          # q, k, dq, dk of one head
    wide = 2 * narrow                                # V, o, do, dV
    row = seq * 4                                    # one float32 per query (lse, delta)
    fwd = pairs * (narrow + wide + row) + kv_pairs * (narrow + wide)          # q -> o, lse; k V
    dq = pairs * (2 * narrow + wide + 2 * row) + kv_pairs * (narrow + wide)   # q do -> dq
    dkv = pairs * (narrow + wide + 2 * row) + kv_pairs * 2 * (narrow + wide)  # q do; k V -> dk dV
    return 2 * (fwd + dq + dkv) * batch * (layers["self"] + layers["cross"])


def window_flash_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the window layers' flash calls of one step need: the BAND's
    operations, ``20 head_dim`` a pair and call, two calls a pair of heads."""
    pairs = attention_pairs(cfg, batch, seq)["window"]
    layers = {"self": layer_counts(cfg)["window"], "cross": 0}
    return {
        "flops": 2 * 20 * pairs * head_dim(cfg),
        "bytes": _flash_bytes(cfg, layers, batch, seq, itemsize),
    }


def flash_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What every flash call of one step needs: the full and cross layers'
    causal half and the window layers' band."""
    counts = layer_counts(cfg)
    pairs = attention_pairs(cfg, batch, seq)["full"]
    band = window_flash_needed(cfg, batch, seq, itemsize)
    whole = _flash_bytes(cfg, {"self": counts["full"], "cross": counts["cross"]}, batch, seq, itemsize)
    return {
        "flops": 2 * 20 * pairs * head_dim(cfg) + band["flops"], "bytes": whole + band["bytes"],
    }


def scan_kept_bytes(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> int:
    """Bytes one step's scans keep for their backward, all Mamba-1 layers: the
    output in the model's dtype and the chunk-start states in float32 (the
    count of ``ops/selective_scan.py::kept_bytes``, made here from the file)."""
    mb = mamba_sizes(cfg)
    chunks = -(-seq // SCAN_CHUNK)
    one = batch * mb["inner"] * (seq * itemsize + chunks * mb["state"] * 4)
    return layer_counts(cfg)["mamba"] * one


def selective_scan_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the selective scans of one step need, all Mamba-1 layers."""
    mb = mamba_sizes(cfg)
    layers = layer_counts(cfg)["mamba"]
    tokens = layers * batch * seq
    scan = selective_scan_flops(cfg, batch, seq)
    per_token = (
        mb["inner"] * (4 * itemsize + 2 * 4)          # u' y dy du | dt d(dt) float32
        + 4 * mb["state"] * itemsize                  # B, C, dB, dC
    )
    starts = 2 * layers * batch * -(-seq // SCAN_CHUNK) * mb["inner"] * mb["state"] * 4
    return {"flops": scan["forward"] + scan["backward"], "bytes": int(per_token * tokens + starts)}


def short_conv_needed(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """What the convolution kernels of one step need, all Mamba-1 layers: the
    taps' multiply-adds forward (2 a tap) and backward (``dx`` and ``dfilters``:
    4 a tap), the bias's as one tap more, and five ``[tokens, inner]`` arrays
    moved once (``ssm_moe_flops.short_conv_needed``)."""
    mb = mamba_sizes(cfg)
    cells = layer_counts(cfg)["mamba"] * batch * seq * mb["inner"]
    return {"flops": 6 * (mb["taps"] + 1) * cells, "bytes": 5 * cells * itemsize}
