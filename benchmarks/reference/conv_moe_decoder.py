"""Plain reference of the ``lfm2_moe`` language model as LFM2-8B-A1B
configures it: gated short convolutions three to one with grouped-query
attention under a per-head q / k norm, leading dense SwiGLU layers, then
sigmoid-and-bias routed experts of which THIS CHIP HOLDS A BLOCK, under a
tied head; and the comparison that decides ``correct`` for it.

Written from the published configuration's keys and the model's public
description (Hugging Face ``Lfm2MoeForCausalLM`` as known to the builder);
the configuration file's ``assumed`` list says what no key states.
``dense_decoder.py``'s ``rms_norm``, ``rotary``, ``causal_attention`` (a
blocked softmax: 16,384 positions fit), ``head_forward`` and ``compare``,
``moe_decoder.py``'s ``expert_forward`` and ``_position_errors`` and
``mla_moe_decoder.py``'s ``_routing_facts`` are used as they are.

Every layer, pre-norm (eps ``norm_eps``), on the residual stream ``x``::

    x = x + mixer(RMSNorm(x; operator_norm))
    x = x + ffn(RMSNorm(x; ffn_norm))

The file's layer ``i`` is published layer ``layer_offset + i`` and its
mixer is ``layer_types[i]``.

* ``conv``: ``[B, C, u] = h W_in`` (three chunks of ``hidden_size`` in
  that order); ``z_t = sum_{j < L} f_j (B * u)_{t - (L - 1 - j)}`` (``L =
  conv_L_cache`` taps a channel, causal, depthwise, zeros before the
  sequence, no bias: ``conv_bias`` false, NO activation), here as ``L``
  shifted multiply-adds; the mixer's output is ``(C * z) W_out``.
* ``full_attention``: q of ``num_attention_heads`` heads, k / v of
  ``num_key_value_heads``, head size ``hidden_size / num_attention_heads``;
  an RMSNorm with ONE learned weight of the head size on each head of q and
  of k; RoPE over the whole head (``rope_theta``, rotate-half); causal
  softmax at scale ``head^-1/2``, KV head ``j`` serving query heads ``j *
  group ..``; ``W_o``.
* the first ``num_dense_layers`` layers' ffn: a dense SwiGLU of
  ``intermediate_size``, ``(silu(h w1) * (h w3)) w2``. The others, on the
  normed ``h``: ``s = sigmoid(h W_r)`` over ALL the routed experts (the
  router's width, ``published.num_experts``); the CHOICE is the top
  ``num_experts_per_tok`` of ``s + expert_bias``; the WEIGHTS are ``s`` at
  the chosen (without the bias), divided by their sum + 1e-6
  (``norm_topk_prob``), times ``routed_scaling_factor``. ``y = sum over the
  chosen experts THAT ARE HELD HERE of w_j E_j(h)``: the file's
  ``num_experts`` experts from ``first_expert_held`` on are held, a Python
  loop over that same block, each applied densely to all tokens; what an
  absent expert would have added is left out, here as in the program. No
  shared expert, no token dropped, no balance loss.
* ``embedding_norm`` and logits through the TRANSPOSED embedding (tied).

``jax.numpy`` only, float32 throughout, ``default_matmul_precision
("highest")``, no kernel, no sort, no grouped matmul, no layer scan.
Imports nothing from ``ray_tpu.models`` or ``ray_tpu.ops``. Weights arrive
as ``[in, out]`` matrices, ``[taps, channels]`` filters and ``[held, in,
out]`` expert stacks: storage layouts (the checkpoint's are ``[out, in]``,
``[channels, 1, taps]`` and one module an expert).

``check`` has three parts. The logits are compared with the reference
FORCED to the program's expert choices, and the choices and weights held to
the reference's own scores (``mla_moe_decoder.py`` has the argument). The
logits cannot see the two precisions this model adds to what the other
cells hold, so each is checked ALONE at the cell's shapes, as Olmo-Hybrid's
scan is: ``check_conv``, the program's convolution on the reference's own
float32 gated input of layer 0, and ``check_router``, the program's router
on the reference's own normed input of the first expert layer.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.dense_decoder import (
    causal_attention, compare, head_forward, rms_norm, rotary,
)
from benchmarks.reference.mla_moe_decoder import _routing_facts
from benchmarks.reference.moe_decoder import _position_errors, expert_forward

# The limits of the comparison that decides ``correct`` (``check``), each
# from two readings on a v5e at the published widths and 16,384 positions
# (my chip runs, PR 39; PERF.md section 6 has the seeds): the largest the
# program gives over its seeds, and what it gives computed in the nearest
# precision below the one the configuration states
# (``harness/conv_moe_controls.py`` prints both).
#
# TOLERANCE_CONV: relative RMS error of the program's convolution ALONE
# (``ops/short_conv.py`` with no activation: the timed path's two kernels'
# forward) against ``causal_conv`` on the reference's own float32 ``B * u``
# of layer 0, over all positions and over the last ones. Both sides multiply
# and add in float32, the kernel oldest tap first: the program reads 4.19e-8
# to 4.24e-8 on every seed (the order of three float32 additions; 4.8e-8 on
# the CPU). With the taps summed in bfloat16 (each product and each partial
# sum rounded) it reads 1.68e-3 and 3.20e-3 to 3.22e-3 on four seeds: NOT correct. 1e-5
# is the geometric middle of the two nearest, a factor of 240 above the
# program's and 170 below the control's: a limit that sees any rounding of a
# tap's product below float32.
TOLERANCE_CONV = 1e-5
# TOLERANCE_ROUTER: relative RMS error of the program's routing weights
# ALONE (``models/transformer.py::_moe_mlp``'s router: float32 logits of a
# bfloat16 ``h``, sigmoid, the choice under the bias, renormalised) against
# ``route_normed`` at highest precision on the SAME bfloat16-rounded normed
# input of the first expert layer, the reference forced to the program's
# choices. On a v5e the program's float32 logits are one bfloat16 pass of
# the MXU (the router's float32 weights rounded on their way in), which is
# what separates it from the reference here; the sigmoid, the sum and the
# division are float32 on both sides: 2.64e-4 to 2.83e-4 over the seeds
# (0.0 on the CPU). With the scores rounded to bfloat16 before the choice
# and the weights it reads 1.21e-3 to 1.24e-3 on three seeds: NOT correct. 4.5e-4 is 1.6 times the
# largest of the program's (which moves by 7 % from seed to seed: an RMS
# over 65,536 pairs) and 2.7 times under the control's. ROUTER_MARGIN: every
# expert the program chose there must have a reference ``s + b`` of at least
# the k-th largest minus it: the program reads 8.3e-4 to 1.63e-3, the
# control 4.15e-3 to 4.38e-3 (it fails by the weights in any case).
TOLERANCE_ROUTER = 4.5e-4
ROUTER_MARGIN = 4e-3
# TOLERANCE, POSITION_TOLERANCE: relative RMS error of the program's logits
# against the reference FORCED to the program's expert choices, over the
# compared positions, and at the worst single position (``mla_moe_decoder.
# py`` has the argument for both). Five pre-norm layers in bfloat16, four of
# them expert layers whose routed share is HALF a weighted average (16 of 32
# experts held): the program reads 1.70e-2 to 1.74e-2 and 1.94e-2 to 2.11e-2
# over thirteen seeds, 3.5e-3 a layer (the other cells read 5e-3: here half of
# every routed sum is absent on both sides), evenly over the positions
# (median 1.71e-2, 99th percentile 1.95e-2): rounding, no token's error.
# 4e-2 and 6e-2 are 2.3 and 2.8 times the largest readings. They cannot see
# the two precisions this model adds (the mixer's output is rounded to
# bfloat16 whatever the taps were summed in), which is why the two checks
# above exist; what they hold is the model's terms
# (benchmarks/tests/test_reference_conv_moe.py, tests/test_conv_moe.py:
# SiLU after the convolution, no per-head norm, another block held, no
# renormalisation each move the logits by far more).
TOLERANCE = 4e-2
POSITION_TOLERANCE = 6e-2
# MARGIN: in the whole model every expert the program chose must have a
# REFERENCE ``s + b`` of at least the k-th largest minus MARGIN (units of
# the score, a sigmoid). The program's router sees a bfloat16 ``h`` that is
# off the reference's by the layers before it, so this grows with depth and
# is looser than ROUTER_MARGIN: 5.2e-3 to 7.6e-3 in the first expert layer,
# 1.28e-2 to 1.75e-2 in the fourth. WEIGHT_TOLERANCE: relative RMS error of
# the program's weights against the reference's own scores of the same
# experts, renormalised: with the drift of ``h``, 1.24e-3 to 1.34e-3 in the
# first expert layer, 2.73e-3 to 2.81e-3 in the fourth; a weight with the
# bias in it or not renormalised is off by tens of percent. 4e-2 and 8e-3
# are 2.3 and 2.8 times the largest readings.
MARGIN = 4e-2
WEIGHT_TOLERANCE = 8e-3

CONV_NAMES = ("operator_norm", "in_proj", "conv", "out_proj")
ATTENTION_NAMES = (
    "operator_norm", "q_proj", "k_proj", "v_proj", "q_layernorm", "k_layernorm", "out_proj",
)
DENSE_MLP_NAMES = ("ffn_norm", "w1", "w3", "w2")


def layer_kinds(cfg: dict) -> list[str]:
    """``conv`` / ``full_attention`` of the file's layers: its own
    ``layer_types`` (the published list from ``layer_offset`` on)."""
    kinds = list(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {"conv", "full_attention"}:
        raise ValueError(f"layer_types {kinds!r} for {cfg['num_hidden_layers']} layers")
    return kinds


def held_block(cfg: dict) -> tuple[int, int]:
    """``(first, count)`` of the experts this chip holds."""
    return cfg.get("first_expert_held", 0), cfg["num_experts"]


def router_width(cfg: dict) -> int:
    """The experts the router scores: the published count."""
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def causal_conv(x, filters):
    """``z_t = sum_j filters[j] x_{t - (taps - 1 - j)}``: ``taps`` shifted
    multiply-adds, zeros before the sequence. x: [batch, seq, channels];
    filters: [taps, channels]."""
    taps, seq = filters.shape[0], x.shape[1]
    out = filters[taps - 1] * x
    for back in range(1, taps):
        moved = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :seq]
        out = out + filters[taps - 1 - back] * moved
    return out


def _gated_input(h, w):
    """``(B * u, C)`` of a conv mixer's normed input."""
    b, c, u = jnp.split(h @ w["in_proj"], 3, axis=-1)
    return b * u, c


@functools.partial(jax.jit, static_argnames=("eps",))
def gated_input(x, w, *, eps):
    with jax.default_matmul_precision("highest"):
        w = {name: value.astype(jnp.float32) for name, value in w.items()}
        return _gated_input(rms_norm(x, w["operator_norm"], eps), w)[0]


@functools.partial(jax.jit, static_argnames=("eps",))
def conv_mixer_forward(x, w, *, eps):
    """x + gated short convolution(norm(x)). x: [b, s, hidden] float32."""
    with jax.default_matmul_precision("highest"):
        w = {name: value.astype(jnp.float32) for name, value in w.items()}
        gated, c = _gated_input(rms_norm(x, w["operator_norm"], eps), w)
        return x + (c * causal_conv(gated, w["conv"])) @ w["out_proj"]


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta", "eps"))
def attention_mixer_forward(x, w, *, heads, kv_heads, theta, eps):
    """x + grouped-query attention(norm(x)) with the per-head q / k norm."""
    with jax.default_matmul_precision("highest"):
        w = {name: value.astype(jnp.float32) for name, value in w.items()}
        batch, seq, _ = x.shape
        h = rms_norm(x, w["operator_norm"], eps)
        q = rms_norm((h @ w["q_proj"]).reshape(batch, seq, heads, -1), w["q_layernorm"], eps)
        k = rms_norm((h @ w["k_proj"]).reshape(batch, seq, kv_heads, -1), w["k_layernorm"], eps)
        v = (h @ w["v_proj"]).reshape(batch, seq, kv_heads, -1)
        attn = causal_attention(rotary(q, theta), rotary(k, theta), v)
        return x + attn.reshape(batch, seq, -1) @ w["out_proj"]


@functools.partial(jax.jit, static_argnames=("eps",))
def dense_mlp_forward(x, w, *, eps):
    """x + SwiGLU(norm(x)): the leading dense layers."""
    with jax.default_matmul_precision("highest"):
        w = {name: value.astype(jnp.float32) for name, value in w.items()}
        h = rms_norm(x, w["ffn_norm"], eps)
        return x + (jax.nn.silu(h @ w["w1"]) * (h @ w["w3"])) @ w["w2"]


def _route_normed(h, router, bias, forced, top_k, norm_topk_prob, scaling):
    scores = jax.nn.sigmoid(h @ router.astype(jnp.float32))
    biased = scores + bias.astype(jnp.float32)
    own = jax.lax.top_k(biased, top_k)[1]
    experts = own if forced is None else forced
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    return {
        "scores": scores, "biased": biased, "own": own, "experts": experts,
        "weights": weights * scaling,
    }


@functools.partial(jax.jit, static_argnames=("top_k", "norm_topk_prob", "scaling"))
def route_normed(h, router, bias, forced, *, top_k, norm_topk_prob, scaling):
    """The routing of normed tokens ``h`` ``[tokens, hidden]`` over ALL the
    router's experts: ``scores`` (sigmoid), ``biased`` (``scores + bias``:
    what chooses), ``own`` (the reference's own choice), the chosen
    ``experts`` (``forced`` if given, else ``own``) and their ``weights``."""
    with jax.default_matmul_precision("highest"):
        return _route_normed(
            h.astype(jnp.float32), router, bias, forced, top_k, norm_topk_prob, scaling
        )


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "norm_topk_prob", "scaling"))
def route(x, norm, router, bias, forced, *, eps, top_k, norm_topk_prob, scaling):
    """``(normed tokens [tokens, hidden], their routing)``."""
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, norm.astype(jnp.float32), eps).reshape(-1, x.shape[-1])
        return h, _route_normed(h, router, bias, forced, top_k, norm_topk_prob, scaling)


def _routing_settings(cfg: dict) -> dict:
    return dict(
        top_k=cfg["num_experts_per_tok"], norm_topk_prob=bool(cfg["norm_topk_prob"]),
        scaling=float(cfg["routed_scaling_factor"]),
    )


def moe_forward(x, w, cfg, forced=None):
    """``(x + held routed experts(norm(x)), the layer's routing, the normed
    tokens)``."""
    h, routing = route(
        x, w["ffn_norm"], w["router"], w["expert_bias"], forced,
        eps=float(cfg["norm_eps"]), **_routing_settings(cfg),
    )
    # [tokens, experts]: a token's weight of each expert, 0 outside its choices
    chosen = routing["experts"][:, :, None] == jnp.arange(w["router"].shape[-1])[None, None, :]
    dense_weights = jnp.sum(jnp.where(chosen, routing["weights"][:, :, None], 0.0), axis=1)
    first, count = held_block(cfg)
    out = jnp.zeros_like(h)
    for e in range(count):                                       # the SAME held block
        out = out + expert_forward(h, w["w1"][e], w["w3"][e], w["w2"][e], dense_weights[:, first + e])
    return x + out.reshape(x.shape), routing, h


def _mixer(x, layer, kind, cfg):
    eps = float(cfg["norm_eps"])
    if kind == "conv":
        return conv_mixer_forward(x, {k: layer[k] for k in CONV_NAMES}, eps=eps)
    return attention_mixer_forward(
        x, {k: layer[k] for k in ATTENTION_NAMES}, heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], theta=float(cfg["rope_theta"]), eps=eps,
    )


def hidden(weights, tokens, cfg, forced=None):
    """``(the last layer's output, [routing of each EXPERT layer], the
    first expert layer's normed tokens)``."""
    x = weights["embed_tokens"].astype(jnp.float32)[tokens]
    routings, first_normed = [], None
    for i, (kind, layer) in enumerate(zip(layer_kinds(cfg), weights["layers"], strict=True)):
        x = _mixer(x, layer, kind, cfg)
        if i < cfg["num_dense_layers"]:
            x = dense_mlp_forward(x, {k: layer[k] for k in DENSE_MLP_NAMES}, eps=float(cfg["norm_eps"]))
        else:
            x, routing, normed = moe_forward(
                x, layer, cfg, None if forced is None else forced[len(routings)]
            )
            first_normed = normed if first_normed is None else first_normed
            routings.append(routing)
    return x, routings, first_normed


def first_expert_layer(weights, cfg):
    """The first expert layer's weights (``hidden`` gives its normed
    tokens): what ``check_router`` is handed."""
    return next(itertools.islice(weights["layers"], cfg["num_dense_layers"], None))


def logits(weights, tokens, cfg, last=None, forced=None):
    """Reference ``(logits [batch, seq or last, vocab] float32, [routing of
    each EXPERT layer])``. ``weights``: ``{"embed_tokens", "layers":
    iterable of per-layer dicts under this file's names, "embedding_norm"}``
    (the head is ``embed_tokens`` transposed); ``forced``: per expert layer
    the choices ``[tokens, num_experts_per_tok]`` to use instead of the
    reference's own."""
    x, routings, _ = hidden(weights, tokens, cfg, forced)
    return _tied_head(weights, x, cfg, last), routings


def _tied_head(weights, x, cfg, last):
    return head_forward(
        x, weights["embedding_norm"], weights["embed_tokens"].T,
        eps=float(cfg["norm_eps"]), last=last,
    )


def loss(weights, tokens, targets, cfg):
    """Mean token cross-entropy; ``jax.grad`` of this is the reference's
    gradient (the embedding's through both its uses). ``weights``'
    ``layers`` must be a list here (one pass)."""
    out, _ = logits(weights, tokens, cfg)
    logp = jax.nn.log_softmax(out, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def _whole_and_tail(got, want, tolerance, last) -> dict:
    whole = compare(got, want, tolerance)
    tail = slice(-last, None) if last else slice(None)
    end = compare(got[:, tail], want[:, tail], tolerance)
    return {
        "rel_rms": whole["rel_rms"], "last_rel_rms": end["rel_rms"], "max_abs": whole["max_abs"],
        "reference_rms": whole["reference_rms"], "tolerance": tolerance,
        "ok": bool(whole["ok"] and end["ok"]),
    }


def check_conv(conv, weights, tokens, cfg, last=None) -> dict:
    """The program's convolution ALONE, at the cell's own shapes, on a
    float32 operand that is the reference's: ``conv(x, filters)`` (the
    family hands the timed path's ``short_conv`` with no activation) against
    ``causal_conv`` for layer 0's gated input ``B * u`` of ``tokens`` (a conv
    layer whose input is the embedding). Relative RMS error over every
    position and over the last ``last``. The logits cannot see this: the
    mixer's output is rounded to bfloat16 whatever the taps were summed in."""
    if layer_kinds(cfg)[0] != "conv":
        raise NotImplementedError("the convolution is checked on layer 0's operand: a conv layer")
    layer = next(iter(weights["layers"]))
    x = weights["embed_tokens"].astype(jnp.float32)[tokens]
    gated = gated_input(x, {k: layer[k] for k in CONV_NAMES}, eps=float(cfg["norm_eps"]))
    filters = layer["conv"].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(causal_conv)(gated, filters)
    return _whole_and_tail(conv(gated, filters), want, TOLERANCE_CONV, last)


def check_router(program_route, layer, normed, cfg) -> dict:
    """The program's router ALONE on the reference's own normed tokens of
    the first expert layer, rounded to bfloat16 (what the program's router
    is handed): ``program_route(h) -> (experts, weights)`` against
    ``route_normed`` on the same ``h``, forced to the program's choices."""
    h = normed.astype(jnp.bfloat16)
    experts, weights = program_route(h)
    reference = route_normed(
        h, layer["router"], layer["expert_bias"], experts, **_routing_settings(cfg)
    )
    facts = _routing_facts(experts, weights, reference, experts=layer["router"].shape[-1])
    rel = float(facts["weights_rel_rms"])
    shortfall = float(facts["worst_shortfall"])
    return {
        "weights_rel_rms": rel, "worst_shortfall": shortfall,
        "same_set_share": float(facts["same_set_share"]),
        "tolerance": TOLERANCE_ROUTER, "margin": ROUTER_MARGIN,
        "ok": bool(rel <= TOLERANCE_ROUTER and shortfall <= ROUTER_MARGIN and facts["distinct"]),
    }


def check(program_logits, program_routing, weights_fn, tokens, cfg, last=None,
          conv=None, program_route=None) -> dict:
    """The comparison that decides ``correct`` for the forward pass.

    ``program_routing``: the program's routing stacked over its expert
    layers: ``experts`` and ``weights`` ``[layers, tokens, k]``, ``counts``
    ``[layers, k, experts]``, ``held_pairs`` ``[layers]``. ``weights_fn()``
    gives the weights. The result's ``layers`` are the expert layers, in
    order; ``tokens_per_expert_*`` are over the experts HELD here.
    ``held_pairs_pct`` is a program counter: the share of all (token,
    choice) pairs whose expert this chip holds, by the program's own count
    (50 is an even routing at 16 of 32)."""
    top_k = cfg["num_experts_per_tok"]
    first, held = held_block(cfg)
    chosen = program_routing["experts"]
    if chosen.shape[-1] != top_k:
        return {"ok": False, "why": f"{chosen.shape[-1]} experts per token, not {top_k}"}
    weights = weights_fn()
    x, routings, normed = hidden(
        weights, tokens, cfg, forced=[chosen[i] for i in range(chosen.shape[0])]
    )
    forced = _tied_head(weights, x, cfg, last)
    published = compare(program_logits, forced, TOLERANCE)
    positions = _position_errors(program_logits, forced)
    worst_position = float(positions["worst"])
    pairs = chosen.shape[1] * top_k
    layers = []
    for i, reference in enumerate(routings):
        facts = _routing_facts(
            chosen[i], program_routing["weights"][i], reference, experts=router_width(cfg)
        )
        per_expert = np.asarray(facts["tokens_per_expert"]).tolist()
        counted = np.asarray(jnp.sum(program_routing["counts"][i], axis=0)).tolist()
        here = per_expert[first:first + held]
        layers.append({
            "worst_shortfall": float(facts["worst_shortfall"]),
            "distinct": bool(facts["distinct"]),
            "same_set_share": float(facts["same_set_share"]),
            "weights_rel_rms": float(facts["weights_rel_rms"]),
            "tokens_per_expert_max": max(here),
            "tokens_per_expert_mean": sum(here) / held or 1.0,
            "tokens_per_expert_min": min(here),
            # the router's bookkeeping, as reference/moe_decoder.py reads it,
            # and the dispatch's: the pairs it sized the held groups for
            "counts_agree": per_expert == counted,
            "pairs": sum(counted),
            "held_pairs": int(program_routing["held_pairs"][i]),
            "held_pairs_agree": int(program_routing["held_pairs"][i]) == sum(here),
        })
    ok = (
        published["ok"]
        and worst_position <= POSITION_TOLERANCE
        and all(
            l["worst_shortfall"] <= MARGIN and l["distinct"] and l["counts_agree"]
            and l["held_pairs_agree"] and l["pairs"] == pairs
            and l["weights_rel_rms"] <= WEIGHT_TOLERANCE
            for l in layers
        )
    )
    out = {
        "published": published,
        "worst_position_rel_rms": worst_position,
        "worst_position_at": int(positions["at"]),
        "position_rel_rms_p50": float(positions["p50"]),
        "position_rel_rms_p99": float(positions["p99"]),
        "position_tolerance": POSITION_TOLERANCE,
        "margin": MARGIN,
        "weight_tolerance": WEIGHT_TOLERANCE,
        "layers": layers,
        "same_set_share": sum(l["same_set_share"] for l in layers) / len(layers),
        "held_pairs_pct": 100.0 * sum(l["held_pairs"] for l in layers) / (pairs * len(layers)),
        "ok": bool(ok),
    }
    if conv is not None:
        out["conv"] = check_conv(conv, weights_fn(), tokens, cfg, last=last)
        out["ok"] = bool(out["ok"] and out["conv"]["ok"])
    if program_route is not None:
        layer = first_expert_layer(weights_fn(), cfg)
        out["router"] = check_router(program_route, layer, normed, cfg)
        out["ok"] = bool(out["ok"] and out["router"]["ok"])
    return out
