"""Plain reference of the SmallThinker language model as
SmallThinker-21BA3B-Instruct configures it: grouped-query attention at a
head size stated apart from the stream's width, a sliding window and the
rotary embedding on three layers in four and neither on the fourth, over
softmax-routed ReLU-gated experts whose router reads the LAYER's input and
of which THIS CHIP HOLDS A BLOCK, under an untied head; and the comparison
that decides ``correct`` for it.

Written from the published configuration's keys and the model's public
description (the catalog row of the ``model-configs`` guide; Hugging Face
``SmallThinkerForCausalLM`` as known to the builder); the configuration
file's ``assumed`` list says what no key states. ``dense_decoder.py``'s
``rms_norm``, ``rotary``, ``query_block``, ``head_forward`` and ``compare``,
``moe_decoder.py``'s ``_position_errors`` and ``mla_moe_decoder.py``'s
``_routing_facts`` are used as they are.

Every layer, pre-norm (eps ``rms_norm_eps``), ``x`` the residual stream at
the LAYER's input::

    r  = x W_r                              (float32; x un-normed)
    x' = x + Attn(RMSNorm(x; input_layernorm))
    x'' = x' + MoE(RMSNorm(x'; post_attention_layernorm); routed by r)

The file's layer ``i`` is published layer ``layer_offset + i``; its
``sliding_window_layout[i]`` and ``rope_layout[i]`` (the same list) say
which attention it has.

* attention, every layer: q of ``num_attention_heads`` heads, k / v of
  ``num_key_value_heads``, each of ``head_dim`` (28 x 128 = 3584 on a
  stream of 2560); KV head ``j`` serves query heads ``j * group ..``;
  softmax at scale ``head_dim^-1/2``; no bias, no q / k norm; ``W_o``.
* layout 0: NO rotary embedding; query i sees every key ``j <= i``.
* layout 1: rotate-half RoPE over the whole head at ``rope_theta``; query i
  sees keys ``i - sliding_window_size < j <= i`` (its own position counted).
  Both as a blocked softmax under an EXPLICIT mask ``(j <= i) & (j > i -
  window)``: the queries walk in blocks (a Python loop) so that the
  ``[heads, block, seq]`` float32 scores fit at 16,384 positions; each block
  sees the whole key sequence under its mask: the same result.
* the experts, every layer: ``r`` over ALL the routed experts (the router's
  width, ``published.moe_num_primary_experts``); the
  ``moe_num_active_primary_experts`` largest are chosen; the weights are the
  softmax over those chosen logits (``moe_primary_router_apply_softmax``;
  the same numbers as a softmax over all of them, its top k, divided by
  their sum: ``norm_topk_prob``). ``MoE(m) = sum over the chosen experts
  THAT ARE HELD HERE of p_e W_down,e (relu(m W_gate,e) * (m W_up,e))``: the
  file's ``moe_num_primary_experts`` experts from ``first_expert_held`` on
  are held, a Python loop over that same block, each applied densely to all
  tokens; what an absent expert would have added is left out, here as in the
  program. No shared expert, no bias on the choice, no balance loss.
* final RMSNorm and an untied head over the file's ``vocab_size`` rows.

``jax.numpy`` only, float32 throughout, ``default_matmul_precision
("highest")``, no kernel, no sort, no grouped matmul, no layer scan.
Imports nothing from ``ray_tpu.models`` or ``ray_tpu.ops``. Departures from
the source: weights arrive as ``[in, out]`` matrices and ``[held, in, out]``
expert stacks (the checkpoint's are ``[out, in]`` and one module an
expert): storage layouts; the depth, the experts held and the vocabulary are
the chip's share (the configuration file's ``deployment``).

``check`` has two parts. The logits are compared with the reference FORCED
to the program's expert choices, and the choices and weights held to the
reference's own logits (``mla_moe_decoder.py`` has the argument). The
logits cannot see the router's precision (fresh logits lie within 0.1 of one
another and the six weights within a percent of 1/6), so the program's
router is checked ALONE at the cell's shapes, as LFM2's is:
``check_router``, on the reference's own un-normed input of the second
layer.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.dense_decoder import (
    compare, head_forward, query_block, rms_norm, rotary,
)
from benchmarks.reference.mla_moe_decoder import _routing_facts
from benchmarks.reference.moe_decoder import _position_errors

# The limits of the comparison that decides ``correct`` (``check``), each
# from two readings on a v5e at the published widths and 16,384 positions
# (my chip runs, PR 45; PERF.md section 6 has the seeds): the largest the
# program gives over its seeds, and what a CONTROL gives
# (``harness/window_moe_controls.py`` prints both): the program with one term
# of another model, or its router computed in the nearest precision below
# the one the configuration states. Every control comes out NOT correct by
# one of these; each limit lies between its two readings with room on both
# sides.
#
# TOLERANCE, POSITION_TOLERANCE: relative RMS error of the program's logits
# against the reference FORCED to the program's expert choices, over the
# compared positions, and at the worst single position. Four pre-norm layers
# in bfloat16 whose routed share is HALF a weighted sum (32 of 64 experts
# held): the program reads 5.1e-3 to 5.6e-3 and 5.6e-3 to 6.2e-3 over eleven
# seeds, evenly over the positions (median 5.15e-3, 99th percentile 5.6e-3):
# rounding, no token's error. The nearest control, the window IGNORED on the
# three window layers (16,384 keys where 4096 are meant), reads 1.07e-1 to
# 1.30e-1 and 1.12e-1 to 1.38e-1 on two seeds; SiLU for ReLU 1.35e-1 to
# 1.53e-1, the router fed the normed input 1.62e-1 to 1.87e-1, RoPE on the
# global layer 2.92e-1 to 3.16e-1. 2.5e-2 and 3e-2 are 4.5 and 4.8 times the
# program's largest and 4.3 and 3.7 times under the nearest control's.
TOLERANCE = 2.5e-2
POSITION_TOLERANCE = 3e-2
# MARGIN: in the whole model every expert the program chose must have a
# REFERENCE logit of at least the k-th largest minus MARGIN (units of the
# logit, whose RMS grows from 0.02 in layer 0, a bare embedding row's, to 1.2
# in layer 3). The program's router reads a bfloat16 stream that is off the
# reference's by the layers before it, so this grows with depth: 0 to 8e-8
# in layer 0, 1.9e-2 to 2.8e-2 in layer 3. The router fed the NORMED input
# reads 2.19 to 2.27 (another model's choices), the other controls 0.35 to
# 1.78 (a stream that is another model's). WEIGHT_TOLERANCE: relative RMS
# error of the program's weights against the softmax of the reference's own
# logits of the same experts: 1e-7 in layer 0, 3.9e-3 to 5.4e-3 in layer 3;
# the window ignored 4.6e-2 to 6.5e-2, the router fed the normed input 5.5e-1
# to 6.7e-1. 0.1 and 1.6e-2 are 3.6 and 3 times the program's largest, 3.5
# and 2.9 times under the nearest control's.
MARGIN = 0.1
WEIGHT_TOLERANCE = 1.6e-2
# TOLERANCE_ROUTER, ROUTER_MARGIN: the program's router ALONE
# (``models/transformer.py::_moe_mlp`` under ``router_precision="highest"``:
# float32 logits of the bfloat16 stream, the top k, their softmax) against
# ``route`` at highest precision on the SAME bfloat16-rounded input of the
# second layer (logits of RMS 0.29), the reference forced to the program's
# choices: relative RMS error of the weights, and the largest shortfall of a
# chosen expert's reference logit under the k-th largest. The program reads
# 8.3e-8 to 8.4e-8 and 0.0 on eleven seeds (every token the reference's own
# six). With the logits rounded to bfloat16 before the choice and the softmax
# it reads 8.0e-4 to 8.9e-4 and 2.8e-3 to 3.6e-3 (1.5 % of the tokens choose
# another expert); with the matmul at the platform's default precision (one
# bfloat16 pass: the router's float32 weights rounded on their way in) 4.4e-4
# and 1.6e-3 to 1.9e-3: NOT correct. 6e-6 is
# the geometric middle of the program's and the nearer control's; 1e-5 is a
# hundred float32 roundings of such a logit and 160 times under the control.
# The whole-model limits above cannot see this: both controls read the
# program's 5.3e-3 there.
TOLERANCE_ROUTER = 6e-6
ROUTER_MARGIN = 1e-5

ATTENTION_NAMES = ("input_layernorm", "q_proj", "k_proj", "v_proj", "o_proj")
MOE_NAMES = ("post_attention_layernorm", "router", "gate", "up", "down")


def layouts(cfg: dict) -> list[int]:
    """1 (window and rope) / 0 (neither) of the file's layers: its own
    ``sliding_window_layout``, which ``rope_layout`` has to repeat."""
    layout = list(cfg["sliding_window_layout"])
    if list(cfg["rope_layout"]) != layout:
        raise ValueError(
            f"{cfg.get('name')}: rope_layout {cfg['rope_layout']!r} differs from "
            f"sliding_window_layout {layout!r}: this block turns exactly the window layers"
        )
    if len(layout) != cfg["num_hidden_layers"] or set(layout) - {0, 1}:
        raise ValueError(f"sliding_window_layout {layout!r} for {cfg['num_hidden_layers']} layers")
    return layout


def held_block(cfg: dict) -> tuple[int, int]:
    """``(first, count)`` of the experts this chip holds."""
    return cfg.get("first_expert_held", 0), cfg["moe_num_primary_experts"]


def router_width(cfg: dict) -> int:
    """The experts the router scores: the published count."""
    return cfg.get("published", {}).get(
        "moe_num_primary_experts", cfg["moe_num_primary_experts"]
    )


def banded_attention(q, k, v, window=None):
    """q: [b, s, H, d]; k, v: [b, s, KV, d] -> [b, s, H, d]. Query i sees
    keys ``j <= i`` and, under ``window``, ``j > i - window``."""
    batch, seq, heads, head_dim = q.shape
    group = heads // k.shape[2]
    k = jnp.repeat(k, group, axis=2)   # KV head j serves query heads j*group ..
    v = jnp.repeat(v, group, axis=2)
    block = query_block(batch, heads, seq)
    key_pos = jnp.arange(seq)[None, :]
    out = []
    for start in range(0, seq, block):
        qb = q[:, start : start + block]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(jnp.float32(head_dim))
        query_pos = (start + jnp.arange(block))[:, None]
        visible = key_pos <= query_pos
        if window is not None:
            visible = visible & (key_pos > query_pos - window)
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v))
    return jnp.concatenate(out, axis=1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta", "window", "eps"))
def attention_forward(x, w, *, heads, kv_heads, theta, window, eps):
    """x + attention(norm(x)). ``theta`` None: no rotary embedding;
    ``window`` None: the whole context. x: [b, s, hidden] float32."""
    with jax.default_matmul_precision("highest"):
        w = {name: value.astype(jnp.float32) for name, value in w.items()}
        batch, seq, _ = x.shape
        h = rms_norm(x, w["input_layernorm"], eps)
        q = (h @ w["q_proj"]).reshape(batch, seq, heads, -1)
        k = (h @ w["k_proj"]).reshape(batch, seq, kv_heads, -1)
        v = (h @ w["v_proj"]).reshape(batch, seq, kv_heads, -1)
        if theta is not None:
            q, k = rotary(q, theta), rotary(k, theta)
        attn = banded_attention(q, k, v, window)
        return x + attn.reshape(batch, seq, -1) @ w["o_proj"]


def _route(x, router, forced, top_k):
    logits = x @ router.astype(jnp.float32)
    own = jax.lax.top_k(logits, top_k)[1]
    experts = own if forced is None else forced
    weights = jax.nn.softmax(jnp.take_along_axis(logits, experts, axis=-1), axis=-1)
    # "biased": what chooses, under ``_routing_facts``'s name for it
    return {"biased": logits, "own": own, "experts": experts, "weights": weights}


@functools.partial(jax.jit, static_argnames=("top_k",))
def route(x, router, forced, *, top_k):
    """The routing of UN-NORMED tokens ``x`` ``[tokens, hidden]`` over ALL
    the router's experts: the float32 logits (``biased``), the reference's
    ``own`` choice, the chosen ``experts`` (``forced`` if given, else ``own``)
    and their ``weights``, the softmax over the chosen logits."""
    with jax.default_matmul_precision("highest"):
        return _route(x.astype(jnp.float32), router, forced, top_k)


@jax.jit
def relu_expert_forward(m, gate, up, down, weight):
    """One ReLU-gated expert applied densely to ALL tokens, weighted per
    token (``weight`` is zero where the token did not choose it)."""
    with jax.default_matmul_precision("highest"):
        gate, up, down = (w.astype(jnp.float32) for w in (gate, up, down))
        return weight[:, None] * ((jax.nn.relu(m @ gate) * (m @ up)) @ down)


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, norm, *, eps):
    return rms_norm(x, norm.astype(jnp.float32), eps).reshape(-1, x.shape[-1])


def moe_forward(x, layer_input, w, cfg, forced=None):
    """``(x + held routed experts(norm(x)), the layer's routing)``, routed by
    ``layer_input``, the stream before the layer's attention."""
    routing = route(
        layer_input.reshape(-1, x.shape[-1]), w["router"], forced,
        top_k=cfg["moe_num_active_primary_experts"],
    )
    m = _normed(x, w["post_attention_layernorm"], eps=float(cfg["rms_norm_eps"]))
    # [tokens, experts]: a token's weight of each expert, 0 outside its choices
    chosen = routing["experts"][:, :, None] == jnp.arange(w["router"].shape[-1])[None, None, :]
    dense_weights = jnp.sum(jnp.where(chosen, routing["weights"][:, :, None], 0.0), axis=1)
    first, count = held_block(cfg)
    out = jnp.zeros_like(m)
    for e in range(count):                                       # the SAME held block
        out = out + relu_expert_forward(
            m, w["gate"][e], w["up"][e], w["down"][e], dense_weights[:, first + e]
        )
    return x + out.reshape(x.shape), routing


def hidden(weights, tokens, cfg, forced=None):
    """``(the last layer's output, [routing of each layer], the second
    layer's input)``."""
    x = weights["embed_tokens"].astype(jnp.float32)[tokens]
    routings, second_input = [], None
    for i, (layout, layer) in enumerate(zip(layouts(cfg), weights["layers"], strict=True)):
        if i == 1:
            second_input = x
        layer_input = x
        x = attention_forward(
            x, {k: layer[k] for k in ATTENTION_NAMES}, heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"],
            theta=float(cfg["rope_theta"]) if layout else None,
            window=int(cfg["sliding_window_size"]) if layout else None,
            eps=float(cfg["rms_norm_eps"]),
        )
        x, routing = moe_forward(
            x, layer_input, layer, cfg, None if forced is None else forced[i]
        )
        routings.append(routing)
    return x, routings, second_input


def _head(weights, x, cfg, last):
    return head_forward(
        x, weights["norm"], weights["lm_head"], eps=float(cfg["rms_norm_eps"]), last=last
    )


def logits(weights, tokens, cfg, last=None, forced=None):
    """Reference ``(logits [batch, seq or last, vocab] float32, [routing of
    each layer])``. ``weights``: ``{"embed_tokens", "layers": iterable of
    per-layer dicts under this file's names, "norm", "lm_head"}``;
    ``forced``: per layer the choices ``[tokens, k]`` to use instead of the
    reference's own."""
    x, routings, _ = hidden(weights, tokens, cfg, forced)
    return _head(weights, x, cfg, last), routings


def loss(weights, tokens, targets, cfg):
    """Mean token cross-entropy; ``jax.grad`` of this is the reference's
    gradient. ``weights``' ``layers`` must be a list here (one pass)."""
    out, _ = logits(weights, tokens, cfg)
    logp = jax.nn.log_softmax(out, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def check_router(program_route, router, layer_input, cfg) -> dict:
    """The program's router ALONE on the reference's own un-normed input of
    the second layer, rounded to bfloat16 (what the program's router is
    handed): ``program_route(x) -> (experts, weights)`` against ``route`` on
    the same ``x``, forced to the program's choices."""
    x = layer_input.reshape(-1, layer_input.shape[-1]).astype(jnp.bfloat16)
    experts, weights = program_route(x)
    reference = route(x, router, experts, top_k=cfg["moe_num_active_primary_experts"])
    facts = _routing_facts(experts, weights, reference, experts=router.shape[-1])
    rel = float(facts["weights_rel_rms"])
    shortfall = float(facts["worst_shortfall"])
    return {
        "weights_rel_rms": rel, "worst_shortfall": shortfall,
        "same_set_share": float(facts["same_set_share"]),
        "logits_rms": float(jnp.sqrt(jnp.mean(reference["biased"] ** 2))),
        "tolerance": TOLERANCE_ROUTER, "margin": ROUTER_MARGIN,
        "ok": bool(rel <= TOLERANCE_ROUTER and shortfall <= ROUTER_MARGIN and facts["distinct"]),
    }


def check(program_logits, program_routing, weights_fn, tokens, cfg, last=None,
          program_route=None) -> dict:
    """The comparison that decides ``correct`` for the forward pass.

    ``program_routing``: the program's routing stacked over its layers:
    ``experts`` and ``weights`` ``[layers, tokens, k]``, ``counts``
    ``[layers, k, experts]``, ``held_pairs`` ``[layers]``. ``weights_fn()``
    gives the weights. ``tokens_per_expert_*`` are over the experts HELD
    here. ``held_pairs_pct`` is a program counter: the share of all (token,
    choice) pairs whose expert this chip holds, by the program's own count
    (50 is an even routing at 32 of 64)."""
    top_k = cfg["moe_num_active_primary_experts"]
    first, held = held_block(cfg)
    chosen = program_routing["experts"]
    if chosen.shape[-1] != top_k:
        return {"ok": False, "why": f"{chosen.shape[-1]} experts per token, not {top_k}"}
    weights = weights_fn()
    x, routings, second_input = hidden(
        weights, tokens, cfg, forced=[chosen[i] for i in range(chosen.shape[0])]
    )
    forced = _head(weights, x, cfg, last)
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
            "logits_rms": float(jnp.sqrt(jnp.mean(reference["biased"] ** 2))),
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
    if program_route is not None:
        router = next(itertools.islice(weights_fn()["layers"], 1, None))["router"]
        out["router"] = check_router(program_route, router, second_input, cfg)
        out["ok"] = bool(out["ok"] and out["router"]["ok"])
    return out
