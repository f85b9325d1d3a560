"""Plain reference of the AFMoE language model as Trinity-Mini configures
it: output-gated grouped-query attention under per-head q / k norms, a
sliding window and the rotary embedding on three layers in four and neither
on the fourth, FOUR norms a layer (one before and one after each branch),
an embedding scaled by the square root of the stream's width, leading dense
layers, then sigmoid-routed experts chosen under a selection bias that a
RULE moves once a step, of which THIS CHIP HOLDS A BLOCK, beside one shared
expert, under an untied head; and the comparison that decides ``correct``.

Written from the published configuration's keys and the model's public
description (the catalog row of the ``model-configs`` guide; Hugging Face
``AfmoeForCausalLM`` and torchtitan's MoE, whose names ``score_func``,
``route_norm``, ``route_scale``, ``load_balance_coeff`` the file uses, as
known to the builder); the configuration file's ``assumed`` list says what no
key states. ``dense_decoder.py``'s ``rms_norm``, ``rotary``, ``head_forward``
and ``compare``, ``window_moe_decoder.py``'s ``banded_attention``,
``moe_decoder.py``'s ``expert_forward`` and ``_position_errors`` and
``mla_moe_decoder.py``'s ``_routing_facts`` are used as they are.

``x0 = embed[tokens] * sqrt(hidden_size)`` (``mup_enabled``). Every layer,
eps ``rms_norm_eps``::

    h  = RMSNorm(x; input_layernorm)
    o  = Attn(q_norm(h W_q), k_norm(h W_k), h W_v) * sigmoid(h W_g)
    x' = x + RMSNorm(o W_o; post_attention_layernorm)
    m  = RMSNorm(x'; pre_mlp_layernorm)
    x'' = x' + RMSNorm(MLP(m); post_mlp_layernorm)

The file's layer ``i`` is published layer ``layer_offset + i``; its
``layer_types[i]`` says which attention it has.

* attention, every layer: q of ``num_attention_heads`` heads, k / v of
  ``num_key_value_heads``, each of ``head_dim``; q and k each under ONE
  learned RMSNorm weight of ``head_dim`` a head, before any rotary
  embedding; KV head ``j`` serves query heads ``j * group ..``; softmax at
  scale ``head_dim^-1/2``; the heads' concatenated outputs times
  ``sigmoid(h W_g)`` element by element; ``W_o``; no bias.
* ``sliding_attention``: rotate-half RoPE over the whole head at
  ``rope_theta``; query i sees keys ``i - sliding_window < j <= i``.
  ``full_attention``: NO rotary embedding; query i sees every ``j <= i``.
* the first ``num_dense_layers`` layers: ``W_down(silu(W_gate m) * W_up m)``
  at ``intermediate_size``.
* the other layers: ``s = sigmoid(m W_r)`` in float32 over ALL the routed
  experts (the router's width, ``published.num_experts``); the
  ``num_experts_per_tok`` largest of ``s + b`` are chosen (``b``:
  ``expert_bias``); the weights are ``s`` of the chosen, divided by their sum
  + 1e-20 (``route_norm``), times ``route_scale``. ``MLP(m) = shared(m) + sum
  over the chosen experts THAT ARE HELD HERE of w_e expert_e(m)``, each a
  SwiGLU of ``moe_intermediate_size``: the file's ``num_experts`` experts
  from ``first_expert_held`` on are held, a Python loop over that block,
  each applied densely to all tokens; what an absent expert would have added
  is left out, here as in the program.
* final RMSNorm and an untied head over the file's ``vocab_size`` rows.
* ``bias_rule``: what moves ``b`` once a step (NumPy, float32): ``n[e]`` the
  (token, choice) pairs of the step that chose ``e``, ``d = c sign(mean(n) -
  n)`` with ``c`` = ``load_balance_coeff``, ``d -= mean(d)``, ``b + d``.

``jax.numpy`` only, float32 throughout, ``default_matmul_precision
("highest")``, no kernel, no sort, no grouped matmul, no layer scan.
Imports nothing from ``ray_tpu.models`` or ``ray_tpu.ops``. Departures from
the source: weights arrive as ``[in, out]`` matrices and ``[held, in, out]``
expert stacks: storage layouts; the depth, the experts held and the
vocabulary are the chip's share (the configuration file's ``deployment``).

``check`` has three parts. The logits are compared with the reference FORCED
to the program's expert choices, and the choices and weights held to the
reference's own scores (``mla_moe_decoder.py`` has the argument). The logits
cannot see the router's precision, so the program's router is checked ALONE
at the cell's shapes: ``check_router``, on the reference's own normed input
of the first expert layer. And the program's bias rule on the check's own
counts is held to ``bias_rule``, sign for sign: ``check_bias_rule``.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.dense_decoder import compare, head_forward, rms_norm, rotary
from benchmarks.reference.mla_moe_decoder import _routing_facts
from benchmarks.reference.moe_decoder import _position_errors, expert_forward
from benchmarks.reference.window_moe_decoder import banded_attention

# The limits of the comparison that decides ``correct`` (``check``), each
# from two readings on a v5e at the published widths and 16,384 positions
# (my chip runs, PR 62, calls 1 and 2; PERF.md section 6 has the seeds): the
# largest the program gives over its seeds, and what a CONTROL gives
# (``harness/gated_window_moe_controls.py`` prints both): the program with one
# term of another model, or its router computed in the nearest precision below
# the one the configuration states. Every control comes out NOT correct by one
# of these; each limit lies between its two readings with room on both sides.
#
# TOLERANCE, POSITION_TOLERANCE: relative RMS error of the program's logits
# against the reference FORCED to the program's expert choices, over the
# compared positions, and at the worst single position. Five layers in
# bfloat16 whose every branch ends in a norm (a branch's rounding is normed
# to unit scale before it joins the stream): the program reads 8.71e-3 to
# 9.16e-3 and 9.49e-3 to 1.025e-2 over sixteen seeds, evenly over the
# positions: rounding, no token's error. The nearest control, the rotary
# embedding ON the global layer, reads 7.00e-2 to 7.41e-2 and 8.86e-2 to
# 9.38e-2 on two seeds; the window ignored 0.41 and 0.49 to 0.56, the gate
# dropped 0.48 to 0.49, the embedding unscaled 0.79 to 0.81, the two
# branch-output norms dropped 1.15 to 1.16. 2.5e-2 and 3e-2 are 2.7 and 2.9
# times the program's largest and 2.8 and 2.95 times under the nearest
# control's.
TOLERANCE = 2.5e-2
POSITION_TOLERANCE = 3e-2
# MARGIN: in the whole model every expert the program chose must have a
# REFERENCE ``s + b`` of at least the k-th largest minus MARGIN (scores lie in
# (0, 1)); the program's router reads a bfloat16 stream that is off the
# reference's by the layers before it, so this grows with depth: the program
# reads 7.8e-3 to 1.12e-2 at the worst layer, RoPE on the global layer 0.100
# to 0.121, the other controls 0.35 to 0.65. WEIGHT_TOLERANCE: relative RMS
# error of the program's weights against the reference's own scores of the
# same experts, renormalised and scaled: 2.1e-3 to 2.9e-3 (3.2e-3 to 3.7e-3
# with every token on the same eight experts, the steering that was not
# kept); RoPE on the global layer 1.92e-2 to 2.13e-2, the others 0.10 to 0.41.
# 0.035 and 8e-3 are 3.1 and 2.2 times the program's largest (2.7 of the
# kept steering's), 2.9 and 2.4
# times under the nearest control's.
MARGIN = 0.035
WEIGHT_TOLERANCE = 8e-3
# TOLERANCE_ROUTER, ROUTER_MARGIN: the program's router ALONE
# (``models/transformer.py::_moe_mlp`` under ``router_precision="highest"``:
# float32 logits of the bfloat16 normed stream, sigmoid, the choice under the
# bias, renormalised and scaled) against ``route`` at highest precision on
# the SAME bfloat16-rounded normed input of the first expert layer, the
# reference forced to the program's choices: relative RMS error of the
# weights, and the largest shortfall of a chosen expert's reference ``s + b``
# under the k-th largest. The program reads 0.0 and 0.0 on every seed of the
# chip (the same float32 operations in the same order; every token the
# reference's own eight; 6e-8 on the CPU). With the matmul at the platform's
# default precision (one bfloat16 pass: the router's float32 weights rounded
# on their way in) it reads 5.1e-4 to 5.2e-4 and 1.1e-3 to 1.5e-3 (0.65 % of
# the tokens choose another expert); with the scores rounded to bfloat16
# before the choice and the weights 1.5e-3 to 1.6e-3 and 3.5e-3 to 3.7e-3:
# NOT correct. 6e-6 and 1e-5 are a hundred float32 roundings of a score and
# 85 and 106 times under the nearer control's. The whole-model limits above
# cannot see this: both controls read the program's 8.9e-3 there.
TOLERANCE_ROUTER = 6e-6
ROUTER_MARGIN = 1e-5
# BIAS_RULE_TOLERANCE: the largest difference between the program's step of
# a bias (``transformer.router_bias_update`` less the bias it read) and
# ``bias_rule``'s on the same counts, as a share of the rate. Both add a
# float32 step of the rate's size to a float32 bias of up to 2: one rounding
# of the sum is 2.4e-7, 2.4e-4 of a rate of 1e-3 (the chip reads 0.0 on every
# seed); a wrong sign is 1 or 2 of it, a step left uncentred 0.25 to 0.75 (16
# held of 128 all over the mean). Every step's SIGN has to agree besides.
BIAS_RULE_TOLERANCE = 1e-2

ATTENTION_NAMES = (
    "input_layernorm", "q_proj", "k_proj", "v_proj", "gate_proj", "q_norm", "k_norm",
    "o_proj", "post_attention_layernorm",
)
LAYER_TYPES = ("sliding_attention", "full_attention")


def layer_kinds(cfg: dict) -> list[str]:
    """``layer_types`` of the file's layers (``LAYER_TYPES``)."""
    kinds = list(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - set(LAYER_TYPES):
        raise ValueError(f"layer_types {kinds!r} for {cfg['num_hidden_layers']} layers")
    return kinds


def held_block(cfg: dict) -> tuple[int, int]:
    """``(first, count)`` of the experts this chip holds."""
    return cfg.get("first_expert_held", 0), cfg["num_experts"]


def router_width(cfg: dict) -> int:
    """The experts the router scores: the published count."""
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def embed(weights, tokens, cfg):
    """The stream's first value: the embedding's rows times ``sqrt(hidden)``
    under ``mup_enabled``."""
    x = weights["embed_tokens"].astype(jnp.float32)[tokens]
    return x * jnp.sqrt(jnp.float32(cfg["hidden_size"])) if cfg["mup_enabled"] else x


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta", "window", "eps"))
def attention_forward(x, w, *, heads, kv_heads, theta, window, eps):
    """``x + post_norm(gated attention(norm(x)))``. ``theta`` None: no rotary
    embedding; ``window`` None: the whole context. x: [b, s, hidden] float32."""
    with jax.default_matmul_precision("highest"):
        w = {name: value.astype(jnp.float32) for name, value in w.items()}
        batch, seq, _ = x.shape
        h = rms_norm(x, w["input_layernorm"], eps)
        q = rms_norm((h @ w["q_proj"]).reshape(batch, seq, heads, -1), w["q_norm"], eps)
        k = rms_norm((h @ w["k_proj"]).reshape(batch, seq, kv_heads, -1), w["k_norm"], eps)
        v = (h @ w["v_proj"]).reshape(batch, seq, kv_heads, -1)
        if theta is not None:
            q, k = rotary(q, theta), rotary(k, theta)
        attn = banded_attention(q, k, v, window).reshape(batch, seq, -1)
        out = (attn * jax.nn.sigmoid(h @ w["gate_proj"])) @ w["o_proj"]
        return x + rms_norm(out, w["post_attention_layernorm"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, norm, *, eps):
    return rms_norm(x, norm.astype(jnp.float32), eps).reshape(-1, x.shape[-1])


@functools.partial(jax.jit, static_argnames=("eps",))
def _joined(x, out, norm, *, eps):
    """``x + post_norm(out)``: a branch's output joins the stream."""
    return x + rms_norm(out.reshape(x.shape), norm.astype(jnp.float32), eps)


def _route(m, router, bias, forced, top_k, norm, scale):
    scores = jax.nn.sigmoid(m @ router.astype(jnp.float32))
    biased = scores + bias.astype(jnp.float32)
    own = jax.lax.top_k(biased, top_k)[1]
    experts = own if forced is None else forced
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if norm:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return {
        "scores": scores, "biased": biased, "own": own, "experts": experts,
        "weights": weights * scale,
    }


@functools.partial(jax.jit, static_argnames=("top_k", "norm", "scale"))
def route(m, router, bias, forced, *, top_k, norm, scale):
    """The routing of NORMED tokens ``m`` ``[tokens, hidden]`` over ALL the
    router's experts: the float32 sigmoid ``scores``, ``biased`` (``scores +
    bias``: what chooses), the reference's ``own`` choice, the chosen
    ``experts`` (``forced`` if given, else ``own``) and their ``weights``."""
    with jax.default_matmul_precision("highest"):
        return _route(m.astype(jnp.float32), router, bias, forced, top_k, norm, scale)


def _routing_settings(cfg: dict) -> dict:
    return {
        "top_k": cfg["num_experts_per_tok"], "norm": bool(cfg["route_norm"]),
        "scale": float(cfg["route_scale"]),
    }


def mlp_forward(x, w, cfg, dense: bool, forced=None):
    """``(x + post_norm(MLP(pre_norm(x))), the layer's routing or None, m)``."""
    eps = float(cfg["rms_norm_eps"])
    m = _normed(x, w["pre_mlp_layernorm"], eps=eps)
    ones = jnp.ones(m.shape[0], jnp.float32)
    if dense:
        out = expert_forward(m, w["mlp_gate_proj"], w["mlp_up_proj"], w["mlp_down_proj"], ones)
        return _joined(x, out, w["post_mlp_layernorm"], eps=eps), None, m
    routing = route(m, w["router"], w["expert_bias"], forced, **_routing_settings(cfg))
    # [tokens, experts]: a token's weight of each expert, 0 outside its choices
    chosen = routing["experts"][:, :, None] == jnp.arange(w["router"].shape[-1])[None, None, :]
    dense_weights = jnp.sum(jnp.where(chosen, routing["weights"][:, :, None], 0.0), axis=1)
    first, count = held_block(cfg)
    out = jnp.zeros_like(m)
    for e in range(count):                                       # the SAME held block
        out = out + expert_forward(
            m, w["gate"][e], w["up"][e], w["down"][e], dense_weights[:, first + e]
        )
    if cfg["num_shared_experts"]:
        out = out + expert_forward(m, w["shared_gate"], w["shared_up"], w["shared_down"], ones)
    return _joined(x, out, w["post_mlp_layernorm"], eps=eps), routing, m


def hidden(weights, tokens, cfg, forced=None):
    """``(the last layer's output, [routing of each EXPERT layer], the first
    expert layer's normed tokens)``."""
    x = embed(weights, tokens, cfg)
    routings, first_normed = [], None
    for i, (kind, layer) in enumerate(zip(layer_kinds(cfg), weights["layers"], strict=True)):
        sliding = kind == "sliding_attention"
        x = attention_forward(
            x, {k: layer[k] for k in ATTENTION_NAMES}, heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"],
            theta=float(cfg["rope_theta"]) if sliding else None,
            window=int(cfg["sliding_window"]) if sliding else None,
            eps=float(cfg["rms_norm_eps"]),
        )
        dense = i < cfg["num_dense_layers"]
        x, routing, m = mlp_forward(
            x, layer, cfg, dense,
            None if forced is None or dense else forced[len(routings)],
        )
        if not dense:
            routings.append(routing)
            first_normed = m if first_normed is None else first_normed
    return x, routings, first_normed


def _head(weights, x, cfg, last):
    return head_forward(
        x, weights["norm"], weights["lm_head"], eps=float(cfg["rms_norm_eps"]), last=last
    )


def logits(weights, tokens, cfg, last=None, forced=None):
    """Reference ``(logits [batch, seq or last, vocab] float32, [routing of
    each EXPERT layer])``. ``weights``: ``{"embed_tokens", "layers": iterable
    of per-layer dicts under this file's names, "norm", "lm_head"}``;
    ``forced``: per expert layer the choices ``[tokens, k]`` to use instead of
    the reference's own."""
    x, routings, _ = hidden(weights, tokens, cfg, forced)
    return _head(weights, x, cfg, last), routings


def loss(weights, tokens, targets, cfg):
    """Mean token cross-entropy (the file states no auxiliary term);
    ``jax.grad`` of this is the reference's gradient. ``weights``'
    ``layers`` must be a list here (one pass)."""
    out, _ = logits(weights, tokens, cfg)
    logp = jax.nn.log_softmax(out, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def bias_rule(bias, tokens_per_expert, rate: float):
    """One step of auxiliary-loss-free balancing on one layer's ``bias``
    ``[experts]``: NumPy float32, from the (token, choice) pairs each expert
    got in THIS step. ``sign(0)`` is 0."""
    n = np.asarray(tokens_per_expert, np.float32)
    step = np.float32(rate) * np.sign(n.mean(dtype=np.float32) - n).astype(np.float32)
    return np.asarray(bias, np.float32) + (step - step.mean(dtype=np.float32))


def first_expert_layer(weights, cfg):
    """The first expert layer's weights (``hidden`` gives its normed tokens):
    what ``check_router`` is handed."""
    return next(itertools.islice(weights["layers"], cfg["num_dense_layers"], None))


def check_router(program_route, layer, normed, cfg) -> dict:
    """The program's router ALONE on the reference's own normed tokens of the
    first expert layer, rounded to bfloat16 (what the program's router is
    handed): ``program_route(m) -> (experts, weights)`` against ``route`` on
    the same ``m``, forced to the program's choices."""
    m = normed.astype(jnp.bfloat16)
    experts, weights = program_route(m)
    reference = route(
        m, layer["router"], layer["expert_bias"], experts, **_routing_settings(cfg)
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


def check_bias_rule(program_biases, biases, tokens_per_expert, cfg) -> dict:
    """The program's rule against ``bias_rule``, a layer at a time, on the
    counts of the check's own routing: ``program_biases`` ``[layers,
    experts]`` is what the program's rule made of ``biases`` and its own
    counts. Every expert's step has the reference's SIGN, and lies within
    ``BIAS_RULE_TOLERANCE`` of the rate of it."""
    rate = float(cfg["load_balance_coeff"])
    worst, signs = 0.0, True
    for got, bias, counts in zip(program_biases, biases, tokens_per_expert, strict=True):
        bias = np.asarray(bias, np.float32)
        want = bias_rule(bias, counts, rate) - bias
        step = np.asarray(got, np.float32) - bias
        signs = signs and bool(np.all(np.sign(step) == np.sign(want)))
        worst = max(worst, float(np.max(np.abs(step - want))) / rate)
    return {
        "worst_over_rate": worst, "signs_agree": signs, "tolerance": BIAS_RULE_TOLERANCE,
        "ok": bool(signs and worst <= BIAS_RULE_TOLERANCE),
    }


def check(program_logits, program_routing, weights_fn, tokens, cfg, last=None,
          program_route=None, program_biases=None) -> dict:
    """The comparison that decides ``correct`` for the forward pass.

    ``program_routing``: the program's routing stacked over its expert
    layers: ``experts`` and ``weights`` ``[layers, tokens, k]``, ``counts``
    ``[layers, k, experts]``, ``held_pairs`` ``[layers]``. ``weights_fn()``
    gives the weights. The result's ``layers`` are the expert layers, in
    order; ``tokens_per_expert_*`` are over the experts HELD here.
    ``held_pairs_pct`` is a program counter: the share of all (token, choice)
    pairs whose expert this chip holds, by the program's own count."""
    top_k = cfg["num_experts_per_tok"]
    first, held = held_block(cfg)
    chosen = program_routing["experts"]
    if chosen.shape[-1] != top_k:
        return {"ok": False, "why": f"{chosen.shape[-1]} experts per token, not {top_k}"}
    weights = weights_fn()
    x, routings, normed = hidden(
        weights, tokens, cfg, forced=[chosen[i] for i in range(chosen.shape[0])]
    )
    forced = _head(weights, x, cfg, last)
    published = compare(program_logits, forced, TOLERANCE)
    positions = _position_errors(program_logits, forced)
    worst_position = float(positions["worst"])
    pairs = chosen.shape[1] * top_k
    layers, per_layer = [], []
    for i, reference in enumerate(routings):
        facts = _routing_facts(
            chosen[i], program_routing["weights"][i], reference, experts=router_width(cfg)
        )
        per_expert = np.asarray(facts["tokens_per_expert"]).tolist()
        per_layer.append(per_expert)
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
        # how ragged the held groups are: the worst layer's fullest held expert
        "held_load_max_over_mean": max(
            l["tokens_per_expert_max"] / l["tokens_per_expert_mean"] for l in layers
        ),
        "ok": bool(ok),
    }
    if program_route is not None:
        layer = first_expert_layer(weights_fn(), cfg)
        out["router"] = check_router(program_route, layer, normed, cfg)
        out["ok"] = bool(out["ok"] and out["router"]["ok"])
    if program_biases is not None:
        expert_layers = itertools.islice(weights_fn()["layers"], cfg["num_dense_layers"], None)
        biases = [layer["expert_bias"] for layer in expert_layers]
        out["bias_rule"] = check_bias_rule(program_biases, biases, per_layer, cfg)
        out["ok"] = bool(out["ok"] and out["bias_rule"]["ok"])
    return out
