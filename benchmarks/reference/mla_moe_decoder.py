"""Plain reference of the DeepSeek-V3 decoder block as Moonlight-16B-A3B
configures it (multi-head latent attention; a dense first layer, then
sigmoid-routed experts with shared experts beside them), and the
routing-aware comparison that decides ``correct`` for it.

Written from the published description (Hugging Face
``DeepseekV3ForCausalLM``, ``model_type`` ``deepseek_v3``; the DeepSeek-V3
report, arXiv:2412.19437; Moonlight, arXiv:2502.16982). Per layer, pre-norm
residual as in the dense block (``reference/dense_decoder.py``, whose
``rms_norm``, ``rotary``, ``query_block``, ``head_forward`` and ``compare``
are used here as they are; ``reference/moe_decoder.py`` lends
``expert_forward`` and ``_position_errors``):

* attention, on ``h = RMSNorm(x)``, with ``q_lora_rank`` null:
  ``q = h W_q`` -> ``[.., heads, qk_nope + qk_rope]``, split per head into
  ``q_nope`` and ``q_rope``. ``h W_kv_a`` -> ``[.., kv_lora_rank + qk_rope]``,
  split into the latent ``c`` and ONE ``k_rope`` shared by all heads;
  ``c = RMSNorm(c; kv_a_layernorm, eps)``; ``c W_kv_b`` -> ``[.., heads,
  qk_nope + v_head_dim]``, split per head into ``k_nope`` and ``v``. RoPE
  (``rope_theta``, over the ``qk_rope`` dims, rotate-half) on ``q_rope`` and
  ``k_rope`` only; ``q = [q_nope, q_rope]``, ``k = [k_nope, k_rope broadcast
  to the heads]``; causal softmax of ``q k^T x (qk_nope + qk_rope)^-0.5`` in
  float32 (``rope_scaling`` null: no mscale); ``out = P v`` -> ``[.., heads,
  v_head_dim]`` -> ``W_o``.
* the first ``first_k_dense_replace`` layers: a dense SwiGLU of width
  ``intermediate_size``. The others, on the normed ``h``:
  ``s = sigmoid(h W_r)`` in float32 over the routed experts; the CHOICE is
  the top ``num_experts_per_tok`` of ``s + b`` (``e_score_correction_bias``,
  a buffer; ``n_group`` = ``topk_group`` = 1, so the group step is the
  identity); the WEIGHTS are ``s`` at the chosen indices (without ``b``),
  divided by their sum + 1e-20 (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``y = sum_j w_j E_{idx_j}(h) + Shared(h)``,
  ``Shared`` one SwiGLU of width ``n_shared_experts x
  moe_intermediate_size``. No token is ever dropped.
* training loss: cross-entropy + ``aux_loss_alpha`` x the sequence-wise
  balance loss of the DeepSeek-V3 report (``seq_aux``): per sequence of T
  tokens, ``f_e = n_routed_experts / (num_experts_per_tok T)`` x (tokens
  that chose ``e``), ``P_e = mean_t s_e / sum_e' s_e'``, loss ``= sum_e f_e
  P_e``, averaged over sequences and expert layers.

``jax.numpy`` only, float32 throughout, ``default_matmul_precision
("highest")``, no kernel, no sort, no grouped matmul: attention is an
explicit ``[block, seq]`` softmax walked in blocks of queries, the experts
a Python loop, each applied DENSELY to all tokens and weighted per token
(zero outside the token's choices). Imports nothing from
``ray_tpu.models`` or ``ray_tpu.ops``.

Departures from the published model:

* Weights arrive as ``[in, out]`` matrices (``x @ w``), the experts'
  stacked ``[experts, in, out]``: storage layouts, not mathematics.
* The published checkpoints store the rope columns of ``W_q`` and
  ``W_kv_a`` interleaved and permute them before rotate-half; with weights
  from a seed that is a column permutation of those matrices, so the
  reference (and the program) rotate halves directly.
* The bias update of auxiliary-loss-free balancing is a training recipe
  the configuration does not state: ``b`` is an input here.
* ``forced``: the comparison can hand each expert layer the PROGRAM's
  choices; the weights are then the reference's own scores of those
  experts. See ``check``.

Routing is discontinuous (``reference/moe_decoder.py`` says at length why a
free-running comparison cannot decide): ``check`` (a) compares logits with
the reference FORCED to the program's choices, overall and at the worst
position, (b) holds each choice to the reference's own ``s + b`` within a
margin, and the weights, and (c) reports how often the choice sets agree
outright and how the tokens spread over the experts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.dense_decoder import (
    compare, head_forward, query_block, rms_norm, rotary,
)
from benchmarks.reference.moe_decoder import _position_errors, expert_forward

# (a) Relative RMS error of the program's logits against the reference
# FORCED to the program's choices, over the compared positions. The dense
# and OLMoE families' reason holds (bfloat16 rounding of activations and of
# the kernel's probabilities, float32 accumulation, two layers), and this
# block adds to it: the routed experts' output enters the residual 2.446
# times a plain weighted average's size, with its bfloat16 rows' rounding,
# and ``P v`` sums 8,192 bfloat16 probabilities. Measured 8.9e-3 to 9.8e-3
# over this PR's fourteen seeds at the published widths (my chip runs, PR
# 30; PERF.md section 6): a narrow band (spread 3e-4), so 1.2e-2 is 1.2
# times the largest reading and seven spreads above it. What must fail,
# fails (tests/test_mla_moe.py, float32 against float32 at a tiny size:
# rope over the whole head, scale 128^-0.5, the bias in the weights, no
# renormalisation, no 2.446, no shared branch, no latent norm, an expert
# layer in place of layer 0, top-5: each reads 1.5 tolerances or more in
# the logits or the weights). The precision below the configuration's
# (bfloat16 values, float32 accumulation) is accumulation in bfloat16: ONE
# expert matmul of this model's lengths accumulated so, element by element,
# reads 5.2e-2 (2048 long) and 4.3e-2 (1408), ONE row of ``P v`` over 8,192
# keys 9.4e-2, each alone four to eight tolerances
# (benchmarks/tests/test_reference_mla_moe.py: synthetic operands of the
# real lengths, CPU; the whole family was not run in that precision). What
# it does NOT catch is what ``reference/moe_decoder.py`` lists: float16
# accumulation, a bfloat16 router, bfloat16 sums of 16.
TOLERANCE = 1.2e-2
# (a') The worst single position's relative RMS error: what sees ONE token
# whose experts' output is wrong (``reference/moe_decoder.py`` has the
# argument). Here 512 positions are compared and the noise has no long
# tail: worst 1.01e-2 to 1.23e-2, 99th percentile 0.98e-2 to 1.17e-2 (my
# chip runs, PR 30). What a wrong expert reads was measured by accident:
# ONE of a token's six experts differing between the logits and the routing
# they were compared under (two programs rounding a near-tie apart, before
# ``families/mla_moe_decoder.py`` took both from one program) read 0.26 at
# that position and 2.7e-2 overall (seed 2147630001). 9e-2 is seven times
# the worst noise seen and a third of one wrong expert.
POSITION_TOLERANCE = 9e-2
# (b) Every expert the program chose must have a REFERENCE ``s + b`` of at
# least the reference's k-th largest minus MARGIN, in units of the score
# (a sigmoid: at the 6th of 64 N(0, 1) logits, score 0.79, its slope is
# 0.16). The program's router is float32 on a bfloat16 ``h`` after the
# dense layer and one attention block: measured worst shortfall of 49,152
# choices 3.9e-3 to 6.3e-3 over fourteen seeds (my chip runs, PR 30).
# 1.25e-2 is twice the worst seen and still means something: the 6th and
# 7th of 64 such scores lie 1.5e-2 apart on average and the 6th and 8th
# 3.0e-2, so a router that is wrong (the bias left out of the choice,
# softmax scores, top-k over another axis) picks experts far below the
# line, while a third of all tokens have their 7th within 6.3e-3 of their
# 6th: a legitimate flip stays inside.
MARGIN = 1.25e-2
# (b') Relative RMS error of the program's weights against the reference's
# (its own scores of the same experts, renormalised and scaled): measured
# 0.9e-3 to 1.2e-3 (my chip runs, PR 30); a weight with the bias in it, not
# renormalised or not scaled is off by tens of percent.
WEIGHT_TOLERANCE = 1.2e-2


def causal_attention(q, k, v):
    """q, k: [b, s, H, d]; v: [b, s, H, dv] -> [b, s, H, dv]. Explicit
    scores, ``d ** -0.5``, float32 softmax, in blocks of queries so that
    ``[b, H, block, s]`` fits beside a training state."""
    batch, seq, heads, dim = q.shape
    block = query_block(batch, heads, seq)
    key_pos = jnp.arange(seq)
    out = []
    for start in range(0, seq, block):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, start:start + block], k) * dim ** -0.5
        visible = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(visible[None, None], scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v))
    return jnp.concatenate(out, axis=1)


@functools.partial(jax.jit, static_argnames=("heads", "rank", "nope", "theta", "eps"))
def attention_forward(x, w, *, heads, rank, nope, theta, eps):
    """x + latent attention(norm(x)); the rope and value widths follow from
    the weights' shapes."""
    with jax.default_matmul_precision("highest"):
        w = {name: value.astype(jnp.float32) for name, value in w.items()}
        batch, seq, _ = x.shape
        h = rms_norm(x, w["input_layernorm"], eps)
        q = (h @ w["q_proj"]).reshape(batch, seq, heads, -1)
        kv_a = h @ w["kv_a_proj_with_mqa"]
        c = rms_norm(kv_a[..., :rank], w["kv_a_layernorm"], eps)
        kv = (c @ w["kv_b_proj"]).reshape(batch, seq, heads, -1)
        k_rope = rotary(kv_a[:, :, None, rank:], theta)                  # one head
        q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], theta)], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (batch, seq, heads, k_rope.shape[-1]))],
            axis=-1,
        )
        attn = causal_attention(q, k, kv[..., nope:])
        return x + attn.reshape(batch, seq, -1) @ w["o_proj"]


@functools.partial(jax.jit, static_argnames=("eps",))
def dense_mlp_forward(x, w, *, eps):
    """x + SwiGLU(norm(x)): the leading dense layers."""
    with jax.default_matmul_precision("highest"):
        w = {name: value.astype(jnp.float32) for name, value in w.items()}
        h = rms_norm(x, w["post_attention_layernorm"], eps)
        return x + (jax.nn.silu(h @ w["gate_proj"]) * (h @ w["up_proj"])) @ w["down_proj"]


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "norm_topk_prob", "scaling"))
def route(x, norm, router, bias, forced, *, eps, top_k, norm_topk_prob, scaling):
    """The normed tokens ``[tokens, hidden]`` and their routing: ``scores``
    (sigmoid) and ``biased`` (``scores + bias``: what chooses) ``[tokens,
    experts]``, the chosen ``experts`` (``forced`` if given, else the top-k
    of ``biased``) and their ``weights`` (from ``scores``)."""
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, norm.astype(jnp.float32), eps).reshape(-1, x.shape[-1])
        scores = jax.nn.sigmoid(h @ router.astype(jnp.float32))
        biased = scores + bias.astype(jnp.float32)
        experts = jax.lax.top_k(biased, top_k)[1] if forced is None else forced
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if norm_topk_prob:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
        return h, {
            "scores": scores, "biased": biased, "experts": experts,
            "weights": weights * scaling,
        }


def moe_forward(x, w, cfg, forced=None):
    """x + routed experts(norm(x)) + shared experts(norm(x)), and the
    layer's routing."""
    experts = cfg["n_routed_experts"]
    h, routing = route(
        x, w["post_attention_layernorm"], w["router"], w["e_score_correction_bias"], forced,
        eps=float(cfg["rms_norm_eps"]), top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        scaling=float(cfg["routed_scaling_factor"]),
    )
    # [tokens, experts]: a token's weight of each expert, 0 outside its choices
    chosen = routing["experts"][:, :, None] == jnp.arange(experts)[None, None, :]
    dense_weights = jnp.sum(jnp.where(chosen, routing["weights"][:, :, None], 0.0), axis=1)
    out = jnp.zeros_like(h)
    for e in range(experts):
        out = out + expert_forward(
            h, w["gate_proj"][e], w["up_proj"][e], w["down_proj"][e], dense_weights[:, e]
        )
    if cfg["n_shared_experts"]:
        out = out + expert_forward(
            h, w["shared_gate_proj"], w["shared_up_proj"], w["shared_down_proj"],
            jnp.ones(h.shape[0], jnp.float32),
        )
    return x + out.reshape(x.shape), routing


ATTENTION_NAMES = (
    "input_layernorm", "q_proj", "kv_a_proj_with_mqa", "kv_a_layernorm", "kv_b_proj", "o_proj",
)
DENSE_MLP_NAMES = ("post_attention_layernorm", "gate_proj", "up_proj", "down_proj")


def logits(weights, tokens, cfg, last=None, forced=None):
    """Reference ``(logits [batch, seq or last, vocab] float32, [routing of
    each EXPERT layer])``. ``weights``: ``{"embed_tokens", "layers":
    iterable of per-layer dicts under the published names (the first
    ``first_k_dense_replace`` dense, the others with the experts stacked),
    "norm", "lm_head"}``; ``forced``: per expert layer the choices
    ``[tokens, num_experts_per_tok]`` to use instead of the reference's own."""
    x = weights["embed_tokens"].astype(jnp.float32)[tokens]
    eps = float(cfg["rms_norm_eps"])
    routings = []
    for i, layer in enumerate(weights["layers"]):
        x = attention_forward(
            x, {k: layer[k] for k in ATTENTION_NAMES},
            heads=cfg["num_attention_heads"], rank=cfg["kv_lora_rank"],
            nope=cfg["qk_nope_head_dim"], theta=float(cfg["rope_theta"]), eps=eps,
        )
        if i < cfg["first_k_dense_replace"]:
            x = dense_mlp_forward(x, {k: layer[k] for k in DENSE_MLP_NAMES}, eps=eps)
        else:
            x, routing = moe_forward(
                x, layer, cfg, None if forced is None else forced[len(routings)]
            )
            routings.append(routing)
    return head_forward(x, weights["norm"], weights["lm_head"], eps=eps, last=last), routings


def balance_loss(routings, cfg, batch):
    """The sequence-wise balance loss, averaged over the ``batch``
    sequences and the expert layers."""
    experts, top_k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    total = 0.0
    for r in routings:
        scores = r["scores"].reshape(batch, -1, experts)                        # [B, T, E]
        seq = scores.shape[1]
        share = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=1)      # P [B, E]
        one_hot = r["experts"].reshape(batch, seq * top_k)[:, :, None] == jnp.arange(experts)
        f = jnp.sum(one_hot, axis=1).astype(jnp.float32) * experts / (top_k * seq)     # [B, E]
        total = total + jnp.mean(jnp.sum(f * share, axis=-1))
    return total / len(routings)


def loss(weights, tokens, targets, cfg):
    """Mean token cross-entropy + ``aux_loss_alpha`` x the balance loss;
    ``jax.grad`` of this is the reference's gradient. ``weights``'
    ``layers`` must be a list here (one pass)."""
    out, routings = logits(weights, tokens, cfg)
    logp = jax.nn.log_softmax(out, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll) + float(cfg["aux_loss_alpha"]) * balance_loss(
        routings, cfg, tokens.shape[0]
    )


@functools.partial(jax.jit, static_argnames=("experts",))
def _routing_facts(program_experts, program_weights, reference, *, experts):
    """One layer's choices and weights against the reference's routing
    (computed under the same choices)."""
    top_k = program_experts.shape[-1]
    kth = jax.lax.top_k(reference["biased"], top_k)[0][:, -1]                 # [T]
    chosen = jnp.take_along_axis(reference["biased"], program_experts, axis=-1)
    own = jax.lax.top_k(reference["biased"], top_k)[1]
    diff = program_weights.astype(jnp.float32) - reference["weights"]
    ranked = jnp.sort(program_experts, axis=-1)
    return {
        "worst_shortfall": jnp.max(kth[:, None] - chosen),
        "distinct": jnp.all(ranked[:, 1:] != ranked[:, :-1]),
        "same_set_share": jnp.mean(jnp.all(ranked == jnp.sort(own, axis=-1), axis=-1)),
        "weights_rel_rms": jnp.sqrt(jnp.mean(diff * diff) / jnp.mean(reference["weights"] ** 2)),
        "tokens_per_expert": jnp.bincount(program_experts.reshape(-1), length=experts),
    }


def check(program_logits, program_routing, weights_fn, tokens, cfg, last=None) -> dict:
    """The comparison that decides ``correct`` for the forward pass.

    ``program_routing``: the program's routing stacked over its expert
    layers: ``experts`` and ``weights`` ``[layers, tokens, k]``, ``counts``
    ``[layers, k, experts]``. ``weights_fn()`` gives the weights. The
    result's ``layers`` are the expert layers, in order."""
    experts, top_k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    chosen = program_routing["experts"]
    if chosen.shape[-1] != top_k:
        return {"ok": False, "why": f"{chosen.shape[-1]} experts per token, not {top_k}"}
    forced, routings = logits(
        weights_fn(), tokens, cfg, last=last, forced=[chosen[i] for i in range(chosen.shape[0])]
    )
    published = compare(program_logits, forced, TOLERANCE)
    positions = _position_errors(program_logits, forced)
    worst_position = float(positions["worst"])
    layers = []
    for i, reference in enumerate(routings):
        facts = _routing_facts(
            chosen[i], program_routing["weights"][i], reference, experts=experts
        )
        per_expert = np.asarray(facts["tokens_per_expert"]).tolist()
        counted = np.asarray(jnp.sum(program_routing["counts"][i], axis=0)).tolist()
        layers.append({
            "worst_shortfall": float(facts["worst_shortfall"]),
            "distinct": bool(facts["distinct"]),
            "same_set_share": float(facts["same_set_share"]),
            "weights_rel_rms": float(facts["weights_rel_rms"]),
            "tokens_per_expert_max": max(per_expert),
            "tokens_per_expert_mean": sum(per_expert) / experts,
            "tokens_per_expert_min": min(per_expert),
            # the router's bookkeeping, as reference/moe_decoder.py reads it
            "counts_agree": per_expert == counted,
            "pairs": sum(counted),
        })
    pairs = chosen.shape[1] * top_k
    ok = (
        published["ok"]
        and worst_position <= POSITION_TOLERANCE
        and all(
            l["worst_shortfall"] <= MARGIN and l["distinct"] and l["counts_agree"]
            and l["pairs"] == pairs and l["weights_rel_rms"] <= WEIGHT_TOLERANCE
            for l in layers
        )
    )
    return {
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
        "ok": bool(ok),
    }
