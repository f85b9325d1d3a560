"""Plain reference of the OLMoE decoder block (a dropless mixture of
experts with q/k norms), and the routing-aware comparison that decides
``correct`` for it.

Written from the published description (Hugging Face ``OlmoeForCausalLM``,
``model_type`` ``olmoe``; arXiv:2409.02060). Per layer, pre-norm residual
as in the dense block (``reference/dense_decoder.py``, whose ``rms_norm``,
``rotary`` and ``causal_attention`` are used here as they are):

* attention: ``q = q_norm(x W_q)``, ``k = k_norm(x W_k)``, ``v = x W_v``,
  where ``q_norm`` / ``k_norm`` are RMSNorms with a learned weight over the
  WHOLE projected vector (``num_attention_heads * head_dim`` for q,
  ``num_key_value_heads * head_dim`` for k), eps ``rms_norm_eps``, applied
  BEFORE the split into heads and before RoPE (rotate-half); then causal
  softmax attention and ``W_o``.
* MoE MLP on the normed ``h``: ``logits = h W_r`` (no bias);
  ``p = softmax(logits)`` in float32 over ALL experts;
  ``(w, idx) = top_k(p, num_experts_per_tok)``; NO renormalisation when
  ``norm_topk_prob`` is false (OLMoE: the eight weights sum to less than
  1); ``out = sum_j w_j * down_{idx_j}(silu(gate_{idx_j}(h)) * up_{idx_j}(h))``.
  No token is ever dropped.
* training loss: cross-entropy + ``router_aux_loss_coef`` x
  ``load_balancing_loss_func``: with ``p`` and the top-k one-hots of ALL
  layers concatenated over tokens, ``f[j, e]`` = mean over (layers x
  tokens) of the one-hot of choice ``j``, ``P[e]`` = mean of ``p[:, e]``,
  loss ``= num_experts * sum_{j,e} f[j, e] * P[e]``.

``jax.numpy`` only, float32 throughout, ``default_matmul_precision
("highest")``, no kernel, no sort, no grouped matmul: the MoE is a Python
loop over the experts, each applied DENSELY to all tokens and weighted by
``w`` (zero outside the token's top-k). Imports nothing from
``ray_tpu.models`` or ``ray_tpu.ops``.

Departures from the published model:

* Weights arrive as ``[in, out]`` matrices (``x @ w``), the experts'
  stacked ``[experts, in, out]``: storage layouts, not mathematics.
* The paper trains with a router z-loss (1e-3) as well; the published
  modelling code has none, and neither has this reference nor the program.
* ``forced``: the comparison can hand each layer the PROGRAM's expert
  choices; the weights are then the reference's own probabilities of those
  experts. See ``check``.

Routing is discontinuous, so the comparison cannot be one RMS over free
-running logits: the program's router sees a bfloat16 ``h`` (errors of
about 1e-3 in logit units in layer 1, 6e-3 in layer 2) while the 8th and
9th of 64 N(0, 1) logits lie 0.076 apart on average, so a few percent of
tokens legitimately pick another 8th expert and their logits then differ
by 10-20 %. ``check`` therefore (a) compares logits with the reference
FORCED to the program's choices, overall and at the worst position, (b)
checks the choices against the reference's own router logits within a
margin, and the weights, and (c) reports how often the choice sets agree
outright and how the tokens spread over the experts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.dense_decoder import (
    causal_attention, compare, head_forward, rms_norm, rotary,
)

# (a) Relative RMS error of the program's logits against the reference
# FORCED to the program's expert choices, over all compared positions. The
# dense family's reason holds (bfloat16 rounding of activations, float32
# accumulation, two layers: 7e-3 to 9e-3 measured there on the chip) and
# forcing takes the routing's discontinuity out: measured 4.7e-3 to 6.9e-3
# over this PR's twelve seeds (my chip runs, PR 26; PERF.md section 6). What must
# fail, fails (benchmarks/tests/test_reference_moe.py): renormalised top-k
# weights (0.34 at the published widths, float32 against float32, CPU), q/k
# norm after the head split, an eps ten times the published (3.7e-2), one
# expert fewer a token. The precision below the configuration's (bfloat16
# values, float32 accumulation): the real family at the published widths
# with its expert matmuls ACCUMULATED in bfloat16 element by element reads
# 2.15e-2 (and 1.39e-2 against WEIGHT_TOLERANCE): not correct; its sound
# twin reads 5.7e-3 (CPU, eager, one sequence of 512; PERF.md section 6).
# What it does NOT catch, because the bfloat16 rounding of the activations
# (8 bits) is the coarser error: float16 accumulation in the experts (11
# bits: 6.3e-3 element by element, 5.7e-3 in sums of 16), a bfloat16
# router matmul (5.8e-3, shortfall 0.018, weights 6.0e-3), bfloat16
# accumulation in sums of 16 (7.8e-3); and eps 1e-6 for 1e-5, which moves
# the logits by 4.2e-3 only (the q/k norms and the second norm divide the
# first norm's scale error out again; the dense block reads 2.1e-2). The
# float32 tests (2e-6) hold the program to the configuration's eps.
TOLERANCE = 1.2e-2
# (a') The worst single position's relative RMS error. An average over 4096
# positions cannot see ONE token that got no expert output in a layer (a
# token past a capacity bucket): at the published widths it moves its own
# logits by 17 to 18 % (float32 against float32, CPU, PR 26) and the
# average by 3e-3. One position's noise has a long tail: median 4.5e-3,
# 99th percentile up to 4.1e-2, worst of 4096 positions 3.2e-2 to 5.2e-2
# over twelve seeds (my chip runs, PR 26). 9e-2 is 1.7 times the worst
# noise seen and half of what a dropped token reads. It does NOT see one
# lost (token, expert) pair of eight: that moves the token's logits by about
# 3 %, inside the noise: the counts below hold only the router's
# bookkeeping to all of its pairs, and the kernel's rows are held by
# tests/test_moe.py (each row of ragged groups that straddle the tiles
# against a dense loop over the experts, forward and both gradients).
POSITION_TOLERANCE = 9e-2
# (b) Every expert the program chose must have a REFERENCE router logit of
# at least the reference's 8th-largest minus MARGIN. The program's router
# is float32 on a bfloat16 input: its logits err by 1e-3 (layer 1) to 6e-3
# (layer 2) RMS, and the worst of 32,768 choices by four to five times
# that: measured worst shortfall 0.012 to 0.026 over twelve seeds (my chip
# runs, PR 26).
# 0.05 admits that and still means something: the mean gap between the 8th
# and 9th of 64 N(0, 1) logits is 0.076 and between the 8th and 10th 0.15,
# so a router that is wrong (another weight matrix, a bfloat16 router,
# top-k over the wrong axis) picks experts far below the line.
MARGIN = 0.05
# (b') Relative RMS error of the program's top-k weights against the
# reference's probabilities of the same experts: a softmax of those logits
# (measured 3.7e-3 to 5.3e-3, my chip runs, PR 26); a renormalised top-k
# is off by a factor.
WEIGHT_TOLERANCE = 1.2e-2


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta", "eps"))
def attention_forward(x, w, *, heads, kv_heads, theta, eps):
    """x + attention(norm(x)), with q/k norms over the whole projections."""
    with jax.default_matmul_precision("highest"):
        w = {name: value.astype(jnp.float32) for name, value in w.items()}
        batch, seq, _ = x.shape
        h = rms_norm(x, w["input_layernorm"], eps)
        q = rms_norm(h @ w["q_proj"], w["q_norm"], eps).reshape(batch, seq, heads, -1)
        k = rms_norm(h @ w["k_proj"], w["k_norm"], eps).reshape(batch, seq, kv_heads, -1)
        v = (h @ w["v_proj"]).reshape(batch, seq, kv_heads, -1)
        attn = causal_attention(rotary(q, theta), rotary(k, theta), v)
        return x + attn.reshape(batch, seq, -1) @ w["o_proj"]


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "norm_topk_prob"))
def route(x, norm, router, forced, *, eps, top_k, norm_topk_prob):
    """The normed tokens ``[tokens, hidden]`` and their routing: router
    ``logits`` and ``probs`` ``[tokens, experts]``, the chosen ``experts``
    (``forced`` if given, else the top-k) and their ``weights``."""
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, norm.astype(jnp.float32), eps).reshape(-1, x.shape[-1])
        logits = h @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        if forced is None:
            weights, experts = jax.lax.top_k(probs, top_k)
        else:
            experts = forced
            weights = jnp.take_along_axis(probs, experts, axis=-1)
        if norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return h, {"logits": logits, "probs": probs, "experts": experts, "weights": weights}


@jax.jit
def expert_forward(h, gate, up, down, weight):
    """One expert applied densely to ALL tokens, weighted per token
    (``weight`` is zero where the token did not choose it)."""
    with jax.default_matmul_precision("highest"):
        gate, up, down = (m.astype(jnp.float32) for m in (gate, up, down))
        return weight[:, None] * ((jax.nn.silu(h @ gate) * (h @ up)) @ down)


def moe_forward(x, w, cfg, forced=None):
    """x + moe(norm(x)) and the layer's routing."""
    experts, top_k = cfg["num_experts"], cfg["num_experts_per_tok"]
    h, routing = route(
        x, w["post_attention_layernorm"], w["router"], forced,
        eps=float(cfg["rms_norm_eps"]), top_k=top_k,
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
    )
    # [tokens, experts]: a token's weight of each expert, 0 outside its top-k
    chosen = routing["experts"][:, :, None] == jnp.arange(experts)[None, None, :]
    dense_weights = jnp.sum(jnp.where(chosen, routing["weights"][:, :, None], 0.0), axis=1)
    out = jnp.zeros_like(h)
    for e in range(experts):
        out = out + expert_forward(
            h, w["gate_proj"][e], w["up_proj"][e], w["down_proj"][e], dense_weights[:, e]
        )
    return x + out.reshape(x.shape), routing


def logits(weights, tokens, cfg, last=None, forced=None):
    """Reference ``(logits [batch, seq or last, vocab] float32, [routing of
    each layer])``. ``weights``: ``{"embed_tokens", "layers": iterable of
    per-layer dicts under the published names (experts stacked), "norm",
    "lm_head"}``; ``forced``: per layer the expert choices ``[tokens,
    num_experts_per_tok]`` to use instead of the reference's own top-k."""
    x = weights["embed_tokens"].astype(jnp.float32)[tokens]
    routings = []
    for i, layer in enumerate(weights["layers"]):
        x = attention_forward(
            x, {k: layer[k] for k in (
                "input_layernorm", "q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm")},
            heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
            theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        )
        x, routing = moe_forward(x, layer, cfg, None if forced is None else forced[i])
        routings.append(routing)
    out = head_forward(
        x, weights["norm"], weights["lm_head"], eps=float(cfg["rms_norm_eps"]), last=last
    )
    return out, routings


def load_balancing_loss(routings, cfg):
    """``load_balancing_loss_func``: all layers' tokens concatenated."""
    experts = cfg["num_experts"]
    probs = jnp.concatenate([r["probs"] for r in routings], axis=0)           # [L*T, E]
    chosen = jnp.concatenate([r["experts"] for r in routings], axis=0)        # [L*T, K]
    one_hot = (chosen[:, :, None] == jnp.arange(experts)[None, None, :]).astype(jnp.float32)
    f = jnp.mean(one_hot, axis=0)                                             # [K, E]
    p = jnp.mean(probs, axis=0)                                               # [E]
    return experts * jnp.sum(f * p[None, :])


def loss(weights, tokens, targets, cfg):
    """Mean token cross-entropy + ``router_aux_loss_coef`` x the balancing
    loss; ``jax.grad`` of this is the reference's gradient. ``weights``'
    ``layers`` must be a list here (one pass)."""
    out, routings = logits(weights, tokens, cfg)
    logp = jax.nn.log_softmax(out, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll) + float(cfg["router_aux_loss_coef"]) * load_balancing_loss(routings, cfg)


@jax.jit
def _position_errors(program, reference):
    """Each position's relative RMS error: the worst, where it is, and the
    median and 99th percentile beside it (facts: how far the tail lies)."""
    diff = program.astype(jnp.float32) - reference
    errors = jnp.sqrt(
        jnp.mean(diff * diff, axis=-1) / jnp.mean(reference * reference, axis=-1)
    ).reshape(-1)
    return {
        "worst": jnp.max(errors), "at": jnp.argmax(errors),
        "p50": jnp.percentile(errors, 50.0), "p99": jnp.percentile(errors, 99.0),
    }


@functools.partial(jax.jit, static_argnames=("experts",))
def _routing_facts(program_experts, program_weights, reference, *, experts):
    """One layer's choices and weights against the reference's routing
    (computed under the same choices)."""
    top_k = program_experts.shape[-1]
    kth = jax.lax.top_k(reference["logits"], top_k)[0][:, -1]                 # [T]
    chosen_logits = jnp.take_along_axis(reference["logits"], program_experts, axis=-1)
    own = jax.lax.top_k(reference["probs"], top_k)[1]
    diff = program_weights.astype(jnp.float32) - reference["weights"]
    return {
        "worst_shortfall": jnp.max(kth[:, None] - chosen_logits),
        "distinct": jnp.all(jnp.sort(program_experts, axis=-1)[:, 1:]
                            != jnp.sort(program_experts, axis=-1)[:, :-1]),
        "same_set_share": jnp.mean(jnp.all(
            jnp.sort(program_experts, axis=-1) == jnp.sort(own, axis=-1), axis=-1)),
        "weights_rel_rms": jnp.sqrt(jnp.mean(diff * diff) / jnp.mean(reference["weights"] ** 2)),
        "tokens_per_expert": jnp.bincount(program_experts.reshape(-1), length=experts),
    }


def check(program_logits, program_routing, weights_fn, tokens, cfg, last=None) -> dict:
    """The comparison that decides ``correct`` for the forward pass.

    ``program_routing``: the program's stacked routing (leading dim:
    layers): ``experts`` and ``weights`` ``[layers, tokens, k]``, ``counts``
    ``[layers, k, experts]``. ``weights_fn()`` gives the weights."""
    experts, top_k = cfg["num_experts"], cfg["num_experts_per_tok"]
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
            # The ROUTER's bookkeeping: its counts, which size the kernels'
            # groups and feed the balancing loss, are those of its choices
            # and cover every (token, choice) pair. Not evidence of what the
            # grouped matmul did with the rows: POSITION_TOLERANCE sees a
            # token that lost all its experts, tests/test_moe.py holds the
            # kernel to each row of ragged groups against a dense loop.
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
