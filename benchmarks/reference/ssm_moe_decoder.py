"""Plain reference of the ``nemotron_h`` language model as
NVIDIA-Nemotron-3-Super-120B-A12B configures it: layers that are ONE residual
block each, a Mamba-2 mixer (``M``), grouped-query attention that carries no
position (``*``) or a LATENT mixture of un-gated ReLU^2 experts of which THIS
CHIP HOLDS A BLOCK beside one shared expert (``E``); and the comparison that
decides ``correct``.

Written from the published configuration's keys, Mamba-2 (Dao and Gu,
arXiv:2405.21060) for the state-space layer and the DeepSeek-V3 report
(arXiv:2412.19437) for the ``noaux_tc`` routine; the configuration file's
``assumed`` list says what no key states. ``dense_decoder.py``'s ``rms_norm``,
``causal_attention``, ``head_forward`` and ``compare``, ``mla_moe_decoder.py``'s
``route`` and ``_routing_facts`` and ``moe_decoder.py``'s ``_position_errors``
are used as they are.

Every layer, on the residual stream ``x`` (eps ``layer_norm_epsilon``)::

    x = x + block(RMSNorm(x; norm))

the block the one ``hybrid_override_pattern`` names at the layer's place (the
file's pattern is the period it keeps).

``M``, Mamba-2, on the normed ``h`` (heads ``i = 1..mamba_num_heads`` of
``mamba_head_dim`` P, a state of ``ssm_state_size`` N, ``n_groups`` groups G):

* ``[z | xBC | dt~] = h W_in``, widths ``H P | H P + 2 G N | H``.
* ``xBC = SiLU(conv(xBC) + b)``: a causal depthwise convolution of
  ``conv_kernel`` taps, one filter a channel, WITH a bias (``use_conv_bias``),
  the last tap on the current token; then ``x`` ``[T, H, P]``, ``B`` and ``C``
  ``[T, G, N]``, head ``i`` reading group ``i // (H / G)``.
* ``dt = softplus(dt~ + dt_bias)``, ``A = -exp(A_log)``, one a head.
* the state ``S_t`` in ``R^{P x N}``, ``S_0 = 0``, ONE TOKEN AT A TIME
  (``recurrence``: a ``lax.scan`` over the positions)::

      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
      y_t = S_t C_t + D x_t

* ``out = RMSNorm_groups(y * SiLU(z); mixer_norm) W_out``: the gate FIRST,
  then a norm over each of G groups of ``H P / G`` channels with one learned
  weight of ``H P`` (``MambaRMSNormGated``).

``*``: ``q, k, v = h W_q, h W_k, h W_v`` at ``num_attention_heads`` /
``num_key_value_heads`` heads of ``head_dim``, NO rotary embedding, no q / k
norm, no bias; causal softmax attention at ``head_dim^-1/2``; ``W_o``.

``E``: ``s = sigmoid(h W_r)`` over ALL the routed experts (the router's width,
``published.n_routed_experts``); the CHOICE is the top ``num_experts_per_tok``
of ``s + e_score_correction_bias``; the WEIGHTS are ``s`` at the chosen
(without the bias), divided by their sum + 1e-20 (``norm_topk_prob``), times
``routed_scaling_factor``. ``u = h W_down`` (hidden -> ``moe_latent_size``);
``routed = sum over the chosen experts THAT ARE HELD HERE of w_j W2_j relu(u
W1_j)^2`` (a Python loop over the file's ``n_routed_experts`` experts from
``first_expert_held`` on, each applied densely to all tokens; what an absent
expert would have added is left out, here as in the program); ``out = routed
W_up + W2_s relu(h W1_s)^2``, the shared expert of
``moe_shared_expert_intermediate_size`` on the stream. No gate in any expert
(``mlp_hidden_act`` ``relu2``), no token dropped, no balance loss.

``jax.numpy`` only, float32 throughout, ``default_matmul_precision
("highest")``, no kernel, no chunking of the recurrence, the convolution as
shifted sums, no sort, no grouped matmul, no layer scan. Imports nothing from
``ray_tpu.models`` or ``ray_tpu.ops``. Weights arrive as ``[in, out]``
matrices, ``[taps, channels]`` filters and ``[held, in, out]`` expert stacks:
storage layouts.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.dense_decoder import causal_attention, compare, head_forward, rms_norm
from benchmarks.reference.mla_moe_decoder import _routing_facts, route
from benchmarks.reference.moe_decoder import _position_errors

# The limits of the comparison that decides ``correct`` (``check``), each
# between two readings on a v5e at the published widths and 8,192 positions
# (my chip runs, PR 55, calls 1, 2, 5 and 6: ten seeds, 2255000203 to 3055000202,
# and 13 more; PERF.md section 6 has them): the largest the program gives
# over its seeds and the lowest a WRONG computation gives
# (``harness/ssm_moe_controls.py`` prints both; seeds 2955000103 and 2355000207,
# and 2955000601 and 2355000602 in call 5).
#
# TOLERANCE_SCAN: relative RMS error of the program's scan ALONE
# (``ops/ssd.py``'s chunked form) against the per-token recurrence on the
# reference's own float32 ``x``, ``dt``, ``B``, ``C`` of the file's first
# Mamba-2 layer (``check_scan``), over all positions and without the skip ``D
# x``, read THREE times, each reading under a limit of its own. "own": the fresh
# weights' decays (``dt A`` -0.29 to -0.32 a token in the mean, -17 to -21 at the
# steepest: most heads carry their state across many chunks). The program reads
# 4.3e-5 to 2.3e-4 over 22 seeds: float32 against float32, two orders of sums
# of a thousand terms (the products are six bfloat16 passes each). With ``x``,
# ``dt``, ``B`` and ``C`` rounded to bfloat16 on their way in it reads 2.34e-3
# and 2.45e-3, with ``B`` / ``C`` of the next group 0.96 and 1.01: NOT correct;
# with the chunk-boundary state rounded to bfloat16 2.8e-4 and 5.0e-4, which
# this reading alone would not hold apart from the program's (a factor of 2):
# 5e-4 lies between the program's largest and the rounded operands' (2.3e-4,
# 2.34e-3: a factor of 2.2 above the one and 4.7 under the other). "opened": decays
# opened to the steepest the initialisation allows (``OPENED``: ``dt`` 0.1 and
# ``A`` -16 in every head, -1.6 a token, -205 a chunk: a state that forgets
# within a few tokens, where a split that exponentiated a positive difference
# would overflow). The program reads 8.3e-7 to 8.7e-7; the rounded operands 2.58e-3
# and 2.63e-3, the bfloat16 state 2.33e-5 and 2.41e-5, the wrong group 1.05 and
# 1.10: 4.5e-6 is the geometric middle of the program's and the bfloat16
# state's, a factor of 5 either way. "timed": the own decays with ``x``, ``B``
# and ``C`` rounded once to the file's bfloat16 and handed to the scan in it, so
# that it compiles to what the step times (one pass of the MXU on bfloat16
# operands; the decay-weighted ``C B^T``, ``dt x`` and the chunk-start state
# rounded to bfloat16 ahead of their products), against the float32 recurrence
# on the same rounded values: what the two float32 readings, which compile
# another program, cannot see. The program reads 2.28e-3 to 2.55e-3 over 13
# seeds (my chip runs, PR 55, calls 5 and 6: the output's own rounding is 1e-3 of
# it); the running sums of ``dt A`` kept in bfloat16 5.16e-2 and 6.87e-2 (and
# as much on both float32 readings); the wrong group 1.0: 7e-3 is 2.7 times
# the program's largest and a factor of 7 under the lowest of those. It does
# NOT hold a bfloat16 carry (2.326e-3 against the program's 2.304e-3, 2.554e-3
# against 2.548e-3: the products round the state once anyway) nor ``dt``
# rounded on its way in (2.55e-3, 2.88e-3): the float32 readings hold those,
# and tests/test_ssd.py the carry's dtype in the bfloat16 instantiation. Every
# control fails at least one limit, the wrong group and the bfloat16 sums all.
TOLERANCE_SCAN = {"own": 5e-4, "opened": 4.5e-6, "timed": 7e-3}
OPENED = {"dt": 0.1, "A": -16.0}
# TOLERANCE, POSITION_TOLERANCE: relative RMS error of the program's logits
# against the reference FORCED to the program's expert choices, over the
# compared positions (the last 256 of 8,192, each against the whole context),
# and at the worst single position (``mla_moe_decoder.py`` has the argument
# for both). Eleven one-block layers in bfloat16 on a 4096-wide stream: the
# program reads 1.205e-2 to 1.223e-2 and 1.335e-2 to 1.364e-2 over ten seeds
# (1.1e-3 a layer: half of Solar's two-block layers'). The wrong models read,
# each against the same program logits under the same choices (two seeds): an
# expert WITH a gate (``SiLU(u W1) * (u W1)`` for ``relu(u W1)^2``, routed and
# shared) 0.287 / 0.320 and 0.289 / 0.323, the gate AFTER the norm
# (``RMSNorm(y) * SiLU(z)``) 0.472 / 0.548 and 0.479 / 0.556, no convolution
# bias 0.848 / 0.937 and 0.859 / 0.938: NOT correct, every one by both limits.
# 3e-2 and 4.5e-2 are 2.5 and 3.3 times the largest readings and a factor of 7
# to 10 under the lowest wrong one. (What they do NOT hold: a rotary embedding
# in the one attention layer reads 1.71e-2 / 1.94e-2, inside both: PERF.md
# section 7; the CPU tests hold that term.)
TOLERANCE = 3e-2
POSITION_TOLERANCE = 4.5e-2
# MARGIN: every expert the program chose must have a REFERENCE ``s + b`` of
# at least the k-th largest minus MARGIN (units of the score, a sigmoid). On
# the cell's own weights the correction bias steers every token to the same 22
# experts (``families/ssm_moe_decoder.py::init``: 1 on them, 0 elsewhere, the
# scores in (0, 1)), so the choice is the reference's own (``same_set_share``
# 1.0, shortfall 0 over six seeds); with a bias of zeros, 512 fresh scores lie
# 1e-3 apart at the 22nd, a token in five chose another set than the
# reference's (0.786 to 0.794) and the worst shortfall of a layer's 180,224
# choices read 2.6e-3 (the first expert layer) to 7.9e-3 (the fifth) over ten
# seeds. 2e-2 is 2.5 times that, and holds a bias that is not applied: the
# program run with its bias zeroed against the reference on the cell's weights
# (``harness/ssm_moe_controls.py``'s wrong routers, through ``check``; my chip
# runs, PR 55, call 5, seeds 2955000601 and 2355000602) reads shortfalls of
# 0.580 and 0.599 (the steered 22 lose to whatever scores highest).
# WEIGHT_TOLERANCE: relative RMS error of the program's weights against the
# reference's own scores of the same experts, renormalised and scaled: the
# program's router is float32 on a bfloat16 ``h``; measured 4.5e-3 to 4.8e-3 on
# the steered choice (its scores lie anywhere in (0.2, 0.8), where a sigmoid is
# steepest; 5.5e-4 to 1.39e-3 on the 22 HIGHEST scores of a zero bias). The
# wrong routers (call 5, the same two seeds): weights not scaled by 5 read
# 0.803 and 0.804, not renormalised 10.4 and 10.8 (the 22 scores sum to about
# 11). 2.5e-2 is five times the program's largest and a factor of 32 under the
# lowest of those. It does NOT hold a router's precision: the program's weights
# rounded to bfloat16 on their way out read 5.10e-3 and 5.00e-3, 6 % over its
# own, since a router on a bfloat16 stream is five times such a rounding.
MARGIN = 2e-2
WEIGHT_TOLERANCE = 2.5e-2

KINDS = {"M": "mamba", "*": "attention", "E": "moe"}
MAMBA_NAMES = (
    "norm", "in_proj", "conv1d_weight", "conv1d_bias", "dt_bias", "A_log", "D", "mixer_norm",
    "out_proj",
)
ATTENTION_NAMES = ("norm", "q_proj", "k_proj", "v_proj", "o_proj")
# What a wrong model computes, by name: ``harness/ssm_moe_controls.py`` hands
# one to ``logits`` as ``cfg["control"]``; no configuration file has the key.
CONTROLS = ("gate_after_norm", "gated_expert", "no_conv_bias")


def layer_kinds(cfg: dict) -> list[str]:
    """``mamba`` / ``attention`` / ``moe`` of the file's layers, from its
    ``hybrid_override_pattern`` (the period it keeps)."""
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError(f"{len(pattern)} kinds for {cfg['num_hidden_layers']} layers")
    return [KINDS[kind] for kind in pattern]


def router_width(cfg: dict) -> int:
    return (cfg.get("published") or {}).get("n_routed_experts", cfg["n_routed_experts"])


def held_block(cfg: dict) -> tuple[int, int]:
    """``(first, count)`` of the experts this chip holds."""
    return cfg.get("first_expert_held", 0), cfg["n_routed_experts"]


def _sizes(cfg: dict) -> dict:
    heads, width = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    return {
        "heads": heads, "width": width, "state": cfg["ssm_state_size"],
        "groups": cfg["n_groups"], "inner": heads * width,
        "eps": float(cfg["layer_norm_epsilon"]),
    }


def short_conv(x, filters, bias):
    """``SiLU(conv(x) + bias)`` as shifted sums: ``x`` ``[b, s, channels]``,
    ``filters`` ``[taps, channels]``, the LAST tap on the current token."""
    taps, seq = filters.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = sum(padded[:, j:j + seq] * filters[j] for j in range(taps))
    return jax.nn.silu(out if bias is None else out + bias)


def recurrence(x, dt, A, B, C, D):
    """The state-space recurrence a token at a time: ``x`` ``[b, s, heads,
    P]``, ``dt`` ``[b, s, heads]``, ``A`` and ``D`` ``[heads]``, ``B`` and ``C``
    ``[b, s, groups, N]`` (a head reads its group's). float32; ``[b, s, heads,
    P]``."""
    batch, seq, heads, width = x.shape
    groups = B.shape[2]
    by_group = lambda t: t.reshape(*t.shape[:2], groups, heads // groups, *t.shape[3:])
    A_, D_ = (t.reshape(groups, heads // groups) for t in (A, D))

    def token(state, operands):
        x_t, dt_t, b_t, c_t = operands            # [b, g, r, P], [b, g, r], [b, g, N] x 2
        decay = jnp.exp(dt_t * A_)[..., None, None]
        write = (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, None, :]
        state = decay * state + write
        return state, jnp.sum(state * c_t[:, :, None, None, :], axis=-1)

    by_time = lambda t: jnp.moveaxis(t, 1, 0)
    state = jnp.zeros((batch, groups, heads // groups, width, B.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(token, state, (by_time(by_group(x)), by_time(by_group(dt)), by_time(B), by_time(C)))
    y = jnp.moveaxis(y, 0, 1) + D_[..., None] * by_group(x)
    return y.reshape(x.shape)


def _ssm_operands(h, w, sizes, control=None):
    """``(z, x, dt, A, B, C)`` of a Mamba-2 layer from its normed input."""
    batch, seq, _ = h.shape
    inner, groups, state = sizes["inner"], sizes["groups"], sizes["state"]
    z, xbc, dt = jnp.split(h @ w["in_proj"], (inner, 2 * inner + 2 * groups * state), axis=-1)
    xbc = short_conv(xbc, w["conv1d_weight"], None if control == "no_conv_bias" else w["conv1d_bias"])
    x, B, C = jnp.split(xbc, (inner, inner + groups * state), axis=-1)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    return (
        z, x.reshape(batch, seq, sizes["heads"], -1), dt, -jnp.exp(w["A_log"]),
        B.reshape(batch, seq, groups, -1), C.reshape(batch, seq, groups, -1),
    )


@functools.partial(jax.jit, static_argnames=("sizes",))
def ssm_operands(x, w, *, sizes):
    """``(x, dt, A, B, C, D)`` of the recurrence of a Mamba-2 layer whose
    input is the stream ``x``: what ``check_scan`` hands both scans."""
    sizes = dict(sizes)
    with jax.default_matmul_precision("highest"):
        w = {name: value.astype(jnp.float32) for name, value in w.items()}
        _, *operands = _ssm_operands(rms_norm(x, w["norm"], sizes["eps"]), w, sizes)
        return (*operands, w["D"])


@functools.partial(jax.jit, static_argnames=("sizes", "control"))
def mamba_forward(x, w, *, sizes, control=None):
    """x + Mamba-2 mixer(norm(x)). x: [b, s, hidden] float32."""
    sizes = dict(sizes)
    with jax.default_matmul_precision("highest"):
        w = {name: value.astype(jnp.float32) for name, value in w.items()}
        batch, seq, _ = x.shape
        z, *operands = _ssm_operands(rms_norm(x, w["norm"], sizes["eps"]), w, sizes, control)
        y = recurrence(*operands, w["D"]).reshape(batch, seq, sizes["groups"], -1)
        gate = jax.nn.silu(z).reshape(y.shape)
        norm = lambda t: t * jax.lax.rsqrt(jnp.mean(t * t, axis=-1, keepdims=True) + sizes["eps"])
        y = norm(y) * gate if control == "gate_after_norm" else norm(y * gate)
        return x + (y.reshape(batch, seq, -1) * w["mixer_norm"]) @ w["out_proj"]


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps"))
def attention_forward(x, w, *, heads, kv_heads, eps):
    """x + grouped-query attention(norm(x)), no position."""
    with jax.default_matmul_precision("highest"):
        w = {name: value.astype(jnp.float32) for name, value in w.items()}
        batch, seq, _ = x.shape
        h = rms_norm(x, w["norm"], eps)
        q = (h @ w["q_proj"]).reshape(batch, seq, heads, -1)
        k = (h @ w["k_proj"]).reshape(batch, seq, kv_heads, -1)
        v = (h @ w["v_proj"]).reshape(batch, seq, kv_heads, -1)
        return x + causal_attention(q, k, v).reshape(batch, seq, -1) @ w["o_proj"]


@functools.partial(jax.jit, static_argnames=("gated",))
def expert_forward(h, up, down, weights, gated=False):
    """``weights[:, None] * (relu(h up)^2 down)``, one un-gated expert on all
    tokens (``gated``: the control's ``SiLU(h up) * (h up)``)."""
    with jax.default_matmul_precision("highest"):
        first = h @ up.astype(jnp.float32)
        act = jax.nn.silu(first) * first if gated else jnp.square(jnp.maximum(first, 0.0))
        return weights[:, None] * (act @ down.astype(jnp.float32))


def moe_forward(x, w, cfg, forced=None):
    """x + (held routed experts in the latent + shared expert)(norm(x)), and
    the layer's routing over ALL the router's experts."""
    h, routing = route(
        x, w["norm"], w["router"], w["e_score_correction_bias"], forced,
        eps=float(cfg["layer_norm_epsilon"]), top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        scaling=float(cfg["routed_scaling_factor"]),
    )
    gated = cfg.get("control") == "gated_expert"
    # [tokens, experts]: a token's weight of each expert, 0 outside its choices
    chosen = routing["experts"][:, :, None] == jnp.arange(w["router"].shape[-1])[None, None, :]
    dense_weights = jnp.sum(jnp.where(chosen, routing["weights"][:, :, None], 0.0), axis=1)
    first, count = held_block(cfg)
    with jax.default_matmul_precision("highest"):
        u = h @ w["fc1_latent_proj"].astype(jnp.float32)
    routed = jnp.zeros_like(u)
    for e in range(count):                                       # the SAME held block
        routed = routed + expert_forward(
            u, w["up_proj"][e], w["down_proj"][e], dense_weights[:, first + e], gated=gated
        )
    with jax.default_matmul_precision("highest"):
        out = routed @ w["fc2_latent_proj"].astype(jnp.float32)
    out = out + expert_forward(
        h, w["shared_up_proj"], w["shared_down_proj"], jnp.ones(h.shape[0], jnp.float32),
        gated=gated,
    )
    return x + out.reshape(x.shape), routing


def _sizes_key(cfg):
    return tuple(sorted(_sizes(cfg).items()))


def hidden(weights, tokens, cfg, forced=None, layers=None):
    """The residual stream after the first ``layers`` layers (None: all) and
    the routing of each EXPERT layer. ``forced``: per expert layer the
    choices to use instead of the reference's own."""
    x = weights["embed_tokens"].astype(jnp.float32)[tokens]
    control, eps = cfg.get("control"), float(cfg["layer_norm_epsilon"])
    routings = []
    for i, (kind, layer) in enumerate(zip(layer_kinds(cfg), weights["layers"])):
        if layers is not None and i >= layers:
            break
        if kind == "mamba":
            x = mamba_forward(
                x, {k: layer[k] for k in MAMBA_NAMES}, sizes=_sizes_key(cfg), control=control
            )
        elif kind == "attention":
            x = attention_forward(
                x, {k: layer[k] for k in ATTENTION_NAMES}, heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"], eps=eps,
            )
        else:
            x, routing = moe_forward(x, layer, cfg, None if forced is None else forced[len(routings)])
            routings.append(routing)
    return x, routings


def logits(weights, tokens, cfg, last=None, forced=None):
    """Reference ``(logits [batch, seq or last, vocab] float32, [routing of
    each expert layer])``. ``weights``: ``{"embed_tokens", "layers": iterable
    of per-layer dicts under this file's names, "norm_f", "lm_head"}``."""
    x, routings = hidden(weights, tokens, cfg, forced)
    eps = float(cfg["layer_norm_epsilon"])
    return head_forward(x, weights["norm_f"], weights["lm_head"], eps=eps, last=last), routings


def loss(weights, tokens, targets, cfg):
    """Mean token cross-entropy; ``jax.grad`` of this is the reference's
    gradient. ``weights``' ``layers`` must be a list here (one pass)."""
    out, _ = logits(weights, tokens, cfg)
    logp = jax.nn.log_softmax(out, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def check_scan(scan, weights, tokens, cfg) -> dict:
    """The program's scan ALONE, at the cell's own shapes, on operands that are
    the reference's: ``scan(x, dt, A, B, C, D)`` (the family hands the timed
    path's ``ops/ssd.py::ssd``) against ``recurrence`` for the operands of the
    FIRST Mamba-2 layer (its input the reference's own stream after the layers
    before it), all WITHOUT the skip term, read three times. "own" and
    "opened": float32 operands, the weights' own decays and the ``OPENED``
    ones: the chunked algorithm and its exponents. "timed": the own decays
    with ``x``, ``B`` and ``C`` rounded ONCE to the file's ``torch_dtype`` and
    handed over IN that dtype (``dt`` float32, as the mixer hands it), so that
    ``scan`` compiles to what the timed step runs, against the float32
    recurrence on the same rounded values: what the timed instantiation
    rounds on its way (a float32 file reads "own" again). Relative RMS error
    over every position. The logits cannot see this: eleven layers' bfloat16
    rounding is a thousand times the float32 scan's own error and five times
    the bfloat16 one's."""
    at = layer_kinds(cfg).index("mamba")
    layers = list(itertools.islice(weights["layers"], at + 1))
    x, _ = hidden(dict(weights, layers=layers), tokens, cfg, layers=at)
    x_, dt, A, B, C, D = ssm_operands(
        x, {k: layers[at][k] for k in MAMBA_NAMES}, sizes=_sizes_key(cfg)
    )
    # without the skip ``D x`` (zeros for D): it is most of a fresh layer's
    # output, exact on both sides, and would dilute the state's error by its size
    own = (x_, dt, A, B, C, jnp.zeros_like(D))
    steep = (x_, jnp.full_like(dt, OPENED["dt"]), jnp.full_like(A, OPENED["A"]), B, C, own[5])
    once = lambda t: t.astype(jnp.dtype(cfg.get("torch_dtype", "float32")))
    timed = (once(x_), dt, A, once(B), once(C), own[5])
    out = {"tolerance": TOLERANCE_SCAN, "layer": at, "ok": True}
    for reading, operands in (("own", own), ("opened", steep), ("timed", timed)):
        want = jax.jit(recurrence)(*(t.astype(jnp.float32) for t in operands))
        found = compare(scan(*operands), want, TOLERANCE_SCAN[reading])
        log_decay = operands[1] * operands[2]
        out[reading] = {
            "rel_rms": found["rel_rms"], "max_abs": found["max_abs"],
            "reference_rms": found["reference_rms"],
            "steepest_log_decay": float(jnp.min(log_decay)),
            "mean_log_decay": float(jnp.mean(log_decay)),
            "ok": bool(found["ok"]),
        }
        out["ok"] = bool(out["ok"] and found["ok"])
    return out


def check(program_logits, program_routing, weights_fn, tokens, cfg, last=None, scan=None) -> dict:
    """The comparison that decides ``correct`` for the forward pass.

    ``program_routing``: the program's routing stacked over its EXPERT
    layers: ``experts`` and ``weights`` ``[layers, tokens, k]``, ``counts``
    ``[layers, k, experts]``, ``held_pairs`` ``[layers]``. ``weights_fn()``
    gives the weights. ``tokens_per_expert_*`` are over the experts HELD
    here. ``held_pairs_pct`` is a program counter: the share of all (token,
    choice) pairs whose expert this chip holds, by the program's own count
    (3.125 is an even routing at 16 of 512)."""
    top_k, experts = cfg["num_experts_per_tok"], router_width(cfg)
    first, held = held_block(cfg)
    chosen = program_routing["experts"]
    if chosen.shape[-1] != top_k:
        return {"ok": False, "why": f"{chosen.shape[-1]} experts per token, not {top_k}"}
    forced, routings = logits(
        weights_fn(), tokens, cfg, last=last, forced=[chosen[i] for i in range(chosen.shape[0])]
    )
    published = compare(program_logits, forced, TOLERANCE)
    positions = _position_errors(program_logits, forced)
    worst_position = float(positions["worst"])
    pairs = chosen.shape[1] * top_k
    layers = []
    for i, reference in enumerate(routings):
        facts = _routing_facts(chosen[i], program_routing["weights"][i], reference, experts=experts)
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
            "held_experts_with_rows": sum(1 for rows in here if rows),
            # the router's bookkeeping, and the dispatch's: the pairs it
            # sized the held groups for
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
    if scan is not None:
        out["scan"] = check_scan(scan, weights_fn(), tokens, cfg)
        out["ok"] = bool(out["ok"] and out["scan"]["ok"])
    return out
