"""Plain reference of the dense decoder block (Mistral / Llama family).

Written from the published description (Hugging Face ``MistralForCausalLM``):
token embedding; per layer a pre-norm RMSNorm, q/k/v projections without
bias, rotary embedding in the rotate-half convention, grouped KV heads
(each KV head serves ``num_attention_heads / num_key_value_heads`` query
heads), causal softmax attention, output projection, residual; a second
RMSNorm, SwiGLU MLP ``down(silu(gate(x)) * up(x))``, residual; a final
RMSNorm and an untied output head.

``jax.numpy`` only, float32 throughout, ``default_matmul_precision
("highest")`` (on a TPU a float32 matmul otherwise runs in bf16 passes),
no kernel, no cache, no layer scan. It imports nothing from
``ray_tpu.models`` or ``ray_tpu.ops``: the family file hands it the
program's weights under the published names.

Departures from the published model, each noted where it applies:

* Weights arrive as ``[in, out]`` matrices (``x @ w``), the transpose of
  the checkpoint's ``[out, in]``: a storage layout, not mathematics.
* Attention walks the queries in blocks (a Python loop) so that the
  ``[heads, block, seq]`` float32 scores fit beside a training state at
  16k context. Each block sees the whole causal context: same result.
* ``rms_norm_eps`` is the PUBLISHED value from the configuration file
  (1e-5); the program hard-codes 1e-6, which the configuration lists under
  ``program_departures``. ``check`` below compares against both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Relative RMS error of the program's logits against this reference, over
# the compared positions: ||program - reference|| / ||reference||.
#
# TOLERANCE holds the program to the mathematics it is meant to compute:
# the published model, with the configuration file's ``program_departures``
# (values the program hard-codes otherwise) applied where there are any.
# Why 1.2e-2: the program computes in bfloat16 (rounding a value errs by up
# to 2^-9) with float32 accumulation; through two layers, the roundings of
# activations and of the kernel's probabilities compound to 7e-3 to 8e-3
# on the chip (my chip runs, PR 22; PERF.md section 6). What must fail, fails
# (benchmarks/tests/test_reference.py): one 4096-long dot product
# accumulated in float16 errs by 0.9e-2 and one 14336-long by 1.7e-2, in
# bfloat16 by 7e-2, and a forward pass chains more than a dozen; a changed
# term (another rotary base, KV heads paired wrongly, a missing norm)
# moves the logits by tens of percent.
TOLERANCE = 1.2e-2
# TOLERANCE_PUBLISHED is used instead against the published values when
# the configuration lists departures: it has to admit them. The one
# departure today, rms_norm_eps 1e-6 for 1e-5, alone moves the logits by
# 2.1e-2 (float32 against float32, 2 layers, CPU, PR 22): the embedding's
# variance at initialisation is 4e-4, so eps is 2.5 % of what the first
# norm divides by. It goes, with the departure, when the program takes
# eps from the configuration (PERF.md section 7, the tracing issue).
TOLERANCE_PUBLISHED = 3e-2


def rms_norm(x, weight, eps):
    variance = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + eps) * weight


def rotary(x, theta):
    """x: [batch, seq, heads, head_dim]; positions are 0..seq-1."""
    seq, head_dim = x.shape[1], x.shape[3]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], axis=-1)[None, :, None, :]
    half = head_dim // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def query_block(batch, heads, seq, limit=1 << 28):
    """Largest power-of-two block of queries whose float32 scores
    ``[batch, heads, block, seq]`` stay under ``limit`` elements."""
    block = seq
    while block > 128 and batch * heads * block * seq > limit:
        block //= 2
    while seq % block:
        block //= 2
    return block


def causal_attention(q, k, v):
    """q: [b, s, H, d]; k, v: [b, s, KV, d] -> [b, s, H, d]."""
    batch, seq, heads, head_dim = q.shape
    group = heads // k.shape[2]
    k = jnp.repeat(k, group, axis=2)   # KV head j serves query heads j*group ..
    v = jnp.repeat(v, group, axis=2)
    block = query_block(batch, heads, seq)
    key_pos = jnp.arange(seq)
    out = []
    for start in range(0, seq, block):
        qb = q[:, start : start + block]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(jnp.float32(head_dim))
        query_pos = start + jnp.arange(block)
        visible = key_pos[None, :] <= query_pos[:, None]
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v))
    return jnp.concatenate(out, axis=1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta", "eps"))
def layer_forward(x, w, *, heads, kv_heads, theta, eps):
    """One decoder layer. x: [b, s, hidden] float32; w: the layer's weights
    under the published names, any float dtype (upcast here)."""
    with jax.default_matmul_precision("highest"):
        w = {name: value.astype(jnp.float32) for name, value in w.items()}
        batch, seq, _ = x.shape
        h = rms_norm(x, w["input_layernorm"], eps)
        q = (h @ w["q_proj"]).reshape(batch, seq, heads, -1)
        k = (h @ w["k_proj"]).reshape(batch, seq, kv_heads, -1)
        v = (h @ w["v_proj"]).reshape(batch, seq, kv_heads, -1)
        attn = causal_attention(rotary(q, theta), rotary(k, theta), v)
        x = x + attn.reshape(batch, seq, -1) @ w["o_proj"]
        h = rms_norm(x, w["post_attention_layernorm"], eps)
        mlp = (jax.nn.silu(h @ w["gate_proj"]) * (h @ w["up_proj"])) @ w["down_proj"]
        return x + mlp


@functools.partial(jax.jit, static_argnames=("eps", "last"))
def head_forward(x, norm, lm_head, *, eps, last):
    with jax.default_matmul_precision("highest"):
        if last is not None:
            x = x[:, -last:]
        x = rms_norm(x, norm.astype(jnp.float32), eps)
        return x @ lm_head.astype(jnp.float32)


def logits(weights, tokens, cfg, last=None):
    """Reference logits ``[batch, seq or last, vocab]`` float32.

    ``weights``: ``{"embed_tokens", "layers": iterable of per-layer dicts,
    "norm", "lm_head"}``; ``cfg``: the configuration file's published keys;
    ``last``: compare only the last so many query positions (every layer
    still runs over the whole context)."""
    x = weights["embed_tokens"].astype(jnp.float32)[tokens]
    for layer in weights["layers"]:
        x = layer_forward(
            x, layer,
            heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
            theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        )
    return head_forward(
        x, weights["norm"], weights["lm_head"],
        eps=float(cfg["rms_norm_eps"]), last=last,
    )


@jax.jit
def _difference(program, reference):
    diff = program.astype(jnp.float32) - reference
    return (
        jnp.sqrt(jnp.mean(diff * diff)),
        jnp.sqrt(jnp.mean(reference * reference)),
        jnp.max(jnp.abs(diff)),
    )


def compare(program_logits, reference_logits, tolerance=TOLERANCE) -> dict:
    """One comparison of logits; ``ok`` is the relative RMS error within
    ``tolerance`` (a NaN is not)."""
    err, ref, worst = (float(v) for v in _difference(program_logits, reference_logits))
    rel = err / ref if ref > 0 else float("inf")
    return {
        "rel_rms": rel,
        "max_abs": worst,
        "reference_rms": ref,
        "tolerance": tolerance,
        "ok": bool(rel <= tolerance),
    }


def check(program_logits, weights_fn, tokens, cfg, last=None) -> dict:
    """The comparison that decides ``correct`` for the forward pass.

    Against the published configuration ``cfg``; and, where it lists
    ``program_departures``, also against the reference with exactly those
    values, at the tight tolerance — so a known departure is admitted by
    name and nothing else hides behind the room it needs. ``weights_fn()``
    gives the weights (a fresh layer iterator for each pass)."""
    departures = cfg.get("program_departures") or {}
    published = compare(
        program_logits, logits(weights_fn(), tokens, cfg, last=last),
        TOLERANCE_PUBLISHED if departures else TOLERANCE,
    )
    out = {"published": published, "ok": published["ok"]}
    if departures:
        as_computed = compare(
            program_logits,
            logits(weights_fn(), tokens, dict(cfg, **departures), last=last),
        )
        out.update(as_computed=as_computed, ok=published["ok"] and as_computed["ok"])
    return out
