"""Plain reference of the ``phi4flash`` language model as
Phi-4-mini-flash-reasoning configures it: SambaY's decoder-hybrid-decoder
(Ren et al., arXiv:2507.06607) under differential attention (Ye et al.,
arXiv:2410.05258); and the comparison that decides ``correct``.

Written from the published configuration's keys, Mamba (Gu and Dao,
arXiv:2312.00752) for the state-space layer and the two papers above for the
rest; the configuration file's ``assumed`` list says what no key states.
``dense_decoder.py``'s ``compare`` and ``query_block`` and ``moe_decoder.py``'s
``_position_errors`` are used as they are.

``n = num_hidden_layers`` (``n % 4 == 0``), ``s = n / 2``. Every layer ``i`` on
the residual stream ``x``, ``LN(x) = (x - mean) / sqrt(var + layer_norm_eps) * w
+ b``::

    h = LN_1(x);  x = x + mixer_i(h)
    [g | u] = LN_2(x) W_gate_up;  x = x + (SiLU(g) * u) W_down

then a final LayerNorm and the TIED head ``LN_f(x) E^T``. No position signal
anywhere. The mixer by index (``layer_kinds``; ``mb_per_layer`` 2):

* even ``i <= s``, ``mamba``: ``[u | z] = h W_in``; ``u' = SiLU(conv(u) + b)``,
  a causal depthwise convolution of 4 taps, the last on the current token;
  ``[d | B | C] = u' W_x`` (160 | 16 | 16); ``dt = softplus(d W_dt + b_dt)``, ``A
  = -exp(A_log)`` ``[5120, 16]``; the state ``S_t`` in ``R^{5120 x 16}``, ``S_0 =
  0``, ONE TOKEN AT A TIME (``recurrence``: a ``lax.scan`` over the positions)::

      S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] u'_t[c]
      y_t[c]    = sum_n C_t[n] S_t[c, n] + D[c] u'_t[c]

  ``out = (y * SiLU(z)) W_out``. Layer ``s`` also hands on its memory ``M = y``
  (BEFORE the gate).
* odd ``i < s``, ``window``: differential attention under the mask ``i - 512 <
  j <= i`` (``sliding_window`` 512, the query's own position counted).
* ``s + 1``, ``full``: differential attention under the causal mask; hands on
  its ``k1, k2, V``.
* even ``i >= s + 2``, ``gmu``: ``out = (SiLU(h W_1) * M) W_2``.
* odd ``i >= s + 3``, ``cross``: differential attention of its OWN queries on
  layer ``s + 1``'s ``k1, k2, V``, causal.

Differential attention (40 query and 20 key-value heads of 64: 20 PAIRS of
query heads on 10 pairs of key-value heads, pair ``j`` reading key-value pair
``j // 2``)::

    q = h W_q + b_q -> [20, 2, 64]: q1_j, q2_j;  k -> [10, 2, 64]: k1_g, k2_g
    v = h W_v + b_v -> [10, 128]: V_g = [v_2g | v_2g+1]
    a1_j = softmax(q1_j k1_g^T / 8 + mask) V_g;  a2_j = softmax(q2_j k2_g^T / 8 + mask) V_g
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,  lam0 = 0.8 - 0.6 exp(-0.3 i_published)
    o_j = RMSNorm(a1_j - lam a2_j; w_sub, eps 1e-5) * (1 - lam0);  out = concat_j(o_j) W_o + b_o

each softmax under an EXPLICIT mask, the subtraction written out, K / V and
``M`` simply reused. ``i_published`` is the file's ``published_layer_index`` of
the layer.

``jax.numpy`` only, float32 throughout, ``default_matmul_precision
("highest")``, no kernel, no chunking of the recurrence, the convolution as
shifted sums, no layer scan; attention walks the queries in blocks
(``lax.map``: one block's program, whatever the length) so that the ``[pairs,
block, seq]`` float32 scores fit at 16k. Imports
nothing from ``ray_tpu.models`` or ``ray_tpu.ops``. Weights arrive as ``[in,
out]`` matrices and ``[taps, channels]`` filters: storage layouts.
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.dense_decoder import compare, query_block
from benchmarks.reference.moe_decoder import _position_errors

# The limits of the comparison that decides ``correct`` (``check``), each
# between two readings on a v5e at the published widths and 16,384 positions:
# the largest the program gives over its seeds and the lowest a WRONG
# computation gives (``harness/sambay_controls.py`` prints both; PERF.md
# section 6, PR 65, has the readings and their seeds).
#
# TOLERANCE, POSITION_TOLERANCE: relative RMS error of the program's logits
# against the reference over the compared positions (the last 256 of 16,384,
# each against the whole context), and at the worst single position. Twelve
# two-block layers in bfloat16 on a 2560-wide stream: the program reads
# 2.121e-2 to 2.604e-2 and 2.360e-2 to 3.016e-2 over fifteen seeds (my chip
# runs, PR 65, calls A, B and C: 3065000102, 3165008119 to 3165047714,
# 3165000299, 3265000611, 3265105429 to 3265629074; 1e-3 a block: Nemotron's
# eleven one-block layers read 1.2e-2). The wrong models
# read, each against the same program logits (call A, seed 3065000102): the
# memory taken after the gate 0.165 / 0.196, no ``lam`` term 0.371 / 0.422, no
# skip 1.41 / 1.45: NOT correct, each by both limits; a window that sees one key
# more 4.58e-2 / 0.130 (three window layers' worth of ONE key in 512), by both
# too. 4e-2 is 1.5 times the program's largest reading and a factor of 4 under
# the lowest of the first three; the window's edge stands 1.15 over it, which is
# no room, and is held by ``check_differential`` at a factor of 2.3 and by the
# position limit at 2.2. 6e-2 is 2.0 times the largest reading and 2.2 under
# the lowest wrong one.
TOLERANCE = 4e-2
POSITION_TOLERANCE = 6e-2
# TOLERANCE_SCAN: relative RMS error of the program's selective scan ALONE
# against the per-token recurrence on the reference's own operands of the
# file's first Mamba-1 layer, without the skip, read three times
# (``check_scan``). "own": float32, the fresh weights' decays (``dt A`` between
# -0.001 and -1.6 a token: most pairs carry their state across many chunks).
# "opened": float32, decays opened towards 1 (``OPENED``: ``dt`` 1e-4 and ``A``
# -1, so that 16,384 steps of state matter: a state's memory is 10,000 tokens).
# "timed": the own decays with ``u``, ``B``, ``C`` rounded once to the file's
# bfloat16 and handed over in it (``dt`` float32, as the mixer hands it), the
# instantiation the step times, against the float32 recurrence on the same
# rounded values: the kernel's state, decays and sums are float32 whatever the
# operands, so what is left is the output's own rounding to bfloat16 (2^-9).
# The program reads 0.0 on both float32 readings in every run (the float32
# kernel IS the recurrence: the same float32 operations a pair in the same
# order) and 1.658e-3 to 1.660e-3 on "timed" over fifteen seeds (calls A, B, C).
# With the carried state rounded to bfloat16 a token it reads 4.37e-2 / 0.527 /
# 4.37e-2, with ``dt`` rounded to bfloat16 on its way in 1.34e-3 / 1.09e-3 /
# 1.34e-3 (``harness/sambay_controls.py``, call B, seed 3165000401): NOT
# correct, the first by all three limits, the second by the float32 two (the
# "timed" reading rounds more than that itself). 2e-5 lies 50 times under the
# lowest wrong float32 reading and over a program that reads 0; 4e-3 is 2.4
# times the program's and a factor of 11 under the bfloat16 state's.
# "gradients": the BACKWARD kernel alone (``scan_gradients``: the "timed"
# operands with the layer's own skip, a seeded ``dy``), all six gradients
# against ``jax.vjp`` of the recurrence, each held by the dtype it leaves the
# kernels in. Over ten seeds (my chip runs, PR 65, call D) the program reads
# ``du`` 1.648e-3 to 1.654e-3, ``dB`` 1.648e-3 to 1.668e-3, ``dC`` 1.640e-3 to
# 1.670e-3 (bfloat16 out: 2^-9) and ``d(dt)`` 0.0, ``dA`` 0.0, ``dD`` 3.5e-7 to
# 3.7e-7 (float32 out). A state carried in bfloat16 reads ``d(dt)`` 3.84e-2,
# ``dA`` 0.116, ``dC`` 6.66e-2, ``dB`` 4.99e-3 (``du`` 1.656e-3: the skip's ``D dy``
# is most of it); ``dt`` in bfloat16 ``d(dt)`` 1.68e-3, ``dA`` 6.7e-4, ``dB``
# 2.39e-3 (call D, seed 3265629074): NOT correct, the first by both limits, the
# second by the float32 one. 2e-5 is 54 times the program's largest float32
# reading and 33 under the lowest wrong one; 4e-3 as for "timed".
TOLERANCE_SCAN = {
    "own": 2e-5, "opened": 2e-5, "timed": 4e-3, "gradients": {"float32": 2e-5, "bfloat16": 4e-3},
}
OPENED = {"dt": 1e-4, "A": -1.0}
# TOLERANCE_DIFF: relative RMS error of the program's differential attention
# ALONE (the mixer's output before the residual add, bfloat16 as the step runs
# it) against ``differential_attention`` on the reference's own normed input of
# one window layer and of the full layer (``check_differential``), PER UNIT OF
# ``conditioning``: the limit a layer is held to is this times ``sqrt(1 + lam^2)
# / |1 - lam|`` of the layer's own ``lam``, up to ``CONDITIONING_CAP`` units. Why: the two flash calls round ``a1``
# and ``a2`` to bfloat16, and on fresh weights the two are nearly alike, so the
# difference ``a1 - lam a2`` is ``(1 - lam)`` of either and carries both
# roundings. The full layer stands for published layer 17, ``lam0`` 0.796, and
# its ``lam`` falls between 0.65 and 1.15 by seed: it read 6.2e-3 at ``lam`` 0.653
# and 1.97e-2 at 0.929 (over twenty seeds, calls A to D; PERF.md section 6),
# which is 1.0e-3 to 1.8e-3 a unit up to twenty units; beyond, the reading
# saturates (2.27e-2 at 70 units, 2.73e-2 at 115, ``lam`` within 0.012 of 1:
# ``a1`` and ``a2`` are alike, not equal); the window layer
# (published layer 1, ``lam`` 0.14 to 0.53) 4.0e-3 to 5.1e-3, 2.1e-3 to 3.4e-3 a
# unit. So the units are CAPPED (``CONDITIONING_CAP``, 5: a limit of 4e-2 at
# most, 1.5 times the largest reading of all, 2.73e-2). The wrong mixers
# read, window / full layer: at ``lam`` 0.285 / 0.653 (call A) a window of 513
# keys 2.60e-2 on the window layer, 1.79e-2 a unit; ``lam`` dropped 0.236 /
# 0.196; no norm on the difference 0.886 / 0.844; and at ``lam`` 0.227 / 1.0124
# (call D, seed 3265629074: under the capped limit, 1.06e-2 / 4e-2) a window of
# 513 keys 2.47e-2; ``lam`` dropped 0.180 / 1.477; no norm 0.876 / 0.955: NOT
# correct at either ``lam``, each by the layer it changes. 8e-3 a unit is 2.4
# times the program's largest and a factor of 2.2 under the lowest wrong
# reading. That the program's reading IS the bfloat16 of ``a1`` and ``a2``
# under the subtraction has a witness: the same mixer with its leaves, its
# input and the flash kernels' operands in float32
# (``sambay_controls.attend_float32``) reads 5.4e-6 where the program reads
# 2.73e-2 (``lam`` 1.0124), 3.8e-6 for 1.97e-2 and 2.4e-6 for 1.30e-2 (seeds
# 3165047714 and 3165000299, which a first limit of 1.3e-2 whatever ``lam``, set
# from call A's one seed, had refused in call B).
TOLERANCE_DIFF = 8e-3

MLP_NAMES = (
    "post_attention_layernorm_weight", "post_attention_layernorm_bias", "gate_up_proj", "down_proj",
)
NORM_NAMES = ("input_layernorm_weight", "input_layernorm_bias")
MAMBA_NAMES = (
    "in_proj", "conv1d_weight", "conv1d_bias", "x_proj", "dt_proj_weight", "dt_proj_bias",
    "A_log", "D", "out_proj",
)
LAMBDA_NAMES = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "subln_weight")
ATTENTION_NAMES = ("Wqkv", "Wqkv_bias", "out_proj", "out_proj_bias", *LAMBDA_NAMES)
CROSS_NAMES = ("Wq", "Wq_bias", "out_proj", "out_proj_bias", *LAMBDA_NAMES)
GMU_NAMES = ("in_proj", "out_proj")
MIXER_NAMES = {
    "mamba": MAMBA_NAMES, "window": ATTENTION_NAMES, "full": ATTENTION_NAMES,
    "gmu": GMU_NAMES, "cross": CROSS_NAMES,
}
# What a wrong model computes, by name: ``harness/sambay_controls.py`` hands one
# to ``logits`` as ``cfg["control"]``; no configuration file has the key.
CONTROLS = ("no_lambda", "window_off_by_one", "no_skip", "memory_after_gate")
SUBLN_EPS = 1e-5


def layer_kinds(cfg: dict) -> list[str]:
    """The kind of each of the file's ``num_hidden_layers`` layers, by the
    model's own rule (the module docstring's table)."""
    n = cfg["num_hidden_layers"]
    if n % 4 or n < 8 or cfg["mb_per_layer"] != 2:
        raise ValueError(f"{n} layers at mb_per_layer {cfg['mb_per_layer']}: the rule needs n % 4 == 0, n >= 8")
    s = n // 2

    def kind(i):
        if i % 2 == 0:
            return "mamba" if i <= s else "gmu"
        return "window" if i < s else "full" if i == s + 1 else "cross"

    return [kind(i) for i in range(n)]


def lam_init(cfg: dict, layer: int) -> float:
    """``lam0`` of the file's layer ``layer``: a constant of its PUBLISHED index."""
    return 0.8 - 0.6 * math.exp(-0.3 * cfg["published_layer_index"][layer])


def mamba_sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    return {
        "inner": cfg["mamba_expand"] * d, "state": cfg["mamba_d_state"],
        "rank": cfg["mamba_dt_rank"], "taps": cfg["mamba_d_conv"],
    }


def layer_norm(x, weight, bias, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps) * weight + bias


def short_conv(x, filters, bias):
    """``SiLU(conv(x) + bias)`` as shifted sums: ``x`` ``[b, s, channels]``,
    ``filters`` ``[taps, channels]``, the LAST tap on the current token."""
    taps, seq = filters.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + seq] * filters[j] for j in range(taps)) + bias)


def recurrence(u, dt, A, B, C, D, state_dtype=jnp.float32, kept_every=None):
    """The selective recurrence a token at a time: ``u`` and ``dt`` ``[b, s,
    channels]``, ``A`` ``[channels, states]``, ``B`` and ``C`` ``[b, s, states]``,
    ``D`` ``[channels]``; float32 (``state_dtype``: a control's carried state,
    rounded to that dtype's bits after every token). ``kept_every``: the same
    tokens in the same order, walked in blocks of that many under
    ``jax.checkpoint``, so that ``jax.vjp`` of it keeps a state a BLOCK and
    makes a block's again (a state a token is 5.4 GB at 16,384 x 5,120 x 16)."""
    rounded = jnp.finfo(state_dtype)

    def token(state, operands):
        u_t, dt_t, b_t, c_t = operands
        state = jnp.exp(dt_t[..., None] * A) * state + (dt_t * u_t)[..., None] * b_t[:, None, :]
        # (a cast there and back is one XLA may drop on a TPU: excess precision is allowed)
        state = jax.lax.reduce_precision(state, rounded.nexp, rounded.nmant)
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    by_time = lambda t: jnp.moveaxis(t, 1, 0)
    state = jnp.zeros((u.shape[0], *A.shape), jnp.float32)
    # (eight tokens a trip of the loop: the same operations in the same order)
    walk = lambda state, tokens: jax.lax.scan(token, state, tokens, unroll=8)
    tokens = tuple(by_time(t) for t in (u, dt, B, C))
    if kept_every is None:
        _, y = walk(state, tokens)
    else:
        blocks = tuple(t.reshape(-1, kept_every, *t.shape[1:]) for t in tokens)
        _, y = jax.lax.scan(jax.checkpoint(walk), state, blocks)
        y = y.reshape(-1, *y.shape[2:])
    return jnp.moveaxis(y, 0, 1) + D * u


def _scan_operands(h, w, sizes):
    """``(z, u', dt, A, B, C)`` of a Mamba-1 layer from its normed input."""
    u, z = jnp.split(h @ w["in_proj"], 2, axis=-1)
    u = short_conv(u, w["conv1d_weight"], w["conv1d_bias"])
    step, B, C = jnp.split(u @ w["x_proj"], (sizes["rank"], sizes["rank"] + sizes["state"]), axis=-1)
    dt = jax.nn.softplus(step @ w["dt_proj_weight"] + w["dt_proj_bias"])
    return z, u, dt, -jnp.exp(w["A_log"]), B, C


def _f32(w):
    return {name: value.astype(jnp.float32) for name, value in w.items()}


@functools.partial(jax.jit, static_argnames=("sizes", "eps"))
def scan_operands(x, w, *, sizes, eps):
    """``(u', dt, A, B, C, D)`` of the recurrence of a Mamba-1 layer whose
    input is the stream ``x``: what ``check_scan`` hands both scans."""
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        h = layer_norm(x, w["input_layernorm_weight"], w["input_layernorm_bias"], eps)
        _, *operands = _scan_operands(h, w, dict(sizes))
        return (*operands, w["D"])


@functools.partial(jax.jit, static_argnames=("eps",))
def normed_input(x, w, *, eps):
    """``LN_1(x)`` of a layer: what ``check_differential`` hands both mixers."""
    w = _f32(w)
    return layer_norm(x, w["input_layernorm_weight"], w["input_layernorm_bias"], eps)


@functools.partial(jax.jit, static_argnames=("sizes", "eps", "control"))
def mamba_mixer(x, w, *, sizes, eps, control=None):
    """``(mixer(LN_1(x)), M)`` of a Mamba-1 layer, ``M`` the scan's output
    BEFORE the gate. x: [b, s, hidden] float32."""
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        h = layer_norm(x, w["input_layernorm_weight"], w["input_layernorm_bias"], eps)
        z, *operands = _scan_operands(h, w, dict(sizes))
        y = recurrence(*operands, jnp.zeros_like(w["D"]) if control == "no_skip" else w["D"])
        gated = y * jax.nn.silu(z)
        return gated @ w["out_proj"], (gated if control == "memory_after_gate" else y)


@functools.partial(jax.jit, static_argnames=("eps",))
def gmu_mixer(x, w, memory, *, eps):
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        h = layer_norm(x, w["input_layernorm_weight"], w["input_layernorm_bias"], eps)
        return (jax.nn.silu(h @ w["in_proj"]) * memory) @ w["out_proj"]


# Elements of one block's float32 scores ``[batch, pairs, block, seq]``: 256 MiB
# (128 queries a block at 20 pairs on 16,384 keys), beside a training state.
SCORES_LIMIT = 1 << 26


def differential_attention(q1, q2, k1, k2, v, w, lam0, window=None, control=None):
    """``concat_j(o_j)`` ``[b, s, pairs x 2 d]`` of the pairs' queries ``[b, s,
    pairs, d]`` on ``k1``, ``k2`` ``[b, s, kv pairs, d]`` and ``v`` ``[b, s, kv
    pairs, 2 d]``: two softmaxes under the explicit mask (causal, or with
    ``window`` the band ``i - window < j <= i``), the subtraction, the norm a
    pair, the scale; the queries in blocks. ``lam0`` is an operand: one program
    serves every layer of a kind."""
    batch, seq, pairs, d = q1.shape
    group = pairs // k1.shape[2]
    k1, k2, v = (jnp.repeat(t, group, axis=2) for t in (k1, k2, v))
    lam = jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"])) - jnp.exp(
        jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + lam0
    if control == "no_lambda":
        lam = 0.0
    block = query_block(batch, pairs, seq, SCORES_LIMIT)
    key_pos = jnp.arange(seq)

    def of_block(start):
        query_pos = start + jnp.arange(block)
        visible = key_pos[None, :] <= query_pos[:, None]
        if window is not None:
            first = query_pos[:, None] - window + (0 if control == "window_off_by_one" else 1)
            visible = visible & (key_pos[None, :] >= first)

        def attend(q, k):
            rows = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
            scores = jnp.einsum("bqhd,bkhd->bhqk", rows, k) / math.sqrt(d)
            probs = jax.nn.softmax(jnp.where(visible[None, None], scores, -jnp.inf), axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

        diff = attend(q1, k1) - lam * attend(q2, k2)                 # [b, block, pairs, 2 d]
        normed = diff * jax.lax.rsqrt(jnp.mean(diff * diff, axis=-1, keepdims=True) + SUBLN_EPS)
        return normed * w["subln_weight"] * (1.0 - lam0)

    out = jax.lax.map(of_block, jnp.arange(0, seq, block))           # [blocks, b, block, pairs, 2 d]
    return jnp.moveaxis(out, 0, 1).reshape(batch, seq, -1)


def _pairs(x, heads, halves):
    batch, seq, _ = x.shape
    x = x.reshape(batch, seq, heads // 2, 2, -1)
    return (x[:, :, :, 0], x[:, :, :, 1]) if halves else x.reshape(batch, seq, heads // 2, -1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "window", "control"))
def self_attention(h, w, *, heads, kv_heads, lam0, window=None, control=None):
    """``(out, (k1, k2, V))`` of a window or full layer's differential
    attention on its NORMED input ``h``."""
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        d = h.shape[-1] // heads
        q, k, v = jnp.split(h @ w["Wqkv"] + w["Wqkv_bias"], (heads * d, (heads + kv_heads) * d), axis=-1)
        q1, q2 = _pairs(q, heads, True)
        k1, k2 = _pairs(k, kv_heads, True)
        v = _pairs(v, kv_heads, False)
        o = differential_attention(q1, q2, k1, k2, v, w, lam0, window, control)
        return o @ w["out_proj"] + w["out_proj_bias"], (k1, k2, v)


@functools.partial(jax.jit, static_argnames=("heads", "control"))
def cross_attention(h, w, shared, *, heads, lam0, control=None):
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        q1, q2 = _pairs(h @ w["Wq"] + w["Wq_bias"], heads, True)
        o = differential_attention(q1, q2, *shared, w, lam0, None, control)
        return o @ w["out_proj"] + w["out_proj_bias"]


@functools.partial(jax.jit, static_argnames=("eps",))
def mlp_forward(x, w, *, eps):
    """x + SwiGLU(LN_2(x)), the gate and the up half of ONE ``W_gate_up``."""
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        h = layer_norm(x, w["post_attention_layernorm_weight"], w["post_attention_layernorm_bias"], eps)
        gate, up = jnp.split(h @ w["gate_up_proj"], 2, axis=-1)
        return x + (jax.nn.silu(gate) * up) @ w["down_proj"]


@functools.partial(jax.jit, static_argnames=("eps", "last"))
def head_forward(x, weight, bias, embed, *, eps, last):
    with jax.default_matmul_precision("highest"):
        if last is not None:
            x = x[:, -last:]
        x = layer_norm(x, weight.astype(jnp.float32), bias.astype(jnp.float32), eps)
        return x @ embed.astype(jnp.float32).T


def _sizes_key(cfg):
    return tuple(sorted(mamba_sizes(cfg).items()))


def hidden(weights, tokens, cfg, layers=None, taps=None):
    """The residual stream after the first ``layers`` layers (None: all).
    ``taps`` ``{layer index: None}``: filled on the way with ``(the stream
    ahead of that layer, its weights)``, what ``check_scan`` and
    ``check_differential`` read, so that one pass serves the three parts."""
    x = weights["embed_tokens"].astype(jnp.float32)[tokens]
    eps, control = float(cfg["layer_norm_eps"]), cfg.get("control")
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    kinds = layer_kinds(cfg)
    bridge = cfg["num_hidden_layers"] // 2
    memory = shared = None
    for i, (kind, layer) in enumerate(zip(kinds, weights["layers"])):
        if layers is not None and i >= layers:
            break
        if taps is not None and i in taps:
            taps[i] = (x, layer)
        own = {name: layer[name] for name in (*NORM_NAMES, *MIXER_NAMES[kind])}
        if kind == "mamba":
            out, handed = mamba_mixer(x, own, sizes=_sizes_key(cfg), eps=eps, control=control)
            memory = handed if i == bridge else memory
        elif kind == "gmu":
            out = gmu_mixer(x, own, memory, eps=eps)
        else:
            h = normed_input(x, {name: layer[name] for name in NORM_NAMES}, eps=eps)
            mixer = {name: layer[name] for name in MIXER_NAMES[kind]}
            lam0 = lam_init(cfg, i)
            if kind == "cross":
                out = cross_attention(h, mixer, shared, heads=heads, lam0=lam0, control=control)
            else:
                out, handed = self_attention(
                    h, mixer, heads=heads, kv_heads=kv_heads, lam0=lam0, control=control,
                    window=cfg["sliding_window"] if kind == "window" else None,
                )
                shared = handed if kind == "full" else shared
        x = mlp_forward(x + out, {name: layer[name] for name in MLP_NAMES}, eps=eps)
    return x


def logits(weights, tokens, cfg, last=None, taps=None):
    """Reference logits ``[batch, seq or last, vocab]`` float32 (``taps``:
    ``hidden``'s). ``weights``:
    ``{"embed_tokens", "layers": iterable of per-layer dicts under this file's
    names, "final_layernorm_weight", "final_layernorm_bias"}`` (the head is the
    embedding's transpose)."""
    x = hidden(weights, tokens, cfg, taps=taps)
    return head_forward(
        x, weights["final_layernorm_weight"], weights["final_layernorm_bias"],
        weights["embed_tokens"], eps=float(cfg["layer_norm_eps"]), last=last,
    )


def loss(weights, tokens, targets, cfg):
    """Mean token cross-entropy; ``jax.grad`` of this is the reference's
    gradient. ``weights``' ``layers`` must be a list here (one pass)."""
    logp = jax.nn.log_softmax(logits(weights, tokens, cfg), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def _stream_before(weights, tokens, cfg, at):
    """``(the stream ahead of layer ``at``, that layer's weights)``;
    ``weights["layers"]`` a list that reaches it."""
    return hidden(weights, tokens, cfg, layers=at), weights["layers"][at]


def _first_layers(weights, count):
    """``weights`` with its first ``count`` layers, listed (the iterator is read once)."""
    return dict(weights, layers=list(itertools.islice(weights["layers"], count)))


def checked_layers(cfg: dict) -> dict:
    """``{layer index: None}`` of the layers ``check`` reads alone: the first
    Mamba-1 layer, the first window layer and the full layer."""
    kinds = layer_kinds(cfg)
    return {kinds.index(kind): None for kind in ("mamba", "window", "full")}


def check_scan(scan, weights, tokens, cfg, taps=None) -> dict:
    """The program's selective scan ALONE, at the cell's own shapes, on
    operands that are the reference's: ``scan(u, dt, A, B, C, D)`` (the family
    hands the timed path's ``ops/selective_scan.py``) against ``recurrence``
    for the operands of the FIRST Mamba-1 layer, all WITHOUT the skip term
    (it is most of a fresh layer's output, exact on both sides, and would
    dilute the state's error by its size), read three times (``TOLERANCE_SCAN``
    says which and why), and a fourth reading of its BACKWARD
    (``scan_gradients``). Relative RMS error over every position. The logits
    cannot see this: twelve layers' bfloat16 rounding is a thousand times the
    float32 scan's own error, and nothing else of the check differentiates."""
    at = layer_kinds(cfg).index("mamba")
    x, layer = taps[at] if taps else _stream_before(_first_layers(weights, at + 1), tokens, cfg, at)
    own = {name: layer[name] for name in (*NORM_NAMES, *MAMBA_NAMES)}
    u, dt, A, B, C, D = scan_operands(x, own, sizes=_sizes_key(cfg), eps=float(cfg["layer_norm_eps"]))
    no_skip = jnp.zeros_like(D)
    once = lambda t: t.astype(jnp.dtype(cfg.get("torch_dtype", "float32")))
    readings = {
        "own": (u, dt, A, B, C, no_skip),
        "opened": (u, jnp.full_like(dt, OPENED["dt"]), jnp.full_like(A, OPENED["A"]), B, C, no_skip),
        "timed": (once(u), dt, A, once(B), once(C), no_skip),
    }
    out = {"tolerance": TOLERANCE_SCAN, "layer": at, "ok": True}
    for reading, operands in readings.items():
        want = jax.jit(recurrence)(*(t.astype(jnp.float32) for t in operands))
        found = compare(scan(*operands), want, TOLERANCE_SCAN[reading])
        # of ``dt A`` (``dt > 0 > A``), without the array a token, channel and state
        step, rate = operands[1], operands[2]
        out[reading] = {
            "rel_rms": found["rel_rms"], "max_abs": found["max_abs"],
            "reference_rms": found["reference_rms"],
            "steepest_log_decay": float(jnp.max(step) * jnp.min(rate)),
            "mean_log_decay": float(jnp.mean(step) * jnp.mean(rate)), "ok": bool(found["ok"]),
        }
        out["ok"] = bool(out["ok"] and found["ok"])
    out["gradients"] = scan_gradients(scan, (*readings["timed"][:5], D), int(jnp.sum(tokens)) % 2**31)
    out["ok"] = bool(out["ok"] and out["gradients"]["ok"])
    return out


GRADIENT_NAMES = ("u", "dt", "A", "B", "C", "D")


def scan_gradients(scan, operands, seed: int) -> dict:
    """The BACKWARD of the program's scan alone: ``jax.vjp`` of ``scan`` on
    the "timed" reading's operands (with the layer's own skip ``D``: what the
    step runs) under a seeded cotangent ``dy`` in the output's dtype, all six
    gradients against ``jax.vjp`` of ``recurrence`` on the same values in
    float32 (``kept_every``: the same recurrence). Relative RMS error of each,
    held to ``TOLERANCE_SCAN["gradients"]`` by the dtype the gradient comes in
    (``d(dt)``, ``dA`` and ``dD`` leave the kernels in float32, ``du``, ``dB``
    and ``dC`` in the operands' dtype)."""
    seq = operands[0].shape[1]
    dy = jax.random.normal(jax.random.PRNGKey(seed), operands[0].shape, jnp.float32)
    dy = dy.astype(operands[0].dtype)
    y, back = jax.vjp(scan, *operands)
    found = back(dy.astype(y.dtype))
    f32 = lambda t: t.astype(jnp.float32)
    plain = functools.partial(recurrence, kept_every=math.gcd(seq, 128))
    wanted = jax.jit(lambda ops, dy: jax.vjp(plain, *ops)[1](dy))(tuple(map(f32, operands)), f32(dy))
    out = {"ok": True}
    for name, got, want in zip(GRADIENT_NAMES, found, wanted):
        one = compare(f32(got), want, TOLERANCE_SCAN["gradients"][jnp.dtype(got.dtype).name])
        out[name] = one["rel_rms"]
        out["ok"] = bool(out["ok"] and one["ok"])
    return out


def conditioning(w, lam0) -> float:
    """How far the pair's subtraction magnifies its operands' rounding: with
    ``a1`` and ``a2`` alike (fresh weights: both softmaxes average nearly the same
    values) ``a1 - lam a2`` is ``(1 - lam) a`` and carries both operands' errors,
    ``sqrt(1 + lam^2) / |1 - lam|`` relative to itself; the norm behind it keeps
    that ratio. ``lam`` from the layer's own vectors, float32."""
    dot = lambda a, b: jnp.sum(w[a].astype(jnp.float32) * w[b].astype(jnp.float32))
    lam = float(jnp.exp(dot("lambda_q1", "lambda_k1")) - jnp.exp(dot("lambda_q2", "lambda_k2")) + lam0)
    return math.sqrt(1.0 + lam * lam) / abs(1.0 - lam) if lam != 1.0 else math.inf


# Units of ``conditioning`` beyond which a layer's limit stops growing, as the
# program's reading does (``TOLERANCE_DIFF``): 5 x 8e-3 = 4e-2 at most.
CONDITIONING_CAP = 5.0


def check_differential(attend, weights, tokens, cfg, taps=None) -> dict:
    """The program's differential attention ALONE on the reference's own
    normed input of the first window layer and of the full layer:
    ``attend(kind, layer, h, lam0)`` (the family hands the timed path's mixer,
    in the file's dtype) against ``self_attention``. What the logits see of a
    window's edge or of ``lam`` is a few key positions' worth of one layer."""
    kinds = layer_kinds(cfg)
    eps = float(cfg["layer_norm_eps"])
    out = {"tolerance": TOLERANCE_DIFF, "ok": True}
    if not taps:
        weights = _first_layers(weights, kinds.index("full") + 1)
    for kind in ("window", "full"):
        at = kinds.index(kind)
        x, layer = taps[at] if taps else _stream_before(weights, tokens, cfg, at)
        h = normed_input(x, {name: layer[name] for name in NORM_NAMES}, eps=eps)
        want, _ = self_attention(
            h, {name: layer[name] for name in ATTENTION_NAMES}, heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], lam0=lam_init(cfg, at), control=cfg.get("control"),
            window=cfg["sliding_window"] if kind == "window" else None,
        )
        magnified = conditioning(layer, lam_init(cfg, at))
        limit = TOLERANCE_DIFF * min(magnified, CONDITIONING_CAP)
        found = compare(attend(kind, at, h, lam_init(cfg, at)), want, limit)
        out[kind] = {
            "layer": at, "rel_rms": found["rel_rms"], "max_abs": found["max_abs"],
            "conditioning": magnified, "limit": found["tolerance"], "ok": bool(found["ok"]),
        }
        out["ok"] = bool(out["ok"] and found["ok"])
    return out


def check(program_logits, weights_fn, tokens, cfg, last=None, scan=None, attend=None) -> dict:
    """The comparison that decides ``correct``, in three parts: (a) the
    program's logits against the reference over the compared positions and at
    the worst single one; (b) with ``scan``, the scan alone, forward and
    backward (``check_scan``); (c) with ``attend``, differential attention alone
    (``check_differential``), all from ONE pass of the reference (``taps``).
    ``weights_fn()`` gives the weights (a fresh layer iterator for each pass)."""
    taps = checked_layers(cfg)
    reference = logits(weights_fn(), tokens, cfg, last=last, taps=taps)
    published = compare(program_logits, reference, TOLERANCE)
    positions = _position_errors(program_logits, reference)
    worst = float(positions["worst"])
    out = {
        "published": published,
        "worst_position_rel_rms": worst,
        "worst_position_at": int(positions["at"]),
        "position_rel_rms_p50": float(positions["p50"]),
        "position_rel_rms_p99": float(positions["p99"]),
        "position_tolerance": POSITION_TOLERANCE,
        "ok": bool(published["ok"] and worst <= POSITION_TOLERANCE),
    }
    if scan is not None:
        out["scan"] = check_scan(scan, None, tokens, cfg, taps)
        out["ok"] = bool(out["ok"] and out["scan"]["ok"])
    if attend is not None:
        out["differential"] = check_differential(attend, None, tokens, cfg, taps)
        out["ok"] = bool(out["ok"] and out["differential"]["ok"])
    return out
