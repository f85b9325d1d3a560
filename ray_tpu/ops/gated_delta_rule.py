"""The gated delta rule (Gated DeltaNet's linear attention) as a chunked
scan: Pallas TPU kernels forward AND backward, beside the per-token
recurrence they are held to.

Per head, with a state ``S`` of ``[d_k, d_v]`` that starts at zero, a decay
``alpha_t = exp(log_alpha_t)`` in (0, 1] and a write strength ``beta_t``
(in (0, 2) where negative eigenvalues are allowed)::

    S_t = alpha_t S_{t-1} + k_t (beta_t (v_t - alpha_t S_{t-1}^T k_t))^T
    o_t = S_t^T q_t

(``S`` here is the transpose of the ``[d_v, d_k]`` state of the papers; q
arrives scaled, k arrives normalised: the caller's business.)

A decay per CHANNEL (Kimi Delta Attention, arXiv:2510.26692): ``log_alpha``
arrives as ``[.., seq, d_k]``, one decay a key channel, and ``alpha_t`` above
becomes ``Diag(e^{g_t})``, scaling the state's ROWS::

    S_t = Diag(e^{g_t}) S_{t-1} + k_t (beta_t (v_t - (Diag(e^{g_t}) S_{t-1})^T k_t))^T

``gated_delta_rule_reference`` is that recurrence as a ``lax.scan``, one
token a step, float32, either decay: the CPU path of the model and the oracle.

``gated_delta_rule`` is the chunked form (Yang et al., "Gated Delta
Networks", the WY representation). A sequence is cut into chunks of
``chunk`` tokens. With ``G_t`` the running sum of ``log_alpha`` inside a
chunk, ``S`` the state at the chunk's start and ``u_t = beta_t (v_t -
alpha_t S_{t-1}^T k_t)`` the value each token really writes::

    (I + A) U = beta V - (beta e^G K) S,   A[t, i] = beta_t e^{G_t - G_i} k_t.k_i  (i < t)
    O  = (e^G Q) S + (Q K^T . e^{G_t - G_i})_{i <= t} U
    S' = e^{G_C} S + (e^{G_C - G} K)^T U

Two halves, four Mosaic kernels (six with the channel decay's own preparation
pair, further down), and ``_prepare`` beside them as what the first two are
held to:

* the chunk preparation (``_delta_prepare_forward``): ``T = (I + A)^-1``
  and from it ``W = T (beta e^G K)``, ``U0 = T (beta V)``, and ``Qg``,
  ``P``, ``Kd``, ``gamma`` of the other two lines, float32 throughout.
  ``gap``, the two decay masks, ``K K^T``, ``Q K^T``, ``A``, every level of
  the inverse and ``T`` are ``[rows, rows]`` values that live and die in
  VMEM; every decay is ``exp`` of a difference that is <= 0, masked BEFORE
  the ``exp``, so a decay near 0 underflows to 0 and never divides. ``T`` is
  built by doubling (``_Masks.inverses``, ``_unit_lower_inverse``'s own
  steps), exactly, block pairs at a time: nothing in it cancels. Its
  transpose is written by hand (``_delta_prepare_backward``): jax never
  sees the chain. ``_prepare`` is the same mathematics in XLA,
  differentiated by jax: the oracle of both (equal to the last bit forward
  on the chip, 6e-7 of the largest gradient backward: my chip runs, PR 33)
  and the ``kernels=False`` path. In XLA every ``[.., 64, 64]`` intermediate
  went out to HBM and back between fusions and jax's transpose ran the
  chain a second time: 50.1 ms a layer of the Olmo-Hybrid cell's ``[30,
  16384, 96 | 192]`` beside 19.9 of scan kernels; the two kernels take 17.4
  (two forward calls) + 7.4 (device trace of the rule alone, my chip runs,
  PR 33), bound by the MXU's float32 rate: a ``[128, 128]`` product at
  fp32 contract precision is six bfloat16 passes, 0.128 us.
* the scan over chunks (``_delta_rule_forward``, ``_delta_rule_backward``):
  ``U = U0 - W S``, ``O = Qg S + P U``, ``S' = gamma S + Kd^T U`` with the
  ``[d_k, d_v]`` state carried in float32 in VMEM from one grid step to the
  next, and the same walk backwards for the six operands' gradients with
  ``dS`` carried (``_scan_reference`` is the same scan in plain jax.numpy).
  Within a chunk everything is a matmul for the MXU.

What the backward needs of the forward, and what of it is KEPT. The chunk
inverse ``T``: most of a preparation call is the doubling (10 of the bounded
forward's 16 right-operand tiles, 10 of the scalar form's 14 products), and
``T`` is small: block-diagonal in its product, so its diagonal blocks side
by side are all of it (``_Masks.packed``: ``[rows, seq / chunks a product,
chunks a product x chunk]`` float32, 256 bytes a head and token at a chunk
of 64, what the output costs at ``d_v`` 128 in bfloat16: 128 MiB a layer at
Ling's ``[32, 16384]``). A gradient's forward (``_chunked_fwd``) writes it,
each group's call its rows of one array; the backward's preparation call
reads it (``inverse="read"``: the same kernels with no ``A`` for the
inverse and no level of the doubling), and so does the preparation's
transpose. Float32, the bits the forward computed. The state at every
chunk's START (``[chunks, d_k, d_v]`` float32 a head: ``d_k x d_v / chunk``
a head and token, 1,024 bytes at 128 | 128, four times ``T``; 566 MB a layer
at 30 heads x 16384 tokens of 96 | 192) is NOT kept: three layers' worth
cost the Olmo-Hybrid cell 2.75 GiB of a v5e's 15.75 and the step then
needs 17.8 (compile for a described v5e, PR 32), so the backward runs the
scan's forward kernel once more, for the states alone (``_chunked_bwd``).
The output and ``T`` carry ``RESIDUAL_NAMES`` (checkpoint_name): a layer
checkpoint whose policy saves those names keeps both for the layers after
and runs no kernel of the rule again. ``kept_bytes`` counts what is kept.

The channel decay in chunks, ``G`` now ``[chunk, d_k]``: the decay no longer
factors out of the dot products, ``A[t, i] = beta_t sum_c k_tc k_ic e^{G_tc -
G_ic}``, ``P[t, i] = sum_c q_tc k_ic e^{G_tc - G_ic}``; ``W = T (beta e^G .
K)``, ``Qg = e^G . Q``, ``Kd = e^{G_C - G} . K`` as before with ``.`` per
channel, and ``gamma = e^{G_C}`` a VECTOR over ``d_k``. ``A`` and ``P`` stay
matmuls over the channels of operands that carry the decay, and must not
overflow: the unsplit ``e^{G_t} . e^{-G_i}`` does inside one chunk at -5 a
token. ANY ``log_alpha <= 0`` is computed (Kimi Linear's own gate, ``-exp(A)
softplus(.)``, has no lower bound: at a rate of 16 single tokens reach -30 on
fresh weights and -200 is a trained gate's "forget"), in one of two exact
forms, neither of which clamps an exponent or leaves a term out:

* by HALVING (``_prepare_channel_xla``, ``_halved_products``; no bound
  stated: the default). Level ``l`` owns the pairs ``(t, i)`` of an aligned
  block of ``2^{l+1}`` tokens with ``t`` in its second half and ``i`` in its
  first, and splits ``e^{G_t - G_i}`` BETWEEN the halves: rows ``x_t . e^{g_m +
  .. + g_t}`` (``m`` the second half's first token), columns ``k_i . e^{g_{i+1}
  + .. + g_{m-1}}``. Both exponents are sums of ``log_alpha`` (<= 0, whatever
  the decay), taken inside a half by doubling, never differences of two
  running sums: ``G_t - G_i`` from a float32 ``G`` of -12,800 (a chunk of
  -200s) is off by 1e-3 where the true gap is -0.01, and the rule read 1.7e-5
  from the recurrence where it now reads 1.7e-7 (tests/test_channel_decay.py's
  ``steep`` draw, CPU, float32). Six levels at a chunk of 64, one decayed copy
  of K each, and ``P``'s diagonal ``q_t . k_t`` beside them. ``Kd`` takes its
  ``G_C - G_t`` as the sum of what follows ``t`` too (``_Blocks.to_end``).
* split at a sub-block's first row (``_bounded_products``), where the caller
  states a bound ``log_alpha_bound`` that ``carries_bound``: the chunk is cut
  into sub-blocks of ``_SUB_CHUNK`` = 16 rows, and row block ``r`` multiplies
  ``x_t . e^{G_t - R_r}`` (``R_r`` the ``G`` of its first token: exponent <= 0)
  by ``k_i . e^{R_r - G_i}``, whose exponent is <= 0 for every column before the
  block and at most ``15 |bound|`` inside it: ``e^75`` at Ling's published
  ``kda_lower_bound`` of -5, under float32's ``e^88``. Four products a pair of
  chunks where halving takes six of four times the rows: the bounded cell pays
  nothing for the other's generality, and a bound too steep for it (-6) or none
  takes the halving form. The two agree to rounding (tests/test_channel_decay.py).

The preparation is a Mosaic pair of its own (``_channel_prepare_forward``,
``_channel_prepare_backward``, under scope ``decay_prepare``, told the form by
a static ``bounded``; ``_prepare_channel`` is the seam the timed forward takes
its operands through), beside the scalar pair and sharing ``_Masks`` with it;
``_prepare_channel_xla`` is the halving form in XLA, differentiated by jax: the
oracle of both forms and the ``kernels=False`` path. What the kernels hold in
VMEM and XLA wrote to HBM: the running sum ``G`` (taken in the kernel by
doubling: sublane rotations and adds), the halves' sums or the rows' decay
``e^{G_t - R_r(t)}``, the decayed copies of K, ``A``, every level of the
inverse, and in the backward ``dT``, ``dA``, each level's or block's ``dX`` and
``dC``. Two chunks ride one 128-row product. Bounded: row block ``r`` of both
stacks ``k . e^{G - R_r}`` and ``q . e^{G - R_r}`` into ONE ``[64, d_k]`` left
operand against one right tile, ``k . e^{R_r - G}`` (``A`` and ``P`` read the
same columns); right-operand tiles a pair of chunks: forward 16 (4 row blocks,
10 for the inverse's five levels, 2 for ``W`` and ``U0``; 6 where ``T`` is
read), backward 14 (``dT``
2, ``T^T dW`` and ``T^T dU0`` 2, ``dA`` 2, ``dX_r = dM_r C_r`` 4, ``dC_r =
dM_r^T X_r`` 4 at half the contraction). Halving: a level stacks ``k . rows``
and ``q . rows`` into one ``[256, d_k]`` left operand against ``k . columns``:
forward 6 such products beside the same 12 (beside 2 where ``T`` is read: the
stacks stay whole there, ``A``'s half with no reader), backward 6 levels of ``dX = dM C``
and ``dC = dM^T X`` (contraction 256) beside the same 6. In both the split
point drops out of the gradient exactly: what reaches ``G`` is the same
expression either way. At the Ling cell's ``[32, 16384, 128 | 128]`` the bounded
forward takes 8.93 ms a layer and call and the backward 7.76 in the step (9.84
/ 8.55 alone, thirty-two heads a call), where XLA took 18.4 / 43.2: 94 % and
95 % of what the MXU's float32 rate allows those tiles (0.128 us each); a
layer's three calls 25.8 ms where XLA's took 61.6 (device traces, my chip
runs, PR 41); PERF.md section 6, PR 48, has the halving form's. The scan
kernels are the same two, told by ``gamma``'s shape to scale the state's
rows: the scalar path's text is unchanged.

Two entries, one implementation (``_rule``), the same values and gradients.
``gated_delta_rule`` takes HEADS-FIRST arrays, ``[batch, heads, seq, .]``: a
head's tokens together, which the six kernels read as ``[rows, seq, width]``
blocks. ``gated_delta_rule_by_token`` takes TOKEN-MAJOR arrays, ``[batch, seq,
heads, .]``, what a projection or a convolution writes: where BOTH head
widths fill whole lanes (``d_k % 128 == 0 and d_v % 128 == 0``, read from the
operands' shapes and from nothing else) the kernels read q, k, v and a
channel decay and write the output and the gradients in that layout as it
stands, head ``i``'s block ``(1, tokens, d)`` at ``(b, n, i)`` of ``[batch, seq,
heads x d]`` (``_Held``); elsewhere (Olmo-Hybrid's 96 | 192: a head's block
would begin inside a tile) it turns the arrays heads first and calls the
other. Either way a GROUP of heads is an index that the kernels' index maps
add (a prefetched scalar, ``_Walk.over_groups``), never a slice of the
operands or a stack of results: the forward's calls write their rows of one
output array, the backward's write a group's gradients where the group's
inputs were. What the kernels hand one another (``w``, ``u0``, ``qg``, ``p``,
``kd``, ``gamma``, the chunk-start states, their gradients) is theirs alone
and stays ``[rows a call, seq, .]``; ``T`` is the rule's own array of every
row, heads first in either layout.

On non-TPU backends the same kernels run in interpreter mode
(ops.resolve_interpret), so tests exercise the code the TPU compiles.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ray_tpu.ops import resolve_interpret

# The names the forward's output and the chunk inverse ``T`` (its diagonal
# blocks, ``_Masks.packed``) carry (models/transformer.py::_remat_policy
# keeps both).
RESIDUAL_NAMES = ("delta_rule_out", "delta_rule_inverse")

# Tokens a chunk; a shorter sequence is one chunk, padded to a multiple of
# ``_CHUNK_MULTIPLE`` (whole sublane tiles in either dtype).
CHUNK = 64
_CHUNK_MULTIPLE = 16
# Chunks one grid step of the kernels walks (static, unrolled): a grid
# step's fixed cost is spread over them.
_CHUNKS_PER_STEP = (8, 4, 2, 1)
# ... as far as a step's rows fit the 16 MiB of scoped VMEM: the backward
# kernel holds eight float32 operands and six results of them, twice (1024
# rows in chunks of 128 asked for 17.5 MiB: compile for a described v5e, PR
# 32). The two preparation kernels take the same steps: 1024 rows are
# refused for both (the forward with its seven results; the chip refused
# the backward too, PR 33), and 512, 256 and 128 rows a step cost the same
# to 2 % (my chip runs, PR 33). The channel decay's pair fits at 512 too
# (compile for a described v5e, PR 41); there 256 rows cost 3 % more and 128
# cost the forward 66 % (9.59 / 9.91 / 15.90 ms a layer and call, the
# backward 8.42 / 8.70 / 9.33: my chip runs, PR 41).
_ROWS_PER_STEP = 512
# The state the kernels carry from chunk to chunk (and the forward hands
# the backward); the preparation computes in float32 too.
_STATE_DTYPE = jnp.float32
# (batch x head) rows x tokens one group of kernel calls takes at once
# (``_heads_per_call`` has the readings it was chosen from).
_TOKENS_PER_CALL = 2 * 16384
_HIGHEST = jax.lax.Precision.HIGHEST
# The oracles' matmuls (``_prepare``, ``_prepare_channel_xla``: XLA, float32 operands).
_PREPARE_PRECISION = _HIGHEST


def gated_delta_rule_reference(q, k, v, log_alpha, beta):
    """The recurrence of the module docstring, one token a step, float32.
    q, k: [batch, heads, seq, d_k]; v: [batch, heads, seq, d_v]; log_alpha,
    beta: [batch, heads, seq], or ``log_alpha`` [batch, heads, seq, d_k]: a
    decay per key channel. Returns [batch, heads, seq, d_v] in v's dtype."""
    f32 = jnp.float32
    batch, heads, _, d_k = q.shape
    d_v = v.shape[-1]
    channel = log_alpha.ndim == q.ndim

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * (jnp.exp(g_t)[..., :, None] if channel else jnp.exp(g_t)[..., None, None])
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=_HIGHEST)
        write = b_t[..., None] * (v_t - read)
        state = state + k_t[..., :, None] * write[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=_HIGHEST)

    by_time = tuple(
        jnp.moveaxis(x.astype(f32), 2, 0) for x in (q, k, v, log_alpha, beta)
    )
    _, out = jax.lax.scan(step, jnp.zeros((batch, heads, d_k, d_v), f32), by_time)
    return jnp.moveaxis(out, 0, 2).astype(v.dtype)


def _default_chunk(seq: int) -> int:
    return min(CHUNK, -(-seq // _CHUNK_MULTIPLE) * _CHUNK_MULTIPLE)


def kept_bytes(batch: int, heads: int, seq: int, d_v: int, itemsize: int,
               chunk: int | None = None) -> int:
    """Bytes one call keeps under ``RESIDUAL_NAMES`` from its forward to
    its backward: the output in the values' dtype and ``T``'s diagonal
    blocks, ``chunk`` float32 a head and token (``kept_inverse_shape``). The
    chunk-start states, ``heads x chunks x d_k x d_v`` float32 (``d_k x d_v
    / chunk`` a head and token: 1,024 at 128 | 128 where ``T`` is 64), are
    made again."""
    chunk = chunk or _default_chunk(seq)
    tokens = batch * heads * -(-seq // chunk) * chunk
    return tokens * (d_v * itemsize + chunk * jnp.dtype(_STATE_DTYPE).itemsize)


# ---------------------------------------------------------------------------
# The chunk preparation in XLA, differentiated by jax: the oracle of the two
# preparation kernels, and the ``kernels=False`` path.
# ---------------------------------------------------------------------------
def _matmul(a, b):
    return jnp.matmul(a, b, precision=_PREPARE_PRECISION)


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I + a)^-1`` of a strictly lower triangular ``a`` ``[..., c, c]`` by
    doubling: the inverse ``T_b`` of the matrix cut to its diagonal blocks
    of ``b`` is known (``b = 2``: ``I - a`` cut to pairs), and with ``a_b``
    the entries that join two neighbouring blocks of ``b`` into one of ``2
    b`` (its lower-left quarter), ``T_2b = T_b - T_b a_b T_b`` EXACTLY:
    ``a_b`` leads from a pair's first block to its second and ``T_b`` stays
    inside a block, so ``(T_b a_b)^2 = 0``. Two batched matmuls a level,
    ten at a chunk of 64, and nothing that cancels: a Neumann product over
    the whole chunk, ``(I - a)(I + a^2)(I + a^4)...``, takes as many and
    loses every float32 digit once ``a``'s entries reach 0.5 (a chunk whose
    keys are alike; SiLU leaves q and k a common positive part), its terms
    growing with the binomial of the chunk's length.

    Its transpose is the inverse's own, ``da = -T^T dT T^T``: two matmuls,
    where jax's transpose of the ten above is twenty and their operands'
    copies (105 of 1250 ms a step in the Olmo-Hybrid cell, my chip run, PR 32).

    Since PR 33 this is the ORACLE's inverse (``_prepare``, ``kernels=False``
    and the tests): the timed path runs the same steps on VMEM values
    (``_Masks.inverses``) and the same identity in
    ``_prepare_backward_kernel``."""
    size = a.shape[-1]
    at = jnp.arange(size)
    # rows and columns in one block of 2^level
    joined = lambda level: (at[:, None] >> level) == (at[None, :] >> level)

    def doubled(level, inverse):
        joining = jnp.where(joined(level + 1) & ~joined(level), a, 0.0)
        return inverse - _matmul(inverse, _matmul(joining, inverse))

    # ONE level's two products in the compiled program, walked by a loop: ten
    # unrolled at a chunk of 64 are 6.5 MiB of a v5e's generated code each
    # time the inverse is taken (PERF.md section 6, PR 36)
    inverse = jnp.eye(size, dtype=a.dtype) - jnp.where(joined(1), a, 0.0)
    return jax.lax.fori_loop(1, max(size - 1, 1).bit_length(), doubled, inverse)


def _unit_lower_inverse_fwd(a):
    inverse = _unit_lower_inverse(a)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, g):
    transposed = jnp.swapaxes(inverse, -1, -2)
    return (-_matmul(transposed, _matmul(g, transposed)),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _prepare(q, k, v, log_alpha, beta, chunk: int):
    """The six operands of the scan over chunks, ``[heads, seq, .]``
    (``gamma``: ``[heads, chunks, 1, 1]``), float32, from q, k ``[heads,
    seq, d_k]``, v ``[heads, seq, d_v]`` and the two gates ``[heads, seq]``;
    ``seq`` a multiple of ``chunk``."""
    f32 = jnp.float32
    bh, seq = q.shape[:2]
    chunks = seq // chunk

    def by_chunk(x):
        return x.astype(f32).reshape(bh, chunks, chunk, *x.shape[2:])

    q, k, v, g, beta = (by_chunk(x) for x in (q, k, v, log_alpha, beta))
    total = jnp.cumsum(g, axis=-1)                               # G_t
    steps = jnp.arange(chunk)
    # exp of a difference that is <= 0 where it is used; where it is not,
    # the exponent is replaced BEFORE the exp: no inf, and no nan behind a
    # where in the backward.
    gap = total[..., :, None] - total[..., None, :]              # G_t - G_i
    decay = lambda mask: jnp.exp(jnp.where(mask, gap, -jnp.inf))

    strictly = steps[:, None] > steps[None, :]
    kk = jnp.einsum("...tk,...ik->...ti", k, k, precision=_PREPARE_PRECISION)
    a = beta[..., :, None] * decay(strictly) * kk
    t = _unit_lower_inverse(a)
    grown = jnp.exp(total)[..., None]                            # e^{G_t}
    w = _matmul(t, beta[..., None] * grown * k)
    u0 = _matmul(t, beta[..., None] * v)
    qg = grown * q
    qk = jnp.einsum("...tk,...ik->...ti", q, k, precision=_PREPARE_PRECISION)
    p = decay(~strictly.T) * qk                                  # i <= t
    last = total[..., -1:]
    kd = jnp.exp(last - total)[..., None] * k
    gamma = jnp.exp(last)[..., None]                             # [bh, chunks, 1, 1]
    flat = lambda x: x.reshape(bh, seq, x.shape[-1])
    return flat(w), flat(u0), flat(qg), flat(p), flat(kd), gamma


# Rows of one sub-block of a chunk in the BOUNDED form of the channel
# preparation kernels (``_bounded_products``): inside it the key side's
# exponent reaches ``(_SUB_CHUNK - 1) |g|``, 75 at a bound of -5 a token, under
# float32's 88. A caller that states no such bound takes the halving form.
_SUB_CHUNK = 16
_EXP_LIMIT = 88.0


def carries_bound(bound: float | None) -> bool:
    """Whether a log-decay never below ``bound`` a token and channel lets a
    diagonal sub-block be ONE product split at its first row (the bounded
    form); None, or a bound too steep for it, takes the halving form."""
    return bound is not None and (_SUB_CHUNK - 1) * -bound < _EXP_LIMIT


def _halving_levels(chunk: int) -> int:
    return max(chunk - 1, 1).bit_length()


def _prepare_channel_xla(q, k, v, log_alpha, beta, chunk: int):
    """``_prepare`` under a decay per channel: the six operands of the scan
    (``gamma``: ``[heads, chunks, 1, d_k]``) from q, k, ``log_alpha``
    ``[heads, seq, d_k]``, v ``[heads, seq, d_v]`` and ``beta`` ``[heads,
    seq]``; ``seq`` a multiple of ``chunk``. ANY ``log_alpha <= 0``.

    ``A`` and ``P`` are matmuls over the channels of operands that carry the
    decay, and every factor either side carries is ``exp`` of a SUM of
    ``log_alpha`` (<= 0, and no difference of two running sums, which loses
    its digits once a steep token lies before both): the lower triangle of
    a chunk is cut by HALVING. Level ``l`` (half ``h = 2^l``) owns the pairs
    ``(t, i)`` of one aligned block of ``2 h`` tokens with ``t`` in its
    second half and ``i`` in its first, and splits ``e^{G_t - G_i}`` between
    the halves: rows ``x_t . e^{g_m + .. + g_t}`` (``m`` the second half's
    first token: the running sum inside ``t``'s own half), columns ``k_i .
    e^{g_{i+1} + .. + g_{m-1}}`` (the sum of what follows ``i`` in its own
    half), masked BEFORE the ``exp`` everywhere else. The levels ``h = 1 ..
    chunk / 2`` cover every ``i < t`` once; ``i = t`` has no decay (``P``'s
    diagonal is ``q_t . k_t``). No ``[chunk, chunk, d_k]`` array exists: a
    level is one decayed copy of ``K``.

    On the chip the rule reads 1.4e-4 to 4.6e-4 of the recurrence's output
    at the Ling cell's gates, where the scalar rule reads 3e-5, and NOT
    because of how the exponents are split: sub-blocks of 8, 16 or 32 rows
    split at their first row, and exponents summed span by span in place of
    differences of running sums, all read the same to five digits (1.379e-4
    on one seed; my chip runs, PR 36); the halving kernels read 6.0e-5 to
    2.1e-4 at Solar-Open2's unbounded gates (my chip runs, PR 48): on the
    chip a float32 product is six bfloat16 passes, and that is the floor."""
    f32 = jnp.float32
    bh, seq, d_k = q.shape
    chunks = seq // chunk
    levels = _halving_levels(chunk)
    padded = 1 << levels                       # the halves are aligned: a power of two

    def by_chunk(x):
        x = x.astype(f32).reshape(bh, chunks, chunk, *x.shape[2:])
        return jnp.pad(x, ((0, 0), (0, 0), (0, padded - chunk)) + ((0, 0),) * (x.ndim - 3))

    q, k, v, g, beta = (by_chunk(x) for x in (q, k, v, log_alpha, beta))
    steps = jnp.arange(padded)

    def following(x, axis):
        """The sum of what FOLLOWS each entry along ``axis`` (its own left out)."""
        after = jnp.roll(x, -1, axis).at[(slice(None),) * axis + (-1,)].set(0.0)
        return jnp.flip(jnp.cumsum(jnp.flip(after, axis), axis), axis)

    def level(l):
        """(the pairs level ``l`` owns, the rows' decay, the columns')."""
        half = 1 << l
        halves = g.reshape(bh, chunks, padded // half, half, d_k)
        upper = ((steps & half) != 0)[:, None]
        # masked BEFORE the exp, as in ``_prepare``: no inf, no nan behind a where
        rows = jnp.exp(jnp.where(upper, jnp.cumsum(halves, axis=3).reshape(g.shape), -jnp.inf))
        columns = jnp.exp(jnp.where(~upper, following(halves, 3).reshape(g.shape), -jnp.inf))
        return (steps[:, None] >> (l + 1)) == (steps[None, :] >> (l + 1)), rows, columns

    by_level = [level(l) for l in range(levels)]

    def decayed_products(x):
        """``sum_c x_tc k_ic e^{G_tc - G_ic}`` ``[.., chunk, chunk]`` for ``i < t``."""
        return sum(
            jnp.where(owned, jnp.einsum(
                "...tc,...ic->...ti", x * rows, k * columns, precision=_PREPARE_PRECISION
            ), 0.0)
            for owned, rows, columns in by_level
        )

    a = beta[..., None] * decayed_products(k)
    t = _unit_lower_inverse(a)
    total = jnp.cumsum(g, axis=2)                                # G_t [bh, chunks, chunk, d_k]
    grown = jnp.exp(total)                                       # e^{G_t}
    w = _matmul(t, beta[..., None] * grown * k)
    u0 = _matmul(t, beta[..., None] * v)
    qg = grown * q
    own = jnp.sum(q * k, axis=-1)                                # i = t: no decay
    p = decayed_products(q) + jnp.where(steps[:, None] == steps[None, :], own[..., None], 0.0)
    kd = jnp.exp(following(g, 2)) * k                            # e^{G_C - G_t}
    gamma = jnp.exp(total[:, :, -1:, :])                         # [bh, chunks, 1, d_k]
    flat = lambda x: x[:, :, :chunk].reshape(bh, seq, -1)
    return flat(w), flat(u0), flat(qg), flat(p[..., :chunk]), flat(kd), gamma


def _scan_reference(w, u0, qg, p, kd, gamma, chunk: int, out_dtype):
    """The scan over chunks in plain jax.numpy: what the two kernels are
    held to operand by operand, and the chunked form with no kernel."""
    bh, seq, d_k = w.shape
    d_v = u0.shape[-1]
    by_chunk = lambda x: jnp.moveaxis(x.reshape(bh, seq // chunk, chunk, -1), 1, 0)

    def step(state, x):
        w, u0, qg, p, kd, gamma = x
        u = u0 - _matmul(w, state)
        out = _matmul(qg, state) + _matmul(p, u)
        # [.., 1, 1], or a channel decay's [.., 1, d_k] down the state's rows
        return jnp.swapaxes(gamma, 1, 2) * state + _matmul(jnp.swapaxes(kd, 1, 2), u), out

    _, out = jax.lax.scan(
        step, jnp.zeros((bh, d_k, d_v), _STATE_DTYPE),
        (*(by_chunk(x) for x in (w, u0, qg, p, kd)), jnp.moveaxis(gamma, 1, 0)),
    )
    return jnp.moveaxis(out, 0, 1).reshape(bh, seq, d_v).astype(out_dtype)


# ---------------------------------------------------------------------------
# The scan over chunks: two Mosaic kernels.
# ---------------------------------------------------------------------------
def _dot(a, b, contract):
    """An MXU product with float32 accumulation; float32 operands multiply
    in float32 (Mosaic's fp32 contract precision), not in one bf16 pass."""
    precision = _HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(
        a, b, ((contract[0], contract[1]), ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    )


_NN = ((1,), (0,))      # a b
_NT = ((1,), (1,))      # a b^T
_TN = ((0,), (0,))      # a^T b


def _eye(n: int):
    at = lambda dim: jax.lax.broadcasted_iota(jnp.int32, (n, n), dim)
    return at(0) == at(1)


def _down(row):
    """``[1, n]`` -> ``[n, 1]``: broadcast, masked to the diagonal, summed
    along the lanes (``_Masks.down``'s exact turn, no matmul)."""
    return jnp.sum(jnp.where(_eye(row.shape[-1]), row, 0.0), axis=1, keepdims=True)


def _across(column):
    """``[n, 1]`` -> ``[1, n]``, as ``_down``."""
    return jnp.sum(jnp.where(_eye(column.shape[0]), column, 0.0), axis=0, keepdims=True)


def _decayed(gamma, state):
    """``gamma S``: ``gamma`` the ``[1, 1]`` decay of a head, or the ``[1,
    d_k]`` row of a decay per channel, which scales ``S``'s rows."""
    return (gamma if gamma.shape[-1] == 1 else _down(gamma)) * state


def _forward_kernel(w_ref, u0_ref, qg_ref, p_ref, kd_ref, gamma_ref,
                    wanted_ref, state, *, chunk, per_step, states):
    """``wanted_ref``: the output ``[1, rows, d_v]``, or with ``states`` the
    state at each chunk's START ``[1, per_step, d_k, d_v]`` (what the
    backward kernel reads; the output is then not computed)."""
    @pl.when(pl.program_id(1) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    for c in range(per_step):
        rows = slice(c * chunk, (c + 1) * chunk)
        s = state[...]
        operand = s.astype(w_ref.dtype)
        u = u0_ref[0, rows, :] - _dot(w_ref[0, rows, :], operand, _NN)
        if states:
            wanted_ref[0, c] = s.astype(wanted_ref.dtype)
        else:
            out = _dot(qg_ref[0, rows, :], operand, _NN) + _dot(
                p_ref[0, rows, :], u.astype(p_ref.dtype), _NN
            )
            wanted_ref[0, rows, :] = out.astype(wanted_ref.dtype)
        grown = _decayed(gamma_ref[0, c], s) + _dot(
            kd_ref[0, rows, :], u.astype(kd_ref.dtype), _TN
        )
        state[...] = grown.astype(state.dtype)


def _backward_kernel(w_ref, u0_ref, qg_ref, p_ref, kd_ref, gamma_ref, states_ref, do_ref,
                     dw_ref, du0_ref, dqg_ref, dp_ref, dkd_ref, dgamma_ref, dstate,
                     *, chunk, per_step):
    """One grid step walks its chunks LAST FIRST; the index maps hand the
    grid the steps last first too. ``dstate`` is the gradient of the state
    a chunk leaves behind."""
    @pl.when(pl.program_id(1) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)

    for c in reversed(range(per_step)):
        rows = slice(c * chunk, (c + 1) * chunk)
        dtype = w_ref.dtype
        s = states_ref[0, c].astype(dtype)
        ds = dstate[...].astype(dtype)
        do = do_ref[0, rows, :].astype(dtype)
        w, qg, p, kd = (ref[0, rows, :] for ref in (w_ref, qg_ref, p_ref, kd_ref))
        u = (u0_ref[0, rows, :] - _dot(w, s, _NN)).astype(dtype)
        du = (_dot(p, do, _TN) + _dot(kd, ds, _NN)).astype(dtype)
        du0_ref[0, rows, :] = du.astype(du0_ref.dtype)
        dw_ref[0, rows, :] = (-_dot(du, s, _NT)).astype(dw_ref.dtype)
        dqg_ref[0, rows, :] = _dot(do, s, _NT).astype(dqg_ref.dtype)
        dp_ref[0, rows, :] = _dot(do, u, _NT).astype(dp_ref.dtype)
        dkd_ref[0, rows, :] = _dot(u, ds, _NT).astype(dkd_ref.dtype)
        product = states_ref[0, c].astype(jnp.float32) * dstate[...].astype(jnp.float32)
        by_row = jnp.sum(product, axis=1, keepdims=True)
        dgamma_ref[0, c] = (
            jnp.sum(by_row, axis=0, keepdims=True) if gamma_ref.shape[-1] == 1
            else _across(by_row)
        )
        before = (
            _dot(qg, do, _TN) + _decayed(gamma_ref[0, c], dstate[...].astype(jnp.float32))
            - _dot(w, du, _TN)
        )
        dstate[...] = before.astype(dstate.dtype)


def _per_step(chunks: int, chunk: int) -> int:
    return next(
        n for n in _CHUNKS_PER_STEP
        if chunks % n == 0 and (n * chunk <= _ROWS_PER_STEP or n == 1)
    )


def _specs(chunk, per_step, widths, index):
    """BlockSpecs of the kernels' OWN operands, ``[rows a call, seq, width]``
    (what one kernel writes and the next reads), ``per_step`` chunks a grid
    step; ``index(n)`` is the step's position along the sequence."""
    return [
        pl.BlockSpec((1, per_step * chunk, width), lambda i, n, _: (i, index(n), 0))
        for width in widths
    ]


class _Held(NamedTuple):
    """Where a call finds its ``per_call`` (batch x head) rows in the arrays
    the CALLER holds, whole, every row of them: the index maps add the rows
    of the groups before this one (``group``, a scalar the call prefetches),
    so no group is sliced out of an operand or stacked into a result.
    ``heads`` 0: ``[rows, seq, width]``, a row's tokens together (heads
    first); else ``[batch, seq, heads x width]``, TOKEN-MAJOR, as a projection
    writes it: row ``r``'s block is ``(1, tokens, width)`` at ``(r // heads,
    n, r % heads)``, whole lanes where ``width`` is a multiple of 128."""
    heads: int
    per_call: int

    def row(self, i, group):
        return group[0] * self.per_call + i

    def width(self, x) -> int:
        return x.shape[2] // (self.heads or 1)

    def specs(self, chunk, per_step, widths, index=lambda n: n):
        """BlockSpecs of the caller's operands, ``per_step`` chunks a step."""
        def at(i, n, group):
            row = self.row(i, group)
            if self.heads:
                return row // self.heads, index(n), row % self.heads
            return row, index(n), 0

        return [pl.BlockSpec((1, per_step * chunk, width), at) for width in widths]

    def lanes(self, products, kinds, width):
        """The BlockSpec of the caller's scalar gates, ``[rows, products,
        kinds, width]``: a row's tokens along the lanes, heads first whatever
        ``heads`` says of the other operands."""
        return pl.BlockSpec(
            (1, products, kinds, width), lambda i, n, group: (self.row(i, group), n, 0, 0)
        )


def _grouped_call(kernel, group, operands, *, grid, in_specs, out_specs, out_shape,
                  interpret, into=(), in_place=0, scratch_shapes=(), compiler_params=None):
    """``pallas_call`` of ``kernel`` over ``operands`` with ``group`` (``[1]``
    int32; None: the first) prefetched for the index maps. Results written
    IN PLACE, so that whatever the call's blocks do not cover keeps what it
    held: ``into``, a buffer for each of the LAST results (aliased to them;
    they reach the kernel as nothing), or ``in_place``, how many of the first
    operands the first results overwrite, block for block (their specs are
    the results' own: a block is read before the same block is written, and
    no other step touches it)."""
    from jax.experimental.pallas import tpu as pltpu

    taken = len(operands)
    first = len(jax.tree.leaves(out_shape)) - len(into)
    aliases = {1 + taken + n: first + n for n in range(len(into))}
    aliases.update({1 + n: n for n in range(in_place)})

    def body(_group, *refs):
        kernel(*refs[:taken], *refs[taken + len(into):])

    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[*in_specs, *(pl.BlockSpec(memory_space=pl.ANY) for _ in into)],
            out_specs=out_specs, scratch_shapes=list(scratch_shapes),
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        compiler_params=compiler_params,
    )(jnp.zeros((1,), jnp.int32) if group is None else group, *operands, *into)


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret", "out_dtype", "states", "heads")
)
def _delta_rule_forward(w, u0, qg, p, kd, gamma, group=None, into=None, *, chunk, interpret,
                        out_dtype, states=False, heads=0):
    """The output of the call's rows in ``out_dtype``, written into ``into``
    (the caller's array of every row, ``_Held``'s layout under ``heads``;
    None: ``[rows a call, seq, d_v]`` of its own); with ``states`` the
    chunk-start states ``[rows a call, chunks, d_k, d_v]`` instead."""
    from jax.experimental.pallas import tpu as pltpu

    bh, seq, d_k = w.shape
    d_v = u0.shape[-1]
    chunks = seq // chunk
    per_step = _per_step(chunks, chunk)
    forward = lambda n: n
    kernel = functools.partial(_forward_kernel, chunk=chunk, per_step=per_step, states=states)
    if states:
        out_spec = pl.BlockSpec((1, per_step, d_k, d_v), lambda i, n, _: (i, n, 0, 0))
        out_shape = jax.ShapeDtypeStruct((bh, chunks, d_k, d_v), _STATE_DTYPE)
    else:
        (out_spec,) = _Held(heads, bh).specs(chunk, per_step, (d_v,))
        out_shape = jax.ShapeDtypeStruct(
            (bh, seq, d_v) if into is None else into.shape, out_dtype
        )
    return _grouped_call(
        kernel, group, (w, u0, qg, p, kd, gamma),
        grid=(bh, chunks // per_step),
        in_specs=[
            *_specs(chunk, per_step, (d_k, d_v, d_k, chunk, d_k), forward),
            pl.BlockSpec((1, per_step, 1, gamma.shape[-1]), lambda i, n, _: (i, n, 0, 0)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        into=() if into is None else (into,),
        scratch_shapes=[pltpu.VMEM((d_k, d_v), _STATE_DTYPE)],
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "heads"))
def _delta_rule_backward(w, u0, qg, p, kd, gamma, states, dout, group=None, *, chunk, interpret,
                         heads=0):
    """The six operands' gradients, ``[rows a call, seq, .]`` as they are,
    from ``dout``: the caller's array of every row (``_Held``'s layout
    under ``heads``), read where the call's rows lie."""
    from jax.experimental.pallas import tpu as pltpu

    bh, seq, d_k = w.shape
    d_v = u0.shape[-1]
    chunks = seq // chunk
    per_step = _per_step(chunks, chunk)
    steps = chunks // per_step
    backward = lambda n: steps - 1 - n
    per_chunk = lambda *tail: pl.BlockSpec(
        (1, per_step, *tail), lambda i, n, _: (i, backward(n), 0, 0)
    )
    kernel = functools.partial(_backward_kernel, chunk=chunk, per_step=per_step)
    widths = (d_k, d_v, d_k, chunk, d_k)
    return _grouped_call(
        kernel, group, (w, u0, qg, p, kd, gamma, states, dout),
        grid=(bh, steps),
        in_specs=[
            *_specs(chunk, per_step, widths, backward),
            per_chunk(1, gamma.shape[-1]),
            per_chunk(d_k, d_v),
            *_Held(heads, bh).specs(chunk, per_step, (d_v,), backward),
        ],
        out_specs=[
            *_specs(chunk, per_step, widths, backward), per_chunk(1, gamma.shape[-1]),
        ],
        out_shape=[
            *(jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (w, u0, qg, p, kd, gamma)),
        ],
        scratch_shapes=[pltpu.VMEM((d_k, d_v), _STATE_DTYPE)],
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# The chunk preparation as Mosaic work: forward and its transpose by hand.
# ---------------------------------------------------------------------------
def _rows_sum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _columns_sum(x):
    return jnp.sum(x, axis=0, keepdims=True)


# Rows one product of the preparation kernels takes: the MXU's tile. A
# 64-row float32 product costs the MXU what a 128-row one does (six bf16
# passes, each bound by loading a 128 x 128 tile of the right-hand operand,
# not by the rows streamed past it), so two chunks of 64 are multiplied as
# ONE block-diagonal matrix: the forward kernel took 24.7 ms a layer and
# call at [30, 16384] one chunk a product, 14.2 two (my chip runs, PR 33).
_PRODUCT_ROWS = 128


def _together(chunk: int, per_step: int) -> int:
    """Chunks one product holds: as many as ``_PRODUCT_ROWS`` has room for
    and a grid step has whole groups of."""
    room = max(_PRODUCT_ROWS // chunk, 1)
    return max(n for n in range(1, room + 1) if per_step % n == 0)


def _product_rows(seq: int, chunk: int) -> int:
    return chunk * _together(chunk, _per_step(seq // chunk, chunk))


class _Masks:
    """Where the chunks of one product lie in its ``[rows, rows]`` matrices
    (the same for every product of a grid step): ``together`` chunks down
    the diagonal, nothing between two of them."""

    def __init__(self, chunk, together):
        rows = chunk * together
        self.chunk, self.together = chunk, together

        def inside(shape, dim):
            """Each position's chunk and its place inside that chunk."""
            at = jax.lax.broadcasted_iota(jnp.int32, shape, dim)
            of = jax.lax.div(at, jnp.int32(chunk))
            return of, at - chunk * of

        t_of, self.t = inside((rows, rows), 0)
        i_of, self.i = inside((rows, rows), 1)
        self.lane_of, lane = inside((1, rows), 1)
        self.same = t_of == i_of
        self.eye = self.same & (self.t == self.i)
        self.strictly = self.same & (self.t > self.i)
        self.upto = self.same & (self.t >= self.i)
        self.ends = lane == chunk - 1                             # [1, rows]

    def down(self, row):
        """``[1, rows]`` -> ``[rows, 1]``: broadcast, masked to the
        diagonal, summed along the lanes: exact, no matmul, no 128-fold
        padded operand."""
        return _rows_sum(jnp.where(self.eye, row, 0.0))

    def across(self, column):
        return _columns_sum(jnp.where(self.eye, column, 0.0))

    def of_chunk(self, c, row):
        """The ``[1, 1]`` entry of a ``[1, rows]`` row at chunk ``c``'s end."""
        return _rows_sum(jnp.where(self.ends & (self.lane_of == c), row, 0.0))

    def joined(self, shift):
        """Entries inside one diagonal block of ``2 ** shift``."""
        return self.same & ((self.t >> shift) == (self.i >> shift))

    def _of(self, c, x):
        return x[c * self.chunk:(c + 1) * self.chunk]

    def packed(self, inverse):
        """The diagonal blocks of a block-diagonal ``[rows, rows]`` value side
        by side, ``[chunk, rows]``: chunk ``c``'s block in chunk ``c``'s
        lanes, selected (nothing added: the same bits). Everything else of
        ``inverse`` is an exact zero, so this is all of it, in a
        ``together``-th of the bytes: how ``T`` is kept."""
        blocks = self._of(0, inverse)
        for c in range(1, self.together):
            blocks = jnp.where(self.lane_of == c, self._of(c, inverse), blocks)
        return blocks

    def unpacked(self, blocks):
        """``packed``'s value back as the block-diagonal ``[rows, rows]``."""
        return jnp.concatenate(
            [jnp.where(self.lane_of == c, blocks, 0.0) for c in range(self.together)], axis=0
        )

    def inverses(self, several):
        """``_unit_lower_inverse``'s doubling on VMEM values: block pairs,
        ``T_2b = T_b - T_b a_b T_b``, two products a level, for SEVERAL
        independent matrices level by level. A level's second product waits
        for its first and the next level for both; issued one matrix after
        the other the MXU waits with them (14.2 ms a layer and call at [30,
        16384], where the backward's fifteen independent products take 7.4:
        my chip runs, PR 33), side by side its pipeline stays full."""
        inner, shift = self.joined(1), 1
        inverses = [jnp.where(self.eye, 1.0, 0.0) - jnp.where(inner, a, 0.0) for a in several]
        while (1 << shift) < self.chunk:
            shift += 1
            outer = self.joined(shift)
            right = [
                _dot(jnp.where(outer & ~inner, a, 0.0), inverse, _NN)
                for a, inverse in zip(several, inverses)
            ]
            inverses = [inverse - _dot(inverse, r, _NN) for inverse, r in zip(inverses, right)]
            inner = outer
        return inverses


class _Span:
    """What both preparation kernels make of the gates of the chunks of one
    product, in VMEM: ``total`` / ``beta`` down the rows ``[rows, 1]`` from
    their lane-dense ``[1, rows]`` form and the decays that need only the
    gates. Every decay is ``exp`` of a difference masked to <= 0 BEFORE the
    ``exp``, as in ``_prepare``; between two chunks it is 0."""

    def __init__(self, masks, total_row, beta_row):
        self.masks, self.total_row = masks, total_row
        self.total, self.beta = masks.down(total_row), masks.down(beta_row)
        self.gap = self.total - total_row                         # G_t - G_i
        self.upto = self.decay(masks.upto)
        self.grown = jnp.exp(self.total)                          # e^{G_t}
        # G_C of each row's own chunk, down the rows
        last = _rows_sum(jnp.where(masks.same & masks.ends, total_row, 0.0))
        self.left = jnp.exp(last - self.total)                    # e^{G_C - G_t}

    def decay(self, mask):
        return jnp.exp(jnp.where(mask, self.gap, -jnp.inf))

    @functools.cached_property
    def strictly(self):
        """``A``'s decay, taken where it is read: where ``T`` is given it is not."""
        return self.decay(self.masks.strictly)


def _kept_rows(s, chunk):
    """Product ``s``'s rows of a grid step's block of the kept ``T``
    (``_Masks.packed``: ``chunk`` rows a product)."""
    return slice(s * chunk, (s + 1) * chunk)


def _prepare_forward_kernel(*refs, chunk, per_step, together, inverse=None):
    """``_prepare`` for ``per_step`` chunks of one head, ``together`` to a
    product; ``gates_ref``: ``[1, per_step / together, 2, together x
    chunk]``, the running sum ``G`` and ``beta`` along the lanes.
    ``inverse``: "write", a seventh result, ``T``'s diagonal blocks
    (``_Masks.packed``; the forward of a gradient keeps it); "read", a fifth
    operand, that array: ``T`` is unpacked from it, ``A`` is not formed and
    no level of the doubling is multiplied (the backward's call)."""
    f32 = jnp.float32
    q_ref, k_ref, v_ref, gates_ref, *refs = refs
    kept_ref = refs.pop(0 if inverse == "read" else -1) if inverse else None
    w_ref, u0_ref, qg_ref, p_ref, kd_ref, gamma_ref = refs
    width = together * chunk
    masks = _Masks(chunk, together)
    products = range(per_step // together)
    rows = [slice(s * width, (s + 1) * width) for s in products]
    spans = [_Span(masks, gates_ref[0, s, 0:1, :], gates_ref[0, s, 1:2, :]) for s in products]
    keys = [k_ref[0, rows[s], :].astype(f32) for s in products]
    if inverse == "read":
        inverses = [masks.unpacked(kept_ref[0, _kept_rows(s, chunk), :]) for s in products]
    else:
        inverses = masks.inverses(
            [at.beta * at.strictly * _dot(k, k, _NT) for at, k in zip(spans, keys)]
        )
    for s, at, k, t in zip(products, spans, keys, inverses):
        q, v = q_ref[0, rows[s], :].astype(f32), v_ref[0, rows[s], :].astype(f32)
        w_ref[0, rows[s], :] = _dot(t, at.beta * at.grown * k, _NN)
        u0_ref[0, rows[s], :] = _dot(t, at.beta * v, _NN)
        qg_ref[0, rows[s], :] = at.grown * q
        kd_ref[0, rows[s], :] = at.left * k
        p = at.upto * _dot(q, k, _NT)
        for c in range(together):
            own = slice(c * chunk, (c + 1) * chunk)
            p_ref[0, s * width + c * chunk:s * width + (c + 1) * chunk, :] = p[own, own]
            gamma_ref[0, s * together + c] = jnp.exp(masks.of_chunk(c, at.total_row))
        if inverse == "write":
            kept_ref[0, _kept_rows(s, chunk), :] = masks.packed(t)


def _prepare_backward_kernel(q_ref, k_ref, v_ref, gates_ref, inverse_ref,
                             dw_ref, du0_ref, dqg_ref, dp_ref, dkd_ref, dgamma_ref,
                             dq_ref, dk_ref, dv_ref, dgates_ref, *, chunk, per_step, together):
    """The transpose of ``_prepare_forward_kernel`` by hand, ``together``
    chunks at a time: ``dT = dW (beta e^G K)^T + dU0 (beta V)^T``, ``dA =
    -T^T dT T^T`` (``_unit_lower_inverse_bwd``'s identity; ``T`` is read,
    as the forward kept it: ``_Masks.packed``), then the product rules
    of ``A = beta . decay . K K^T`` and ``P = decay . Q K^T``: the gap ``G_t
    - G_i`` gets ``dA . A + dP . P``, whose row sums less column sums are
    ``dG``. ``dgates_ref``: ``dG`` and ``dbeta`` along the lanes."""
    f32 = jnp.float32
    width = together * chunk
    masks = _Masks(chunk, together)
    for s in range(per_step // together):
        rows = slice(s * width, (s + 1) * width)
        q, k, v, dw, du0, dqg, dp, dkd = (
            ref[0, rows, :].astype(f32) for ref in
            (q_ref, k_ref, v_ref, dw_ref, du0_ref, dqg_ref, dp_ref, dkd_ref)
        )
        inverse = masks.unpacked(inverse_ref[0, _kept_rows(s, chunk), :])
        at = _Span(masks, gates_ref[0, s, 0:1, :], gates_ref[0, s, 1:2, :])
        kk, qk = _dot(k, k, _NT), _dot(q, k, _NT)
        dinverse = _dot(dw, at.beta * at.grown * k, _NT) + _dot(du0, at.beta * v, _NT)
        dkb, dvb = _dot(inverse, dw, _TN), _dot(inverse, du0, _TN)
        da = -_dot(_dot(inverse, dinverse, _TN), inverse, _NT)
        weighed = da * at.strictly * kk                          # dA . A / beta
        dkk = da * at.beta * at.strictly
        # dP of every chunk beside its own columns; the decay is 0 elsewhere
        dqk = jnp.tile(dp, (1, together)) * at.upto
        dgap = at.beta * weighed + dqk * qk                      # dA . A + dP . P
        dscale = _rows_sum(dkb * k)                              # of beta e^G
        dleft = at.left * _rows_sum(dkd * k)                     # of G_C - G_t
        dq = at.grown * dqg + _dot(dqk, k, _NN)
        dk = (at.beta * at.grown * dkb + at.left * dkd + _dot(dkk, k, _NN)
              + _dot(dkk, k, _TN) + _dot(dqk, q, _TN))
        dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, rows, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, rows, :] = (at.beta * dvb).astype(dv_ref.dtype)
        dbeta = _rows_sum(weighed) + at.grown * dscale + _rows_sum(dvb * v)
        dtotal = _rows_sum(dgap) + at.grown * (at.beta * dscale + _rows_sum(dqg * q)) - dleft
        # G_C is its chunk's last G: what reached it through ``left`` and
        # through gamma = e^{G_C} lands on that token
        dgamma = sum(
            jnp.where(masks.lane_of == c, dgamma_ref[0, s * together + c], 0.0)
            for c in range(together)
        )
        dlast = _columns_sum(jnp.where(masks.same, dleft, 0.0)) + jnp.exp(at.total_row) * dgamma
        dgates_ref[0, s, 0:1, :] = (
            masks.across(dtotal) - _columns_sum(dgap) + jnp.where(masks.ends, dlast, 0.0)
        )
        dgates_ref[0, s, 1:2, :] = masks.across(dbeta)


def _parallel():
    """Both grid axes of a preparation call are independent: no state is
    carried from one step to the next."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


def _prepare_layout(held, q, v, gates, chunk):
    """(grid, chunks a step, chunks a product, the specs of q, k, v and the
    gates: the caller's, ``held`` says where) of both preparation calls."""
    seq = q.shape[1]
    chunks = seq // chunk
    per_step = _per_step(chunks, chunk)
    width = gates.shape[-1]
    together = width // chunk
    in_specs = [
        *held.specs(chunk, per_step, (held.width(q), held.width(q), held.width(v))),
        held.lanes(per_step // together, 2, width),
    ]
    return (held.per_call, chunks // per_step), per_step, together, in_specs


def kept_inverse_shape(rows: int, seq: int, chunk: int) -> tuple[int, int, int]:
    """``T`` of ``rows`` (batch x head) rows as it is KEPT (``_Masks.packed``):
    ``chunk`` rows of ``chunks a product x chunk`` lanes a product, float32:
    ``4 x chunk`` bytes a row and token."""
    width = _product_rows(seq, chunk)
    return rows, seq // width * chunk, width


def _kept_spec(held, chunk, per_step, together):
    """The BlockSpec of a grid step's block of the kept ``T``, in the
    caller's array of every row (heads first whatever ``held.heads`` says of
    the other operands)."""
    return pl.BlockSpec(
        (1, per_step // together * chunk, together * chunk),
        lambda i, n, group: (held.row(i, group), n, 0),
    )


def _prepare_forward_call(kernel, held, operands, layout, gamma_width, chunk, group, kept,
                          inverse, interpret):
    """The call both preparation forwards make (``kernel`` already told its
    form): six results ``[rows a call, seq, .]`` of the rows ``held`` and
    ``group`` name. ``inverse`` "write": ``T`` besides, as it is kept
    (``kept_inverse_shape``), into ``kept``, the caller's array of EVERY
    row, where the call's rows lie (None: one of the call's own); "read":
    ``T`` is taken from ``kept`` there and not computed."""
    grid, per_step, together, in_specs = layout
    seq = operands[0].shape[1]
    d_k, d_v = held.width(operands[0]), held.width(operands[2])
    widths = (d_k, d_v, d_k, chunk, d_k)
    kept_spec = _kept_spec(held, chunk, per_step, together)
    out_specs = [
        *_specs(chunk, per_step, widths, lambda n: n),
        pl.BlockSpec((1, per_step, 1, gamma_width), lambda i, n, _: (i, n, 0, 0)),
    ]
    out_shape = [
        *(jax.ShapeDtypeStruct((held.per_call, seq, width), jnp.float32) for width in widths),
        jax.ShapeDtypeStruct((held.per_call, seq // chunk, 1, gamma_width), jnp.float32),
    ]
    if inverse == "write":
        out_specs.append(kept_spec)
        out_shape.append(jax.ShapeDtypeStruct(
            kept_inverse_shape(held.per_call, seq, chunk) if kept is None else kept.shape,
            jnp.float32,
        ))
    elif inverse == "read":
        operands, in_specs = (*operands, kept), [*in_specs, kept_spec]
    return _grouped_call(
        functools.partial(
            kernel, chunk=chunk, per_step=per_step, together=together, inverse=inverse
        ),
        group, operands,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        into=(kept,) if inverse == "write" and kept is not None else (),
        interpret=interpret,
        compiler_params=_parallel(),
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "inverse", "heads", "rows"))
def _delta_prepare_forward(q, k, v, gates, group=None, kept=None, *, chunk, interpret,
                           inverse=None, heads=0, rows=None):
    """``_prepare``'s six operands ``[rows, seq, .]`` of ``rows`` (None:
    all) of the (batch x head) rows of q, k, v (``_Held``'s layout under
    ``heads``) and ``gates`` (``_gates``), those of group ``group``.
    ``inverse`` and ``kept``: ``_prepare_forward_call``'s."""
    held = _Held(heads, rows or q.shape[0])
    return _prepare_forward_call(
        _prepare_forward_kernel, held, (q, k, v, gates),
        _prepare_layout(held, q, v, gates, chunk), 1, chunk, group, kept, inverse, interpret,
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "heads"))
def _delta_prepare_backward(q, k, v, gates, inverse, dw, du0, dqg, dp, dkd, dgamma,
                            group=None, *, chunk, interpret, heads=0):
    """``dq``, ``dk``, ``dv`` in their operands' dtypes and the gates'
    gradients in ``gates``' layout (of the running sum ``G``, not yet of
    ``log_alpha``) from the scan backward's six: of the rows of group
    ``group``, written where q, k, v and ``gates`` hold those rows (every
    other row of the four results is its operand's). ``inverse``: ``T`` as
    the forward kept it, every row's."""
    held = _Held(heads, dw.shape[0])
    d_k, d_v = held.width(q), held.width(v)
    grid, per_step, together, in_specs = _prepare_layout(held, q, v, gates, chunk)
    return _grouped_call(
        functools.partial(
            _prepare_backward_kernel, chunk=chunk, per_step=per_step, together=together
        ),
        group, (q, k, v, gates, inverse, dw, du0, dqg, dp, dkd, dgamma),
        grid=grid,
        in_specs=[
            *in_specs,
            _kept_spec(held, chunk, per_step, together),
            *_specs(chunk, per_step, (d_k, d_v, d_k, chunk, d_k), lambda n: n),
            pl.BlockSpec((1, per_step, 1, 1), lambda i, n, _: (i, n, 0, 0)),
        ],
        out_specs=in_specs,
        out_shape=[
            *(jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)),
            jax.ShapeDtypeStruct(gates.shape, jnp.float32),
        ],
        in_place=4,
        interpret=interpret,
        compiler_params=_parallel(),
    )


# ---------------------------------------------------------------------------
# The chunk preparation under a decay per CHANNEL as Mosaic work: forward
# and its transpose by hand, ``_prepare_channel_xla`` the oracle of both.
# ---------------------------------------------------------------------------
class _Blocks:
    """Where the sub-blocks (``_SUB_CHUNK`` rows) of the ``together`` chunks
    of one product lie down its ``[rows, d_k]`` values, and what
    ``_prepare_channel_xla`` makes of the running sum ``total`` there: the
    references ``R_r`` spread down the rows and the key side's decay, ``exp``
    of a difference masked BEFORE the ``exp``."""

    def __init__(self, chunk, together, d_k):
        self.chunk, self.together, self.d_k = chunk, together, d_k
        self.sub = min(_SUB_CHUNK, chunk)
        self.blocks = chunk // self.sub
        self.row = jax.lax.broadcasted_iota(jnp.int32, (chunk * together, d_k), 0)
        # each row's place inside its chunk
        self.inside = self.row - chunk * jax.lax.div(self.row, jnp.int32(chunk))

    def running(self, x, back=False):
        """The running sum of ``x`` down the rows of each chunk
        (``_prepare_channel_xla``'s ``total`` of ``log_alpha``), by doubling:
        sublane rotations and float32 adds, nothing of it on the MXU. With
        ``back`` its transpose: each row's sum from itself to its chunk's end."""
        from jax.experimental.pallas import tpu as pltpu

        rows, shift = self.chunk * self.together, 1
        while shift < self.chunk:
            # x[t - shift] (x[t + shift]) where that token is in t's chunk
            moved = pltpu.roll(x, rows - shift if back else shift, 0)
            inside = self.inside < self.chunk - shift if back else self.inside >= shift
            x, shift = x + jnp.where(inside, moved, 0.0), 2 * shift
        return x

    def _spread(self, x, starts, count):
        """Row ``s`` of ``x`` over ``count`` rows, for each ``s`` in turn."""
        return jnp.concatenate(
            [jnp.broadcast_to(x[s:s + 1], (count, self.d_k)) for s in starts], axis=0
        )

    def own_first(self, total):
        """``R_r(t)``: the ``G`` of the first token of each row's own block."""
        return self._spread(total, range(0, self.chunk * self.together, self.sub), self.sub)

    def last(self, total):
        """``G_C`` of each row's own chunk."""
        ends = [(c + 1) * self.chunk - 1 for c in range(self.together)]
        return self._spread(total, ends, self.chunk)

    def columns(self, total, r):
        """``e^{R_r - G_i}`` for ``i`` up to the end of block ``r`` of its
        chunk (<= 0 before the block, at most ``(_SUB_CHUNK - 1) |g|`` inside
        it), 0 after."""
        firsts = [c * self.chunk + r * self.sub for c in range(self.together)]
        gap = self._spread(total, firsts, self.chunk) - total
        return jnp.exp(jnp.where(self.inside < (r + 1) * self.sub, gap, -jnp.inf))

    def stacked(self, r, *several):
        """Block ``r``'s rows of every chunk, of each array in turn: the left
        operand of ONE product against block ``r``'s columns."""
        rows = [
            slice(c * self.chunk + r * self.sub, c * self.chunk + (r + 1) * self.sub)
            for c in range(self.together)
        ]
        return jnp.concatenate([x[at] for x in several for at in rows], axis=0)

    def unstacked(self, by_block, n):
        """Array ``n`` of ``stacked``'s order back in its rows' order, from
        one ``[arrays x together x sub, .]`` value a block."""
        return jnp.concatenate([
            by_block[r][(n * self.together + c) * self.sub:(n * self.together + c + 1) * self.sub]
            for c in range(self.together) for r in range(self.blocks)
        ], axis=0)

    def to_end(self, log_alpha, total, bounded):
        """``e^{G_C - G_t}``: under a bound the difference of the running sum
        ``total`` as it stands; else the sum of what FOLLOWS each row in its
        chunk, its own left out, with no difference taken."""
        from jax.experimental.pallas import tpu as pltpu

        if bounded:
            return jnp.exp(self.last(total) - total)
        after = pltpu.roll(log_alpha, self.chunk * self.together - 1, 0)    # g[t + 1]
        after = jnp.where(self.inside < self.chunk - 1, after, 0.0)
        return jnp.exp(self.running(after, back=True))

    def halves(self, log_alpha):
        """``_prepare_channel_xla``'s levels on VMEM values: for each, ``(l,
        the rows' decay, the columns')``, both ``[rows, d_k]``. ``inner`` is
        each row's running sum inside its own aligned half, ``rest`` the sum
        of what follows it there, ``whole`` the half's sum; a level doubles
        the halves with two sublane rotations. Sums of ``log_alpha`` only:
        every exponent is <= 0 whatever the decay, and exact."""
        from jax.experimental.pallas import tpu as pltpu

        rows = self.chunk * self.together
        inner, rest, whole = log_alpha, jnp.zeros_like(log_alpha), log_alpha
        for l in range(_halving_levels(self.chunk)):
            half = 1 << l
            upper = (self.inside & half) != 0
            # ... and the half after it begins inside the chunk (a chunk of 48)
            lower = jnp.logical_not(upper) & ((self.inside | (half - 1)) + 1 < self.chunk)
            yield (
                l, jnp.exp(jnp.where(upper, inner, -jnp.inf)),
                jnp.exp(jnp.where(lower, rest, -jnp.inf)),
            )
            before, after = pltpu.roll(whole, half, 0), pltpu.roll(whole, rows - half, 0)
            inner = inner + jnp.where(upper, before, 0.0)
            rest = rest + jnp.where(upper, 0.0, after)
            whole = whole + jnp.where(upper, before, after)

    def at_ends(self, by_chunk):
        """``[rows, d_k]``: chunk ``c``'s ``[1, d_k]`` row at its last token,
        0 elsewhere."""
        return sum(
            jnp.where(self.row == (c + 1) * self.chunk - 1, row, 0.0)
            for c, row in enumerate(by_chunk)
        )


def _bounded_products(at, q, k, total):
    """``sum_c x_tc k_ic e^{G_tc - G_ic}`` of ``x = k`` and ``x = q``,
    ``[rows, rows]`` each, valid inside a chunk up to the diagonal, where
    ``log_alpha`` is BOUNDED (``carries_bound``). Row block ``r``
    (``_SUB_CHUNK`` tokens) of every chunk of a product multiplies ``k .
    e^{G - R_r}`` and ``q . e^{G - R_r}`` (``R_r`` the ``G`` of its first
    token: exponent <= 0), stacked, by ONE right operand, ``k . e^{R_r - G}``
    (``A`` and ``P`` read the same columns), whose exponent is <= 0 before
    the block and at most ``(_SUB_CHUNK - 1) |bound|`` inside it."""
    decay = jnp.exp(total - at.own_first(total))                 # e^{G_t - R_r(t)}
    left = k * decay, q * decay
    by_block = [
        _dot(at.stacked(r, *left), k * at.columns(total, r), _NT) for r in range(at.blocks)
    ]
    return at.unstacked(by_block, 0), at.unstacked(by_block, 1)


def _halved_products(at, masks, q, k, log_alpha):
    """``_bounded_products`` for ANY ``log_alpha <= 0``, by halving
    (``_prepare_channel_xla`` has the split): a level multiplies ``k .
    rows`` and ``q . rows``, stacked, by ONE right operand, ``k . columns``,
    and keeps the pairs it owns; ``P``'s diagonal is ``q_t . k_t``."""
    a = p = 0.0
    for l, rows, columns in at.halves(log_alpha):
        both = _dot(jnp.concatenate([k * rows, q * rows], axis=0), k * columns, _NT)
        owned = masks.joined(l + 1)
        a = a + jnp.where(owned, both[:k.shape[0]], 0.0)
        p = p + jnp.where(owned, both[k.shape[0]:], 0.0)
    return a, jnp.where(masks.eye, _rows_sum(q * k), p)


def _channel_forward_kernel(*refs, chunk, per_step, together, bounded, inverse=None):
    """``_prepare_channel_xla`` for ``per_step`` chunks of one head,
    ``together`` to a product. ``beta_ref``: ``[1, per_step / together, 1,
    together x chunk]``, along the lanes; the running sum ``G`` of
    ``log_alpha`` is taken here (``_Blocks.running``). ``A / beta`` and ``P``
    are ``_bounded_products``' where the caller stated a bound that form
    carries, else ``_halved_products``'; the decayed copies of K live and
    die here. ``inverse``: ``_prepare_forward_kernel``'s; where ``T`` is read
    the stacked products are multiplied as they are and ``A``'s half of
    them has no reader."""
    f32 = jnp.float32
    q_ref, k_ref, v_ref, log_alpha_ref, beta_ref, *refs = refs
    kept_ref = refs.pop(0 if inverse == "read" else -1) if inverse else None
    w_ref, u0_ref, qg_ref, p_ref, kd_ref, gamma_ref = refs
    width = together * chunk
    masks = _Masks(chunk, together)
    at = _Blocks(chunk, together, q_ref.shape[-1])
    products = range(per_step // together)
    rows = [slice(s * width, (s + 1) * width) for s in products]
    betas = [masks.down(beta_ref[0, s, 0:1, :]) for s in products]
    several, weighed = [], []
    for s in products:
        q, k = q_ref[0, rows[s], :].astype(f32), k_ref[0, rows[s], :].astype(f32)
        log_alpha = log_alpha_ref[0, rows[s], :].astype(f32)
        total = at.running(log_alpha)
        grown = jnp.exp(total)                                   # e^{G_t}
        weighed.append(betas[s] * grown * k)
        if bounded:
            a, p = _bounded_products(at, q, k, total)
        else:
            a, p = _halved_products(at, masks, q, k, log_alpha)
        left = at.to_end(log_alpha, total, bounded)
        if inverse != "read":
            several.append(jnp.where(masks.strictly, betas[s] * a, 0.0))
        p = jnp.where(masks.upto, p, 0.0)
        qg_ref[0, rows[s], :] = grown * q
        kd_ref[0, rows[s], :] = left * k
        for c in range(together):
            own = slice(c * chunk, (c + 1) * chunk)
            p_ref[0, s * width + c * chunk:s * width + (c + 1) * chunk, :] = p[own, own]
            gamma_ref[0, s * together + c] = jnp.exp(total[(c + 1) * chunk - 1:(c + 1) * chunk])
    if inverse == "read":
        inverses = [masks.unpacked(kept_ref[0, _kept_rows(s, chunk), :]) for s in products]
    else:
        inverses = masks.inverses(several)
    for s, t in zip(products, inverses):
        w_ref[0, rows[s], :] = _dot(t, weighed[s], _NN)
        u0_ref[0, rows[s], :] = _dot(t, betas[s] * v_ref[0, rows[s], :].astype(f32), _NN)
        if inverse == "write":
            kept_ref[0, _kept_rows(s, chunk), :] = masks.packed(t)


def _bounded_transpose(at, q, k, beta, total, da, dp):
    """The transpose of ``_bounded_products``: the product rule of each row
    block's ``M_r = mask . (X_r C_r^T)`` (``X_r`` the stacked ``k . e^{G -
    R_r}``, ``q . e^{G - R_r}``; ``C_r = k . e^{R_r - G}``): ``dX_r = dM_r
    C_r``, ``dC_r = dM_r^T X_r``. From ``da`` (of ``A``) and ``dp``, each
    masked to its own chunk's pairs: what reaches q and k through the rows,
    k through the columns, ``sum_i dA_ti A_ti / beta_t`` channel by channel
    (``dbeta``'s and, times ``beta``, ``dG``'s) and the rest of ``dG``: ``X .
    dX - sum_r C_r . dC_r``. ``R_r`` drops out exactly (what ``X . dX`` sends
    it, ``C_r . dC_r`` takes back under the same mask), so nothing lands on
    a block's first token."""
    decay = jnp.exp(total - at.own_first(total))                 # e^{G_t - R_r(t)}
    xk, xq = k * decay, q * decay
    right = beta * xk, xq
    dleft, dk_columns = [], 0.0
    for r in range(at.blocks):
        columns = at.columns(total, r)
        dm = at.stacked(r, da, dp)
        dleft.append(_dot(dm, columns * k, _NN))
        dk_columns += columns * _dot(dm, at.stacked(r, *right), _TN)
    dxk, dxq = at.unstacked(dleft, 0), at.unstacked(dleft, 1)      # dxk: of A / beta's X
    return decay * dxq, decay * dxk, dk_columns, xk * dxk, xq * dxq - k * dk_columns


def _halved_transpose(at, masks, q, k, beta, log_alpha, da, dp):
    """``_bounded_transpose`` of ``_halved_products``: level by level ``dX =
    dM C``, ``dC = dM^T X`` over the pairs the level owns, and ``P``'s
    diagonal. What the halves' sums send ``log_alpha`` is what the same
    factors, written ``e^{G_t - G_m} e^{G_m - G_i}``, send ``G``: ``G_m``
    drops out, and the caller's walk back from ``dG`` holds."""
    rows = k.shape[0]
    dq_rows = dk_rows = dk_columns = through = dgap = 0.0
    for l, up, columns in at.halves(log_alpha):
        owned = masks.joined(l + 1)
        dm = jnp.concatenate([jnp.where(owned, da, 0.0), jnp.where(owned, dp, 0.0)], axis=0)
        xk, xq, c = k * up, q * up, k * columns
        dleft = _dot(dm, c, _NN)
        dxk, dxq = dleft[:rows], dleft[rows:]                    # dxk: of A / beta's X
        dc = _dot(dm, jnp.concatenate([beta * xk, xq], axis=0), _TN)
        dq_rows, dk_rows = dq_rows + up * dxq, dk_rows + up * dxk
        dk_columns = dk_columns + columns * dc
        through = through + xk * dxk
        dgap = dgap + xq * dxq - c * dc
    own = _rows_sum(jnp.where(masks.eye, dp, 0.0))               # of P's diagonal, q_t . k_t
    return dq_rows + own * k, dk_rows, dk_columns + own * q, through, dgap


def _channel_backward_kernel(q_ref, k_ref, v_ref, log_alpha_ref, beta_ref, inverse_ref,
                             dw_ref, du0_ref, dqg_ref, dp_ref, dkd_ref, dgamma_ref,
                             dq_ref, dk_ref, dv_ref, dlog_alpha_ref, dbeta_ref,
                             *, chunk, per_step, together, bounded):
    """The transpose of ``_channel_forward_kernel`` by hand, ``together``
    chunks at a time: ``dT`` and ``dA = -T^T dT T^T`` as
    ``_prepare_backward_kernel``'s, then ``_bounded_transpose`` or
    ``_halved_transpose`` of the decayed products, and ``dG`` channel by
    channel. What reaches ``G`` goes back through the running sum
    (``_Blocks.running`` backwards) to ``log_alpha``; ``dbeta_ref`` along the lanes."""
    f32 = jnp.float32
    width = together * chunk
    masks = _Masks(chunk, together)
    at = _Blocks(chunk, together, q_ref.shape[-1])
    for s in range(per_step // together):
        rows = slice(s * width, (s + 1) * width)
        q, k, v, log_alpha, dw, du0, dqg, dp, dkd = (
            ref[0, rows, :].astype(f32) for ref in
            (q_ref, k_ref, v_ref, log_alpha_ref, dw_ref, du0_ref, dqg_ref, dp_ref, dkd_ref)
        )
        inverse = masks.unpacked(inverse_ref[0, _kept_rows(s, chunk), :])
        total = at.running(log_alpha)
        beta = masks.down(beta_ref[0, s, 0:1, :])
        grown = jnp.exp(total)                                   # e^{G_t}
        scaled = grown * k
        dinverse = _dot(dw, beta * scaled, _NT) + _dot(du0, beta * v, _NT)
        dkb, dvb = _dot(inverse, dw, _TN), _dot(inverse, du0, _TN)
        da = -_dot(_dot(inverse, dinverse, _TN), inverse, _NT)
        # of A / beta and of P, each beside its own chunk's columns
        da = jnp.where(masks.strictly, da, 0.0)
        dp = jnp.where(masks.upto, jnp.tile(dp, (1, together)), 0.0)
        if bounded:
            dq_rows, dk_rows, dk_columns, through, dgap = _bounded_transpose(
                at, q, k, beta, total, da, dp
            )
        else:
            dq_rows, dk_rows, dk_columns, through, dgap = _halved_transpose(
                at, masks, q, k, beta, log_alpha, da, dp
            )
        left = at.to_end(log_alpha, total, bounded)
        through_beta = through + scaled * dkb                    # d(beta) and, times beta, dG
        dq_ref[0, rows, :] = (dq_rows + grown * dqg).astype(dq_ref.dtype)
        dk_ref[0, rows, :] = (
            beta * (dk_rows + grown * dkb) + dk_columns + left * dkd
        ).astype(dk_ref.dtype)
        dv_ref[0, rows, :] = (beta * dvb).astype(dv_ref.dtype)
        dbeta_ref[0, s, 0:1, :] = masks.across(_rows_sum(through_beta) + _rows_sum(dvb * v))
        dkept = left * k * dkd                                   # of G_C - G_t
        # G_C is its chunk's last G: what reached it through ``left`` and
        # through gamma = e^{G_C} lands on that token
        dlast = [
            _columns_sum(dkept[c * chunk:(c + 1) * chunk])
            + jnp.exp(total[(c + 1) * chunk - 1:(c + 1) * chunk]) * dgamma_ref[0, s * together + c]
            for c in range(together)
        ]
        dtotal = beta * through_beta + dgap + grown * q * dqg - dkept + at.at_ends(dlast)
        dlog_alpha_ref[0, rows, :] = at.running(dtotal, back=True).astype(dlog_alpha_ref.dtype)


def _channel_layout(held, q, v, beta, chunk):
    """(grid, chunks a step, chunks a product, the specs of q, k, v,
    ``log_alpha`` and ``beta``: the caller's, ``held`` says where) of both
    channel preparation calls."""
    seq, d_k = q.shape[1], held.width(q)
    per_step = _per_step(seq // chunk, chunk)
    width = beta.shape[-1]
    together = width // chunk
    in_specs = [
        *held.specs(chunk, per_step, (d_k, d_k, held.width(v), d_k)),
        held.lanes(per_step // together, 1, width),
    ]
    return (held.per_call, seq // chunk // per_step), per_step, together, in_specs


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret", "inverse", "bounded", "heads", "rows")
)
def _channel_prepare_forward(q, k, v, log_alpha, beta, group=None, kept=None, *, chunk,
                             interpret, inverse=None, bounded=False, heads=0, rows=None):
    """``_prepare_channel_xla``'s six operands ``[rows, seq, .]`` of ``rows``
    (None: all) of the (batch x head) rows of q, k, ``log_alpha``, v
    (``_Held``'s layout under ``heads``) and ``beta`` (``_beta_lanes``),
    those of group ``group``. ``inverse`` and ``kept``:
    ``_prepare_forward_call``'s. ``bounded``: the caller's ``log_alpha``
    keeps a bound that ``carries_bound``."""
    held = _Held(heads, rows or q.shape[0])
    return _prepare_forward_call(
        functools.partial(_channel_forward_kernel, bounded=bounded), held,
        (q, k, v, log_alpha, beta), _channel_layout(held, q, v, beta, chunk), held.width(q),
        chunk, group, kept, inverse, interpret,
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "bounded", "heads"))
def _channel_prepare_backward(q, k, v, log_alpha, beta, inverse, dw, du0, dqg, dp, dkd, dgamma,
                              group=None, *, chunk, interpret, bounded=False, heads=0):
    """``dq``, ``dk``, ``dv``, ``dlog_alpha`` in their operands' dtypes and
    ``dbeta`` in ``_beta_lanes``' layout from the scan backward's six: of
    the rows of group ``group``, written where q, k, v, ``log_alpha`` and
    ``beta`` hold those rows (every other row of the five results is its
    operand's). ``inverse``: ``T`` as the forward kept it, every row's."""
    held = _Held(heads, dw.shape[0])
    d_k, d_v = held.width(q), held.width(v)
    grid, per_step, together, in_specs = _channel_layout(held, q, v, beta, chunk)
    return _grouped_call(
        functools.partial(
            _channel_backward_kernel, chunk=chunk, per_step=per_step, together=together,
            bounded=bounded,
        ),
        group, (q, k, v, log_alpha, beta, inverse, dw, du0, dqg, dp, dkd, dgamma),
        grid=grid,
        in_specs=[
            *in_specs,
            _kept_spec(held, chunk, per_step, together),
            *_specs(chunk, per_step, (d_k, d_v, d_k, chunk, d_k), lambda n: n),
            pl.BlockSpec((1, per_step, 1, d_k), lambda i, n, _: (i, n, 0, 0)),
        ],
        out_specs=in_specs,
        out_shape=[
            *(jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v, log_alpha)),
            jax.ShapeDtypeStruct(beta.shape, jnp.float32),
        ],
        in_place=5,
        interpret=interpret,
        compiler_params=_parallel(),
    )


def _beta_lanes(beta, chunk: int):
    """``beta`` ``[heads, seq]`` as ``[heads, seq / width, 1, width]``
    float32: the tokens of one product's chunks along the lanes (``_gates``
    has why)."""
    bh, seq = beta.shape
    width = _product_rows(seq, chunk)
    return beta.astype(jnp.float32).reshape(bh, seq // width, 1, width)


def _prepare_channel(q, k, v, log_alpha, beta, chunk: int, interpret=None, bounded=False,
                     group=None, held=None):
    """The six operands of the scan under a decay per channel on the
    kernels' path: ``_prepare_channel_xla``'s, from ``_channel_prepare_forward``,
    of the rows ``held`` (None: every row of ``[rows, seq, .]`` operands)
    and ``group`` name; ``beta`` ``[rows, seq]`` of every row. The ONE place
    the timed forward takes its operands from, looked up as a module global
    when ``_channel_prepare_and_scan`` is traced."""
    held = held or _Held(0, q.shape[0])
    return _channel_prepare_forward(
        q, k, v, log_alpha, _beta_lanes(beta, chunk), group,
        chunk=chunk, interpret=resolve_interpret(interpret), bounded=bounded,
        heads=held.heads, rows=held.per_call,
    )


def _gates(log_alpha, beta, chunk: int):
    """``[heads, seq / width, 2, width]`` float32: the running sum of
    ``log_alpha`` inside each chunk (``_prepare``'s ``total``) and ``beta``,
    the tokens of the ``width / chunk`` chunks of one product
    (``_product_rows``) along the lanes: 8 bytes a head and token, where a
    ``[.., seq, 1]`` operand pads 128-fold."""
    bh, seq = log_alpha.shape
    width = _product_rows(seq, chunk)
    total = jnp.cumsum(log_alpha.astype(jnp.float32).reshape(bh, -1, chunk), axis=-1)
    by_product = lambda x: x.reshape(bh, -1, width)
    return jnp.stack([by_product(total), by_product(beta.astype(jnp.float32))], axis=2)


class _Walk(NamedTuple):
    """What is static of one rule: the chunk, whether the interpreter runs
    the kernels, where the caller's arrays hold a row (``_Held``), how many
    groups of ``held.per_call`` rows they hold, and what the caller stated
    of a channel decay (``carries_bound``)."""
    chunk: int
    interpret: bool
    held: _Held
    groups: int
    bounded: bool = False

    def over_groups(self, one_group, carried):
        """``carried = one_group(group, carried)`` group after group, ``group``
        a ``[1]`` int32 for the index maps: the kernels' own operands, their
        gradients and the chunk-start states live for one group at a time,
        and what is carried (arrays of EVERY row, each call writing its rows
        in place) is never sliced, stacked or copied."""
        return jax.lax.fori_loop(
            0, self.groups,
            lambda g, carried: one_group(jnp.reshape(g, (1,)).astype(jnp.int32), carried),
            carried,
        )

    @property
    def static(self):
        return dict(chunk=self.chunk, interpret=self.interpret, heads=self.held.heads)


def _unwritten(v):
    """An array like ``v`` for the groups' forward calls to write the output
    into, with nothing in it yet (on a TPU an allocation and no pass): every
    block of it is written by its group's call before anything reads it."""
    return jax.lax.empty(v.shape, v.dtype)


def _scan_keeping(prepare, v, walk):
    """The forward as a gradient traces it: ``prepare(group, kept)`` is a
    group's preparation call with ``inverse="write"``, which also writes the
    group's rows of ``T`` into ``kept``, ONE array of every row
    (``kept_inverse_shape``), as the scan's call writes the output's. Both
    under ``RESIDUAL_NAMES``."""
    def one_group(group, carried):
        out, kept = carried
        *operands, kept = prepare(group, kept)
        return _delta_rule_forward(*operands, group, out, out_dtype=v.dtype, **walk.static), kept

    rows = walk.groups * walk.held.per_call
    kept = jax.lax.empty(kept_inverse_shape(rows, v.shape[1], walk.chunk), jnp.float32)
    out, kept = walk.over_groups(one_group, (_unwritten(v), kept))
    return checkpoint_name(out, RESIDUAL_NAMES[0]), checkpoint_name(kept, RESIDUAL_NAMES[1])


def _prepare_and_scan(q, k, v, gates, walk):
    def one_group(group, out):
        operands = _delta_prepare_forward(
            q, k, v, gates, group, rows=walk.held.per_call, **walk.static
        )
        return _delta_rule_forward(*operands, group, out, out_dtype=v.dtype, **walk.static)

    return walk.over_groups(one_group, _unwritten(v))


# Preparation and forward kernel over every group of the caller's q, k, v
# (``_Held``'s layout) and their gates (``_gates``, which jax differentiates
# itself); the backward below is the whole of what a gradient runs.
_chunked = jax.custom_vjp(_prepare_and_scan, nondiff_argnums=(4,))


def _chunked_fwd(q, k, v, gates, walk):
    """``_prepare_and_scan`` as a gradient traces it (``_scan_keeping``)."""
    def prepare(group, kept):
        return _delta_prepare_forward(
            q, k, v, gates, group, kept, rows=walk.held.per_call, inverse="write",
            **walk.static,
        )

    out, kept = _scan_keeping(prepare, v, walk)
    return out, (q, k, v, gates, kept)


def _chunked_bwd(walk, residuals, dout):
    """Of the forward its inputs and ``T`` are kept: group by group the
    preparation kernel runs again FROM ``T`` (no ``A``, no level of the
    doubling), the forward kernel once more for the chunk-start states, then
    the backward kernel and the preparation's own (which reads the same
    ``T``), which writes the group's rows of the four gradients where the
    group's rows of the inputs were: ``held`` is the inputs in the groups
    still to come and their gradients in those done (a group's rows are
    read by that group's calls alone), so the gradients need no arrays of
    their own and nothing to fill them."""
    *inputs, kept = residuals

    def one_group(group, held):
        operands = _delta_prepare_forward(
            *held, group, kept, rows=walk.held.per_call, inverse="read", **walk.static
        )
        states = _delta_rule_forward(
            *operands, out_dtype=dout.dtype, states=True, **walk.static
        )
        inner = _delta_rule_backward(*operands, states, dout, group, **walk.static)
        return tuple(_delta_prepare_backward(*held, kept, *inner, group, **walk.static))

    return walk.over_groups(one_group, tuple(inputs))


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def _channel_prepare_and_scan(q, k, v, log_alpha, beta, walk):
    def one_group(group, out):
        with jax.named_scope("decay_prepare"):
            operands = _prepare_channel(
                q, k, v, log_alpha, beta, walk.chunk, walk.interpret, walk.bounded, group,
                walk.held,
            )
        return _delta_rule_forward(*operands, group, out, out_dtype=v.dtype, **walk.static)

    return walk.over_groups(one_group, _unwritten(v))


# ``_chunked`` under a decay per channel: its own preparation pair (scope
# ``decay_prepare``) around the same scan kernels; ``beta`` ``[rows, seq]``.
_chunked_channel = jax.custom_vjp(_channel_prepare_and_scan, nondiff_argnums=(5,))


def _chunked_channel_fwd(q, k, v, log_alpha, beta, walk):
    """``_channel_prepare_and_scan`` as a gradient traces it (``_scan_keeping``)."""
    lanes = _beta_lanes(beta, walk.chunk)

    def prepare(group, kept):
        with jax.named_scope("decay_prepare"):
            return _channel_prepare_forward(
                q, k, v, log_alpha, lanes, group, kept, rows=walk.held.per_call,
                inverse="write", bounded=walk.bounded, **walk.static,
            )

    out, kept = _scan_keeping(prepare, v, walk)
    return out, (q, k, v, log_alpha, beta, kept)


def _chunked_channel_bwd(walk, residuals, dout):
    """As ``_chunked_bwd``, the inputs and ``T`` kept: group by group the
    preparation kernel runs again from ``T``, the forward kernel for the
    chunk-start states, the backward kernel, then the preparation's own,
    which writes the group's rows of the five gradients where the group's
    rows of the inputs were (``held``, as in ``_chunked_bwd``)."""
    *inputs, beta, kept = residuals
    rule = walk.static
    prepare = dict(rule, bounded=walk.bounded)

    def one_group(group, held):
        with jax.named_scope("decay_prepare"):
            operands = _channel_prepare_forward(
                *held, group, kept, rows=walk.held.per_call, inverse="read", **prepare
            )
        states = _delta_rule_forward(*operands, out_dtype=dout.dtype, states=True, **rule)
        inner = _delta_rule_backward(*operands, states, dout, group, **rule)
        with jax.named_scope("decay_prepare"):
            return tuple(_channel_prepare_backward(*held, kept, *inner, group, **prepare))

    *grads, dlanes = walk.over_groups(one_group, (*inputs, _beta_lanes(beta, walk.chunk)))
    return (*grads, dlanes.reshape(beta.shape).astype(beta.dtype))


_chunked_channel.defvjp(_chunked_channel_fwd, _chunked_channel_bwd)


def _heads_per_call(heads: int, seq: int) -> int:
    """How many (batch x head) rows one group of kernel calls takes: the
    most that divide ``heads`` with ``rows x seq`` under
    ``_TOKENS_PER_CALL``. What a head and token costs between the calls of
    ``_chunked_bwd``: the six float32 operands and their gradients (2 x
    2,176 bytes as counted, 2 x 3,072 as HBM tiles them: 96 and 64 columns
    take 128 lanes, 192 take 256), until PR 52 a copy of ``T`` (512; kept
    from the forward since, for every row) and the chunk-start states
    (1,536); the rule's temporaries alone were 1.19 GiB two heads a call,
    1.79 six, 4.69 all thirty at once (compiles for a described v5e, PR
    33). Fewer at a time is still FASTER in the step, down to two: the
    Olmo-Hybrid cell reads 14,573 tokens/s two heads a call (``hbm_step_gib``
    11.76), 14,557 three (12.12), 14,497 five (12.70), 14,540 six (12.93),
    14,446 fifteen (14.51), 14,509 thirty (14.38) (my chip runs, PR 33, one
    seed; the parent 13,988), though the rule ALONE is faster in one call
    (49.5 ms a layer against 52.9): in the step the larger temporaries
    move what the scheduler keeps where. Those are PR 33's readings, taken
    when a group was sliced out of every operand by a ``lax.map`` and
    stacked back; since PR 50 a group is an index (``_Walk.over_groups``)
    and they have not been taken again. One call of every head also lost
    the kernels their jitted names in the compiled step
    (``transpose_jvp_jit__delta_rule_backward___``), which the benchmark's
    readers find them by."""
    fitting = max(_TOKENS_PER_CALL // seq, 1)
    return max(n for n in range(1, heads + 1) if heads % n == 0 and n <= fitting)


def _rule(q, k, v, log_alpha, beta, *, heads, chunk, interpret, kernels, log_alpha_bound):
    """The rule over every (batch x head) row of the caller's arrays:
    ``heads`` 0, q ``[batch, heads, seq, d_k]`` (heads first); else q
    ``[batch, seq, heads, d_k]`` (token-major), and v, a channel decay and the
    result alike; the scalar gates follow q's first three dims."""
    seq_axis = 1 if heads else 2
    seq = q.shape[seq_axis]
    rows = q.shape[0] * q.shape[3 - seq_axis]
    chunk = chunk or _default_chunk(seq)
    padded = -(-seq // chunk) * chunk
    channel = log_alpha.ndim == q.ndim

    def pad(x):
        """Tokens that write nothing (``beta`` 0, ``log_alpha`` 0) at the end."""
        widths = [(0, 0)] * x.ndim
        widths[seq_axis] = (0, padded - seq)
        return jnp.pad(x, widths)

    def flat(x):
        """``_Held``'s three dims."""
        x = pad(x)
        return x.reshape(x.shape[0], padded, -1) if heads else x.reshape(rows, padded, -1)

    def by_row(x):
        """A scalar gate ``[rows, padded]``, a row's tokens together."""
        return (jnp.swapaxes(pad(x), 1, 2) if heads else pad(x)).reshape(rows, padded)

    shape = v.shape[:seq_axis] + (padded,) + v.shape[seq_axis + 1:]
    q, k, v = (flat(x) for x in (q, k, v))
    log_alpha, beta = flat(log_alpha) if channel else by_row(log_alpha), by_row(beta)
    if not kernels:
        prepare = _prepare_channel_xla if channel else _prepare
        out = _scan_reference(*prepare(q, k, v, log_alpha, beta, chunk), chunk, v.dtype)
    else:
        per_call = _heads_per_call(rows, padded)
        walk = _Walk(
            chunk, resolve_interpret(interpret), _Held(heads, per_call), rows // per_call,
            channel and carries_bound(log_alpha_bound),
        )
        if channel:
            out = _chunked_channel(q, k, v, log_alpha, beta, walk)
        else:
            # the running sums are taken once, over every head, not once a group
            out = _chunked(q, k, v, _gates(log_alpha, beta, chunk), walk)
    return jax.lax.slice_in_dim(out.reshape(shape), 0, seq, axis=seq_axis)


def gated_delta_rule(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    log_alpha: jax.Array,
    beta: jax.Array,
    *,
    chunk: int | None = None,
    interpret: bool | None = None,
    kernels: bool = True,
    log_alpha_bound: float | None = None,
) -> jax.Array:
    """The gated delta rule over ``q, k [batch, heads, seq, d_k]``, ``v
    [batch, heads, seq, d_v]`` and ``log_alpha, beta [batch, heads, seq]``
    (``log_alpha <= 0``), HEADS FIRST, as chunks of ``chunk`` tokens (None:
    ``CHUNK``, or the sequence where that is shorter): ``[batch, heads, seq,
    d_v]`` in v's dtype, differentiable in all five. ``log_alpha [batch,
    heads, seq, d_k]`` is a decay per key channel (the module docstring has
    what changes), ANY ``log_alpha <= 0``: nothing the chunked form
    exponentiates is above 0. ``log_alpha_bound`` is a caller's statement
    that no entry lies below it; where ``carries_bound`` holds, the channel
    preparation kernels multiply a diagonal sub-block as ONE product (a
    third fewer tiles). It changes no value, and a scalar decay ignores it.

    A sequence that is no multiple of the chunk is padded at its end with
    tokens that write nothing (``beta`` 0, ``log_alpha`` 0). Heads need
    nothing of one another: they are walked ``_heads_per_call`` at a time,
    a group an INDEX the kernels' index maps add (``_Walk.over_groups``,
    ``_Held``): the six kernels read q, k, v and a channel decay and write
    the output and the five gradients where the caller's arrays hold the
    group's rows, and a call keeps for its backward its inputs, its output
    and ``T``'s diagonal blocks (``kept_bytes``), so only the kernels' own
    operands, their gradients and the chunk-start states exist a group at a
    time, forward and backward. ``kernels=False``
    runs the scan over chunks in plain jax.numpy (the chunked form with no
    kernel, for tests).

    ``gated_delta_rule_by_token`` is the same rule over token-major arrays."""
    return _rule(
        q, k, v, log_alpha, beta, heads=0, chunk=chunk, interpret=interpret, kernels=kernels,
        log_alpha_bound=log_alpha_bound,
    )


# Lanes of a vector register.
_LANES = 128


def whole_lanes(*head_widths: int) -> bool:
    """Whether every head width fills whole lanes: only then is a head's
    block of a token-major array (``[.., seq, heads x d]``) whole tiles, and
    only then do the kernels read that layout (``gated_delta_rule_by_token``)."""
    return not any(width % _LANES for width in head_widths)


def by_token(rule):
    """``rule`` (heads first, as ``gated_delta_rule``) over token-major
    arrays: ``[batch, seq, heads, .]`` in, ``[batch, seq, heads, d_v]`` out,
    each turned on its way. The price of a rule that wants a head's tokens
    together: one pass over every operand and one over the result."""
    heads_first = lambda x: jnp.swapaxes(x, 1, 2)
    return lambda *operands, **how: heads_first(rule(*map(heads_first, operands), **how))


def gated_delta_rule_by_token(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    log_alpha: jax.Array,
    beta: jax.Array,
    *,
    chunk: int | None = None,
    interpret: bool | None = None,
    kernels: bool = True,
    log_alpha_bound: float | None = None,
) -> jax.Array:
    """``gated_delta_rule`` over TOKEN-MAJOR arrays, the layout a projection
    writes: ``q, k [batch, seq, heads, d_k]``, ``v [batch, seq, heads,
    d_v]``, ``log_alpha, beta [batch, seq, heads]`` or ``log_alpha [batch,
    seq, heads, d_k]``; ``[batch, seq, heads, d_v]`` in v's dtype. The same
    values and gradients.

    Which layout the kernels read is decided HERE, from the shapes alone.
    Where both head widths fill whole lanes (``d_k`` and ``d_v`` multiples of
    128) the arrays are read and written as they stand: seen as ``[batch,
    seq, heads x d]``, head ``i``'s block is ``(1, tokens, d)`` at ``(b, n,
    i)`` (``_Held``), and nothing is transposed; only the scalar gates (4
    bytes a head and token) are turned to a head's tokens together, which
    the kernels read along the lanes. Elsewhere (Olmo-Hybrid's 96 | 192: a
    head's block would begin inside a tile) and with ``kernels=False`` the
    arrays are turned heads first (``by_token``), which is what unaligned
    heads cost until someone pads them."""
    how = dict(chunk=chunk, interpret=interpret, kernels=kernels, log_alpha_bound=log_alpha_bound)
    if not kernels or not whole_lanes(q.shape[-1], v.shape[-1]):
        return by_token(gated_delta_rule)(q, k, v, log_alpha, beta, **how)
    return _rule(q, k, v, log_alpha, beta, heads=q.shape[2], **how)
