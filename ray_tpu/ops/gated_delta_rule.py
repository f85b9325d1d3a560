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

``gated_delta_rule_reference`` is that recurrence as a ``lax.scan``, one
token a step, float32: the CPU path of the model and the oracle.

``gated_delta_rule`` is the chunked form (Yang et al., "Gated Delta
Networks", the WY representation). A sequence is cut into chunks of
``chunk`` tokens. With ``G_t`` the running sum of ``log_alpha`` inside a
chunk, ``S`` the state at the chunk's start and ``u_t = beta_t (v_t -
alpha_t S_{t-1}^T k_t)`` the value each token really writes::

    (I + A) U = beta V - (beta e^G K) S,   A[t, i] = beta_t e^{G_t - G_i} k_t.k_i  (i < t)
    O  = (e^G Q) S + (Q K^T . e^{G_t - G_i})_{i <= t} U
    S' = e^{G_C} S + (e^{G_C - G} K)^T U

Two halves, by what each is good at:

* the chunk preparation, XLA, float32, differentiated by jax itself
  (``_prepare``): ``T = (I + A)^-1`` and from it ``W = T (beta e^G K)``,
  ``U0 = T (beta V)``, and ``Qg``, ``P``, ``Kd``, ``gamma`` of the other
  two lines. Every product is a matmul batched over chunks; every decay is
  ``exp`` of a difference that is <= 0, so a decay near 0 underflows to 0
  and never divides. ``T`` is built by doubling (``_unit_lower_inverse``),
  exactly, block pairs at a time: nothing in it cancels.
* the scan over chunks, two Mosaic kernels (``_delta_rule_forward``,
  ``_delta_rule_backward``): ``U = U0 - W S``, ``O = Qg S + P U``, ``S' =
  gamma S + Kd^T U`` with the ``[d_k, d_v]`` state carried in float32 in
  VMEM from one grid step to the next, and the same walk backwards for the
  six operands' gradients with ``dS`` carried (``_scan_reference`` is the
  same scan in plain jax.numpy). Within a chunk everything
  is a matmul for the MXU.

What the backward needs of the forward: the state at every chunk's START
(``[chunks, d_k, d_v]`` float32 a head: 566 MB a layer at 30 heads x 16384
tokens in chunks of 64). It is NOT kept: three layers' worth cost the
Olmo-Hybrid cell 2.75 GiB of a v5e's 15.75 and the step then needs 17.8
(compile for a described v5e, PR 32), so the backward runs the forward
kernel once more, for the states alone (``_chunked_bwd``). The output
carries ``RESIDUAL_NAMES`` (checkpoint_name): a layer checkpoint whose
policy saves that name keeps ``O`` for the layers after and runs no kernel
for it again; the preparation is XLA work and is recomputed like the
projections before it. ``kept_bytes`` counts what is kept.

On non-TPU backends the same kernels run in interpreter mode
(ops.resolve_interpret), so tests exercise the code the TPU compiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ray_tpu.ops import resolve_interpret

# The name the forward kernel's output carries
# (models/transformer.py::_remat_policy keeps it).
RESIDUAL_NAMES = ("delta_rule_out",)

# Tokens a chunk; a shorter sequence is one chunk, padded to a multiple of
# ``_CHUNK_MULTIPLE`` (whole sublane tiles in either dtype).
CHUNK = 64
_CHUNK_MULTIPLE = 16
# Chunks one grid step of the kernels walks (static, unrolled): a grid
# step's fixed cost is spread over them.
_CHUNKS_PER_STEP = (8, 4, 2, 1)
# ... as far as a step's rows fit the 16 MiB of scoped VMEM: the backward
# kernel holds eight float32 operands and six results of them, twice (1024
# rows in chunks of 128 asked for 17.5 MiB: compile for a described v5e, PR 32).
_ROWS_PER_STEP = 512
# The state the kernels carry from chunk to chunk (and the forward hands
# the backward); the preparation computes in float32 too.
_STATE_DTYPE = jnp.float32
# (batch x head) rows x tokens one preparation takes at once.
_TOKENS_PER_CALL = 2 * 16384
_HIGHEST = jax.lax.Precision.HIGHEST
# The chunk preparation's matmuls (XLA, float32 operands).
_PREPARE_PRECISION = _HIGHEST


def gated_delta_rule_reference(q, k, v, log_alpha, beta):
    """The recurrence of the module docstring, one token a step, float32.
    q, k: [batch, heads, seq, d_k]; v: [batch, heads, seq, d_v]; log_alpha,
    beta: [batch, heads, seq]. Returns [batch, heads, seq, d_v] in v's
    dtype."""
    f32 = jnp.float32
    batch, heads, _, d_k = q.shape
    d_v = v.shape[-1]

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., None, None]
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
    its backward: the output in the values' dtype (the chunk-start states,
    ``heads x chunks x d_k x d_v`` float32, are made again)."""
    chunk = chunk or _default_chunk(seq)
    return batch * heads * -(-seq // chunk) * chunk * d_v * itemsize


# ---------------------------------------------------------------------------
# The chunk preparation: XLA, differentiated by jax.
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
    copies (105 of 1250 ms a step in the Olmo-Hybrid cell, my chip run, PR 32)."""
    size = a.shape[-1]
    at = jnp.arange(size)
    joined = lambda block: (at[:, None] // block) == (at[None, :] // block)
    inverse = jnp.eye(size, dtype=a.dtype) - jnp.where(joined(2), a, 0.0)
    block = 2
    while block < size:
        joining = jnp.where(joined(2 * block) & ~joined(block), a, 0.0)
        inverse = inverse - _matmul(inverse, _matmul(joining, inverse))
        block *= 2
    return inverse


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
        return gamma * state + _matmul(jnp.swapaxes(kd, 1, 2), u), out

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
        grown = gamma_ref[0, c] * s + _dot(kd_ref[0, rows, :], u.astype(kd_ref.dtype), _TN)
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
        dgamma_ref[0, c] = jnp.sum(
            jnp.sum(product, axis=1, keepdims=True), axis=0, keepdims=True
        )
        before = (
            _dot(qg, do, _TN) + gamma_ref[0, c] * dstate[...].astype(jnp.float32)
            - _dot(w, du, _TN)
        )
        dstate[...] = before.astype(dstate.dtype)


def _per_step(chunks: int, chunk: int) -> int:
    return next(
        n for n in _CHUNKS_PER_STEP
        if chunks % n == 0 and (n * chunk <= _ROWS_PER_STEP or n == 1)
    )


def _specs(chunk, per_step, widths, index):
    """BlockSpecs of ``[bh, seq, width]`` operands, ``per_step`` chunks a
    grid step; ``index(i, n)`` is the step's position along the sequence."""
    return [
        pl.BlockSpec((1, per_step * chunk, width), lambda i, n: (i, index(n), 0))
        for width in widths
    ]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "out_dtype", "states"))
def _delta_rule_forward(w, u0, qg, p, kd, gamma, *, chunk, interpret, out_dtype, states=False):
    """The output ``[heads, seq, d_v]`` in ``out_dtype``; with ``states``
    the chunk-start states ``[heads, chunks, d_k, d_v]`` instead."""
    from jax.experimental.pallas import tpu as pltpu

    bh, seq, d_k = w.shape
    d_v = u0.shape[-1]
    chunks = seq // chunk
    per_step = _per_step(chunks, chunk)
    forward = lambda n: n
    kernel = functools.partial(_forward_kernel, chunk=chunk, per_step=per_step, states=states)
    if states:
        out_spec = pl.BlockSpec((1, per_step, d_k, d_v), lambda i, n: (i, n, 0, 0))
        out_shape = jax.ShapeDtypeStruct((bh, chunks, d_k, d_v), _STATE_DTYPE)
    else:
        (out_spec,) = _specs(chunk, per_step, (d_v,), forward)
        out_shape = jax.ShapeDtypeStruct((bh, seq, d_v), out_dtype)
    return pl.pallas_call(
        kernel,
        grid=(bh, chunks // per_step),
        in_specs=[
            *_specs(chunk, per_step, (d_k, d_v, d_k, chunk, d_k), forward),
            pl.BlockSpec((1, per_step, 1, 1), lambda i, n: (i, n, 0, 0)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((d_k, d_v), _STATE_DTYPE)],
        interpret=interpret,
    )(w, u0, qg, p, kd, gamma)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _delta_rule_backward(w, u0, qg, p, kd, gamma, states, dout, *, chunk, interpret):
    from jax.experimental.pallas import tpu as pltpu

    bh, seq, d_k = w.shape
    d_v = u0.shape[-1]
    chunks = seq // chunk
    per_step = _per_step(chunks, chunk)
    steps = chunks // per_step
    backward = lambda n: steps - 1 - n
    per_chunk = lambda *tail: pl.BlockSpec(
        (1, per_step, *tail), lambda i, n: (i, backward(n), 0, 0)
    )
    kernel = functools.partial(_backward_kernel, chunk=chunk, per_step=per_step)
    widths = (d_k, d_v, d_k, chunk, d_k)
    return pl.pallas_call(
        kernel,
        grid=(bh, steps),
        in_specs=[
            *_specs(chunk, per_step, widths, backward),
            per_chunk(1, 1),
            per_chunk(d_k, d_v),
            *_specs(chunk, per_step, (d_v,), backward),
        ],
        out_specs=[*_specs(chunk, per_step, widths, backward), per_chunk(1, 1)],
        out_shape=[
            *(jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (w, u0, qg, p, kd, gamma)),
        ],
        scratch_shapes=[pltpu.VMEM((d_k, d_v), _STATE_DTYPE)],
        interpret=interpret,
    )(w, u0, qg, p, kd, gamma, states, dout)


def _prepare_and_scan(q, k, v, log_alpha, beta, chunk, interpret):
    operands = _prepare(q, k, v, log_alpha, beta, chunk)
    return _delta_rule_forward(*operands, chunk=chunk, interpret=interpret, out_dtype=v.dtype)


# Preparation and forward kernel of ``[heads, seq, .]`` operands; the
# backward below is the whole of what a gradient runs.
_chunked = jax.custom_vjp(_prepare_and_scan, nondiff_argnums=(5, 6))


def _chunked_fwd(q, k, v, log_alpha, beta, chunk, interpret):
    out = _prepare_and_scan(q, k, v, log_alpha, beta, chunk, interpret)
    return checkpoint_name(out, RESIDUAL_NAMES[0]), (q, k, v, log_alpha, beta)


def _chunked_bwd(chunk, interpret, inputs, dout):
    """Nothing of the forward is kept but its inputs: the preparation runs
    again (XLA work, as a layer checkpoint would run it), the forward
    kernel once more for the chunk-start states, then the backward kernel
    and jax's own transpose of the preparation."""
    operands, prepare_vjp = jax.vjp(
        lambda *inputs: _prepare(*inputs, chunk), *inputs
    )
    states = _delta_rule_forward(
        *operands, chunk=chunk, interpret=interpret, out_dtype=dout.dtype, states=True
    )
    grads = _delta_rule_backward(*operands, states, dout, chunk=chunk, interpret=interpret)
    return prepare_vjp(tuple(grads))


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def _heads_per_call(heads: int, seq: int) -> int:
    """How many (batch x head) rows one preparation and one pair of kernel
    calls take: the most that divide ``heads`` with ``rows x seq`` under
    ``_TOKENS_PER_CALL``. The preparation's float32 operands, their
    gradients and what jax keeps of ``_prepare`` for its backward come to
    about 17 KB a (head, token): 8 GiB at 30 heads x 16384 tokens at once
    (compile for a described v5e, PR 32). Fewer at a time is also FASTER,
    down to two: the Olmo-Hybrid cell reads 13,163 tokens/s ten heads a
    call (13.82 GiB), 13,440 six (12.95), 13,854 three (12.15), 13,979 two
    (11.82), 13,863 one (12.09) (my chip runs, PR 32, one seed)."""
    fitting = max(_TOKENS_PER_CALL // seq, 1)
    return max(n for n in range(1, heads + 1) if heads % n == 0 and n <= fitting)


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
) -> jax.Array:
    """The gated delta rule over ``q, k [batch, heads, seq, d_k]``, ``v
    [batch, heads, seq, d_v]`` and ``log_alpha, beta [batch, heads, seq]``
    (``log_alpha <= 0``), as chunks of ``chunk`` tokens (None: ``CHUNK``,
    or the sequence where that is shorter): ``[batch, heads, seq, d_v]`` in
    v's dtype, differentiable in all five.

    A sequence that is no multiple of the chunk is padded at its end with
    tokens that write nothing (``beta`` 0, ``log_alpha`` 0). Heads need
    nothing of one another: they are walked ``_heads_per_call`` at a time
    (``lax.map``), and a call keeps nothing for its backward but its
    inputs, so the preparation's intermediates live for one group of heads
    at a time, forward and backward.
    ``kernels=False`` runs the scan over chunks in plain jax.numpy (the
    chunked form with no kernel, for tests)."""
    batch, heads, seq, _ = q.shape
    chunk = chunk or _default_chunk(seq)
    padded = -(-seq // chunk) * chunk
    interpret = resolve_interpret(interpret)

    def one_call(q, k, v, log_alpha, beta):
        if kernels:
            return _chunked(q, k, v, log_alpha, beta, chunk, interpret)
        return _scan_reference(*_prepare(q, k, v, log_alpha, beta, chunk), chunk, v.dtype)

    rows = batch * heads
    per_call = _heads_per_call(rows, padded)

    def grouped(x):
        x = jnp.pad(x, ((0, 0), (0, 0), (0, padded - seq)) + ((0, 0),) * (x.ndim - 3))
        return x.reshape(rows // per_call, per_call, padded, *x.shape[3:])

    groups = tuple(grouped(x) for x in (q, k, v, log_alpha, beta))
    if per_call == rows:
        out = one_call(*(x[0] for x in groups))
    else:
        out = jax.lax.map(lambda group: one_call(*group), groups)
    return out.reshape(batch, heads, padded, v.shape[-1])[:, :, :seq]
