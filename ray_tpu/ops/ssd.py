"""Mamba-2's state-space recurrence in its chunked (state-space-duality)
form, with a hand-written backward: XLA einsums under one ``custom_vjp``.

A head ``h`` of width ``P`` keeps a state ``S`` in ``R^{P x N}``; ``B`` and
``C`` (``[.., groups, N]``) are shared by the ``heads / groups`` heads of a
group, head ``h`` reading group ``h // (heads / groups)``. With ``dt > 0`` a
head and token and ``A < 0`` a head::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T          (S_0 = 0)
    y_t = S_t C_t + D x_t

It is NOT a case of the delta rule (ops/gated_delta_rule.py): that one erases
along ``k`` before it writes, this one only decays and writes, so there is no
chunk inverse and no preparation. ``ssd_reference`` is the recurrence a token
at a time (``attention="reference"`` and the oracle of the tests).

The chunked form (``ssd``) walks the sequence in chunks of ``chunk`` tokens,
``_CHUNKS_PER_BLOCK`` of them a trip of ONE ``lax.scan`` whose carry is the
state. With ``a_t = dt_t A``, ``cum_t`` its running sum inside a chunk and
``S0`` the state at the chunk's start::

    y_t  = sum_{s <= t} (C_t . B_s) exp(cum_t - cum_s) dt_s x_s
           + exp(cum_t) S0 C_t + D x_t
    S1   = exp(cum_L) S0 + sum_s exp(cum_L - cum_s) dt_s x_s B_s^T

What holds, each by a test (tests/test_ssd.py):

* every exponent is a difference of running sums of ``dt A <= 0`` taken so
  that it is ``<= 0`` (``cum_t - cum_s`` under the mask ``s <= t``, ``cum_L -
  cum_s``, ``cum_t``): nothing above 0 is exponentiated, whatever the decay;
* the products are in the operands' dtype with float32 accumulators; the
  decay, the running sums and the state are float32;
* the state is in HBM at chunk boundaries only (``[batch, seq / chunk, heads,
  P, N]`` float32, made by the backward's first pass; the forward hands on a
  block's last), never a token; no array is ``[seq, seq]``;
* ``C B^T`` is computed once a GROUP and ``B``, ``C`` and their gradients are
  read and written at ``groups``: nothing repeats them to the heads.

The backward keeps the inputs alone: a first pass makes the chunk-start
states again (a third of the forward's products), a second walks the blocks
backwards with the state's cotangent as its carry. The output carries
``RESIDUAL_NAMES`` (checkpoint_name): a layer checkpoint keeps it, as it keeps
the delta rule's, and its second forward runs none of this.

Both passes open ``jax.named_scope("ssd")`` themselves (a ``custom_vjp``'s
backward is traced where the gradient is taken, outside the caller's scopes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

RESIDUAL_NAMES = ("ssd_out",)

# Chunks a trip of the block scan takes at once: the ``[chunk, chunk]``
# arrays of a trip are ``batch x this x heads`` of them in float32 (32 MiB
# each at 128 heads and chunks of 128).
_CHUNKS_PER_BLOCK = 4


def ssd_reference(x, dt, A, B, C, D):
    """The recurrence a token at a time, float32: ``x`` ``[batch, seq, heads,
    P]``, ``dt`` ``[batch, seq, heads]``, ``A`` and ``D`` ``[heads]``, ``B``
    and ``C`` ``[batch, seq, groups, N]``. Returns ``x``'s shape and dtype."""
    batch, seq, heads, width = x.shape
    repeats = heads // B.shape[2]
    f32 = jnp.float32
    by_head = lambda t: jnp.repeat(t.astype(f32), repeats, axis=2)

    def token(state, operands):
        x_t, dt_t, b_t, c_t = operands                     # [batch, heads, .]
        decay = jnp.exp(dt_t * A.astype(f32))[..., None, None]
        state = decay * state + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t, precision="highest")

    by_time = lambda t: jnp.moveaxis(t, 1, 0)
    state = jnp.zeros((batch, heads, width, B.shape[-1]), f32)
    _, y = jax.lax.scan(token, state, (
        by_time(x.astype(f32)), by_time(dt.astype(f32)), by_time(by_head(B)), by_time(by_head(C)),
    ))
    y = jnp.moveaxis(y, 0, 1) + D.astype(f32)[:, None] * x.astype(f32)
    return y.astype(x.dtype)


def _per_block(chunks: int) -> int:
    """The largest divisor of ``chunks`` up to ``_CHUNKS_PER_BLOCK``."""
    return max(n for n in range(1, _CHUNKS_PER_BLOCK + 1) if chunks % n == 0)


def _blocks(x, dt, B, C, chunk):
    """The operands by block, the scan's ``xs``: ``[blocks, batch, chunks a
    block, chunk, groups, heads a group, .]`` (``B``, ``C`` without the
    heads' axis), free reshapes of the token-major arrays but for the
    leading axis the scan walks."""
    batch, seq, heads, _ = x.shape
    groups = B.shape[2]
    if seq % chunk or heads % groups:
        raise ValueError(
            f"ssd: a sequence of {seq} is no multiple of the chunk {chunk}, or {heads} heads "
            f"are no multiple of {groups} groups"
        )
    per_block = _per_block(seq // chunk)
    lead = (batch, seq // chunk // per_block, per_block, chunk)
    by_block = lambda t, *rest: jnp.moveaxis(t.reshape(*lead, *rest), 1, 0)
    per_group = heads // groups
    return (
        by_block(x, groups, per_group, x.shape[-1]), by_block(dt, groups, per_group),
        by_block(B, groups, B.shape[-1]), by_block(C, groups, C.shape[-1]),
    )


def _from_blocks(t, shape):
    return jnp.moveaxis(t, 0, 1).reshape(shape)


def _dot(spec, a, b):
    """An einsum of the operands as they are, accumulated in float32: one
    pass of the MXU in the model's dtype; float32 operands (the tests', a
    check's) are multiplied as float32, which a TPU's default would round."""
    precision = "highest" if a.dtype == jnp.float32 else None
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32, precision=precision)


def _decays(dt, A):
    """``(cum, total, to_end)`` of a block's ``dt`` ``[b, k, L, g, r]``: the
    running sum of ``dt A`` inside each chunk, its last entry ``[b, k, g,
    r]``, and ``exp(total - cum)``, a token's decay to its chunk's end."""
    cum = jnp.cumsum(dt * A, axis=2)
    total = cum[:, :, -1]
    return cum, total, jnp.exp(total[:, :, None] - cum)


def _local_states(x, dt, B, to_end):
    """``(xd, written, local)``: ``dt x`` in float32, ``dt x`` decayed to the
    chunk's end in the operands' dtype, and what a chunk's own tokens leave
    in the state at its end, ``[b, k, g, r, P, N]`` float32."""
    xd = x.astype(jnp.float32) * dt[..., None]
    written = (xd * to_end[..., None]).astype(x.dtype)
    return xd, written, _dot("bklgrp,bklgn->bkgrpn", written, B)


def _walk(state, total, local, reverse=False):
    """The recurrence over a block's chunks: ``state <- exp(total_j) state +
    local_j``, forwards or backwards. Returns the last state and, stacked by
    chunk, the state each chunk STARTED from (forwards: its ``S0``;
    backwards: the cotangent of its end state)."""
    chunks = range(total.shape[1])
    seen = {}
    for j in (reversed(chunks) if reverse else chunks):
        seen[j] = state
        state = jnp.exp(total[:, j])[..., None, None] * state + local[:, j]
    return state, jnp.stack([seen[j] for j in chunks], axis=1)


def _within(cum, G):
    """``(decay, weights)``: ``exp(cum_t - cum_s)`` under ``s <= t`` (0 above
    the diagonal) ``[b, k, g, r, L, L]`` float32, and its product with the
    group's ``C B^T``: the chunk's own attention-like matrix."""
    rows = jnp.moveaxis(cum, 2, -1)                                   # [b, k, g, r, L]
    chunk = rows.shape[-1]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    # masked BEFORE the exponential: above the diagonal the difference is positive
    decay = jnp.exp(jnp.where(causal, rows[..., :, None] - rows[..., None, :], -jnp.inf))
    return decay, decay * G[:, :, :, None]


def _block_states(A, state, block):
    """A block's chunk-start states alone (the backward's first pass)."""
    x, dt, B, _ = block
    _, total, to_end = _decays(dt, A)
    _, _, local = _local_states(x, dt, B, to_end)
    return _walk(state, total, local)


def _block_forward(A, D, state, block):
    x, dt, B, C = block
    dtype = x.dtype
    cum, total, to_end = _decays(dt, A)
    xd, _, local = _local_states(x, dt, B, to_end)
    state, starts = _walk(state, total, local)
    _, weights = _within(cum, _dot("bklgn,bksgn->bkgls", C, B))
    y = _dot("bkgrls,bksgrp->bklgrp", weights.astype(dtype), xd.astype(dtype))
    carried = _dot("bklgn,bkgrpn->bklgrp", C, starts.astype(dtype))
    y = y + carried * jnp.exp(cum)[..., None] + D[..., None] * x.astype(jnp.float32)
    return state, y.astype(dtype)


def _block_backward(A, D, dstate, block):
    """One block's gradients from ``dy``, its chunk-start states and the
    cotangent ``dstate`` of the state at its end (the module docstring's two
    formulas, term by term)."""
    x, dt, B, C, starts, dy = block
    dtype, f32 = x.dtype, jnp.float32
    cum, total, to_end = _decays(dt, A)
    xd, written, _ = _local_states(x, dt, B, to_end)
    from_start = jnp.exp(cum)
    dy32 = dy.astype(f32)
    read = (dy32 * from_start[..., None]).astype(dtype)               # exp(cum_t) dy_t
    # the state's cotangent, chunk by chunk backwards
    dstate, dends = _walk(dstate, total, _dot("bklgrp,bklgn->bkgrpn", read, C), reverse=True)
    starts_, dends_ = starts.astype(dtype), dends.astype(dtype)
    # y's part through the chunk's start state
    dC = _dot("bklgrp,bkgrpn->bklgn", read, starts_)
    carried = _dot("bklgn,bkgrpn->bklgrp", C, starts_) * from_start[..., None]
    dcum = jnp.sum(dy32 * carried, axis=-1)
    # the end state's part
    through = _dot("bkgrpn,bklgn->bklgrp", dends_, B)                 # (dS1 B_s)
    dxd = to_end[..., None] * through
    moved = jnp.sum(xd * dxd, axis=-1)                                # f_s xd_s . dS1 B_s
    at_end = jnp.exp(total) * jnp.sum(dends * starts, axis=(-2, -1)) + jnp.sum(moved, axis=2)
    dcum = (dcum - moved).at[:, :, -1].add(at_end)
    dB = _dot("bklgrp,bkgrpn->bklgn", written, dends_)
    # the chunk's own part
    G = _dot("bklgn,bksgn->bkgls", C, B)
    decay, weights = _within(cum, G)
    dweights = _dot("bklgrp,bksgrp->bkgrls", dy.astype(dtype), xd.astype(dtype)) * decay
    dG = jnp.sum(dweights, axis=3).astype(dtype)                      # at the groups
    through_decay = dweights * G[:, :, :, None]
    dcum = dcum + jnp.moveaxis(
        jnp.sum(through_decay, axis=-1) - jnp.sum(through_decay, axis=-2), -1, 2
    )
    dxd = dxd + _dot("bkgrls,bklgrp->bksgrp", weights.astype(dtype), dy.astype(dtype))
    dC = dC + _dot("bkgls,bksgn->bklgn", dG, B)
    dB = dB + _dot("bkgls,bklgn->bksgn", dG, C)
    # from dt x, the running sums and D back to the operands
    x32 = x.astype(f32)
    da = jnp.flip(jnp.cumsum(jnp.flip(dcum, axis=2), axis=2), axis=2)
    dx = dxd * dt[..., None] + D[..., None] * dy32
    ddt = jnp.sum(dxd * x32, axis=-1) + da * A
    sums = (jnp.sum(da * dt, axis=(0, 1, 2)), jnp.sum(dy32 * x32, axis=(0, 1, 2, 5)))
    return dstate, (dx.astype(dtype), ddt, dB.astype(B.dtype), dC.astype(C.dtype), sums)


def _zero_state(x, B):
    batch, _, heads, width = x.shape
    groups = B.shape[2]
    return jnp.zeros((batch, groups, heads // groups, width, B.shape[-1]), jnp.float32)


def _by_group(t, groups):
    return t.astype(jnp.float32).reshape(groups, -1)


def _forward(x, dt, A, B, C, D, chunk):
    groups = B.shape[2]
    with jax.named_scope("ssd"):
        step = functools.partial(_block_forward, _by_group(A, groups), _by_group(D, groups))
        blocks = _blocks(x, dt.astype(jnp.float32), B, C, chunk)
        _, y = jax.lax.scan(step, _zero_state(x, B), blocks)
        return _from_blocks(y, x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd(x, dt, A, B, C, D, chunk):
    return _forward(x, dt, A, B, C, D, chunk)


def _ssd_fwd(x, dt, A, B, C, D, chunk):
    y = checkpoint_name(_forward(x, dt, A, B, C, D, chunk), RESIDUAL_NAMES[0])
    return y, (x, dt, A, B, C, D)


def _ssd_bwd(chunk, kept, dy):
    x, dt, A, B, C, D = kept
    groups = B.shape[2]
    with jax.named_scope("ssd"):
        A_, D_ = _by_group(A, groups), _by_group(D, groups)
        blocks = _blocks(x, dt.astype(jnp.float32), B, C, chunk)
        _, starts = jax.lax.scan(functools.partial(_block_states, A_), _zero_state(x, B), blocks)
        dy = _blocks(dy, dt, B, C, chunk)[0]
        step = functools.partial(_block_backward, A_, D_)
        _, (dx, ddt, dB, dC, (dA, dD)) = jax.lax.scan(
            step, _zero_state(x, B), (*blocks, starts, dy), reverse=True
        )
        return (
            _from_blocks(dx, x.shape), _from_blocks(ddt, dt.shape).astype(dt.dtype),
            jnp.sum(dA, axis=0).reshape(A.shape).astype(A.dtype),
            _from_blocks(dB, B.shape), _from_blocks(dC, C.shape),
            jnp.sum(dD, axis=0).reshape(D.shape).astype(D.dtype),
        )


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x, dt, A, B, C, D, *, chunk: int = 128):
    """The chunked form of the module docstring. ``x`` ``[batch, seq, heads,
    P]`` and ``B``, ``C`` ``[batch, seq, groups, N]`` in the model's dtype,
    ``dt`` ``[batch, seq, heads]`` (positive: after its softplus), ``A``
    (negative) and ``D`` ``[heads]``; ``seq`` a multiple of ``chunk``.
    Returns ``x``'s shape and dtype; differentiable in all six."""
    return _ssd(x, dt, A, B, C, D, chunk)
