"""Mamba-1's selective scan: a first-order recurrence for every (channel,
state) pair, two Mosaic kernels under one ``custom_vjp``, a hand-written
backward.

A channel ``c`` of ``D`` keeps ``N`` states; ``B_t`` and ``C_t`` (``[.., N]``)
are shared by all the channels of a token, and the decay differs for every
pair: with ``dt > 0`` a channel and token and ``A < 0`` a channel AND state::

    S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] u_t[c]     (S_0 = 0)
    y_t[c]    = sum_n C_t[n] S_t[c, n] + D[c] u_t[c]

It is NOT Mamba-2's scan (ops/ssd.py): there a head's decay is one scalar a
token, so a chunk is a masked matmul; here ``exp(A[c, n] (cum_t[c] -
cum_s[c]))`` couples channel and state and no product of two matrices gives
it. So the kernels run the recurrence itself, a token at a time, on the
vector unit: ``D x N`` independent chains a token, none of them on the MXU.
``selective_scan_reference`` is the same recurrence as an XLA scan
(``attention="reference"`` and the oracle of the tests), which materialises
nothing but one state: as a kernel-free TRAINING path it would keep ``[seq, D,
N]`` float32 for its backward (5.4 GB a layer at 16,384 x 5,120 x 16).

Layouts. The state is ``[N, W]``: the states down the sublanes, ``W`` channels
(``_tile``: up to four lane tiles) along the lanes, float32, in VMEM scratch
across a channel tile's chunks (the grid is ``(batch, channel tiles, chunks)``,
the chunks last and in order). ``u``, ``dt``, ``y`` and their gradients are
TOKEN-MAJOR as the mixer's projections write them, ``[batch, seq, D]``, a
``[chunk, W]`` block a step; ``A`` enters as ``A^T`` ``[N, D]``; ``B`` and ``C``
enter transposed, ``[batch, N, seq]`` (a turn of ``seq x N`` numbers in front
of the kernels), so that a token's ``B_t`` is a COLUMN of the block, states
down the sublanes as the state has them: selected by a lane mask, summed along
the lanes (one nonzero: exact) and broadcast back by the product with the
``[1, W]`` row ``dt_t u_t``. A trip of the token loop takes ``_GROUP`` tokens,
one packed tile of a bfloat16 block's rows.

* ``_forward_kernel`` writes ``y`` and the state at each chunk's START,
  ``[batch, seq / chunk, N, D]`` float32 (42 MB a layer at 16,384 x 5,120 and
  a chunk of 128): both carry ``RESIDUAL_NAMES`` (checkpoint_name), so a layer
  checkpoint keeps them and its second forward runs no scan.
* ``_backward_kernel`` walks the chunks LAST FIRST: it makes a chunk's states
  again from its start state (``[chunk + 1, N, W]`` float32 of VMEM scratch),
  then walks the chunk's tokens backwards with the state's cotangent in a
  second scratch, and returns ``du``, ``d(dt)``, ``dA`` (summed over the tokens
  in VMEM, over the batch outside), ``dB`` and ``dC`` (a channel tile's part
  each, ``[batch, tiles, N, seq]``, summed over the tiles outside). ``dD`` is
  ``sum(dy u)``, XLA's, under the same scope.

A sequence that is no multiple of the chunk is padded behind its end with
``dt = 0`` (a decay of 1 and nothing written: the state passes through) and
cut again. What holds, each by a test (tests/test_sambay.py): the state, the
decays and every sum are float32 whatever the operands' dtype; the exponent is
``dt A <= 0``; HBM holds the state at chunk boundaries only.

Both passes open ``jax.named_scope("selective_scan")`` themselves (a
``custom_vjp``'s backward is traced where the gradient is taken).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ray_tpu.ops import resolve_interpret

RESIDUAL_NAMES = ("selective_scan_out", "selective_scan_states")

_LANES = 128
# Tokens between two states the forward keeps for the backward (a multiple of
# 128, whole lane tiles; no result depends on it): 42 MB a layer at 16,384 x
# 5,120 x 16, and the backward's states of a chunk 4.2 MB of VMEM.
CHUNK = 128
# Tokens a trip of the token loop takes: the rows of one packed bfloat16 tile.
_GROUP = 16
# Lane tiles of channels a grid step holds at most: the state is then 8 vector
# registers, and a token's column of B / C is found once for all of them.
_TILES = 4
_F32 = jnp.float32
# What a kernel may hold of VMEM: the backward's states of a chunk of 256 tokens
# are 8.4 MB at four lane tiles, beside ten double-buffered blocks.
_VMEM_LIMIT = 48 << 20


def selective_scan_reference(u, dt, a, b, c, d_skip):
    """The recurrence a token at a time, float32 state: ``u`` and ``dt``
    ``[batch, seq, D]``, ``a`` ``[D, N]``, ``b`` and ``c`` ``[batch, seq, N]``,
    ``d_skip`` ``[D]``. Returns ``u``'s shape and dtype."""
    f32 = lambda t: t.astype(_F32)

    def token(state, operands):
        u_t, dt_t, b_t, c_t = operands                     # [batch, D] x 2, [batch, N] x 2
        decay = jnp.exp(dt_t[..., None] * f32(a))
        state = decay * state + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    by_time = lambda t: jnp.moveaxis(f32(t), 1, 0)
    state = jnp.zeros((u.shape[0], *a.shape), _F32)
    _, y = jax.lax.scan(token, state, (by_time(u), by_time(dt), by_time(b), by_time(c)))
    return (jnp.moveaxis(y, 0, 1) + f32(d_skip) * f32(u)).astype(u.dtype)


def kept_bytes(batch: int, seq: int, channels: int, states: int, itemsize: int) -> int:
    """Bytes ONE scan keeps from its forward for its backward beside its
    operands: the output in the model's dtype and the chunk-start states in
    float32 (``RESIDUAL_NAMES``)."""
    chunks = -(-seq // CHUNK)
    return batch * channels * (seq * itemsize + chunks * states * 4)


def _tile(channels: int) -> int:
    """Channels a grid step holds: the most whole lane tiles up to ``_TILES``
    that divide them; channels that are no whole lane tiles go as one block."""
    if channels % _LANES:
        return channels
    return max(n for n in range(1, _TILES + 1) if (channels // _LANES) % n == 0) * _LANES


def _column(block, at):
    """``[N, 1]``: the column of ``block`` ``[N, chunk]`` (float32) that the
    lane mask ``at`` selects, states down the sublanes. One nonzero a row: the
    sum is exact."""
    return jnp.sum(jnp.where(at, block, 0.0), axis=1, keepdims=True)


def _rows_into(rows):
    """``[_GROUP, W]`` whose row ``j`` is ``rows[j]`` ``[1, W]``."""
    row = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, rows[0].shape[1]), 0)
    out = jnp.broadcast_to(rows[0], row.shape)
    for j in range(1, _GROUP):
        out = jnp.where(row == j, rows[j], out)
    return out


def _forward_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, start_ref, state):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    start_ref[0, 0] = state[...]
    chunk = u_ref.shape[1]
    A, skip = a_ref[...], d_ref[...]                       # [N, W], [1, W]
    B, C = b_ref[0].astype(_F32), c_ref[0].astype(_F32)    # [N, chunk]
    lane = jax.lax.broadcasted_iota(jnp.int32, B.shape, 1)

    def trip(g, S):
        first = pl.multiple_of(g * _GROUP, _GROUP)
        u, dt = u_ref[0, pl.ds(first, _GROUP), :].astype(_F32), dt_ref[0, pl.ds(first, _GROUP), :]
        rows = []
        for j in range(_GROUP):
            at = lane == first + j
            u_t, dt_t = u[j:j + 1], dt[j:j + 1]            # [1, W]
            S = jnp.exp(dt_t * A) * S + _column(B, at) * (dt_t * u_t)
            rows.append(jnp.sum(_column(C, at) * S, axis=0, keepdims=True) + skip * u_t)
        y_ref[0, pl.ds(first, _GROUP), :] = _rows_into(rows).astype(y_ref.dtype)
        return S

    state[...] = jax.lax.fori_loop(0, chunk // _GROUP, trip, state[...])


def _backward_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, start_ref, dy_ref,
                     du_ref, ddt_ref, da_ref, db_ref, dc_ref, states, dstate):
    """A chunk's gradients from ``dy``, its start state and the cotangent
    ``dstate`` of the state at its end; the index maps hand the grid the chunks
    last first. With ``G_t`` the whole cotangent of ``S_t`` (the carried one and
    ``C_t dy_t``) and ``decay_t = exp(dt_t A)``::

        dC_t[n] = sum_c dy_t[c] S_t[n, c]          dB_t[n] = sum_c G_t[n, c] dt_t[c] u_t[c]
        E_t     = G_t S_{t-1} decay_t              dA     += E_t dt_t
        H_t[c]  = sum_n G_t[n, c] B_t[n]           d(dt_t) = sum_n E_t A + H_t u_t
        du_t    = H_t dt_t + D dy_t                carried = G_t decay_t
    """
    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)
        da_ref[...] = jnp.zeros_like(da_ref)

    chunk = u_ref.shape[1]
    trips = chunk // _GROUP
    A, skip = a_ref[...], d_ref[...]
    B, C = b_ref[0].astype(_F32), c_ref[0].astype(_F32)
    lane = jax.lax.broadcasted_iota(jnp.int32, B.shape, 1)
    block = lambda ref, first: ref[0, pl.ds(first, _GROUP), :].astype(_F32)

    # the chunk's states again: ``states[t + 1]`` is the state after token t
    states[0] = start_ref[0, 0]

    def again(g, S):
        first = pl.multiple_of(g * _GROUP, _GROUP)
        u, dt = block(u_ref, first), block(dt_ref, first)
        for j in range(_GROUP):
            u_t, dt_t = u[j:j + 1], dt[j:j + 1]
            S = jnp.exp(dt_t * A) * S + _column(B, lane == first + j) * (dt_t * u_t)
            states[first + j + 1] = S
        return S

    jax.lax.fori_loop(0, trips, again, states[0])

    def back(i, carried):
        dS, dA, dB, dC = carried
        first = pl.multiple_of((trips - 1 - i) * _GROUP, _GROUP)
        u, dt, dy = block(u_ref, first), block(dt_ref, first), block(dy_ref, first)
        du, ddt = [None] * _GROUP, [None] * _GROUP
        for j in reversed(range(_GROUP)):
            at = lane == first + j
            u_t, dt_t, dy_t = u[j:j + 1], dt[j:j + 1], dy[j:j + 1]
            G = dS + _column(C, at) * dy_t
            dC = jnp.where(at, jnp.sum(states[first + j + 1] * dy_t, axis=1, keepdims=True), dC)
            dB = jnp.where(at, jnp.sum(G * (dt_t * u_t), axis=1, keepdims=True), dB)
            decay = jnp.exp(dt_t * A)
            E = G * states[first + j] * decay
            dA = dA + E * dt_t
            H = jnp.sum(G * _column(B, at), axis=0, keepdims=True)
            ddt[j] = jnp.sum(E * A, axis=0, keepdims=True) + H * u_t
            du[j] = H * dt_t + skip * dy_t
            dS = G * decay
        du_ref[0, pl.ds(first, _GROUP), :] = _rows_into(du).astype(du_ref.dtype)
        ddt_ref[0, pl.ds(first, _GROUP), :] = _rows_into(ddt).astype(ddt_ref.dtype)
        return dS, dA, dB, dC

    zeros = jnp.zeros(B.shape, _F32)
    dS, dA, dB, dC = jax.lax.fori_loop(
        0, trips, back, (dstate[...], jnp.zeros(A.shape, _F32), zeros, zeros)
    )
    dstate[...] = dS
    da_ref[0] += dA
    db_ref[0, 0], dc_ref[0, 0] = dB, dC


def _layout(u, dt, a, b, c, d_skip):
    """The kernels' operands, padded to whole chunks, and their BlockSpecs for
    a grid ``(batch, channel tiles, chunks)``; ``at(k)``: the chunk a grid step
    takes."""
    batch, seq, channels = u.shape
    states, width = a.shape[1], _tile(channels)
    behind = -seq % CHUNK
    rows = lambda t: jnp.pad(t, ((0, 0), (0, behind), (0, 0)))
    turned = lambda t: jnp.swapaxes(rows(t), 1, 2)
    operands = (
        rows(u), rows(dt.astype(_F32)), a.astype(_F32).T, turned(b), turned(c),
        d_skip.astype(_F32)[None],
    )

    def specs(at):
        token_major = pl.BlockSpec((1, CHUNK, width), lambda b, i, k: (b, at(k), i))
        by_state = pl.BlockSpec((1, states, CHUNK), lambda b, i, k: (b, 0, at(k)))
        return token_major, by_state, [
            token_major, token_major, pl.BlockSpec((states, width), lambda b, i, k: (0, i)),
            by_state, by_state, pl.BlockSpec((1, width), lambda b, i, k: (0, i)),
        ]

    grid = (batch, channels // width, (seq + behind) // CHUNK)
    return operands, specs, grid, (states, width)


def _sequential():
    """Batch rows and channel tiles are independent; a tile's chunks follow
    each other (the state in the scratch)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT,
    )


@functools.partial(jax.jit, static_argnames="interpret")
def _selective_scan_forward(u, dt, a, b, c, d_skip, *, interpret):
    """``(y, the chunk-start states [batch, chunks, N, D] float32)``."""
    from jax.experimental.pallas import tpu as pltpu

    operands, specs, grid, (states, width) = _layout(u, dt, a, b, c, d_skip)
    token_major, _, in_specs = specs(lambda k: k)
    batch, tiles, chunks = grid
    y, starts = pl.pallas_call(
        _forward_kernel, grid=grid, in_specs=in_specs,
        out_specs=[
            token_major, pl.BlockSpec((1, 1, states, width), lambda b, i, k: (b, k, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(operands[0].shape, u.dtype),
            jax.ShapeDtypeStruct((batch, chunks, states, tiles * width), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((states, width), _F32)],
        interpret=interpret, compiler_params=_sequential(),
    )(*operands)
    return y[:, :u.shape[1]], starts


@functools.partial(jax.jit, static_argnames="interpret")
def _selective_scan_backward(u, dt, a, b, c, d_skip, starts, dy, *, interpret):
    """The six operands' gradients from ``dy`` and the chunk-start states."""
    from jax.experimental.pallas import tpu as pltpu

    operands, specs, grid, (states, width) = _layout(u, dt, a, b, c, d_skip)
    batch, tiles, chunks = grid
    backward = lambda k: chunks - 1 - k
    token_major, by_state, in_specs = specs(backward)
    seq, padded = u.shape[1], operands[0].shape[1]
    by_tile = pl.BlockSpec((1, 1, states, CHUNK), lambda b, i, k: (b, i, 0, backward(k)))
    du, ddt, da, db, dc = pl.pallas_call(
        _backward_kernel, grid=grid,
        in_specs=[
            *in_specs,
            pl.BlockSpec((1, 1, states, width), lambda b, i, k: (b, backward(k), 0, i)),
            token_major,
        ],
        out_specs=[
            token_major, token_major,
            pl.BlockSpec((1, states, width), lambda b, i, k: (b, 0, i)), by_tile, by_tile,
        ],
        out_shape=[
            jax.ShapeDtypeStruct(operands[0].shape, u.dtype),
            jax.ShapeDtypeStruct(operands[0].shape, _F32),
            jax.ShapeDtypeStruct((batch, states, tiles * width), _F32),
            jax.ShapeDtypeStruct((batch, tiles, states, padded), _F32),
            jax.ShapeDtypeStruct((batch, tiles, states, padded), _F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((CHUNK + 1, states, width), _F32), pltpu.VMEM((states, width), _F32),
        ],
        interpret=interpret, compiler_params=_sequential(),
    )(*operands, starts, jnp.pad(dy, ((0, 0), (0, padded - seq), (0, 0))))
    by_token = lambda t, like: jnp.swapaxes(jnp.sum(t, axis=1), 1, 2)[:, :seq].astype(like.dtype)
    dd = jnp.sum(dy.astype(_F32) * u.astype(_F32), axis=(0, 1))
    return (
        du[:, :seq], ddt[:, :seq].astype(dt.dtype), jnp.sum(da, axis=0).T.astype(a.dtype),
        by_token(db, b), by_token(dc, c), dd.astype(d_skip.dtype),
    )


def _forward(u, dt, a, b, c, d_skip, interpret):
    with jax.named_scope("selective_scan"):
        return _selective_scan_forward(u, dt, a, b, c, d_skip, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(u, dt, a, b, c, d_skip, interpret):
    return _forward(u, dt, a, b, c, d_skip, interpret)[0]


def _scan_fwd(u, dt, a, b, c, d_skip, interpret):
    y, starts = _forward(u, dt, a, b, c, d_skip, interpret)
    y, starts = checkpoint_name(y, RESIDUAL_NAMES[0]), checkpoint_name(starts, RESIDUAL_NAMES[1])
    return y, (u, dt, a, b, c, d_skip, starts)


def _scan_bwd(interpret, kept, dy):
    with jax.named_scope("selective_scan"):
        return _selective_scan_backward(*kept, dy, interpret=interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(u, dt, a, b, c, d_skip, *, interpret: bool | None = None):
    """The recurrence of the module docstring. ``u`` ``[batch, seq, D]`` and
    ``b``, ``c`` ``[batch, seq, N]`` in the model's dtype, ``dt`` ``[batch, seq,
    D]`` (positive: after its softplus; float32), ``a`` ``[D, N]`` (negative) and
    ``d_skip`` ``[D]``; any ``seq``.
    Returns ``u``'s shape and dtype; differentiable in all six. ``interpret``:
    ``ops.resolve_interpret``'s."""
    return _scan(u, dt, a, b, c, d_skip, resolve_interpret(interpret))
