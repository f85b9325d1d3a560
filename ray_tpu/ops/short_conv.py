"""``SiLU(causal depthwise convolution over time)``, the short convolution
in front of a linear-attention layer's q, k and v (Gated DeltaNet, Mamba),
and the same convolution with NO activation, the one inside a gated
short-convolution mixer (LFM2: ``activation=None``): two Pallas TPU
kernels, forward and a hand-written backward, each ONE pass over its
operands.

With ``x`` ``[batch, seq, channels]`` and one filter ``filters[:, c]`` of
``taps`` weights a channel, the LAST tap on the current token (a Conv1d
padded on the left), and where a ``bias`` ``[channels]`` is given (Mamba-2's
``use_conv_bias``) that bias ahead of the activation::

    pre[t] = sum_j filters[j] x[t - (taps - 1 - j)] (+ bias)   (x[t < 0] = 0)
    y[t]   = pre[t] sigmoid(pre[t])                       (activation "silu")
    y[t]   = pre[t]                                       (activation None)

float32 arithmetic, the taps summed oldest first, one rounding to
``x.dtype`` at the end: what ``models/transformer.py::_short_conv`` computes
in XLA, which stays as this module's oracle and the
``attention="reference"`` path. There the float32 copy of the padded
input, its four row-shifted reads (sublane-misaligned), the sum and the
SiLU are HBM passes, jax's transpose adds as many, and full remat runs the
forward again: 80.3 ms a step of the Olmo-Hybrid cell for 9.7 ms of bytes
(``PERF.md`` section 6, PR 35).

* ``_short_conv_forward``: grid (batch, channel blocks, sequence blocks),
  every axis independent. A step reads a ``[rows, lanes]`` block of ``x``
  and, through a second BlockSpec on the same array, the ``_HALO_BLOCK``
  rows before it (zeros at the sequence's start: a batch row never sees
  the one before). Both go to a float32 VMEM scratch once; the shifted
  reads are sublane rotations of that (``pltpu.roll``), strip by strip so
  the sums stay in vector registers.
* ``_short_conv_backward``: the same walk with the rows AFTER the block
  too. Nothing is kept from the forward but ``x`` and ``filters``: ``pre``
  is recomputed for the block and ``_HALO`` rows after it,
  ``g = dy SiLU'(pre)`` (``g = dy`` with no activation, and ``pre`` is not
  computed), ``dx[t] = sum_j filters[j] g[t + taps - 1 - j]``
  (``g`` past the sequence's end is zero), and ``dfilters[j] = sum_t g[t]
  x[t - (taps - 1 - j)]`` accumulates in float32 in an output block that
  stays resident across the sequence axis (eight partial rows a tap,
  folded with the batch outside). The bias rides in the filters' block, the
  row after the last tap (``_padded``), and its gradient ``sum_t g[t]`` is one
  more group of partial rows: a call without one traces the kernels it
  traced before there was one (tests/test_ssd.py holds their Mosaic modules
  to the ones recorded then).

Channels are independent, so a last channel block that overhangs the array
(2880 is 22.5 lane tiles) needs no mask: what it computes from the overhang
stays in lanes that are never written back. A sequence the row block does
not divide is masked in the backward (the forward is causal: rows past the
end only reach rows past the end).

On non-TPU backends the same kernels run in interpreter mode
(ops.resolve_interpret), so tests exercise the code the TPU compiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_tpu.ops import resolve_interpret

# Rows a block takes of its neighbour: one float32 sublane tile, so
# ``taps - 1`` may be at most that.
_HALO = 8
# Rows of the BlockSpec that fetches them: one sublane tile of bfloat16.
_HALO_BLOCK = 16
# Rows of a block the kernels take through the vector registers at once.
_STRIP = 64


def _blocks(seq: int, channels: int, dtype) -> tuple[int, int]:
    """(rows, lanes) of a grid step's block of ``x`` from what the call is
    given. Lanes: the whole width up to four lane tiles, else three tiles
    (5760 = 15 blocks, 2880 = 7.5: half a tile of overhang in 23); a strip
    of three tiles is 24 vector registers an array, and the backward, which
    holds a dozen, is slower with five (1.59 ms for 1.34 at ``[16384,
    5760]``) as with two or one (1.44, 1.66: my chip runs, PR 35). Rows:
    whole strips, 2 KiB of one channel (1024 in bfloat16: a grid step costs
    0.46 us, and 512 rows read 0.84 ms forward for 0.73); the backward's
    two float32 scratches and its three double-buffered blocks then take
    7.6 MiB of the 16 MiB of scoped VMEM in bfloat16 and 6.0 in float32
    (2048 rows ran too, 1.30 ms backward for 1.31, at 15 MiB)."""
    lanes = channels if channels <= 512 else 384
    rows = min(2048 // jnp.dtype(dtype).itemsize, -(-seq // _STRIP) * _STRIP)
    return rows, lanes


def _shifted(window, taps):
    """``window`` moved down by 0 .. taps - 1 rows (row ``i`` of entry ``d``
    is row ``i - d`` of ``window``; its first ``d`` rows wrap around)."""
    from jax.experimental.pallas import tpu as pltpu

    return [window] + [pltpu.roll(window, d, axis=0) for d in range(1, taps)]


def _conv(shifted, filters, keep):
    """``pre`` of the window's rows ``keep`` (a slice), taps summed oldest
    first as the oracle sums them."""
    taps = len(shifted)
    pre = None
    for j in range(taps):
        term = shifted[taps - 1 - j][keep] * filters[j:j + 1]
        pre = term if pre is None else pre + term
    return pre


def _forward_kernel(before_ref, x_ref, filters_ref, y_ref, xe, *, taps, activation, bias=False):
    """``xe``: float32 scratch ``[_HALO + rows, lanes]``, the block under
    the last rows of the one before it. With ``bias`` row ``taps`` of the
    filters' block is the bias."""
    rows = x_ref.shape[1]
    before = before_ref[0].astype(jnp.float32)[_HALO_BLOCK - _HALO:]
    xe[:_HALO] = jnp.where(pl.program_id(2) == 0, 0.0, before)
    xe[_HALO:] = x_ref[0].astype(jnp.float32)
    filters = filters_ref[...]

    def one_strip(i, carry):
        start = pl.multiple_of(i * _STRIP, _STRIP)
        shifted = _shifted(xe[pl.ds(start, _HALO + _STRIP)], taps)
        pre = _conv(shifted, filters, slice(_HALO, None))
        if bias:
            pre = pre + filters[taps:taps + 1]
        y = pre * jax.nn.sigmoid(pre) if activation == "silu" else pre
        y_ref[0, pl.ds(start, _STRIP)] = y.astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows // _STRIP, one_strip, None)


def _fold(x):
    """``[8 n, lanes]`` to the sum of its n sublane tiles ``[8, lanes]``:
    vector adds, no reduction across sublanes."""
    tiles = [x[i:i + _HALO] for i in range(0, x.shape[0], _HALO)]
    while len(tiles) > 1:
        tiles = [a + b for a, b in zip(tiles[::2], tiles[1::2])] + tiles[len(tiles) & ~1:]
    return tiles[0]


def _backward_kernel(before_ref, x_ref, after_ref, dy_ref, dy_after_ref, filters_ref,
                     dx_ref, dfilters_ref, xe, dye, *, taps, seq, activation, bias=False):
    """``xe``: float32 scratch ``[_HALO + rows + _HALO, lanes]``, the block
    between its neighbours' rows; ``dye``: ``[rows + _HALO, lanes]``, the
    block of ``dy`` over the rows after it. Rows outside the sequence are
    zero in both. With ``bias`` (row ``taps`` of the filters' block) the
    partial sums hold one group more, the bias's own: ``sum_t g[t]``."""
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    rows = x_ref.shape[1]
    step = pl.program_id(2)

    def inside(count, first):
        row = jax.lax.broadcasted_iota(jnp.int32, (count, 1), 0)
        return step * rows + first + row < seq

    before = before_ref[0].astype(f32)[_HALO_BLOCK - _HALO:]
    xe[:_HALO] = jnp.where(step == 0, 0.0, before)
    x, dy = x_ref[0].astype(f32), dy_ref[0].astype(f32)
    if seq % rows:                      # the last block overhangs the sequence
        x, dy = (jnp.where(inside(rows, 0), value, 0.0) for value in (x, dy))
    xe[_HALO:_HALO + rows] = x
    dye[:rows] = dy
    after = inside(_HALO, rows)
    xe[_HALO + rows:] = jnp.where(after, after_ref[0].astype(f32)[:_HALO], 0.0)
    dye[rows:] = jnp.where(after, dy_after_ref[0].astype(f32)[:_HALO], 0.0)
    filters = filters_ref[...]

    def one_strip(i, sums):
        start = pl.multiple_of(i * _STRIP, _STRIP)
        # rows start - _HALO .. start + _STRIP + _HALO of the block
        shifted = _shifted(xe[pl.ds(start, _STRIP + 2 * _HALO)], taps)
        g = dye[pl.ds(start, _STRIP + _HALO)]
        if activation == "silu":
            pre = _conv(shifted, filters, slice(_HALO, None))
            if bias:
                pre = pre + filters[taps:taps + 1]
            sig = jax.nn.sigmoid(pre)
            g = g * (sig * (1.0 + pre * (1.0 - sig)))
        dx = None
        for j in range(taps):
            ahead = taps - 1 - j
            moved = g if ahead == 0 else pltpu.roll(g, _STRIP + _HALO - ahead, axis=0)
            term = moved[:_STRIP] * filters[j:j + 1]
            dx = term if dx is None else dx + term
        dx_ref[0, pl.ds(start, _STRIP)] = dx.astype(dx_ref.dtype)
        own = g[:_STRIP]
        of_taps = tuple(
            sums[j] + _fold(own * shifted[taps - 1 - j][_HALO:_HALO + _STRIP])
            for j in range(taps)
        )
        return of_taps + ((sums[taps] + _fold(own),) if bias else ())

    zeros = jnp.zeros((_HALO, x_ref.shape[2]), f32)
    sums = jax.lax.fori_loop(0, rows // _STRIP, one_strip, (zeros,) * (taps + bias))

    @pl.when(step == 0)
    def _start():
        dfilters_ref[...] = jnp.zeros_like(dfilters_ref)

    dfilters_ref[0] += jnp.concatenate(sums, axis=0)


def _layout(x, filters):
    """(grid, the spec of a block of ``x``, of the halo block before it, of
    the one after it, of the padded filters) of both kernels."""
    batch, seq, channels = x.shape
    rows, lanes = _blocks(seq, channels, x.dtype)
    per_block = rows // _HALO_BLOCK
    last_halo = pl.cdiv(seq, _HALO_BLOCK) - 1
    grid = (batch, pl.cdiv(channels, lanes), pl.cdiv(seq, rows))
    block = pl.BlockSpec((1, rows, lanes), lambda b, c, s: (b, s, c))
    before = pl.BlockSpec(
        (1, _HALO_BLOCK, lanes), lambda b, c, s: (b, jnp.maximum(s * per_block - 1, 0), c)
    )
    after = pl.BlockSpec(
        (1, _HALO_BLOCK, lanes),
        lambda b, c, s: (b, jnp.minimum((s + 1) * per_block, last_halo), c),
    )
    taps = pl.BlockSpec((filters.shape[0], lanes), lambda b, c, s: (0, c))
    return grid, block, before, after, taps


def _padded(filters, bias=None):
    """float32 ``[_HALO, channels]``: the taps over zero rows, the first of
    which holds the ``bias`` where there is one."""
    taps = filters.shape[0]
    if taps - 1 > _HALO or (bias is not None and taps >= _HALO):
        raise NotImplementedError(
            f"{taps} taps: a block reads {_HALO} rows of its neighbour, and a bias takes the "
            "filters' next row"
        )
    filters = filters.astype(jnp.float32)
    if bias is not None:
        filters = jnp.concatenate([filters, bias.astype(jnp.float32)[None]])
    return jnp.pad(filters, ((0, _HALO - filters.shape[0]), (0, 0)))


@functools.partial(jax.jit, static_argnames=("interpret", "activation"))
def _short_conv_forward(x, filters, bias=None, *, interpret, activation="silu"):
    from jax.experimental.pallas import tpu as pltpu

    padded = _padded(filters, bias)
    grid, block, before, _, taps = _layout(x, padded)
    _, rows, lanes = block.block_shape
    return pl.pallas_call(
        functools.partial(
            _forward_kernel, taps=filters.shape[0], activation=activation, bias=bias is not None
        ),
        grid=grid,
        in_specs=[before, block, taps],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((_HALO + rows, lanes), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
    )(x, x, padded)


@functools.partial(jax.jit, static_argnames=("interpret", "activation"))
def _short_conv_backward(x, filters, dy, bias=None, *, interpret, activation="silu"):
    """``dx`` in ``x``'s dtype and ``dfilters`` in float32 (with a ``bias``,
    ``(dx, dfilters, dbias)``)."""
    from jax.experimental.pallas import tpu as pltpu

    batch, seq, channels = x.shape
    taps = filters.shape[0]
    padded = _padded(filters, bias)
    grid, block, before, after, taps_spec = _layout(x, padded)
    _, rows, lanes = block.block_shape
    sums = taps + (bias is not None)
    dx, partial = pl.pallas_call(
        functools.partial(
            _backward_kernel, taps=taps, seq=seq, activation=activation, bias=bias is not None
        ),
        grid=grid,
        in_specs=[before, block, after, block, after, taps_spec],
        out_specs=[block, pl.BlockSpec((1, sums * _HALO, lanes), lambda b, c, s: (b, 0, c))],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((batch, sums * _HALO, channels), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows + 2 * _HALO, lanes), jnp.float32),
            pltpu.VMEM((rows + _HALO, lanes), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(x, x, x, dy, dy, padded)
    sums = partial.reshape(batch, sums, _HALO, channels).sum(axis=(0, 2))
    return (dx, sums) if bias is None else (dx, sums[:taps], sums[taps])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _vjp(x, filters, bias, interpret, activation):
    return _short_conv_forward(x, filters, bias, interpret=interpret, activation=activation)


def _vjp_fwd(x, filters, bias, interpret, activation):
    y = _short_conv_forward(x, filters, bias, interpret=interpret, activation=activation)
    return y, (x, filters, bias)


def _vjp_bwd(interpret, activation, kept, dy):
    x, filters, bias = kept
    dx, dfilters, *dbias = _short_conv_backward(
        x, filters, dy, bias, interpret=interpret, activation=activation
    )
    dbias = dbias[0].astype(bias.dtype) if dbias else None
    return dx, dfilters.astype(filters.dtype), dbias


_vjp.defvjp(_vjp_fwd, _vjp_bwd)


def short_conv(x, filters, bias=None, *, activation: str | None = "silu",
               interpret: bool | None = None):
    """``SiLU(conv(x))`` of the module docstring, or with ``activation=None``
    ``conv(x)`` alone; with ``bias`` ``[channels]`` that is added before the
    activation. ``x``: [batch, seq, channels]; ``filters``: [taps, channels].
    Returns ``x``'s shape and dtype; differentiable in all it is given."""
    if activation not in ("silu", None):
        raise ValueError(f"unknown activation {activation!r}: 'silu' or None")
    return _vjp(x, filters, bias, resolve_interpret(interpret), activation)
