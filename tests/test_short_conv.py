"""The short-convolution kernels (``ray_tpu/ops/short_conv.py``) against
their oracle, ``models/transformer.py::_short_conv`` and ``jax.grad`` of it,
on the CPU, where ``ops.resolve_interpret`` runs the kernels' own code in
the interpreter.

Tolerances, each of the largest value compared. float32 inputs: 2e-6 for
the value and ``dx`` (both sides compute in float32; the sigmoid's last bit
and the order of ``dx``'s four terms are what is left), 1e-5 for
``dfilters`` (a sum over batch x seq positions taken in another order).
bfloat16 inputs: the value and ``dx`` are rounded once from float32 on both
sides, so they differ by at most one bfloat16 step (2^-8 of the value)
where the float32 results straddle a rounding boundary. A dropped or
reversed tap, a row leaked from the neighbouring block or batch row, or a
missing mask at the sequence's end is off by 1e-1 or more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.transformer import _short_conv
from ray_tpu.ops import short_conv as SC
from ray_tpu.ops.short_conv import short_conv


def _inputs(batch, seq, channels, taps, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], (batch, seq, channels), jnp.float32).astype(dtype)
    filters = jax.random.uniform(keys[1], (taps, channels), jnp.float32, -0.5, 0.5)
    dy = jax.random.normal(keys[2], (batch, seq, channels), jnp.float32).astype(dtype)
    return x, filters, dy


def _value_and_grads(conv, x, filters, dy):
    y, back = jax.vjp(conv, x, filters)
    return (y, *back(dy))


def _close(got, want, tol, what):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, what
    worst = np.abs(got - want).max() / np.abs(want).max()
    assert worst <= tol, f"{what}: {worst:.3g} of the largest value, limit {tol:.3g}"


# Sequences against the row block ``_blocks`` picks (512 rows in float32,
# 1024 in bfloat16, or the sequence rounded up to whole strips of 64): 7, 40
# and 200 lie inside one block that overhangs them, 1024 is whole blocks in
# either dtype, 1040 and 1100 leave 16 and 76 rows to a last block, 2200 has
# a block with a neighbour on both sides in bfloat16 too.
@pytest.mark.parametrize("batch,seq,channels,taps,dtype", [
    (1, 1024, 128, 4, "float32"),     # the row block divides the sequence
    (1, 1100, 128, 4, "float32"),     # ... and does not: the last block is masked
    (3, 1040, 96, 4, "float32"),      # under a lane tile, three batch rows
    (1, 1040, 360, 4, "bfloat16"),    # 2.8 lane tiles in one block
    (3, 200, 360, 4, "bfloat16"),
    (1, 40, 96, 2, "float32"),
    (3, 1100, 128, 2, "bfloat16"),
    (1, 1024, 640, 4, "bfloat16"),    # two channel blocks of 384, the last overhangs
    (3, 200, 640, 2, "float32"),
    (1, 2200, 96, 4, "bfloat16"),
    (3, 40, 128, 4, "bfloat16"),
    (1, 7, 128, 4, "float32"),        # shorter than a sublane tile
])
def test_kernels_against_the_oracle(batch, seq, channels, taps, dtype):
    """Value, ``dx`` and ``dfilters``; and with three batch rows, that
    nothing leaks across them: every position of a row, its first ``taps -
    1`` among them, is to the bit what a call on that row alone gives,
    forward and backward."""
    dtype = jnp.dtype(dtype)
    x, filters, dy = _inputs(batch, seq, channels, taps, dtype)
    rows, lanes = SC._blocks(seq, channels, dtype)
    assert rows % SC._STRIP == 0 and (lanes == channels or lanes % 128 == 0)
    got = _value_and_grads(short_conv, x, filters, dy)
    want = _value_and_grads(_short_conv, x, filters, dy)
    assert [g.dtype for g in got] == [dtype, dtype, jnp.float32]
    step = 2.0 ** -7 if dtype == jnp.bfloat16 else 2e-6
    _close(got[0], want[0], step, "value")
    _close(got[1], want[1], step, "dx")
    _close(got[2], want[2], 1e-5, "dfilters")
    for row in range(batch if batch > 1 else 0):
        alone = _value_and_grads(short_conv, x[row:row + 1], filters, dy[row:row + 1])
        for whole, single in zip(got[:2], alone[:2]):
            np.testing.assert_array_equal(
                np.asarray(whole[row], np.float32), np.asarray(single[0], np.float32)
            )


@pytest.mark.parametrize("batch,seq,channels,taps,dtype", [
    (1, 1024, 256, 3, "float32"),     # a conv mixer's 3 taps; the row block divides the sequence
    (2, 1100, 256, 3, "float32"),     # ... and does not: the last block is masked
    (1, 1100, 640, 3, "bfloat16"),    # two channel blocks, the last overhangs
    (3, 200, 128, 4, "bfloat16"),
    (1, 1040, 96, 4, "float32"),
    (1, 7, 128, 3, "float32"),        # shorter than a sublane tile
])
def test_kernels_with_no_activation_against_the_oracle(batch, seq, channels, taps, dtype):
    """``activation=None`` (a gated short-convolution mixer's): value,
    ``dx`` and ``dfilters`` against XLA's ``_short_conv`` with the
    activation off, which is linear, and NOT what the SiLU kernels give."""
    dtype = jnp.dtype(dtype)
    x, filters, dy = _inputs(batch, seq, channels, taps, dtype)
    plain = lambda x, filters: short_conv(x, filters, activation=None)
    oracle = lambda x, filters: _short_conv(x, filters, activation=None)
    got = _value_and_grads(plain, x, filters, dy)
    want = _value_and_grads(oracle, x, filters, dy)
    assert [g.dtype for g in got] == [dtype, dtype, jnp.float32]
    step = 2.0 ** -7 if dtype == jnp.bfloat16 else 2e-6
    _close(got[0], want[0], step, "value")
    _close(got[1], want[1], step, "dx")
    _close(got[2], want[2], 1e-5, "dfilters")
    # linear: twice the input, twice the output (float32: exactly)
    if dtype == jnp.float32:
        np.testing.assert_array_equal(np.asarray(plain(2 * x, filters)), 2 * np.asarray(got[0]))
    with_silu = short_conv(x, filters)
    assert np.abs(np.asarray(with_silu, np.float32) - np.asarray(got[0], np.float32)).max() > 0.1
    with pytest.raises(ValueError, match="unknown activation"):
        short_conv(x, filters, activation="gelu")


def _summed_in_bfloat16(x, filters):
    """The convolution with every product and every partial sum rounded to
    bfloat16: the nearest precision below the kernels' float32."""
    taps, seq = filters.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0))).astype(jnp.bfloat16)
    out = None
    for j in range(taps):
        term = padded[:, j:j + seq] * filters[j].astype(jnp.bfloat16)
        out = term if out is None else out + term
    return out.astype(x.dtype)


@pytest.mark.parametrize("taps", [3, 4])
def test_taps_summed_in_bfloat16_fail_the_float32_limit(taps):
    """The limit that holds the kernels to their oracle on float32 operands
    (2e-6 of the largest value) is one a bfloat16 sum does NOT pass, by three
    orders: what ``benchmarks/reference/conv_moe_decoder.py::check_conv``
    rests on at the cell's size."""
    x, filters, _ = _inputs(1, 1100, 256, taps, jnp.float32, seed=2)
    want = np.asarray(_short_conv(x, filters, activation=None), np.float64)
    kernel = np.asarray(short_conv(x, filters, activation=None), np.float64)
    rounded = np.asarray(_summed_in_bfloat16(x, filters), np.float64)
    worst = lambda got: np.abs(got - want).max() / np.abs(want).max()
    assert worst(kernel) <= 2e-6
    assert worst(rounded) > 1e-3
    with pytest.raises(AssertionError, match="limit"):
        _close(rounded, want, 2e-6, "value")


def test_checkpointed_gradients_are_the_plain_ones():
    """Nothing but ``x`` and ``filters`` goes from the forward to the
    backward, so a ``jax.checkpoint`` that saves nothing hands the backward
    kernel what the plain gradient hands it: equal to the bit."""
    x, filters, dy = _inputs(2, 1100, 360, 4, jnp.bfloat16, seed=1)

    def loss(conv):
        return lambda x, filters: jnp.sum(conv(x, filters).astype(jnp.float32) * dy)

    plain = jax.grad(loss(short_conv), argnums=(0, 1))(x, filters)
    kept = jax.grad(
        loss(jax.checkpoint(short_conv, policy=jax.checkpoint_policies.nothing_saveable)),
        argnums=(0, 1),
    )(x, filters)
    for a, b in zip(plain, kept):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_too_many_taps_are_refused_by_name():
    x, filters, _ = _inputs(1, 64, 128, SC._HALO + 2, jnp.float32)
    with pytest.raises(NotImplementedError, match="taps"):
        short_conv(x, filters)
