"""The linear mixers' kernels: the delta rule's scan and its preparation, the
same under a decay per channel, the short convolution (alone, at the conv
mixer's size, with a bias beside the state-space scan) and rmsnorm.

Compiled for a TPU v5e that is described, not attached: nothing executes,
so these say what the chip's compiler accepts and nothing about results or
times. One of the ``test_chip_compile_*`` files, a kernel family each:
``tests/test_chip_compile_flash.py`` says why and how.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.rmsnorm import rmsnorm

from model_helpers import custom_calls, mosaic_calls


# What the rule's value-and-gradient program may hold beside its arguments
# and results at the cell's size: two heads a call need 1.41 GiB. Under PR
# 33's ``lax.map`` they needed 1.19 (three 1.32, six 1.79, all thirty at once
# 4.69: compiles for a described v5e, PR 33); since PR 50 a group's gradients
# are written where its inputs were, and in THIS program the inputs are the
# program's arguments, which XLA copies before the loop may write them: 0.22
# GiB of such copies counted as temporaries. In a step the inputs are the
# step's own temporaries and nothing is copied: Olmo-Hybrid's whole step went
# from 13.19 to 12.12 GiB (compiles for a described v5e, PR 50).
DELTA_RULE_TEMPORARIES = int(1.45 * 2**30)


def _delta_rule_shapes(one_chip, heads=30, seq=16384):
    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return (
        shape(1, heads, seq, 96), shape(1, heads, seq, 96),
        shape(1, heads, seq, 192, dtype=jnp.bfloat16),
        shape(1, heads, seq), shape(1, heads, seq),
    )


def test_delta_rule_kernels_compile_for_v5e(one_chip):
    """The gated delta rule at the Olmo-Hybrid cell's size, ``[1, 30, 16384,
    96 | 192]``: float32 q / k and gates, bfloat16 v, chunks of 64, eight
    chunks (512 rows) a grid step, two heads a call. The forward is the
    preparation kernel and the scan kernel; a gradient runs both again (the
    preparation hands over ``T``, the scan the chunk-start states), then
    the scan's backward kernel, whose eight float32 operands and six
    results of 512 rows fit the scoped VMEM, and the preparation's."""
    from ray_tpu.ops import gated_delta_rule as G

    assert G._heads_per_call(30, 16384) == 2 and G._per_step(256, 64) == 8
    rule = functools.partial(G.gated_delta_rule, interpret=False)
    shapes = _delta_rule_shapes(one_chip)
    assert jax.eval_shape(rule, *shapes).shape == (1, 30, 16384, 192)
    text = jax.jit(rule).lower(*shapes).compile().as_text()
    assert mosaic_calls(text) == ["_delta_prepare_forward", "_delta_rule_forward"]

    def grads(*args):
        loss = lambda *a: jnp.sum(rule(*a).astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)

    assert sorted(mosaic_calls(jax.jit(grads).lower(*shapes).compile().as_text())) == [
        "_delta_prepare_backward", "_delta_prepare_forward", "_delta_prepare_forward",
        "_delta_rule_backward", "_delta_rule_forward", "_delta_rule_forward",
    ]
    assert [g.dtype for g in jax.eval_shape(grads, *shapes)] == [
        jnp.float32, jnp.float32, jnp.bfloat16, jnp.float32, jnp.float32,
    ]


def test_delta_rule_preparation_kernels_compile_for_v5e(one_chip):
    """The chunk preparation's two kernels alone at the cell's ``[30,
    16384, 96 | 192]``, every head in one call: two chunks of 64 to a
    128-row product, four products a grid step, ``T`` handed from the
    forward call to the backward as ``[30, 16384, 128]``; and the rule's
    value-and-gradient program at two heads a call holds all four kernels
    by name with its temporaries under the figure ``_TOKENS_PER_CALL`` was
    chosen for."""
    from ray_tpu.ops import gated_delta_rule as G

    assert G._product_rows(16384, 64) == 128 and G._together(64, 8) == 2
    q, k, v, log_alpha, beta = (
        jax.ShapeDtypeStruct(x.shape[1:], x.dtype, sharding=one_chip)
        for x in _delta_rule_shapes(one_chip)
    )
    gates = jax.ShapeDtypeStruct((30, 128, 2, 128), jnp.float32, sharding=one_chip)
    assert jax.eval_shape(functools.partial(G._gates, chunk=64), log_alpha, beta).shape == gates.shape
    forward = functools.partial(G._delta_prepare_forward, chunk=64, interpret=False, inverse="write")
    assert custom_calls(forward, q, k, v, gates) == 1
    *operands, inverse = (
        jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
        for x in jax.eval_shape(forward, q, k, v, gates)
    )
    assert [x.shape[-1] for x in operands] == [96, 192, 96, 64, 96, 1]
    assert inverse.shape == (30, 8192, 128)                      # T: its diagonal blocks
    # the backward's call of the same kernel, from the kept T
    read = functools.partial(G._delta_prepare_forward, chunk=64, interpret=False, inverse="read")
    assert custom_calls(lambda *a: read(*a[:-1], None, a[-1]), q, k, v, gates, inverse) == 1
    backward = functools.partial(G._delta_prepare_backward, chunk=64, interpret=False)
    assert custom_calls(backward, q, k, v, gates, inverse, *operands) == 1
    got = jax.eval_shape(backward, q, k, v, gates, inverse, *operands)
    assert [(x.shape, x.dtype) for x in got] == [
        (x.shape, x.dtype) for x in (q, k, v, gates)
    ]

    def value_and_grads(*args):
        loss = lambda *a: jnp.sum(
            G.gated_delta_rule(*a, interpret=False).astype(jnp.float32) ** 2
        )
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(*args)

    compiled = jax.jit(value_and_grads).lower(*_delta_rule_shapes(one_chip)).compile()
    assert set(mosaic_calls(compiled.as_text())) == {
        "_delta_prepare_forward", "_delta_prepare_backward",
        "_delta_rule_forward", "_delta_rule_backward",
    }
    # two heads a call: what the rule holds beside its inputs and gradients
    assert compiled.memory_analysis().temp_size_in_bytes < DELTA_RULE_TEMPORARIES


# ... and under a decay per channel at ``[1, 32, 16384, 128 | 128]``, two heads
# a call: 0.286 GiB and 4.5 MiB of generated code, where XLA's preparation
# held 0.384 and 18.9 (compiles for a described v5e, PR 41). Since PR 52 the
# forward keeps ``T``'s diagonal blocks for the backward, ``[32, 8192, 128]``
# float32, 128 MiB: 0.500 GiB where the parent read 0.266 (compiles for a
# described v5e, PR 52; the compiler's own ``peak_memory_in_bytes`` grows by
# the 128 MiB, its packing by 240).
CHANNEL_RULE_TEMPORARIES = int(0.55 * 2**30)


@pytest.mark.parametrize("bound", [-5.0, None], ids=["bounded", "halving"])
def test_channel_decay_kernels_compile_for_v5e(one_chip, bound):
    """Both forms of the channel preparation (``log_alpha_bound=-5``: a
    sub-block split at its first row, Ling's; None: by halving, what an
    unbounded gate needs: PR 48), each under the same jitted names.

    The delta rule under a decay per key CHANNEL at the Ling cell's size,
    ``[1, 32, 16384, 128 | 128]`` with ``log_alpha`` ``[1, 32, 16384, 128]``:
    the preparation is a Mosaic pair of its own since PR 41 (sub-blocks of 16
    rows on VMEM values, no ``[64, 64, 128]`` array and no decayed copy of K
    in HBM), both kernels at ``[2, 16384, 128 | 128]`` inside the scoped VMEM
    at the 512 rows a grid step the scalar pair takes; the scan kernels are
    the scalar rule's two, handed ``gamma`` as a ``[.., 1, 128]`` row a chunk
    and turning it down the state's rows in VMEM; two heads a call, so what
    the rule holds beside its inputs stays under half a GiB."""
    from ray_tpu.ops import gated_delta_rule as G

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    shapes = (
        shape(1, 32, 16384, 128), shape(1, 32, 16384, 128),
        shape(1, 32, 16384, 128, dtype=jnp.bfloat16),
        shape(1, 32, 16384, 128), shape(1, 32, 16384),
    )
    assert G._heads_per_call(32, 16384) == 2 and G._SUB_CHUNK == 16
    assert G._per_step(256, 64) == 8 and G._product_rows(16384, 64) == 128

    # the two preparation kernels alone, two heads a call: each ONE Mosaic
    # call that fits (a kernel that asks for more VMEM is refused here)
    q, k, v, log_alpha, beta = (shape(*x.shape[1:], dtype=x.dtype) for x in shapes)
    lanes = shape(*jax.eval_shape(functools.partial(G._beta_lanes, chunk=64), beta).shape)
    assert (q.shape, lanes.shape) == ((32, 16384, 128), (32, 128, 1, 128))
    two = lambda x: shape(2, *x.shape[1:], dtype=x.dtype)
    inputs = tuple(two(x) for x in (q, k, v, log_alpha, lanes))
    bounded = G.carries_bound(bound)
    assert bounded == (bound is not None)
    forward = functools.partial(
        G._channel_prepare_forward, chunk=64, interpret=False, inverse="write", bounded=bounded
    )
    assert custom_calls(forward, *inputs) == 1
    *operands, inverse = (two(x) for x in jax.eval_shape(forward, *inputs))
    assert [x.shape[1:] for x in operands] == [
        (16384, 128), (16384, 128), (16384, 128), (16384, 64), (16384, 128), (256, 1, 128),
    ]
    assert inverse.shape == (2, 8192, 128)                       # T: two chunks' blocks a row
    # the backward's call of the same kernel, from the kept T
    read = functools.partial(
        G._channel_prepare_forward, chunk=64, interpret=False, inverse="read", bounded=bounded
    )
    assert custom_calls(lambda *a: read(*a[:-1], None, a[-1]), *inputs, inverse) == 1
    backward = functools.partial(
        G._channel_prepare_backward, chunk=64, interpret=False, bounded=bounded
    )
    assert custom_calls(backward, *inputs, inverse, *operands) == 1
    got = jax.eval_shape(backward, *inputs, inverse, *operands)
    assert [(x.shape, x.dtype) for x in got] == [(x.shape, x.dtype) for x in inputs]

    rule = functools.partial(G.gated_delta_rule, interpret=False, log_alpha_bound=bound)
    assert jax.eval_shape(rule, *shapes).shape == (1, 32, 16384, 128)
    text = jax.jit(rule).lower(*shapes).compile().as_text()
    assert mosaic_calls(text) == ["_channel_prepare_forward", "_delta_rule_forward"]
    assert "f32[2,256,1,128]" in text                            # gamma, a row a chunk

    def value_and_grads(*args):
        loss = lambda *a: jnp.sum(rule(*a).astype(jnp.float32) ** 2)
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(*args)

    compiled = jax.jit(value_and_grads).lower(*shapes).compile()
    assert sorted(mosaic_calls(compiled.as_text())) == [
        "_channel_prepare_backward", "_channel_prepare_forward", "_channel_prepare_forward",
        "_delta_rule_backward", "_delta_rule_forward", "_delta_rule_forward",
    ]
    assert [g.dtype for g in jax.eval_shape(value_and_grads, *shapes)[1]] == [
        jnp.float32, jnp.float32, jnp.bfloat16, jnp.float32, jnp.float32,
    ]
    assert jax.eval_shape(value_and_grads, *shapes)[1][3].shape == (1, 32, 16384, 128)
    assert compiled.memory_analysis().temp_size_in_bytes < CHANNEL_RULE_TEMPORARIES
    # no [.., 64, 64, 128] intermediate (17 GB a layer at this size), and none
    # of XLA's four decayed copies of K a chunk (67 MB a call)
    assert "64,64,128]" not in compiled.as_text()
    assert "f32[2,256,4,64,128]" not in compiled.as_text()


@pytest.mark.parametrize("channels", [2880, 5760])
def test_short_conv_kernels_compile_for_v5e(one_chip, channels):
    """The linear mixers' convolutions at the Olmo-Hybrid cell's sizes,
    ``[1, 16384, 2880]`` (q and k: 22.5 lane tiles, the last channel block
    overhangs) and ``[1, 16384, 5760]`` (v) in bfloat16 with four taps, in
    the blocks ``_blocks`` picks: each kernel is one Mosaic call named after
    its jitted function, and a value-and-gradient program holds exactly the
    two (nothing but ``x`` and the filters is kept, so no forward runs for
    the gradient)."""
    from ray_tpu.ops import short_conv as SC

    x = jax.ShapeDtypeStruct((1, 16384, channels), jnp.bfloat16, sharding=one_chip)
    filters = jax.ShapeDtypeStruct((4, channels), jnp.float32, sharding=one_chip)
    assert SC._blocks(16384, channels, jnp.bfloat16) == (1024, 384)
    forward = functools.partial(SC._short_conv_forward, interpret=False)
    backward = functools.partial(SC._short_conv_backward, interpret=False)
    assert mosaic_calls(jax.jit(forward).lower(x, filters).compile().as_text()) == [
        "_short_conv_forward"
    ]
    assert mosaic_calls(jax.jit(backward).lower(x, filters, x).compile().as_text()) == [
        "_short_conv_backward"
    ]
    dx, dfilters = jax.eval_shape(backward, x, filters, x)
    assert (dx.shape, dx.dtype) == (x.shape, x.dtype)
    assert (dfilters.shape, dfilters.dtype) == (filters.shape, jnp.float32)

    def value_and_grads(x, filters):
        conv = functools.partial(SC.short_conv, interpret=False)
        loss = lambda *a: jnp.sum(conv(*a).astype(jnp.float32) ** 2)
        return jax.value_and_grad(loss, argnums=(0, 1))(x, filters)

    # under jvp / transpose jax wraps the names: jvp_jit__short_conv_forward__
    calls = mosaic_calls(jax.jit(value_and_grads).lower(x, filters).compile().as_text())
    assert len(calls) == 2
    assert sum("_short_conv_forward" in name for name in calls) == 1
    assert sum("_short_conv_backward" in name for name in calls) == 1


@pytest.mark.parametrize("activation", [None, "silu"])
def test_short_conv_kernels_compile_at_the_conv_mixers_size_for_v5e(one_chip, activation):
    """A gated short-convolution mixer's call at the LFM2 cell's size, ``[1,
    16384, 2048]`` in bfloat16 with THREE taps and no activation (and the
    SiLU form beside it at the same size): 16 lane tiles in blocks of 384 (the
    last overhangs by a third), each kernel one Mosaic call under its own
    name, two in a value-and-gradient program."""
    from ray_tpu.ops import short_conv as SC

    x = jax.ShapeDtypeStruct((1, 16384, 2048), jnp.bfloat16, sharding=one_chip)
    filters = jax.ShapeDtypeStruct((3, 2048), jnp.float32, sharding=one_chip)
    assert SC._blocks(16384, 2048, jnp.bfloat16) == (1024, 384)
    forward = functools.partial(SC._short_conv_forward, interpret=False, activation=activation)
    backward = functools.partial(SC._short_conv_backward, interpret=False, activation=activation)
    assert mosaic_calls(jax.jit(forward).lower(x, filters).compile().as_text()) == [
        "_short_conv_forward"
    ]
    assert mosaic_calls(jax.jit(backward).lower(x, filters, x).compile().as_text()) == [
        "_short_conv_backward"
    ]

    def value_and_grads(x, filters):
        conv = functools.partial(SC.short_conv, interpret=False, activation=activation)
        loss = lambda *a: jnp.sum(conv(*a).astype(jnp.float32) ** 2)
        return jax.value_and_grad(loss, argnums=(0, 1))(x, filters)

    calls = mosaic_calls(jax.jit(value_and_grads).lower(x, filters).compile().as_text())
    assert len(calls) == 2
    assert sum("_short_conv_forward" in name for name in calls) == 1
    assert sum("_short_conv_backward" in name for name in calls) == 1


def test_short_conv_with_a_bias_and_the_state_space_scan_compile_for_v5e(one_chip):
    """A Mamba-2 layer's two device programs at the Nemotron-3-Super cell's
    sizes. The convolution over ``xBC`` ``[1, 8192, 10240]`` with four taps and
    a BIAS (the filters' block carries it as its fifth row): the same two Mosaic
    calls under the same names, the backward handing back ``dbias`` too. The
    scan (``ops/ssd.py``: three Mosaic kernels under one custom VJP, a grid
    step a group's 16 heads for one chunk of 128, the state in a VMEM scratch)
    for 128 heads of 64 on a state of 128 with B / C in 8 groups: a gradient
    is the states pass and the backward kernel (the forward kernel too where
    the output is read), in bfloat16 as the step compiles it and in float32 as
    the benchmark's check does; its program holds no array that repeats B or C
    to the heads (``[.., 8192, 128, 128]``), no state a token, the chunk-start
    states as ONE float32 array, and under 1.5 GiB of temporaries beside its
    operands."""
    from ray_tpu.ops import short_conv as SC
    from ray_tpu.ops.ssd import ssd

    x = jax.ShapeDtypeStruct((1, 8192, 10240), jnp.bfloat16, sharding=one_chip)
    filters = jax.ShapeDtypeStruct((4, 10240), jnp.bfloat16, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((10240,), jnp.bfloat16, sharding=one_chip)

    def value_and_grads(x, filters, bias):
        conv = functools.partial(SC.short_conv, interpret=False)
        loss = lambda *a: jnp.sum(conv(*a).astype(jnp.float32) ** 2)
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(x, filters, bias)

    compiled = jax.jit(value_and_grads).lower(x, filters, bias).compile()
    calls = mosaic_calls(compiled.as_text())
    assert len(calls) == 2
    assert sum("_short_conv_forward" in name for name in calls) == 1
    assert sum("_short_conv_backward" in name for name in calls) == 1
    _, (dx, dfilters, dbias) = jax.eval_shape(value_and_grads, x, filters, bias)
    assert (dx.shape, dfilters.shape, dbias.shape) == (x.shape, filters.shape, bias.shape)

    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    operands = (
        shaped((1, 8192, 128, 64)), shaped((1, 8192, 128), jnp.float32), shaped((128,), jnp.float32),
        shaped((1, 8192, 8, 128)), shaped((1, 8192, 8, 128)), shaped((128,), jnp.float32),
    )
    scan = functools.partial(ssd, interpret=False)
    # the loss reads the output: the forward kernel stays in the gradient's program
    grads = jax.grad(lambda *a: jnp.sum(scan(*a).astype(jnp.float32) ** 2), argnums=tuple(range(6)))
    compiled = jax.jit(grads).lower(*operands).compile()
    text = compiled.as_text()
    assert mosaic_calls(text) == ["_ssd_forward", "_ssd_states", "_ssd_backward"]
    assert "8192,128,128]" not in text and "[1,8192,128,64,128]" not in text
    # the chunk-start states of the backward's first pass: 64 chunks x (128 heads x 64) rows of 128
    assert len(set(re.findall(r"f32\[1,64,8192,128\]", text))) == 1
    assert not re.search(r"f32\[1,(8192|4096|2048|1024|512|256|128),8192,128\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * 2**30
    assert [g.shape for g in jax.eval_shape(grads, *operands)] == [a.shape for a in operands]
    # float32, as the benchmark's check hands the scan its operands: Mosaic's fp32 products
    exact = tuple(jax.ShapeDtypeStruct(a.shape, jnp.float32, sharding=one_chip) for a in operands)
    text = jax.jit(grads).lower(*exact).compile().as_text()
    assert mosaic_calls(text) == ["_ssd_forward", "_ssd_states", "_ssd_backward"]


def test_rmsnorm_compiles_for_v5e(one_chip):
    x = jax.ShapeDtypeStruct((8192, 4096), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((4096,), jnp.bfloat16, sharding=one_chip)
    assert custom_calls(functools.partial(rmsnorm, interpret=False), x, w) == 1
