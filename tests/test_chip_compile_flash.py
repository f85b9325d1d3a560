"""The flash-attention kernels (forward, backward, a long sequence, a block
refused and a small one, two head dims, heads of 64, a window, a selection,
the walked bodies at the seven shapes they were read alone at and under the
two narrower windows, one of them a band call) and the index scorer's term at
the cells' shapes.

Compiled for a TPU v5e that is described, not attached
(``on-chip-measurement`` guide, section 2): the TPU's compiler is installed
wherever jax's TPU plugin is, so Mosaic refuses here what it would refuse on
the chip (a block not aligned to the tiling, too much VMEM) at real widths
and at no chip time. A compile that passes is not a chip run: nothing
executes, so these say nothing about results or times.

One file a kernel family (``test_chip_compile_flash``, ``_linear``,
``_linear_layout``, ``_gqa``, ``_experts``, ``_steps``), so that ``--dist
loadfile`` spreads them over its workers: each worker's process loads the
TPU's library for itself, which the tier-1 command allows with
``ALLOW_MULTIPLE_LIBTPU_LOAD=1``. The topology is described inside a fixture
(``conftest.py``: ``topo``, ``one_chip``) that skips when it cannot be, never
at import, in a ``skipif`` or in ``parametrize``; what the files share in
reading a compiled program is in ``model_helpers.py``.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.flash_attention import flash_attention

from model_helpers import custom_calls, mosaic_calls


def _qkv(one_chip, seq):
    return [
        jax.ShapeDtypeStruct((2, 32, seq, 128), jnp.bfloat16, sharding=one_chip)
    ] * 3


# interpret=False: jax.default_backend() is the CPU here, and the platform
# rule (ops.resolve_interpret) would pick the interpreter.
_flash = functools.partial(flash_attention, causal=True, interpret=False)


def _flash_grads(q, k, v, block=None):
    return jax.grad(
        lambda q, k, v: _flash(
            q, k, v, block_q=block, block_k=block
        ).astype(jnp.float32).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)


def test_flash_forward_compiles_for_v5e(one_chip):
    assert custom_calls(_flash, *_qkv(one_chip, 4096)) == 1


def test_flash_backward_compiles_for_v5e(one_chip):
    # forward (for the residuals) + dq + dkv
    assert custom_calls(_flash_grads, *_qkv(one_chip, 4096)) == 3


@pytest.mark.parametrize("dtype,head_dim,heads,block", [
    ("bfloat16", 128, 32, 1024),   # the 16k cell's call
    ("float32", 128, 4, 1024),     # 512-byte operand rows: the last that fit
    ("bfloat16", 256, 4, 1024),
    ("float32", 256, 4, 512),      # 1024-byte rows: _block_sizes falls back
])
def test_flash_long_sequence_compiles_for_v5e(one_chip, dtype, head_dim,
                                              heads, block):
    """seq 16384 takes the block shape _block_sizes picks for its head_dim
    and dtype (1024 x 1024 is a 4 MiB float32 score tile): it has to fit
    the chip's scoped VMEM, forward and backward."""
    from ray_tpu.ops.flash_attention import _block_sizes

    dtype = jnp.dtype(dtype)
    assert _block_sizes(16384, 16384, None, None, head_dim, dtype) == (
        block, block)
    shapes = [
        jax.ShapeDtypeStruct((1, heads, 16384, head_dim), dtype, sharding=one_chip)
    ] * 3
    assert custom_calls(_flash, *shapes) == 1
    assert custom_calls(_flash_grads, *shapes) == 3


def test_flash_block_too_large_for_vmem_is_refused(one_chip):
    """Why _block_sizes falls back at 1024-byte operand rows: asked for
    1024 x 1024 there, the backward does not fit the scoped VMEM."""
    shapes = [
        jax.ShapeDtypeStruct((1, 4, 16384, 256), jnp.float32, sharding=one_chip)
    ] * 3
    with pytest.raises(Exception, match="(?i)vmem"):
        custom_calls(functools.partial(_flash_grads, block=1024), *shapes)


def test_flash_small_block_length_compiles_for_v5e(one_chip):
    """seq 1000 is not a multiple of 512: asked for 512, _block_sizes halves
    down to 8, the smallest block the (8, 128) tiling accepts. Left to
    itself it takes the sequence as one block."""
    from ray_tpu.ops.flash_attention import _block_sizes

    assert _block_sizes(1000, 1000, 512, 512, 128, jnp.bfloat16) == (8, 8)
    assert _block_sizes(1000, 1000, None, None, 128, jnp.bfloat16) == (
        1000, 1000)
    for block in (512, None):
        grads = functools.partial(_flash_grads, block=block)
        assert custom_calls(grads, *_qkv(one_chip, 1000)) == 3


def test_flash_two_head_dims_compile_for_v5e(one_chip):
    """Latent attention's call at the Moonlight cell's size: q / k of 192
    (a lane tile and a half), v / out / dO of 128, one sequence of 8192
    and 16 heads, in the 1024 x 1024 blocks ``_block_sizes`` picks from the
    larger dim: forward, and forward + dq + dkv, fit the scoped VMEM."""
    from ray_tpu.ops.flash_attention import _block_sizes

    assert _block_sizes(8192, 8192, None, None, 192, jnp.bfloat16) == (1024, 1024)
    wide = jax.ShapeDtypeStruct((1, 16, 8192, 192), jnp.bfloat16, sharding=one_chip)
    narrow = jax.ShapeDtypeStruct((1, 16, 8192, 128), jnp.bfloat16, sharding=one_chip)
    out = jax.eval_shape(_flash, wide, wide, narrow)
    assert out.shape == narrow.shape
    assert custom_calls(_flash, wide, wide, narrow) == 1
    assert custom_calls(_flash_grads, wide, wide, narrow) == 3
    grads = jax.eval_shape(_flash_grads, wide, wide, narrow)
    assert [g.shape[-1] for g in grads] == [192, 192, 128]


def test_flash_at_head_size_64_compiles_for_v5e(one_chip):
    """The LFM2 cell's call, ``[1, 32, 16384, 64]`` in bfloat16: a last
    dimension of HALF a lane tile and a contraction that half-fills the MXU,
    in the 1024 x 1024 blocks ``_block_sizes`` picks: forward, and forward +
    dq + dkv, compile and fit the scoped VMEM; the head size is not padded
    (every gradient comes back 64 wide)."""
    from ray_tpu.ops.flash_attention import _block_sizes

    assert _block_sizes(16384, 16384, None, None, 64, jnp.bfloat16) == (1024, 1024)
    shapes = [jax.ShapeDtypeStruct((1, 32, 16384, 64), jnp.bfloat16, sharding=one_chip)] * 3
    assert custom_calls(_flash, *shapes) == 1
    text = jax.jit(_flash_grads).lower(*shapes).compile().as_text()
    calls = mosaic_calls(text)
    assert len(calls) == 3
    assert sum("_flash_forward" in name for name in calls) == 1
    assert sum("_flash_backward" in name for name in calls) == 2      # dq, and dk + dv
    assert [g.shape for g in jax.eval_shape(_flash_grads, *shapes)] == [(1, 32, 16384, 64)] * 3


def test_flash_under_a_window_compiles_for_v5e(one_chip):
    """The window layers' call of ``smallthinker-21b-a3b``, ``[1, 28, 16384,
    128]`` in bfloat16 under a window of 4096 keys: forward, and forward + dq
    + dkv, compile for the chip in the 1024 x 1024 blocks (the table's reads
    in the index maps and the second mask are scalar and vector code Mosaic
    has to take); the band is 70 of a head's 256 tiles, and the grid walks
    those 70 and no other step."""
    from ray_tpu.ops.flash_attention import _block_sizes, causal_tile_counts

    blocks = _block_sizes(16384, 16384, None, None, 128, jnp.bfloat16)
    counts = causal_tile_counts(16384, 16384, *blocks, 4096)
    assert counts["executed"] == counts["grid_steps"] == 70
    shapes = [jax.ShapeDtypeStruct((1, 28, 16384, 128), jnp.bfloat16, sharding=one_chip)] * 3
    windowed = functools.partial(_flash, window=4096)
    assert custom_calls(windowed, *shapes) == 1
    grads = jax.grad(
        lambda q, k, v: windowed(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2)
    )
    calls = mosaic_calls(jax.jit(grads).lower(*shapes).compile().as_text())
    assert len(calls) == 3
    assert sum("_flash_forward" in name for name in calls) == 1
    assert sum("_flash_backward" in name for name in calls) == 2      # dq, and dk + dv


def test_flash_under_a_selection_compiles_for_v5e(one_chip):
    """The sparse layers' call of ``keye-vl-2.0-30b-a3b``, q ``[1, 32, 16384,
    128]`` on K / V of 4 heads in bfloat16 under a selection that is DATA
    (int8 ``[1, 16384, 16384]``): forward with ``lse`` handed out, and forward
    + dq + dkv, compile for the chip in the 1024 x 1024 blocks
    ``_block_sizes`` keeps beside the selection's tile (an int8 tile of 1 MiB,
    double-buffered and widened to int32 in the kernel, fits the scoped VMEM
    at operand rows of 256 bytes); wider rows take 512."""
    from ray_tpu.ops.flash_attention import _block_sizes

    assert _block_sizes(16384, 16384, None, None, 128, jnp.bfloat16, True) == (1024, 1024)
    assert _block_sizes(16384, 16384, None, None, 128, jnp.float32, True) == (512, 512)
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    shapes = (shape(1, 32, 16384, 128), shape(1, 4, 16384, 128), shape(1, 4, 16384, 128),
              shape(1, 16384, 16384, dtype=jnp.int8))

    def selected(q, k, v, selection):
        out, lse = flash_attention(q, k, v, selection=selection, return_lse=True, interpret=False)
        return out.astype(jnp.float32).sum() + lse.sum()

    assert custom_calls(selected, *shapes) == 1
    grads = jax.grad(selected, argnums=(0, 1, 2))
    text = jax.jit(grads).lower(*shapes).compile().as_text()
    calls = mosaic_calls(text)
    assert len(calls) == 3
    assert sum("_flash_forward" in name for name in calls) == 1
    assert sum("_flash_backward" in name for name in calls) == 2      # dq, and dk + dv
    assert "s8[1,16384,16384]" in text and "[32,16384,16384]" not in text
    assert [g.shape for g in jax.eval_shape(grads, *shapes)] == [
        (1, 32, 16384, 128), (1, 4, 16384, 128), (1, 4, 16384, 128)]


# The shapes the kernels were read alone at (PERF.md section 6, PR 60), one a
# cell's call: (heads, kv_heads, seq, head_dim, v_dim, mode).
_WALKED = {
    "causal_16k": (32, 32, 16384, 128, 128, {}),
    "window_16k_group7": (28, 4, 16384, 128, 128, {"window": 4096}),
    "block_diffusion_8k_group8": (32, 4, 16384, 128, 128, {"causal": False, "block_diffusion": (8192, 4)}),
    "selection_16k_group8": (32, 4, 16384, 128, 128, {"selection": True}),
    "causal_4k_group8": (64, 8, 4096, 128, 128, {}),
    "two_head_dims_8k": (16, 16, 8192, 192, 128, {}),
    "head_64_16k": (32, 32, 16384, 64, 64, {}),
    # a window narrower than the tile takes the band (``_band``: one body a
    # kernel); one two tiles wide keeps the walk
    "window_512_differential": (20, 10, 16384, 64, 128, {"window": 512}),
    "window_2048_group8": (32, 4, 16384, 128, 128, {"window": 2048}),
}


@pytest.mark.parametrize("name", list(_WALKED))
def test_the_walked_bodies_compile_for_v5e(one_chip, name):
    """A tile of 1024 x 1024 that a mask cuts is walked in sub-blocks of 512
    (``_walk``: three bodies a kernel, the whole tile's and, in a
    ``fori_loop`` over the carried axis' halves, one half of the other axis at
    a traced offset or both; the selection's int8 tile is sliced with them):
    forward + dq + dkv stay three Mosaic calls under the jitted names the
    trace reads, ask for no scoped VMEM beyond Mosaic's own 16 MiB and use
    under it. Under Phi-4-flash's window of 512 keys the three kernels are
    BAND calls (``_band``: the other axis' block ``1024 + 512`` rows at an
    element offset, fwd and dq in parts of 256 rows against 768 keys, dkv in
    parts of 512 keys against 1024 queries, one body each); under Trinity's
    2,048 they walk the tiles."""
    from ray_tpu.ops.flash_attention import _band, _block_sizes, _sub_block

    heads, kv_heads, seq, dim, v_dim, mode = _WALKED[name]
    mode = dict(mode)
    chosen = mode.pop("selection", False)
    blocks = _block_sizes(seq, seq, None, None, max(dim, v_dim), jnp.bfloat16, chosen)
    assert blocks == (1024, 1024) and _sub_block(1024) == 512
    bands = [_band(seq, seq, *blocks, mode.get("window"), chosen, mode.get("block_diffusion"), kernel)
             for kernel in ("fwd", "dq", "dkv")]
    assert bands == ([(256, 512), (256, 512), (512, 512)] if mode.get("window") == 512 else [None] * 3)
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    shapes = [shape(1, heads, seq, dim), shape(1, kv_heads, seq, dim), shape(1, kv_heads, seq, v_dim)]
    if chosen:
        shapes.append(shape(1, seq, seq, dtype=jnp.int8))

    def loss(q, k, v, *selection):
        return flash_attention(
            q, k, v, interpret=False, selection=selection[0] if selection else None, **mode
        ).astype(jnp.float32).sum()

    grads = jax.grad(loss, argnums=(0, 1, 2))
    text = jax.jit(grads).lower(*shapes).compile().as_text()
    calls = mosaic_calls(text)
    assert len(calls) == 3
    assert sum("_flash_forward" in call for call in calls) == 1
    assert sum("_flash_backward" in call for call in calls) == 2      # dq, and dk + dv
    lines = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert all('"scoped_memory_configs":[]' in line for line in lines)
    used = [int(size) for line in lines
            for size in re.findall(r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', line)]
    assert len(used) == 3 and max(used) < 16 * 2**20, used
    assert [g.shape for g in jax.eval_shape(grads, *shapes)] == [s.shape for s in shapes[:3]]


def test_the_index_term_s_kernels_compile_for_v5e(one_chip):
    """The scorer's term of ``keye-vl-2.0-30b-a3b`` beside the masked flash
    kernels above: ``index_loss`` at q ``[1, 32, 16384, 128]`` on K of 4 heads,
    16 index heads of 64 on one key, all bfloat16, under the int8 selection
    ``[1, 16384, 16384]``: its value and the three gradients made in its
    forward are TWO Mosaic calls (``lseI``; then the term, ``dqI``, ``dw`` and
    ``dkI``), in the 256 x 512 tiles ``_index_blocks`` gives, under the VMEM
    ``_vmem_limit`` counts from the shapes (the 32 heads' ``q`` tile and the
    whole row's ``dkI`` resident: more than Mosaic's own 16 MiB, far under a
    v5e's 128), and nothing ``[.., rows, keys]`` exists in float32."""
    from ray_tpu.ops import sparse_index

    assert sparse_index._index_blocks(16384, None, None) == (256, 512)
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    shapes = (
        shape(1, 16384, 16, 64), shape(1, 16384, 64), shape(1, 16384, 16, dtype=jnp.float32),
        shape(1, 32, 16384, 128), shape(1, 4, 16384, 128),
        shape(1, 16384, 16384, dtype=jnp.int8), shape(1, 32, 16384, dtype=jnp.float32),
    )
    term = functools.partial(sparse_index.index_loss, scale=128 ** -0.5, interpret=False)
    grads = jax.value_and_grad(term, argnums=(0, 1, 2))
    text = jax.jit(grads).lower(*shapes).compile().as_text()
    calls = mosaic_calls(text)
    assert len(calls) == 2
    assert "_index_loss_lse" in calls[0] and "_index_loss_terms" in calls[1]
    assert not any("_flash" in name for name in calls)             # flash_ms reads by that name
    limits = [
        int(re.search(r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', line).group(1))
        for line in text.splitlines() if "tpu_custom_call" in line
    ]
    assert len(limits) == 2 and 16 * 2**20 <= min(limits) and max(limits) <= 64 * 2**20
    assert "s8[1,16384,16384]" in text
    assert not re.search(r"f32\[[\d,]*(?:256|512|16384),16384\]", text)
    loss, (dq_index, dk_index, dw) = jax.eval_shape(grads, *shapes)
    assert (loss.shape, loss.dtype) == ((), jnp.float32)
    assert [(g.shape, g.dtype) for g in (dq_index, dk_index, dw)] == [
        (s.shape, s.dtype) for s in shapes[:3]]
