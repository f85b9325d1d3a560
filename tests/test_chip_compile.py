"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached (``on-chip-measurement`` guide, section 2).

The TPU's compiler is installed wherever jax's TPU plugin is, so Mosaic
refuses here what it would refuse on the chip — a block not aligned to the
tiling, too much VMEM — at real widths (Llama-2-7B attention,
``[2, 32, 4096, 128]`` bf16) and at no chip time. A compile that passes is
not a chip run: nothing executes, so these say nothing about results or
times.

All in ONE file and in the test's own process: only one process at a time
may load the TPU's library, and the xdist worker that is handed this file
is the one that loads it. The topology is described inside a fixture that
skips when it cannot be — never at import, in a ``skipif`` or in
``parametrize``.
"""

import contextlib
import functools
import pathlib
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.rmsnorm import rmsnorm


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; the next one would warn.
    Module-scoped autouse is local to this file."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _qkv(one_chip, seq):
    return [
        jax.ShapeDtypeStruct((2, 32, seq, 128), jnp.bfloat16, sharding=one_chip)
    ] * 3


def _custom_calls(fn, *shapes) -> int:
    return jax.jit(fn).lower(*shapes).compile().as_text().count("tpu_custom_call")


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
    assert _custom_calls(_flash, *_qkv(one_chip, 4096)) == 1


def test_flash_backward_compiles_for_v5e(one_chip):
    # forward (for the residuals) + dq + dkv
    assert _custom_calls(_flash_grads, *_qkv(one_chip, 4096)) == 3


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
    assert _custom_calls(_flash, *shapes) == 1
    assert _custom_calls(_flash_grads, *shapes) == 3


def test_flash_block_too_large_for_vmem_is_refused(one_chip):
    """Why _block_sizes falls back at 1024-byte operand rows: asked for
    1024 x 1024 there, the backward does not fit the scoped VMEM."""
    shapes = [
        jax.ShapeDtypeStruct((1, 4, 16384, 256), jnp.float32, sharding=one_chip)
    ] * 3
    with pytest.raises(Exception, match="(?i)vmem"):
        _custom_calls(functools.partial(_flash_grads, block=1024), *shapes)


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
        assert _custom_calls(grads, *_qkv(one_chip, 1000)) == 3


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
    assert _custom_calls(_flash, wide, wide, narrow) == 1
    assert _custom_calls(_flash_grads, wide, wide, narrow) == 3
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
    assert _custom_calls(_flash, *shapes) == 1
    text = jax.jit(_flash_grads).lower(*shapes).compile().as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 3
    assert sum("_flash_forward" in name for name in calls) == 1
    assert sum("_flash_backward" in name for name in calls) == 2      # dq, and dk + dv
    assert [g.shape for g in jax.eval_shape(_flash_grads, *shapes)] == [(1, 32, 16384, 64)] * 3


def test_flash_under_a_window_compiles_for_v5e(one_chip):
    """The window layers' call of ``smallthinker-21b-a3b``, ``[1, 28, 16384,
    128]`` in bfloat16 under a window of 4096 keys: forward, and forward + dq
    + dkv, compile for the chip in the 1024 x 1024 blocks (the index maps'
    two clamps and the second mask are scalar and vector code Mosaic has to
    take); the band is 70 of a head's 256 tiles, walked in a grid of 16 x 5
    steps."""
    from ray_tpu.ops.flash_attention import _block_sizes, band_steps, causal_tile_counts

    blocks = _block_sizes(16384, 16384, None, None, 128, jnp.bfloat16)
    assert causal_tile_counts(16384, 16384, *blocks, 4096)["executed"] == 70
    assert band_steps(16384, 16384, *blocks, 4096) == {"kv": 5, "q": 5}
    shapes = [jax.ShapeDtypeStruct((1, 28, 16384, 128), jnp.bfloat16, sharding=one_chip)] * 3
    windowed = functools.partial(_flash, window=4096)
    assert _custom_calls(windowed, *shapes) == 1
    grads = jax.grad(
        lambda q, k, v: windowed(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2)
    )
    calls = _mosaic_calls(jax.jit(grads).lower(*shapes).compile().as_text())
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

    assert _custom_calls(selected, *shapes) == 1
    grads = jax.grad(selected, argnums=(0, 1, 2))
    text = jax.jit(grads).lower(*shapes).compile().as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 3
    assert sum("_flash_forward" in name for name in calls) == 1
    assert sum("_flash_backward" in name for name in calls) == 2      # dq, and dk + dv
    assert "s8[1,16384,16384]" in text and "[32,16384,16384]" not in text
    assert [g.shape for g in jax.eval_shape(grads, *shapes)] == [
        (1, 32, 16384, 128), (1, 4, 16384, 128), (1, 4, 16384, 128)]


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
    calls = _mosaic_calls(text)
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


def test_the_sparse_step_needs_no_more_of_the_chip_than_its_parent_s(topo):
    """``keye-vl2-seq16k-fixed``'s whole step (the benchmark's own
    configuration and traffic: six sparse layers over 16 held experts, 1 x
    16384, full remat) with the term as kernels: one call of each a layer's
    forward, none in its backward (the gradients are kept by name), no KV
    group's ``[8, 512, keys]`` float32 probabilities left, and no more of the
    chip than the parent's step, whose term was XLA's walk of chunks
    (``hbm_step_gib`` 12.515: ledger, PR 53)."""
    import importlib

    import ray_tpu.ops.grouped_matmul as gm
    from benchmarks.harness import described
    from benchmarks.harness.manifest import Manifest

    manifest = Manifest(str(pathlib.Path(__file__).resolve().parents[1]))
    cell = manifest.cell("keye-vl2-seq16k-fixed")
    config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    family = importlib.import_module(f"benchmarks.families.{config['family']}").build(config, traffic)
    with mock.patch.object(gm, "resolve_interpret", lambda _i: False):
        compiled = described.compile_step(
            family, topo.devices, config["mesh_axes"], traffic["batch_size"], traffic["seq_len"])[1]
    calls = _mosaic_calls(compiled.as_text())
    assert calls.count("_index_loss_lse") == 1 and calls.count("_index_loss_terms") == 1
    assert sum("_flash" in name for name in calls) == 3
    assert "f32[1,8,512," not in compiled.as_text()
    assert described.step_memory(compiled)["total_bytes"] / 2**30 <= 12.52


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


def _mosaic_calls(text: str) -> list[str]:
    """The jitted names of a compiled program's Mosaic calls, in order."""
    return re.findall(r"^\s*(?:ROOT )?%(\w+?)[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\"", text, re.M)


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
    assert _mosaic_calls(text) == ["_delta_prepare_forward", "_delta_rule_forward"]

    def grads(*args):
        loss = lambda *a: jnp.sum(rule(*a).astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)

    assert sorted(_mosaic_calls(jax.jit(grads).lower(*shapes).compile().as_text())) == [
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
    assert _custom_calls(forward, q, k, v, gates) == 1
    *operands, inverse = (
        jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
        for x in jax.eval_shape(forward, q, k, v, gates)
    )
    assert [x.shape[-1] for x in operands] == [96, 192, 96, 64, 96, 1]
    assert inverse.shape == (30, 8192, 128)                      # T: its diagonal blocks
    # the backward's call of the same kernel, from the kept T
    read = functools.partial(G._delta_prepare_forward, chunk=64, interpret=False, inverse="read")
    assert _custom_calls(lambda *a: read(*a[:-1], None, a[-1]), q, k, v, gates, inverse) == 1
    backward = functools.partial(G._delta_prepare_backward, chunk=64, interpret=False)
    assert _custom_calls(backward, q, k, v, gates, inverse, *operands) == 1
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
    assert set(_mosaic_calls(compiled.as_text())) == {
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
    assert _custom_calls(forward, *inputs) == 1
    *operands, inverse = (two(x) for x in jax.eval_shape(forward, *inputs))
    assert [x.shape[1:] for x in operands] == [
        (16384, 128), (16384, 128), (16384, 128), (16384, 64), (16384, 128), (256, 1, 128),
    ]
    assert inverse.shape == (2, 8192, 128)                       # T: two chunks' blocks a row
    # the backward's call of the same kernel, from the kept T
    read = functools.partial(
        G._channel_prepare_forward, chunk=64, interpret=False, inverse="read", bounded=bounded
    )
    assert _custom_calls(lambda *a: read(*a[:-1], None, a[-1]), *inputs, inverse) == 1
    backward = functools.partial(
        G._channel_prepare_backward, chunk=64, interpret=False, bounded=bounded
    )
    assert _custom_calls(backward, *inputs, inverse, *operands) == 1
    got = jax.eval_shape(backward, *inputs, inverse, *operands)
    assert [(x.shape, x.dtype) for x in got] == [(x.shape, x.dtype) for x in inputs]

    rule = functools.partial(G.gated_delta_rule, interpret=False, log_alpha_bound=bound)
    assert jax.eval_shape(rule, *shapes).shape == (1, 32, 16384, 128)
    text = jax.jit(rule).lower(*shapes).compile().as_text()
    assert _mosaic_calls(text) == ["_channel_prepare_forward", "_delta_rule_forward"]
    assert "f32[2,256,1,128]" in text                            # gamma, a row a chunk

    def value_and_grads(*args):
        loss = lambda *a: jnp.sum(rule(*a).astype(jnp.float32) ** 2)
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(*args)

    compiled = jax.jit(value_and_grads).lower(*shapes).compile()
    assert sorted(_mosaic_calls(compiled.as_text())) == [
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
    assert _mosaic_calls(jax.jit(forward).lower(x, filters).compile().as_text()) == [
        "_short_conv_forward"
    ]
    assert _mosaic_calls(jax.jit(backward).lower(x, filters, x).compile().as_text()) == [
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
    calls = _mosaic_calls(jax.jit(value_and_grads).lower(x, filters).compile().as_text())
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
    assert _mosaic_calls(jax.jit(forward).lower(x, filters).compile().as_text()) == [
        "_short_conv_forward"
    ]
    assert _mosaic_calls(jax.jit(backward).lower(x, filters, x).compile().as_text()) == [
        "_short_conv_backward"
    ]

    def value_and_grads(x, filters):
        conv = functools.partial(SC.short_conv, interpret=False, activation=activation)
        loss = lambda *a: jnp.sum(conv(*a).astype(jnp.float32) ** 2)
        return jax.value_and_grad(loss, argnums=(0, 1))(x, filters)

    calls = _mosaic_calls(jax.jit(value_and_grads).lower(x, filters).compile().as_text())
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
    calls = _mosaic_calls(compiled.as_text())
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
    assert _mosaic_calls(text) == ["_ssd_forward", "_ssd_states", "_ssd_backward"]
    assert "8192,128,128]" not in text and "[1,8192,128,64,128]" not in text
    # the chunk-start states of the backward's first pass: 64 chunks x (128 heads x 64) rows of 128
    assert len(set(re.findall(r"f32\[1,64,8192,128\]", text))) == 1
    assert not re.search(r"f32\[1,(8192|4096|2048|1024|512|256|128),8192,128\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * 2**30
    assert [g.shape for g in jax.eval_shape(grads, *operands)] == [a.shape for a in operands]
    # float32, as the benchmark's check hands the scan its operands: Mosaic's fp32 products
    exact = tuple(jax.ShapeDtypeStruct(a.shape, jnp.float32, sharding=one_chip) for a in operands)
    text = jax.jit(grads).lower(*exact).compile().as_text()
    assert _mosaic_calls(text) == ["_ssd_forward", "_ssd_states", "_ssd_backward"]


def test_rmsnorm_compiles_for_v5e(one_chip):
    x = jax.ShapeDtypeStruct((8192, 4096), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((4096,), jnp.bfloat16, sharding=one_chip)
    assert _custom_calls(functools.partial(rmsnorm, interpret=False), x, w) == 1


def _grouped_loss(lhs, rhs, group_sizes, tile=None):
    import ray_tpu.ops.grouped_matmul as gm

    ctx = mock.patch.object(gm, "TILE", tile) if tile else contextlib.nullcontext()
    with ctx:
        out = gm.grouped_matmul(lhs, rhs, group_sizes, interpret=False)
    return jnp.sum(out.astype(jnp.float32))


@pytest.mark.parametrize("rows,k,n", [
    (65536, 2048, 1024), (65536, 1024, 2048),     # OLMoE
    (49152, 2048, 1408), (49152, 1408, 2048),     # Moonlight: 1408 = 11 x 128
    (65536, 2048, 1792), (65536, 1792, 2048),     # LFM2: 1792 = 2 x 896, tiles of 896
])
def test_grouped_matmul_compiles_for_v5e(one_chip, rows, k, n):
    """The expert matmuls at the benchmark cells' sizes: OLMoE's 65,536
    (token, choice) rows over 64 experts of width 1024, Moonlight's 49,152
    over 64 of width 1408 and LFM2's 65,536 at width 1792 (in tiles of 896,
    which must fit the scoped VMEM in all three calls), gate / up and down, forward, and both
    gradients (the input's is ``gmm`` on the transposed experts, the
    weights' is ``tgmm``), at the tiles ``grouped_matmul`` picks."""
    shapes = (
        jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((64, k, n), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip),
    )
    assert _custom_calls(_grouped_loss, *shapes) == 1
    grads = jax.grad(_grouped_loss, argnums=(0, 1))
    text = jax.jit(grads).lower(*shapes).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert len(re.findall(r"%\S*tgmm\S* = \S+ custom-call", text)) == 1


def test_grouped_matmul_tile_too_large_for_vmem_is_refused(one_chip):
    """Why TILE stops at 512 x 1024 x 1024: the next size up needs more
    VMEM than a kernel may use on a v5e (the chip refused it too: my chip
    run, PR 26)."""
    shapes = (
        jax.ShapeDtypeStruct((65536, 2048), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((64, 2048, 1024), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip),
    )
    big = functools.partial(_grouped_loss, tile=(1024, 2048, 1024))
    with pytest.raises(Exception, match="(?i)vmem|memory"):
        # the value keeps the forward call, the one tiled 1024 x 2048 x 1024
        # (each gradient is tiled for its own dimensions)
        jax.jit(jax.value_and_grad(big, argnums=(0, 1))).lower(*shapes).compile()


def _loss_and_grads_text(topo, config, axes, batch, seq) -> str:
    """The optimized program of ``loss_fn`` and its gradients, compiled from
    shapes for the described chips under ``axes``, traced under the mesh as
    ``build_sharded_train_step`` traces it and with the Mosaic kernels
    themselves (the platform rule would pick the interpreter: the backend
    here is the CPU)."""
    import ray_tpu.ops.flash_attention as flash_mod
    import ray_tpu.ops.grouped_matmul as gm
    from ray_tpu.models import transformer as T
    from ray_tpu.parallel.mesh import LogicalRules, MeshSpec

    spec = MeshSpec(axes)
    mesh = spec.build(topo.devices[:spec.size])
    rules = LogicalRules()
    params = jax.tree.map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        jax.eval_shape(lambda: T.init_params(config, jax.random.PRNGKey(0))),
        rules.tree_shardings(T.param_logical_dims(config), mesh),
    )
    tokens = jax.ShapeDtypeStruct(
        (batch, seq), jnp.int32, sharding=rules.sharding(["batch", None], mesh))

    def loss(params, tokens):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return T.loss_fn(params, tokens, tokens, config)

    with mock.patch.object(flash_mod, "resolve_interpret", lambda _i: False), \
            mock.patch.object(gm, "resolve_interpret", lambda _i: False):
        return jax.jit(jax.value_and_grad(loss)).lower(params, tokens).compile().as_text()


@pytest.mark.parametrize("axes", [
    {"dp": 4}, {"dp": 2, "ep": 2}, {"fsdp": 2, "tp": 2},
], ids=lambda axes: "-".join(f"{k}{v}" for k, v in axes.items()))
def test_moe_step_compiles_for_a_v5e_mesh(topo, axes):
    """A MoE model's loss and gradients across four chips: GSPMD refuses to
    partition the Mosaic grouped matmuls ("wrap the call in a shard_map"),
    which the CPU, interpreting them as plain HLO, never shows. Traced
    under the mesh as ``build_sharded_train_step`` traces it, the block
    runs per data shard (``transformer._moe_over_mesh``), data parallel
    alone, with the experts sharded over ep, and under fsdp x tp."""
    from ray_tpu.models import transformer as T

    config = T.TransformerConfig(
        vocab_size=512, dim=256, n_layers=2, n_heads=2, n_kv_heads=2,
        hidden_dim=128, max_seq=512, qk_norm=True, attention="flash",
        moe=T.MoEConfig(num_experts=4, top_k=2, aux_loss_coef=0.01),
    )
    text = _loss_and_grads_text(topo, config, axes, batch=4, seq=512)
    # three flash kernels; gate / up / down forward, input and weight gradients
    assert text.count("tpu_custom_call") == 12
    assert len(re.findall(r"%\S*tgmm\S* = \S+ custom-call", text)) == 3


@pytest.mark.parametrize("axes", [
    {"dp": 2, "ep": 2}, {"fsdp": 2, "tp": 2},
], ids=lambda axes: "-".join(f"{k}{v}" for k, v in axes.items()))
def test_latent_attention_moe_step_compiles_for_a_v5e_mesh(topo, axes):
    """DeepSeek-V3's block across four chips: the two-dim flash kernels per
    (batch, head) shard under ``shard_map`` (tp shards ``W_q``, ``W_kv_b``
    and ``W_o`` by whole heads, the latent and the shared rope key stay
    whole), a dense first layer in a scan of its own, then the expert layer
    per data shard with its shared experts outside the per-shard call, where
    GSPMD shards them as a dense MLP."""
    from ray_tpu.models import transformer as T

    config = T.TransformerConfig(
        vocab_size=512, dim=256, n_layers=2, n_heads=2, n_kv_heads=2,
        hidden_dim=384, max_seq=512, rms_norm_eps=1e-5, attention="flash",
        latent=T.LatentAttentionConfig(
            kv_lora_rank=128, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
        first_dense_layers=1,
        moe=T.MoEConfig(
            num_experts=4, top_k=2, norm_topk_prob=True, aux_loss_coef=0.001, expert_dim=128,
            shared_experts=2, scoring="sigmoid", routed_scaling=2.446),
    )
    text = _loss_and_grads_text(topo, config, axes, batch=4, seq=512)
    # three flash kernels in each of the two scans; gate / up / down forward,
    # input and weight gradients in the expert layer's
    assert text.count("tpu_custom_call") == 15
    assert len(re.findall(r"%\S*tgmm\S* = \S+ custom-call", text)) == 3


@pytest.mark.parametrize("axes", [
    {"dp": 1}, {"dp": 2, "fsdp": 2},
], ids=lambda axes: "-".join(f"{k}{v}" for k, v in axes.items()))
def test_patterned_step_over_held_experts_compiles_for_a_v5e_mesh(topo, axes):
    """A pattern over expert layers (Ling-3.0-flash-VL's shape at lane
    widths) on one chip and across four under data parallelism: a dense
    prefix with a linear mixer, then (linear, full) whose linear layers carry
    a decay per channel and whose full layers are gated latent attention,
    over 8 group-routed experts of which 4 are held. The convolutions, the
    scan kernels and the expert layer run per data shard under ``shard_map``;
    the grouped matmuls run over the held experts' groups alone."""
    from ray_tpu.models import transformer as T
    from ray_tpu.ops import gated_delta_rule as G, short_conv as S

    config = T.TransformerConfig(
        vocab_size=512, dim=256, n_layers=3, n_heads=2, n_kv_heads=2, hidden_dim=384,
        max_seq=512, attention="flash", remat="full",
        latent=T.LatentAttentionConfig(
            kv_lora_rank=128, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            output_gate="head"),
        first_dense_layers=1, first_dense_kind="linear", layer_pattern=("linear", "full"),
        linear=T.LinearAttentionConfig(
            num_key_heads=2, num_value_heads=2, key_head_dim=128, value_head_dim=128,
            allow_neg_eigval=False, decay="channel", gate_lower_bound=-5.0,
            output_gate="sigmoid"),
        moe=T.MoEConfig(
            num_experts=8, top_k=2, norm_topk_prob=True, expert_dim=128, shared_experts=1,
            scoring="sigmoid", routed_scaling=2.5, n_group=4, topk_group=2, held=(4, 4)),
    )
    with mock.patch.object(G, "resolve_interpret", lambda _i: False), \
            mock.patch.object(S, "resolve_interpret", lambda _i: False):
        text = _loss_and_grads_text(topo, config, axes, batch=4, seq=512)
    calls = _mosaic_calls(text)
    # two linear layers (one in each scan): the scan's forward, the forward
    # again for the chunk-start states, the backward
    assert calls.count("_delta_rule_forward") == 4 and calls.count("_delta_rule_backward") == 2
    # per channel: the preparation's own pair, not the scalar rule's
    assert not [c for c in calls if c.startswith("_delta_prepare")]
    assert calls.count("_channel_prepare_forward") == 4
    assert calls.count("_channel_prepare_backward") == 2
    assert calls.count("_flash_forward") == 1
    # two expert layers: nine grouped matmuls and the recompute's three forward ones
    assert len(re.findall(r"%\S*tgmm\S* = \S+ custom-call", text)) == 6
    assert len([c for c in calls if c.startswith("_short_conv")]) >= 12
    # the held experts' stack, [periods x count, held, k, n] flattened: 4 of the 8
    assert "bf16[4,256,128]" in text and "bf16[8,256,128]" not in text


@pytest.mark.parametrize("axes,batch", [
    ({"dp": 1}, 1), ({"fsdp": 2, "tp": 2}, 2),
], ids=["one-chip", "fsdp2-tp2"])
def test_full_remat_runs_the_forward_kernel_once(topo, axes, batch):
    """Two scanned layers at the 16k cell's attention shape (a device's
    call is ``[1, 32, 16384, 128]`` on one chip, hidden 4096) under
    ``remat="full"``: the layer checkpoint keeps the kernel's ``out`` and
    ``lse`` by name, so loss and gradients hold exactly three Mosaic calls
    (forward, dq, dkv; per shard under ``shard_map`` on the 2 x 2 mesh) and
    not a fourth, the forward again in the backward."""
    from ray_tpu.models import transformer as T

    config = T.TransformerConfig(
        vocab_size=512, dim=4096, n_layers=2, n_heads=32, n_kv_heads=8,
        hidden_dim=1024, max_seq=16384, attention="flash", remat="full",
    )

    def custom_calls():
        return _loss_and_grads_text(topo, config, axes, batch, 16384).count("tpu_custom_call")

    assert custom_calls() == 3
    with mock.patch.object(
        T, "_remat_policy", lambda _r: jax.checkpoint_policies.nothing_saveable
    ):
        assert custom_calls() == 4   # what the names are for


def _one_chip_step(topo, config, batch, seq):
    """The compiled one-chip training step of ``config`` (bfloat16, AdamW),
    built and compiled from shapes as the benchmark's own rehearsal builds
    a cell's (``benchmarks/harness/described.py``), with the Mosaic
    kernels."""
    import types

    import ray_tpu.ops.grouped_matmul as gm
    from benchmarks.harness import described
    from ray_tpu.models import transformer as T

    family = types.SimpleNamespace(
        init=lambda key: T.init_params(config, key),
        logical_dims=T.param_logical_dims(config),
        loss=lambda params, batch: T.loss_fn(params, batch["x"], batch["y"], config),
    )
    # described.compile_step steers the flash kernels off the interpreter;
    # the grouped matmul asks the same platform rule from its own module.
    with mock.patch.object(gm, "resolve_interpret", lambda _i: False):
        return described.compile_step(family, topo.devices, {"dp": 1}, batch, seq)[1]


def _mistral_7b_step(topo, batch, seq, remat):
    """At the benchmark's Mistral-7B widths (hidden 4096, 32 / 8 heads, MLP
    14336, vocabulary 32768, depth cut to 2)."""
    from ray_tpu.models import transformer as T

    config = T.TransformerConfig(
        vocab_size=32768, dim=4096, n_layers=2, n_heads=32, n_kv_heads=8,
        hidden_dim=14336, max_seq=seq, rope_theta=1e6, attention="flash", remat=remat,
    )
    return _one_chip_step(topo, config, batch, seq)


@pytest.mark.parametrize("batch,seq,remat,parent_gib", [
    (2, 4096, None, 11.5391),      # mistral7b-seq4k-ingest
    (1, 16384, "full", 11.1715),   # mistral7b-seq16k-fixed
], ids=["seq4k", "seq16k"])
def test_head_loss_keeps_no_float32_logits_of_the_whole_batch(topo, batch, seq, remat, parent_gib):
    """``loss_fn`` ends in ``head_loss``: in the optimized step no float32
    array with the vocabulary as its last dimension has ``batch * seq``
    rows (what there is of float32 at that width is a chunk's, inside the
    loop's fusions, and AdamW's update of ``lm_head``, 4096 rows both);
    what is kept for the backward is the bfloat16 ``dlogits``, chunks of
    4096 rows in both cells. The step needs no more of the chip than its
    parent's did (``parent_gib``: the cell's ``hbm_step_gib``, ledger, PR
    28, where ``loss_fn`` was ``logits_loss(_head(...))``; arguments +
    temporaries + outputs - aliased)."""
    import math

    compiled = _mistral_7b_step(topo, batch, seq, remat)
    tokens, vocab = batch * seq, 32768
    of_vocab = {
        (dtype, dims) for dtype, dims in (
            (dtype, tuple(int(d) for d in dims.split(",")))
            for dtype, dims in re.findall(r"\b(f32|bf16)\[([\d,]+)\]", compiled.as_text())
        ) if len(dims) > 1 and dims[-1] == vocab
    }
    assert not [dims for dtype, dims in of_vocab if dtype == "f32" and math.prod(dims[:-1]) >= tokens]
    assert ("bf16", (tokens // 4096, 4096, vocab)) in of_vocab      # dlogits, by chunk
    from benchmarks.harness import described

    assert described.step_memory(compiled)["total_bytes"] / 2**30 <= parent_gib


def test_expert_kernels_read_the_layer_stack_in_place(topo):
    """``olmoe-seq4k-ingest``'s step (hidden 2048, 16 heads with q/k norm,
    64 experts of width 1024, 8 a token, vocabulary 50304, depth cut to 2;
    2 x 4096 tokens, no remat): the six ``gmm`` calls, forward and input
    gradient of gate / up / down, take the layer STACK seen as
    ``[layers x experts, k, n]`` (a bitcast of the loop's invariant), so no
    instruction of either scan's body, slice or copy, produces an
    ``[experts, k, n]`` array: only the three ``tgmm`` calls, whose results
    the weight gradients are, and the parameters of the fusions that stack
    those. The parent had six ``dynamic-slice_bitcast_fusion`` copies of 268
    MB in the loop bodies, each run once a layer. Twelve Mosaic calls as
    the parent, and no more of the chip than the parent's step needed
    (``parent_gib``: the cell's ``hbm_step_gib``, ledger, PR 30)."""
    from benchmarks.harness import described
    from ray_tpu.models import transformer as T

    parent_gib = 14.118
    layers, experts, dim, width = 2, 64, 2048, 1024
    config = T.TransformerConfig(
        vocab_size=50304, dim=dim, n_layers=layers, n_heads=16, n_kv_heads=16,
        hidden_dim=width, max_seq=4096, rope_theta=1e4, rms_norm_eps=1e-5, qk_norm=True,
        moe=T.MoEConfig(num_experts=experts, top_k=8, aux_loss_coef=0.01),
        attention="flash",
    )
    compiled = _one_chip_step(topo, config, batch=2, seq=4096)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 12
    in_place = rf"bf16\[{layers * experts},({dim},{width}|{width},{dim})\]"
    gmm = re.findall(r"%gmm\S* = \S+ custom-call\(.*?operand_layout_constraints=(.*?)frontend_attributes", text)
    assert len(gmm) == 6 and all(re.search(in_place, operands) for operands in gmm)
    one_layer = rf"= bf16\[{experts},(?:{dim},{width}|{width},{dim})\]\S* ([\w-]+)\("
    assert sorted(re.findall(one_layer, text)) == ["custom-call"] * 3 + ["parameter"] * 3
    assert described.step_memory(compiled)["total_bytes"] / 2**30 <= parent_gib


def _fusions_that_only_select(text: str, rows: str) -> list[str]:
    """Fused computations of ``text`` that produce a ``[rows]`` array and whose
    only work is a ``select``: a pass over the buffer that does nothing but
    zero some of it."""
    idle = {"parameter", "constant", "broadcast", "bitcast", "convert", "compare", "iota",
            "tuple", "reshape", "select"}
    found = []
    for block in re.split(r"\n(?=%?fused_computation[\w.\-]* \()", text):
        head = re.match(r"%?(fused_computation[\w.\-]*) \(.*?\) -> (.*?) \{\n", block)
        if head and rows in head.group(2):
            ops = set(re.findall(r"= \S+\s+([\w-]+)\(", block.split("\n}")[0]))
            if "select" in ops and ops <= idle:
                found.append(head.group(1))
    return found


@pytest.mark.parametrize("dim,top_k,held,experts,width,parent_mib", [
    (2560, 6, 32, 64, 768, 2431),      # smallthinker-seq16k-fixed's expert layer
    (2048, 4, 16, 32, 1792, 1429),     # lfm2-moe-seq16k-fixed's
], ids=["top6-of-2560", "top4-of-2048"])
def test_every_pair_dispatch_has_no_array_with_top_k_second_minor(
    one_chip, dim, top_k, held, experts, width, parent_mib
):
    """Value and gradient of ONE expert layer (``_moe_mlp``) at the two dear
    cells' shapes, 16,384 tokens, half of the experts held, so the block is
    ``_by_every_pair``: the pairs are numbered choice-major, so the compiled
    program has no array ``[tokens, top_k, d]`` in any dtype (with ``top_k``
    second-minor the TPU's (8, 128) tile pads 6 to 8 or is swapped for a
    (4, 128) one: the parent's ``reshape f32[16384,6,2560]`` and its
    ``broadcast`` were physical copies, 1.34 GB each), a token's rows by
    choice are a BITCAST of the gathered ``[tokens x top_k, d]`` buffer, the
    entry computation holds no float32 array of the buffer's size (the
    backward stays in expert order: the cotangent's rows are gathered from
    the ``[tokens, d]`` array), and no fusion's only work is a ``select`` over
    the buffer (the held selects ride in the sums). Nine Mosaic calls as the
    parent. Temporaries against the parent's (``parent_mib``: the same
    function at commit 8d93ff2, compiled the same way; both printed): a sixth
    less at ``top_k`` 6, where the padded copies were; at ``top_k`` 4 this
    function ALONE reads 1 % over (1,446 against 1,429 MiB: the backward keeps
    the experts' output beside the gathered cotangent for one fusion), while
    the cell's whole step needs 8.56 GiB where the parent's needs 9.23
    (PERF.md section 6, PR 46)."""
    import ray_tpu.ops.grouped_matmul as gm
    from ray_tpu.models import transformer as T

    tokens, pairs = 16384, 16384 * top_k
    config = T.TransformerConfig(
        vocab_size=512, dim=dim, n_layers=1, n_heads=2, n_kv_heads=2, hidden_dim=width,
        max_seq=tokens,
        moe=T.MoEConfig(
            num_experts=experts, top_k=top_k, norm_topk_prob=True, expert_dim=width,
            scoring="sigmoid", held=(0, held)),
    )
    shaped = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    layer = {
        "router": shaped(dim, experts), "router_bias": shaped(experts, dtype=jnp.float32),
        "w_gate": shaped(held, dim, width), "w_up": shaped(held, dim, width),
        "w_down": shaped(held, width, dim),
    }
    h = shaped(1, tokens, dim)

    def probed(h, layer, probe):
        out, _ = T._moe_mlp(h, layer, config)
        return jnp.sum(out.astype(jnp.float32) * probe.astype(jnp.float32))

    with mock.patch.object(gm, "resolve_interpret", lambda _i: False):
        compiled = jax.jit(jax.value_and_grad(probed, argnums=(0, 1))).lower(h, layer, h).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 9
    assert not re.findall(rf"\w+\[{tokens},{top_k},{dim}\]", text)
    by_choice = rf"\[{top_k},{tokens},{dim}\]"
    entry = text[text.index("ENTRY"):]
    # forward and in the first gather's transpose: the gathered rows seen by choice, for free
    assert len(re.findall(rf"= bf16{by_choice}\S* bitcast\(", entry)) == 2
    assert not re.findall(rf"= f32(?:{by_choice}|\[{pairs},{dim}\])", entry)
    assert _fusions_that_only_select(text, f"[{pairs},{dim}]") == []
    temporaries = compiled.memory_analysis().temp_size_in_bytes / 2**20
    print(f"temporaries {temporaries:.0f} MiB, the parent's {parent_mib} MiB")
    assert temporaries <= 1.02 * parent_mib
