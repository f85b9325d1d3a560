"""Pallas kernel numerics vs pure-jax references (CPU interpret mode — the
same kernel code the TPU compiles, SURVEY §4.4 'CPU twin' trick)."""

import functools

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.flash_attention import attention_reference, flash_attention
from ray_tpu.ops.rmsnorm import rmsnorm, rmsnorm_reference
from ray_tpu.ops.rope import apply_rope, rope_frequencies

from model_helpers import flash_tile_tables


def _grouped_reference(q, k, v, **kwargs):
    """``attention_reference`` on K and V repeated by hand to q's heads: the
    oracle of the kernels' grouped KV heads (a group of 1 repeats nothing)."""
    group = q.shape[1] // k.shape[1]
    return attention_reference(
        q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1), **kwargs)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_reference(causal):
    key = jax.random.PRNGKey(0)
    batch, heads, seq, dim = 2, 4, 256, 64
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (batch, heads, seq, dim))
        for i in range(3)
    )
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = attention_reference(q, k, v, causal=causal)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_flash_attention_rectangular_blocks():
    key = jax.random.PRNGKey(1)
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (1, 2, 128, 32))
        for i in range(3)
    )
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=32)
    ref = attention_reference(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_flash_attention_bf16():
    key = jax.random.PRNGKey(2)
    q, k, v = (
        jax.random.normal(
            jax.random.fold_in(key, i), (1, 2, 128, 64), jnp.bfloat16
        )
        for i in range(3)
    )
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = attention_reference(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))) < 3e-2


def test_rmsnorm_matches_reference():
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (4, 128, 512))
    w = jax.random.normal(jax.random.fold_in(key, 1), (512,))
    out = rmsnorm(x, w)
    ref = rmsnorm_reference(x, w)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_rmsnorm_odd_rows_padded_to_a_block():
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (7, 512))
    w = jnp.ones((512,))
    out = rmsnorm(x, w, block_rows=4)
    ref = rmsnorm_reference(x, w)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_rope_rotation_properties():
    cos, sin = rope_frequencies(64, 128)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 2, 16, 64))
    rotated = apply_rope(x, cos, sin)
    # Norm-preserving per position.
    assert jnp.allclose(
        jnp.linalg.norm(rotated, axis=-1), jnp.linalg.norm(x, axis=-1), atol=1e-4
    )
    # Position 0 is identity.
    assert jnp.allclose(rotated[..., 0, :], x[..., 0, :], atol=1e-6)
    # Explicit positions select rows of the table: rotating x2's two vectors
    # with positions [3, 7] must equal placing those vectors at seq positions
    # 3 and 7 and applying the default (implicit-position) rope.
    positions = jnp.array([[3, 7]])
    x2 = x[:, :, :2]
    shifted = apply_rope(x2, cos, sin, positions=positions)
    placed = jnp.zeros_like(x).at[:, :, 3, :].set(x2[:, :, 0, :])
    placed = placed.at[:, :, 7, :].set(x2[:, :, 1, :])
    full = apply_rope(placed, cos, sin)
    assert jnp.allclose(shifted[0, :, 0], full[0, :, 3], atol=1e-5)
    assert jnp.allclose(shifted[0, :, 1], full[0, :, 7], atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_backward_matches_reference(causal):
    key = jax.random.PRNGKey(7)
    batch, heads, seq, dim = 2, 2, 256, 64
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (batch, heads, seq, dim))
        for i in range(3)
    )

    def flash_loss(q, k, v):
        o = flash_attention(
            q, k, v, causal=causal, block_q=64, block_k=64,
            precision=jax.lax.Precision.HIGHEST,
        )
        return jnp.sum(o * jnp.cos(o))

    def ref_loss(q, k, v):
        o = attention_reference(q, k, v, causal=causal)
        return jnp.sum(o * jnp.cos(o))

    gq, gk, gv = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    rq, rk, rv = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for g, r, name in ((gq, rq, "dq"), (gk, rk, "dk"), (gv, rv, "dv")):
        err = float(jnp.max(jnp.abs(g - r)))
        assert err < 2e-4, (name, err)


def test_flash_attention_backward_rectangular():
    key = jax.random.PRNGKey(8)
    q = jax.random.normal(key, (1, 2, 64, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 128, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 128, 32))

    def flash_loss(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=True, block_q=32, block_k=32,
                precision=jax.lax.Precision.HIGHEST,
            ) ** 2
        )

    def ref_loss(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    grads = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    refs = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(grads, refs):
        assert float(jnp.max(jnp.abs(g - r))) < 2e-4


def test_flash_attention_backward_bf16():
    key = jax.random.PRNGKey(9)
    q, k, v = (
        jax.random.normal(
            jax.random.fold_in(key, i), (1, 2, 128, 64), jnp.bfloat16
        )
        for i in range(3)
    )

    def flash_loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def ref_loss(q, k, v):
        o = attention_reference(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    grads = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    refs = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(grads, refs):
        err = float(
            jnp.max(jnp.abs(g.astype(jnp.float32) - r.astype(jnp.float32)))
        )
        assert err < 0.15, err


# -- causal tiles: skipped or executed ----------------------------------------
# (seq_q, seq_k, block_q, block_k): square, seq_q < seq_k (the decode
# convention's positive causal_offset), rectangular blocks both ways, one
# block a side, seq_q > seq_k, a sequence shorter than the block asked for.
_TILE_CASES = [
    (256, 256, 64, 64),
    (128, 256, 32, 64),
    (64, 256, 64, 32),
    (256, 256, 128, 32),
    (256, 256, 32, 128),
    (512, 512, 512, 512),
    (256, 128, 64, 64),
    (48, 48, 64, 64),
    # no multiple of the asked block: _block_sizes halves 64 down to 8, as it does
    # 512 for a sequence of 1000, and 15 blocks a side say what 125 did
    (120, 120, 64, 64),
]


def _executed_pairs(mask, block_q, block_k):
    """The pairs the kernels compute under ``mask``, by enumeration: those of
    the sub-blocks (``_sub_block``: a tile under 256 a side is its own one)
    that hold a visible pair."""
    from ray_tpu.ops.flash_attention import _sub_block

    sub_q, sub_k = _sub_block(block_q), _sub_block(block_k)
    held = mask.reshape(mask.shape[0] // sub_q, sub_q, mask.shape[1] // sub_k, sub_k).any(axis=(1, 3))
    return int(held.sum()) * sub_q * sub_k


@pytest.mark.parametrize("by", ["q", "kv"])
@pytest.mark.parametrize("seq_q,seq_k,block_q,block_k", _TILE_CASES)
def test_causal_tile_skip_matches_dense_mask(seq_q, seq_k, block_q, block_k, by):
    """``_tile_needed``, the table a call prefetches (``by="q"``: fwd and dq,
    ``"kv"``: dkv; it does not depend on the group, whose heads are the dkv
    grid's innermost axis) and ``causal_tile_counts`` against the mask itself:
    a grid step is a tile that holds a visible pair, each once, and there is
    no other step but the one of a row that sees nothing (``seq_q > seq_k``:
    the first q rows)."""
    import numpy as np

    from ray_tpu.ops.flash_attention import _block_sizes, _tile_needed, causal_tile_counts

    block_q, block_k = _block_sizes(
        seq_q, seq_k, block_q, block_k, 128, jnp.bfloat16)
    nq, nk = seq_q // block_q, seq_k // block_k
    offset = seq_k - seq_q
    mask = np.tril(np.ones((seq_q, seq_k), dtype=bool), offset)
    needed = np.array([
        [mask[j * block_q:(j + 1) * block_q,
              kv * block_k:(kv + 1) * block_k].any() for kv in range(nk)]
        for j in range(nq)
    ])
    for j in range(nq):
        for kv in range(nk):
            assert _tile_needed(
                True, offset, j, kv, block_q, block_k) == needed[j, kv], (j, kv)
    entries = flash_tile_tables(mask, block_q, block_k)[by]
    rows = needed if by == "q" else needed.T
    assert len(entries) == int(needed.sum()) + int((~rows.any(axis=1)).sum())
    assert causal_tile_counts(seq_q, seq_k, block_q, block_k) == {
        "skipped": int((~needed).sum()), "executed": int(needed.sum()),
        "executed_pairs": _executed_pairs(mask, block_q, block_k),
        "grid_steps": int(needed.sum()) + int((~needed.any(axis=1)).sum())}


# ``executed_pairs``: a diagonal tile runs 3 of its 4 sub-blocks of 512 x 512;
# ``grid_steps``: the forward's grid walks the executed tiles and no other step
@pytest.mark.parametrize("seq,counts", [
    (16384, {"skipped": 120, "executed": 136, "executed_pairs": (136 * 4 - 16) * 512 ** 2, "grid_steps": 136}),
    (4096, {"skipped": 6, "executed": 10, "executed_pairs": (10 * 4 - 4) * 512 ** 2, "grid_steps": 10}),
])
def test_causal_tile_counts_of_the_benchmark_cells(seq, counts):
    """At the block shape the cells run (head_dim 128, bfloat16)."""
    from ray_tpu.ops.flash_attention import _block_sizes, causal_tile_counts

    blocks = _block_sizes(seq, seq, None, None, 128, jnp.bfloat16)
    assert blocks == (1024, 1024)
    assert causal_tile_counts(seq, seq, *blocks) == counts


# -- a sliding window: the band's tiles, its edge, its gradients ---------------
def _window_mask(seq_q, seq_k, window):
    import numpy as np

    q_pos = (seq_k - seq_q) + np.arange(seq_q)[:, None]
    k_pos = np.arange(seq_k)[None, :]
    return (k_pos <= q_pos) & (k_pos > q_pos - window)


# (seq_q, seq_k, block_q, block_k, window): the window under, at and over a
# block, one key, seq_q < seq_k, rectangular blocks, a window over the whole
# sequence, kv rows no query sees (seq_q << seq_k).
_WINDOW_TILE_CASES = [
    (256, 256, 64, 64, 8),
    (256, 256, 64, 64, 63),
    (256, 256, 64, 64, 64),
    (256, 256, 64, 64, 65),
    (256, 256, 64, 64, 1),
    (256, 256, 64, 64, 150),
    (256, 256, 64, 64, 256),
    (256, 256, 64, 64, 1000),
    (128, 256, 32, 64, 40),
    (64, 256, 64, 32, 16),
    (256, 256, 128, 32, 100),
    (256, 256, 32, 128, 100),
    (32, 512, 32, 64, 8),
]


@pytest.mark.parametrize("by", ["q", "kv"])
@pytest.mark.parametrize("seq_q,seq_k,block_q,block_k,window", _WINDOW_TILE_CASES)
def test_window_tile_skip_matches_dense_mask(seq_q, seq_k, block_q, block_k, window, by):
    """``_tile_needed``, the table of either orientation and
    ``causal_tile_counts`` under a window against the mask itself: the grid's
    steps are the band's tiles, a row's in order, each once; a kv row no
    query sees (``seq_q << seq_k``) keeps the one step that writes its zeros."""
    import numpy as np

    from ray_tpu.ops.flash_attention import _tile_needed, causal_tile_counts

    nq, nk = seq_q // block_q, seq_k // block_k
    offset = seq_k - seq_q
    mask = _window_mask(seq_q, seq_k, window)
    needed = np.array([
        [mask[j * block_q:(j + 1) * block_q,
              kv * block_k:(kv + 1) * block_k].any() for kv in range(nk)]
        for j in range(nq)
    ])
    for j in range(nq):
        for kv in range(nk):
            assert bool(_tile_needed(
                True, offset, j, kv, block_q, block_k, window)) == needed[j, kv], (j, kv)
    entries = flash_tile_tables(mask, block_q, block_k, window=window)[by]
    rows = needed if by == "q" else needed.T
    assert needed.any(axis=1).all()          # a q row sees its own position
    assert len(entries) == int(needed.sum()) + int((~rows.any(axis=1)).sum())
    assert causal_tile_counts(seq_q, seq_k, block_q, block_k, window) == {
        "skipped": int((~needed).sum()), "executed": int(needed.sum()),
        "executed_pairs": _executed_pairs(mask, block_q, block_k), "grid_steps": int(needed.sum())}


def test_window_tile_counts_of_the_benchmark_cell():
    """[16384, 16384] in 1024-blocks under a window of 4096: rows of 1, 2,
    3, 4, then twelve of 5 tiles; by pairs 43.75 % of the causal half."""
    from ray_tpu.ops.flash_attention import _block_sizes, _entry, _tile_table, causal_tile_counts

    blocks = _block_sizes(16384, 16384, None, None, 128, jnp.bfloat16)
    # 16 diagonal tiles run 3 of their 4 sub-blocks, the 12 lower-edge tiles too;
    # the grid walks the band's 70 tiles a head, where a global layer walks 136
    assert causal_tile_counts(16384, 16384, *blocks, 4096) == {
        "skipped": 186, "executed": 70, "executed_pairs": (70 * 4 - 16 - 12) * 512 ** 2,
        "grid_steps": 70}
    for by in ("q", "kv"):
        table = _tile_table(16384, 16384, *blocks, by=by, window=4096)
        rows = [int(_entry(table, step)[0]) for step in range(len(table))]
        longest = max(rows.count(row) for row in set(rows))
        assert (len(table), longest) == (70, 5)
    mask = _window_mask(16384, 16384, 4096)
    assert int(mask.sum()) == 58_722_304
    assert int(mask.sum()) / (16384 * 16385 // 2) == pytest.approx(0.4375, abs=2e-4)


def test_a_call_without_a_mask_walks_every_tile():
    """``causal=False`` and nothing else: every tile in the table of either
    orientation, every sub-block of it, the step count it always had."""
    import numpy as np

    from ray_tpu.ops.flash_attention import _tile_table

    for block_q, block_k in ((64, 32), (256, 512)):
        tables = flash_tile_tables(np.ones((512, 1024), bool), block_q, block_k, causal=False)
        assert len(tables["q"]) == len(tables["kv"]) == (512 // block_q) * (1024 // block_k)
    # static and small: 4 bytes a tile, 8,256 entries for a causal 131,072 in 1024-tiles
    assert _tile_table(131072, 131072, 1024, 1024, by="q").nbytes == 4 * 8256


# The grid of each of the three calls IS its table: ``(batch * heads, T)`` for
# fwd and dq, ``(batch * kv_heads, T, group)`` for dkv, whose table does not
# depend on the group. (batch, heads, kv_heads): groups of 1, 4 and 8.
@pytest.mark.parametrize("heads", [(2, 2, 2), (1, 8, 2), (1, 8, 1)])
@pytest.mark.parametrize("mode", [
    {}, {"window": 100}, {"causal": False}, {"causal": False, "block_diffusion": (128, 4)},
], ids=["causal", "window", "no_mask", "block_diffusion"])
def test_the_grids_are_the_tables(mode, heads):
    import numpy as np

    from ray_tpu.ops.flash_attention import _tile_table

    batch, heads, kv_heads = heads
    q = jnp.zeros((batch, heads, 256, 16))
    k = jnp.zeros((batch, kv_heads, 256, 16))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, block_q=64, block_k=32, **mode).sum(), argnums=(0, 1, 2),
    ))(q, k, k)

    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(inner)

    grids = [tuple(eqn.params["grid_mapping"].grid) for eqn in calls(jaxpr.jaxpr)]
    by_q, by_kv = (len(_tile_table(256, 256, 64, 32, by=by, **mode)) for by in ("q", "kv"))
    assert grids == [(batch * heads, by_q), (batch * heads, by_q), (batch * kv_heads, by_kv, heads // kv_heads)]
    mask = mode.get("causal", True) or "block_diffusion" in mode
    assert (by_q == by_kv) and (by_q < 4 * 8 if mask else by_q == 4 * 8)


# -- the same work as the parent's kernels -------------------------------------
# name: (batch, heads, kv_heads, seq_q, seq_k, dim, dtype, block, mode). Every
# mask, ``seq_q < seq_k``, groups of 1 to 4, and tiles of 256 that the masks
# cut and ``_walk`` reads in sub-blocks of 128.
_PARENT_CASES = {
    "causal": (1, 4, 2, 256, 256, 32, "float32", 64, {}),
    "causal_offset": (1, 4, 4, 128, 256, 32, "float32", 64, {}),
    "window": (2, 8, 2, 256, 256, 32, "float32", 64, {"window": 100}),
    "window_offset": (1, 2, 2, 64, 128, 16, "float32", 32, {"window": 8}),
    "block_diffusion": (1, 4, 1, 256, 256, 32, "float32", 64, {"causal": False, "block_diffusion": (128, 4)}),
    "no_mask": (1, 2, 2, 128, 256, 32, "float32", 64, {"causal": False}),
    "selection": (2, 4, 1, 256, 256, 32, "float32", 64, {"selection": True}),
    "cut_tiles": (1, 2, 1, 512, 512, 128, "bfloat16", 256, {}),
    "cut_window": (1, 2, 1, 512, 512, 128, "bfloat16", 256, {"window": 300}),
    "cut_block_diffusion": (1, 2, 1, 1024, 1024, 128, "bfloat16", 256,
                            {"causal": False, "block_diffusion": (512, 4)}),
}
# sha256 (16 hex digits) of ``out`` and ``dq`` as float32 bytes, from PR 61's
# PARENT (commit b5b6ded: the full ``rows x blocks`` grids, the closed-form
# index maps, the longest-row schedule) on the interpreter, made by running
# ``parent_outputs`` below against that commit's ``ops/flash_attention.py``.
_OUTPUTS_OF_THE_PARENT = {
    "causal": ("41a34330bb5b4685", "5b7e29715359041e"),
    "causal_offset": ("96d372ab238e2b5f", "04eb9c97233f1c62"),
    "window": ("669f502d73090d9a", "3dec9d182049fe10"),
    "window_offset": ("bf7354ba50c29268", "2dc77715762aa93a"),
    "block_diffusion": ("f9a8206aef204e35", "554bbf632f37d18f"),
    "no_mask": ("9a20f88a411c035e", "0eae0197e1e93d04"),
    "selection": ("01db79723a61ed5c", "c475c03c417f3252"),
    "cut_tiles": ("c38a12bf813af51b", "1d85f43f2bdc8d5a"),
    "cut_window": ("fb1786f7299b4ba7", "941b41d9e891df94"),
    "cut_block_diffusion": ("3193d763ab70baad", "95852dc36122c32d"),
}
# ``_rounding_of_this_cpu()`` on the machine that made them: the digests are
# of ITS float32 rounding (XLA's CPU code for a dot and an exponential)
_ROUNDING_OF_THE_PARENT_S_CPU = "40b30aef80e03dd4"


def _digest(x):
    import hashlib

    import numpy as np

    return hashlib.sha256(np.asarray(x.astype(jnp.float32)).tobytes()).hexdigest()[:16]


@functools.cache
def _rounding_of_this_cpu():
    key = jax.random.PRNGKey(61)
    a = jax.random.normal(key, (128, 128), jnp.float32)
    return _digest(jnp.exp(a @ a.T * 0.125) @ a)


def parent_outputs(flash, name):
    """``(out, dq, dk, dv)`` of ``flash`` (a ``flash_attention``) on case
    ``name``'s operands, made from the name alone."""
    batch, heads, kv_heads, seq_q, seq_k, dim, dtype, block, mode = _PARENT_CASES[name]
    mode = dict(mode)
    keys = jax.random.split(jax.random.PRNGKey(sum(map(ord, name))), 5)
    normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32).astype(dtype)
    q, g = normal(keys[0], batch, heads, seq_q, dim), normal(keys[3], batch, heads, seq_q, dim)
    k, v = normal(keys[1], batch, kv_heads, seq_k, dim), normal(keys[2], batch, kv_heads, seq_k, dim)
    if mode.pop("selection", False):
        chosen = jax.random.uniform(keys[4], (batch, seq_q, seq_k)) < 0.3
        mode["selection"] = (chosen | jnp.eye(seq_q, seq_k, dtype=bool)).astype(jnp.int8)
    out, vjp = jax.vjp(lambda q, k, v: flash(q, k, v, **mode), q, k, v)
    return (out, *vjp(g))


@pytest.mark.parametrize("name", list(_PARENT_CASES))
def test_forward_and_dq_are_bit_equal_to_the_parent_s(name):
    """The table changes which grid steps exist, not what a step does: the
    same tiles in the same order inside a q row, so ``out`` and ``dq`` are
    the parent's bit for bit. ``dk`` / ``dv`` add a kv row's tiles with the
    group's heads innermost (the parent: a head's tiles, then the next
    head's): another order of float32 sums, held to the oracle."""
    if _rounding_of_this_cpu() != _ROUNDING_OF_THE_PARENT_S_CPU:
        pytest.skip("the pinned digests are of another CPU's float32 rounding")
    block = _PARENT_CASES[name][7]
    out, dq, dk, dv = parent_outputs(
        functools.partial(flash_attention, block_q=block, block_k=block), name)
    assert (_digest(out), _digest(dq)) == _OUTPUTS_OF_THE_PARENT[name]
    _, _, want_dk, want_dv = parent_outputs(_grouped_reference, name)
    tolerance = 2e-4 if dk.dtype == jnp.float32 else 0.15
    assert float(jnp.max(jnp.abs(dk.astype(jnp.float32) - want_dk.astype(jnp.float32)))) < tolerance
    assert float(jnp.max(jnp.abs(dv.astype(jnp.float32) - want_dv.astype(jnp.float32)))) < tolerance


# One block is 32 keys here: windows of 1, 8, a block, a block +- 1, and the
# whole sequence or more, at seq_q == seq_k and at seq_q < seq_k.
# (batch, heads, kv_heads): as many KV heads as query heads, then groups of 4
# and 7 with two batch rows (the K / V row ``i // group`` crosses a batch row).
@pytest.mark.parametrize("heads", [(1, 2, 2), (2, 8, 2), (2, 7, 1)])
@pytest.mark.parametrize("seq_q", [128, 64])
@pytest.mark.parametrize("window", [1, 8, 31, 32, 33, 128, 500])
def test_flash_attention_window_matches_reference(seq_q, window, heads):
    seq_k, dim, block = 128, 16, 32
    batch, heads, kv_heads = heads
    key = jax.random.PRNGKey(23)
    q = jax.random.normal(key, (batch, heads, seq_q, dim), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (batch, kv_heads, seq_k, dim), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (batch, kv_heads, seq_k, dim), jnp.float32)

    def flash(q, k, v, window=window):
        return flash_attention(
            q, k, v, block_q=block, block_k=block, window=window,
            precision=jax.lax.Precision.HIGHEST,
        )

    def ref(q, k, v):
        return _grouped_reference(q, k, v, window=window)

    assert float(jnp.max(jnp.abs(flash(q, k, v) - ref(q, k, v)))) < 2e-5
    loss = lambda fn: lambda *a: jnp.sum(fn(*a) ** 2)
    grads = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    refs = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for g, r, name in zip(grads, refs, ("dq", "dk", "dv")):
        assert float(jnp.max(jnp.abs(g - r))) < 2e-4, name
    if window >= seq_k:
        # a window over every key is no window: bit for bit
        whole = functools.partial(flash, window=None)
        assert jnp.array_equal(flash(q, k, v), whole(q, k, v))
        for g, w in zip(grads, jax.grad(loss(whole), argnums=(0, 1, 2))(q, k, v)):
            assert jnp.array_equal(g, w)


@pytest.mark.parametrize("window", [1, 5, 32, 33])
@pytest.mark.parametrize("kernel", [True, False])
def test_window_edge_is_exact(window, kernel):
    """With one-hot values (key j's value is e_j) and equal scores, query
    i's output is 1 / count on the keys it sees: it holds key ``i - window +
    1`` and not key ``i - window``, in the kernel and in the oracle."""
    seq, block = 128, 32
    q = jnp.zeros((1, 1, seq, 8), jnp.float32)
    v = jnp.eye(seq, dtype=jnp.float32)[None, None]
    if kernel:
        out = flash_attention(
            q, q, v, block_q=block, block_k=block, window=window,
            precision=jax.lax.Precision.HIGHEST,
        )
    else:
        out = attention_reference(q, q, v, window=window)
    seen = out[0, 0] > 0
    assert jnp.array_equal(seen, _window_mask(seq, seq, window))
    i = 100
    assert seen[i, i - window + 1] and not seen[i, i - window] and not seen[i, i + 1]


def test_window_needs_causal():
    q = jnp.zeros((1, 1, 32, 8), jnp.float32)
    for fn in (flash_attention, attention_reference):
        with pytest.raises(ValueError, match="window"):
            fn(q, q, q, causal=False, window=8)
        with pytest.raises(ValueError, match="window"):
            fn(q, q, q, window=0)


# Shapes where one call holds skipped tiles, tiles the mask leaves whole and
# tiles it cuts (when causal): (batch, heads, kv_heads, seq_q, seq_k, dim,
# v_dim, block_q, block_k). The fourth has whole-vreg widths (dim and block_k
# multiples of 128), the forward's lane-dense statistics' other path. Then
# grouped KV heads, two batch rows each (query row ``b * heads + h`` reads K
# / V row ``b * kv_heads + h // group``, dkv sums a group in its scratch):
# groups of 4 and 7, the second with seq_q < seq_k and rectangular blocks;
# and two head dims (d_v != d_k) at a group of 1 with seq_q < seq_k.
_SKIP_SHAPES = [
    (1, 2, 2, 256, 256, 64, 64, 64, 64),
    (1, 2, 2, 128, 256, 32, 32, 32, 64),
    (1, 2, 2, 256, 256, 32, 32, 128, 32),
    (1, 1, 1, 512, 512, 128, 128, 128, 128),
    (2, 8, 2, 256, 256, 64, 64, 64, 64),
    (2, 7, 1, 128, 256, 32, 32, 32, 64),
    (2, 2, 2, 128, 256, 48, 32, 64, 64),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", _SKIP_SHAPES)
def test_flash_attention_skipping_matches_reference(shape, causal, dtype):
    from ray_tpu.ops.flash_attention import causal_tile_counts

    batch, heads, kv_heads, seq_q, seq_k, dim, v_dim, block_q, block_k = shape
    assert all(causal_tile_counts(seq_q, seq_k, block_q, block_k).values())
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(key, (batch, heads, seq_q, dim), dtype)
    k = jax.random.normal(
        jax.random.fold_in(key, 1), (batch, kv_heads, seq_k, dim), dtype)
    v = jax.random.normal(
        jax.random.fold_in(key, 2), (batch, kv_heads, seq_k, v_dim), dtype)
    exact = dtype == jnp.float32
    precision = jax.lax.Precision.HIGHEST if exact else None

    def flash(q, k, v):
        return flash_attention(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            precision=precision,
        ).astype(jnp.float32)

    def ref(q, k, v):
        return _grouped_reference(q, k, v, causal=causal).astype(jnp.float32)

    err = float(jnp.max(jnp.abs(flash(q, k, v) - ref(q, k, v))))
    assert err < (2e-5 if exact else 3e-2), err
    grads = jax.grad(lambda *a: jnp.sum(flash(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    refs = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    for g, r, name in zip(grads, refs, ("dq", "dk", "dv")):
        err = float(
            jnp.max(jnp.abs(g.astype(jnp.float32) - r.astype(jnp.float32)))
        )
        # a KV head's gradient is the sum of its group's: so is its rounding
        sums = heads // kv_heads if name != "dq" else 1
        assert err < (2e-4 if exact else 0.15) * sums, (name, err)


# -- a cut tile is walked in sub-blocks -----------------------------------------
# Tiles of 256 in sub-blocks of 128 (``_sub_block``), by mode: seq_q != seq_k
# (a causal offset of one tile: a row's diagonal tile is its second), a window whose lower
# edge falls inside a tile AND inside a sub-block, a selection beside the
# causal edge, and a block-diffusion block (96) that straddles sub-block edges.
_WALK_MODES = {
    "causal_rect": dict(seq_q=512, seq_k=768, causal=True),
    "window": dict(seq_q=768, seq_k=768, causal=True, window=200),
    "selection": dict(seq_q=512, seq_k=512, causal=True, selection=True),
    "block_diffusion": dict(seq_q=768, seq_k=768, causal=False, block_diffusion=(384, 96)),
}
# (heads, kv_heads, head_dim, v_dim)
_WALK_HEADS = [(2, 2, 64, 64), (4, 1, 128, 128), (4, 1, 192, 128), (1, 1, 128, 128)]


def _walk_selection(seq_q, seq_k):
    """Every third key, and a query's own: each query has a key it may see."""
    import numpy as np

    q_pos = (seq_k - seq_q) + np.arange(seq_q)[:, None]
    k_pos = np.arange(seq_k)[None, :]
    return jnp.asarray(((k_pos % 3 == 0) | (k_pos == q_pos)).astype(np.int8))[None]


@pytest.mark.parametrize("heads", _WALK_HEADS, ids=lambda h: "h{}kv{}d{}v{}".format(*h))
@pytest.mark.parametrize("mode", list(_WALK_MODES))
def test_a_cut_tile_s_walk_matches_reference(mode, heads):
    """Value and all three gradients of the kernels in the interpreter against
    ``attention_reference``, at tiles of 256 x 256: the ones a mask cuts are
    walked in sub-blocks of 128, the interior ones run whole, in one call."""
    from ray_tpu.ops.flash_attention import _sub_block

    assert _sub_block(256) == 128
    kwargs = dict(_WALK_MODES[mode])
    seq_q, seq_k = kwargs.pop("seq_q"), kwargs.pop("seq_k")
    if kwargs.pop("selection", False):
        kwargs["selection"] = _walk_selection(seq_q, seq_k)
    n_heads, kv_heads, dim, v_dim = heads
    keys = jax.random.split(jax.random.PRNGKey(seq_k + dim), 4)
    q = jax.random.normal(keys[0], (1, n_heads, seq_q, dim), jnp.float32)
    k = jax.random.normal(keys[1], (1, kv_heads, seq_k, dim), jnp.float32)
    v = jax.random.normal(keys[2], (1, kv_heads, seq_k, v_dim), jnp.float32)
    w = jax.random.normal(keys[3], (1, n_heads, seq_q, v_dim), jnp.float32)
    kernels = lambda q, k, v: jnp.sum(w * flash_attention(
        q, k, v, block_q=256, block_k=256, precision=jax.lax.Precision.HIGHEST, **kwargs))
    oracle = lambda q, k, v: jnp.sum(w * _grouped_reference(q, k, v, **kwargs))
    got = jax.jit(jax.value_and_grad(kernels, (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.value_and_grad(oracle, (0, 1, 2)))(q, k, v)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5, abs=1e-3)
    for name, mine, theirs in zip(("dq", "dk", "dv"), got[1], want[1]):
        assert mine.shape == theirs.shape, name
        err = float(jnp.max(jnp.abs(mine - theirs)))
        assert err < 2e-4 * (n_heads // kv_heads if name != "dq" else 1), (name, err)


# (seq_q, seq_k, block_q, block_k, window or None, block_diffusion or None):
# square and rectangular tiles, seq_q < seq_k, windows under, at and over a
# sub-block, a tile walked on one side only (64 rows are one part), the
# block-diffusion mask with blocks that divide and that straddle a sub-block,
# and its tiles across the clean / noised boundary.
_SUB_BLOCK_CASES = [
    (1024, 1024, 256, 256, None, None),
    (512, 1024, 256, 256, None, None),
    (1024, 1024, 512, 256, None, None),
    (768, 768, 64, 256, None, None),
    (768, 1024, 256, 512, None, None),      # the diagonal crosses sub-blocks off their corners
    (768, 1024, 256, 512, 300, None),
    (1024, 1024, 256, 256, 100, None),
    (1024, 1024, 256, 256, 128, None),
    (1024, 1024, 256, 256, 129, None),
    (1024, 1024, 256, 512, 300, None),
    (512, 1024, 256, 256, 400, None),
    (1024, 1024, 256, 256, None, (512, 4)),
    (768, 768, 256, 256, None, (384, 96)),
    (1024, 1024, 256, 512, None, (512, 32)),
    (1536, 1536, 512, 512, None, (768, 256)),
]


@pytest.mark.parametrize("by", ["q", "kv"])
@pytest.mark.parametrize("seq_q,seq_k,block_q,block_k,window,block_diffusion", _SUB_BLOCK_CASES)
def test_the_sub_block_rule_is_the_mask_s(seq_q, seq_k, block_q, block_k, window, block_diffusion, by):
    """``_sub_needed``, the rule ``_walk`` skips a sub-block by, for every
    entry of the table against the mask by enumeration: a sub-block it skips
    holds no visible pair, one it runs holds at least one. The bits are read
    as the kernels of either orientation read them (``"q"``: fwd and dq, a q
    row's steps; ``"kv"``: dkv, a kv row's), each needed tile once. The
    counters count what it runs."""
    import numpy as np

    from ray_tpu.ops import flash_attention as flash

    sub_q, sub_k = flash._sub_block(block_q), flash._sub_block(block_k)
    parts_q, parts_k = block_q // sub_q, block_k // sub_k
    if block_diffusion is None:
        mask = _window_mask(seq_q, seq_k, window or seq_k)
        mode = {"window": window}
        counts = flash.causal_tile_counts(seq_q, seq_k, block_q, block_k, window)
    else:
        rows = np.arange(seq_q)[:, None]
        mask = flash.block_diffusion_visible(rows, rows.T, *block_diffusion)
        mode = {"causal": False, "block_diffusion": block_diffusion}
        counts = flash.block_diffusion_tile_counts(*block_diffusion, block_q, block_k)
    entries = flash_tile_tables(mask, block_q, block_k, **mode)[by]
    tiles = [((row, col) if by == "q" else (col, row), bits) for row, col, _, _, bits in entries if bits]
    assert len(set(tile for tile, _ in tiles)) == len(tiles) == counts["executed"]
    assert counts["grid_steps"] == len(flash_tile_tables(mask, block_q, block_k, **mode)["q"])
    ran = np.zeros_like(mask)
    for (j, kv), bits in tiles:
        for a in range(parts_q):
            for b in range(parts_k):
                at = (slice(j * block_q + a * sub_q, j * block_q + (a + 1) * sub_q),
                      slice(kv * block_k + b * sub_k, kv * block_k + (b + 1) * sub_k))
                held = flash._sub_needed(a, b, bits, parts_k)
                assert bool(held) == bool(mask[at].any()), ((j, kv), a, b)
                ran[at] = bool(held)
    assert not (mask & ~ran).any()                            # skipped: no visible pair
    assert counts["executed_pairs"] == int(ran.sum())
    assert int(mask.sum()) <= counts["executed_pairs"] <= counts["executed"] * block_q * block_k
