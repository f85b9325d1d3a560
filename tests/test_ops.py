"""Pallas kernel numerics vs pure-jax references (CPU interpret mode — the
same kernel code the TPU compiles, SURVEY §4.4 'CPU twin' trick)."""

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.flash_attention import attention_reference, flash_attention
from ray_tpu.ops.rmsnorm import rmsnorm, rmsnorm_reference
from ray_tpu.ops.rope import apply_rope, rope_frequencies


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
    (1000, 1000, 512, 512),
]


@pytest.mark.parametrize("seq_q,seq_k,block_q,block_k", _TILE_CASES)
def test_causal_tile_skip_matches_dense_mask(seq_q, seq_k, block_q, block_k):
    import numpy as np

    from ray_tpu.ops.flash_attention import (
        _block_sizes, _kv_index_map, _q_index_map, _tile_needed,
        causal_tile_counts,
    )

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
    kv_map = _kv_index_map(True, offset, block_q, block_k, nk)
    q_map = _q_index_map(True, offset, block_q, block_k, nq)
    for j in range(nq):
        for kv in range(nk):
            assert _tile_needed(
                True, offset, j, kv, block_q, block_k) == needed[j, kv], (j, kv)
            # An executed step fetches its own blocks; a skipped one names
            # a block of the same row that is needed (or, in a row with
            # none, one block for the whole row): no new fetch.
            fetched_kv = int(kv_map(0, j, kv)[1])
            fetched_q = int(q_map(0, kv, j)[1])
            if needed[j, kv]:
                assert (fetched_kv, fetched_q) == (kv, j)
            else:
                row = np.flatnonzero(needed[j])
                col = np.flatnonzero(needed[:, kv])
                assert fetched_kv == (row[-1] if row.size else 0)
                assert fetched_q == (col[0] if col.size else nq - 1)
    assert causal_tile_counts(seq_q, seq_k, block_q, block_k) == {
        "skipped": int((~needed).sum()), "executed": int(needed.sum())}


@pytest.mark.parametrize("seq,counts", [
    (16384, {"skipped": 120, "executed": 136}),
    (4096, {"skipped": 6, "executed": 10}),
])
def test_causal_tile_counts_of_the_benchmark_cells(seq, counts):
    """At the block shape the cells run (head_dim 128, bfloat16)."""
    from ray_tpu.ops.flash_attention import _block_sizes, causal_tile_counts

    blocks = _block_sizes(seq, seq, None, None, 128, jnp.bfloat16)
    assert blocks == (1024, 1024)
    assert causal_tile_counts(seq, seq, *blocks) == counts


# Shapes where one call holds skipped tiles, tiles the mask leaves whole and
# tiles it cuts (when causal): (batch, heads, seq_q, seq_k, dim, block_q,
# block_k). The last has
# whole-vreg widths (dim and block_k multiples of 128), the forward's
# lane-dense statistics' other path.
_SKIP_SHAPES = [
    (1, 2, 256, 256, 64, 64, 64),
    (1, 2, 128, 256, 32, 32, 64),
    (1, 2, 256, 256, 32, 128, 32),
    (1, 1, 512, 512, 128, 128, 128),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", _SKIP_SHAPES)
def test_flash_attention_skipping_matches_reference(shape, causal, dtype):
    from ray_tpu.ops.flash_attention import causal_tile_counts

    batch, heads, seq_q, seq_k, dim, block_q, block_k = shape
    assert all(causal_tile_counts(seq_q, seq_k, block_q, block_k).values())
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(key, (batch, heads, seq_q, dim), dtype)
    k = jax.random.normal(
        jax.random.fold_in(key, 1), (batch, heads, seq_k, dim), dtype)
    v = jax.random.normal(
        jax.random.fold_in(key, 2), (batch, heads, seq_k, dim), dtype)
    exact = dtype == jnp.float32
    precision = jax.lax.Precision.HIGHEST if exact else None

    def flash(q, k, v):
        return flash_attention(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            precision=precision,
        ).astype(jnp.float32)

    def ref(q, k, v):
        return attention_reference(q, k, v, causal=causal).astype(jnp.float32)

    err = float(jnp.max(jnp.abs(flash(q, k, v) - ref(q, k, v))))
    assert err < (2e-5 if exact else 3e-2), err
    grads = jax.grad(lambda *a: jnp.sum(flash(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    refs = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    for g, r, name in zip(grads, refs, ("dq", "dk", "dv")):
        err = float(
            jnp.max(jnp.abs(g.astype(jnp.float32) - r.astype(jnp.float32)))
        )
        assert err < (2e-4 if exact else 0.15), (name, err)
