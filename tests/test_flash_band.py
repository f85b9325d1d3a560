"""The flash kernels under a window narrower than their tile (ops/flash_attention.py:
``_band``, ``_band_walk``): a q tile of fwd / dq reads the keys its band
crosses as ONE block at the element offset where the band begins (a kv tile
of dkv the queries that see it), and inside it each part of the tile runs
against the rows its own band crosses. Held here: which calls take the band
(a count of pairs and steps, from the shapes and the mask alone) and that no
call without a window does; the pairs a band call executes against the tile
walk's; the forward and the three gradients against the plain float32
reference, in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention as flash

KERNELS = ("fwd", "dq", "dkv")

# The shapes the kernels were read alone at (test_chip_compile_flash.py's
# seven), and the unwindowed calls of the newest cell: (seq, head_dim, v_dim,
# mode).
_PARENTS = {
    "causal_16k": (16384, 128, 128, {}),
    "window_16k_group7": (16384, 128, 128, {"window": 4096}),
    "block_diffusion_8k_group8": (16384, 128, 128, {"block_diffusion": (8192, 4)}),
    "selection_16k_group8": (16384, 128, 128, {"selection": True}),
    "causal_4k_group8": (4096, 128, 128, {}),
    "two_head_dims_8k": (8192, 192, 128, {}),
    "head_64_16k": (16384, 64, 64, {}),
    "differential_16k": (16384, 64, 128, {}),
    "window_2048_group8": (16384, 128, 128, {"window": 2048}),
}


@pytest.mark.parametrize("name", list(_PARENTS))
def test_a_call_the_band_is_not_for_keeps_the_parent_s_tile(name):
    """No window, a selection, block diffusion, or a window as wide as two
    tiles or more: the 1024 x 1024 tile walked in 512 x 512 sub-blocks, and
    the tile walk's table, in all three kernels."""
    seq, dim, v_dim, mode = _PARENTS[name]
    chosen = mode.get("selection", False)
    blocks = flash._block_sizes(seq, seq, None, None, max(dim, v_dim), jnp.bfloat16, chosen)
    assert blocks == (1024, 1024) and flash._sub_block(1024) == 512
    mask = (mode.get("window"), chosen, mode.get("block_diffusion"))
    assert [flash._band(seq, seq, *blocks, *mask, kernel) for kernel in KERNELS] == [None] * 3
    if not chosen and "block_diffusion" not in mode:
        window = mode.get("window")
        assert flash.causal_tile_counts(seq, seq, *blocks, window) == flash._tile_counts(
            seq, seq, *blocks, window=window)


@pytest.mark.parametrize("window,band,ratio", [
    (512, {"fwd": (256, 512), "dq": (256, 512), "dkv": (512, 512)}, (2.0, 1.524)),
    (2048, {}, (1.25, 1.25)),
    (4096, {}, (1.125, 1.125)),
])
def test_the_pairs_a_windowed_call_executes_over_the_pairs_its_mask_allows(window, band, ratio):
    """At 16,384 positions in 1024-tiles: under 512 keys the tile walk ran
    two pairs for one allowed (a 512 x 512 sub-block walk runs ``512 +
    window`` keys a query whatever the key tile), the band runs 1.52 (fwd and
    dq in parts of 256 rows; dkv, whose 256-key body lost on the chip, stays
    at 2 in half the steps); at 2,048 and 4,096 the count keeps the tile
    walk."""
    seq, blocks = 16384, (1024, 1024)
    picked = {kernel: flash._band(seq, seq, *blocks, window, kernel=kernel) for kernel in KERNELS}
    assert {kernel: pick for kernel, pick in picked.items() if pick} == band
    allowed = window * seq - window * (window - 1) // 2
    walked = flash._tile_counts(seq, seq, *blocks, window=window)
    counts = flash.causal_tile_counts(seq, seq, *blocks, window)
    assert walked["executed_pairs"] / allowed == pytest.approx(ratio[0], abs=1e-3)
    assert counts["executed_pairs"] / allowed == pytest.approx(ratio[1], abs=1e-3)
    assert counts["executed_pairs"] <= walked["executed_pairs"]
    if band:
        assert counts["executed_pairs"] < walked["executed_pairs"]
        assert counts["grid_steps"] == 16 < walked["grid_steps"] == 31
        # one entry a row: where the other axis' block begins, in parts
        by_q = flash._tile_table(seq, seq, *blocks, by="q", window=window, band=band["fwd"])
        by_kv = flash._tile_table(seq, seq, *blocks, by="kv", window=window, band=band["dkv"])
        rows = [tuple(int(x) for x in flash._entry(by_q, step)[:4]) for step in range(16)]
        assert rows == [(t, max(t * 1024 - 512, 0) // 256, 1, 1) for t in range(16)]
        rows = [tuple(int(x) for x in flash._entry(by_kv, step)[:4]) for step in range(16)]
        assert rows == [(t, min(t * 1024, seq - 1536) // 512, 1, 1) for t in range(16)]


@pytest.mark.parametrize("seq,window,blocks,band", [
    (16384, 1024, (1024, 1024), [(256, 1024), (256, 1024), None]),   # dkv's body would be 512 x 1536
    (16384, 100, (1024, 1024), [(256, 256), (256, 256), (512, 512)]),
    (16384, 512, (512, 512), [(256, 512), (256, 512), None]),         # 256-sub-blocks run fewer pairs
    (1024, 512, (1024, 1024), [None] * 3),                            # no longer than tile + reach
    (2048, 512, (256, 256), [(256, 512), (256, 512), None]),          # dkv's part is two such tiles
    (1024, 600, (512, 512), [None] * 3),
    (256, 64, (256, 256), [None] * 3),
])
def test_the_band_is_decided_by_a_count_of_the_shapes_and_the_mask(seq, window, blocks, band):
    assert [flash._band(seq, seq, *blocks, window, kernel=kernel) for kernel in KERNELS] == band
    # cross attention's lengths, a selection's tile and block diffusion keep the tiles
    assert flash._band(seq, 2 * seq, *blocks, window) is None
    assert flash._band(seq, seq, *blocks, window, True) is None


def _operands(heads, kv_heads, seq, dim, v_dim, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)
    return (normal(keys[0], 1, heads, seq, dim), normal(keys[1], 1, kv_heads, seq, dim),
            normal(keys[2], 1, kv_heads, seq, v_dim), normal(keys[3], 1, heads, seq, v_dim))


@pytest.mark.parametrize("heads,kv_heads,seq,dim,v_dim,window,block,band", [
    (4, 2, 2048, 64, 128, 512, None, True),    # Phi-4-flash's call: 20 / 10 heads, 64 | 128
    (2, 1, 2048, 128, 128, 300, None, True),   # a window that is no whole part
    (4, 2, 2048, 128, 128, 512, 256, True),    # fwd and dq alone: dkv walks its 256-tiles
    (8, 1, 1024, 128, 128, 600, 512, False),   # Trinity's 32 / 4 heads, a window two tiles wide
])
def test_forward_and_gradients_match_the_reference_under_a_band(heads, kv_heads, seq, dim, v_dim,
                                                                 window, block, band):
    q, k, v, g = _operands(heads, kv_heads, seq, dim, v_dim)
    blocks = flash._block_sizes(seq, seq, block, block, max(dim, v_dim), q.dtype)
    assert (flash._band(seq, seq, *blocks, window) is not None) == band
    repeat = lambda x: jnp.repeat(x, heads // kv_heads, axis=1)

    def reference(q, k, v):
        return flash.attention_reference(q, repeat(k), repeat(v), window=window)

    def kernels(q, k, v):
        return flash.flash_attention(q, k, v, window=window, block_q=block, block_k=block,
                                     precision=jax.lax.Precision.HIGHEST)

    want, want_vjp = jax.vjp(reference, q, k, v)
    got, got_vjp = jax.vjp(kernels, q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    for name, mine, theirs in zip(("dq", "dk", "dv"), got_vjp(g), want_vjp(g)):
        assert mine.shape == theirs.shape, name
        np.testing.assert_allclose(mine, theirs, atol=1e-4, rtol=1e-4, err_msg=name)


def test_a_band_call_hands_out_the_reference_s_lse_in_bfloat16():
    """bfloat16 operands on the MXU's own multiply, ``lse`` handed out: the
    band's first q tile (no key before key 0) and its last kv tile (no query
    past the last) read their blocks from where the sequence begins and
    ends."""
    q, k, v, _ = (x.astype(jnp.bfloat16) for x in _operands(2, 1, 2048, 64, 64, seed=1))
    out, lse = flash.flash_attention(q, k, v, window=512, return_lse=True)
    want, want_lse = flash.attention_reference(
        q, jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1), window=512, return_lse=True)
    np.testing.assert_allclose(lse, want_lse, atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(out.astype(jnp.float32), want.astype(jnp.float32), atol=3e-2, rtol=3e-2)
