"""Block-diffusion training (SDAR-30B-A3B-Chat's objective, BD3-LM's): the
flash kernels' fourth mask in the interpreter against the oracle under the
explicit mask, their tile walk against an enumeration, the model's loss and
every gradient against the plain reference on routers that route, the three
properties of the mask on the model, the noise, what is refused by name, and
that the three older masks lower to the Mosaic modules they did.

Small sizes (hidden 48, 32 to 128 trained positions, blocks of 4 and 16):
the cell's real step is compiled for a described v5e in
``benchmarks/tests/test_compile_v5e_sdar.py``.
"""

import dataclasses
import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import block_diffusion_moe_decoder as family_module
from benchmarks.reference import block_diffusion_moe_decoder as reference
from ray_tpu.models import transformer as T
from ray_tpu.ops import flash_attention as flash
from ray_tpu.ops.flash_attention import attention_reference, flash_attention

import model_helpers
from model_helpers import close, flash_mosaic_modules, flash_tile_tables, listed

SEQ, BLOCK = 32, 4
CONFIG = {
    "name": "tiny-noised-moe", "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 48, "mlp_only_layers": [], "moe_intermediate_size": 24,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 256, "torch_dtype": "float32",
    "first_expert_held": 4, "published": {"num_experts": 8},
    "block_length": BLOCK, "mask_token_id": 255, "t_min": 1e-3,
}
TRAFFIC = {"seq_len": SEQ, "batch_size": 2, "remat": None}
FAMILY = family_module.build(CONFIG, TRAFFIC)
MODEL = FAMILY.model
NOISE = jnp.array([11, 12], jnp.int32)


@pytest.fixture(scope="module")
def params():
    """The program's initialiser's weights (routers that ROUTE), every norm
    weight moved off 1."""
    params = jax.jit(lambda key: T.init_params(MODEL, key))(jax.random.PRNGKey(3))
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 16))
    layers = params["layers"]
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        layers[name] = layers[name] + 0.2 * jax.random.normal(next(keys), layers[name].shape)
    params["final_norm"] = params["final_norm"] + 0.2 * jax.random.normal(next(keys), (MODEL.dim,))
    return params


ids = functools.partial(model_helpers.ids, seq=SEQ)


def reference_weights(params):
    return listed(FAMILY.reference_weights(params))


# -- the kernels under the fourth mask ----------------------------------------
@pytest.mark.parametrize("clean_len,block,heads,kv_heads,tile", [
    (32, 4, 2, 2, 16),        # tiles divide L; a group of 1
    (48, 4, 8, 1, 32),        # a tile straddles the clean / noised boundary; a group of 8
    (96, 16, 4, 2, 64),       # blocks of 16, a straddling tile, a group of 2
    (128, 4, 8, 1, (32, 64)),  # block_q != block_k, a group of 8
], ids=["divides", "straddles_group8", "blocks16", "uneven_tiles_group8"])
def test_the_kernels_match_the_oracle_under_the_explicit_mask(clean_len, block, heads, kv_heads, tile):
    """Forward and all three gradients in the interpreter against
    ``attention_reference`` under the ``[2L, 2L]`` mask, K and V at their own
    heads."""
    block_q, block_k = tile if isinstance(tile, tuple) else (tile, tile)
    keys = jax.random.split(jax.random.PRNGKey(clean_len), 4)
    normal = lambda key, n: jax.random.normal(key, (2, n, 2 * clean_len, 16), jnp.float32)
    q, k, v, w = normal(keys[0], heads), normal(keys[1], kv_heads), normal(keys[2], kv_heads), \
        normal(keys[3], heads)
    mode = (clean_len, block)
    kernels = lambda q, k, v: jnp.sum(w * flash_attention(
        q, k, v, causal=False, block_diffusion=mode, block_q=block_q, block_k=block_k,
        precision=jax.lax.Precision.HIGHEST))
    repeats = heads // kv_heads
    oracle = lambda q, k, v: jnp.sum(w * attention_reference(
        q, jnp.repeat(k, repeats, axis=1), jnp.repeat(v, repeats, axis=1), causal=False,
        block_diffusion=mode))
    got = jax.jit(jax.value_and_grad(kernels, (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.value_and_grad(oracle, (0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for name, mine, theirs in zip("qkv", got[1], want[1]):
        assert mine.shape == theirs.shape, name
        close(mine, theirs, 1e-4, name)


def _explicit(clean_len, block):
    rows = np.arange(2 * clean_len)[:, None]
    return flash.block_diffusion_visible(rows, rows.T, clean_len, block)


def test_the_mask_is_the_definition():
    """``block_diffusion_visible`` against the four cases written out a pair
    at a time, and against the plain reference's rows."""
    clean_len, block = 24, 4
    got = _explicit(clean_len, block)
    for i in range(2 * clean_len):
        for j in range(2 * clean_len):
            blk_i, blk_j = (i % clean_len) // block, (j % clean_len) // block
            if i < clean_len:
                want = j < clean_len and blk_j <= blk_i
            else:
                want = blk_j < blk_i if j < clean_len else blk_j == blk_i
            assert got[i, j] == want, (i, j)
    assert np.array_equal(got, reference.visible(jnp.arange(2 * clean_len), clean_len, block))
    assert got.any(axis=1).all()                              # every row has a key
    assert np.array_equal(_explicit(24, 12), np.asarray(reference.visible(jnp.arange(48), 24, 12)))


@pytest.mark.parametrize("clean_len,block,block_q,block_k", [
    (32, 4, 16, 16), (48, 4, 32, 32), (96, 16, 64, 64), (64, 16, 16, 32), (128, 4, 32, 64),
    (96, 4, 64, 32), (24, 12, 16, 16), (64, 4, 128, 128),
    # tiles walked in sub-blocks of 128: blocks that divide one and that straddle its edges
    (512, 4, 256, 256), (384, 96, 256, 256), (512, 32, 256, 512),
])
def test_the_tile_counts_and_the_walk_are_the_enumeration_s(clean_len, block, block_q, block_k):
    rows = 2 * clean_len
    mask = _explicit(clean_len, block)
    needed = mask.reshape(rows // block_q, block_q, rows // block_k, block_k).any(axis=(1, 3))
    sub_q, sub_k = flash._sub_block(block_q), flash._sub_block(block_k)
    assert (sub_q, sub_k) == (block_q // 2 if block_q >= 256 else block_q, block_k // 2 if block_k >= 256 else block_k)
    parts_q, parts_k = block_q // sub_q, block_k // sub_k
    # [q tile, kv tile, q part, kv part]: whether the sub-block holds an allowed pair
    held = mask.reshape(rows // block_q, parts_q, sub_q, rows // block_k, parts_k, sub_k).any(axis=(2, 5))
    held = held.transpose(0, 2, 1, 3)
    counts = flash.block_diffusion_tile_counts(clean_len, block, block_q, block_k)
    assert counts == {
        "skipped": int((~needed).sum()), "executed": int(needed.sum()),
        "allowed_pairs": int(mask.sum()), "executed_pairs": int(held.sum()) * sub_q * sub_k,
        "grid_steps": int(needed.sum()),
    }
    assert counts["allowed_pairs"] == clean_len ** 2 + clean_len * block
    # the table of either orientation: each needed tile once, a row's in order, its sub-blocks' bits
    tables = flash_tile_tables(mask, block_q, block_k, causal=False, block_diffusion=(clean_len, block))
    assert counts["grid_steps"] == len(tables["q"]) == len(tables["kv"]) == counts["executed"]
    assert all(bits for _, _, _, _, bits in tables["q"] + tables["kv"])
    for row, col, _, _, bits in tables["q"]:                      # bit a * parts_k + b: part (a, b)
        got = [[bits >> (a * parts_k + b) & 1 for b in range(parts_k)] for a in range(parts_q)]
        assert np.array_equal(got, held[row, col]), (row, col)


def test_the_cell_s_walk():
    """8,192 trained positions in blocks of 4 under 1024 x 1024 tiles: 80 of
    256 tiles a head (a causal 16,384 runs 136), rows of 1 .. 8 and 2 .. 9.
    In sub-blocks of 512: the 16 half-masked diagonal tiles (clean rows and
    noised rows on the clean keys) run 3 of their 4, the 8 noised-diagonal
    tiles 2: 72 tiles' worth of pairs for the 64.03 the mask allows."""
    counts = flash.block_diffusion_tile_counts(8192, 4, 1024, 1024)
    assert counts == {"skipped": 176, "executed": 80, "allowed_pairs": 8192 ** 2 + 8192 * 4,
                      "executed_pairs": (80 * 4 - 16 - 2 * 8) * 512 ** 2, "grid_steps": 80}
    assert 100 * counts["allowed_pairs"] / counts["executed_pairs"] == pytest.approx(88.9, abs=0.05)
    assert flash.causal_tile_counts(16384, 16384, 1024, 1024)["executed"] == 136
    rows = lambda by: [[entry for entry in _entries(by) if entry[0] == row] for row in range(16)]
    assert [len(row) for row in rows("q")] == [*range(1, 9), *range(2, 10)]
    # a clean key tile: the clean rows from it on and the noised rows behind it; a noised one: one
    assert [len(row) for row in rows("kv")] == [2 * (8 - c) for c in range(8)] + [1] * 8
    # q row 9 (noised rows 1024 ..): clean tile 0 whole, clean tile 1 its lower half, its own noised diagonal
    assert [(col, bits) for _, col, _, _, bits in rows("q")[9]] == [(0, 0b1111), (1, 0b1101), (9, 0b1001)]
    # the grid: 80 steps a head in all three kernels, where the longest rows walked 16 x 9 and 16 x 16
    assert counts["grid_steps"] == len(_entries("q")) == len(_entries("kv")) == 80


def _entries(by):
    table = flash._tile_table(16384, 16384, 1024, 1024, by=by, causal=False, block_diffusion=(8192, 4))
    return [tuple(int(field) for field in flash._entry(table, step)) for step in range(len(table))]


# -- the three older masks lower to what they did -----------------------------
# sha256 of the three Mosaic modules of ``jax.grad(flash_attention)`` (fwd,
# dq, dkv; [1, 4 / 2, 256, 128] bfloat16), lowered for a TPU, parsed and
# printed WITHOUT source locations, by mode. What they hold: that ONE mask's
# arrival or change leaves the others' modules alone, not that the modules
# never change. PR 59 pinned what ITS parent lowered, to show the fourth
# mask's arrival changed none; PR 60 changed the kernels on purpose (a 256 x
# 256 tile a mask cuts is walked in sub-blocks of 128); PR 61 again (every
# mask's grid is its prefetched table of tiles: ``_tile_table``) and these are
# what its tree lowers. A change to the kernels that is meant changes these
# lines; one that is not must not.
MODULES_OF_THE_PARENT = {
    "causal": "cee61e46bb6d140a815cf836607ff0a5377bf4dce77da9d0468e2863488430a3",
    "window": "ebca63f44de39cc43cd377ca9544cfb495d8873193574259cca05401e685ce8b",
    "selection": "3f73944085b5ef09926e5236639819a60ed3c3170f6d54c529ce9ba93507f6f0",
}


@pytest.mark.parametrize("name,mode", [
    ("causal", {}), ("window", {"window": 64}), ("selection", {"selection": True}),
])
def test_the_older_masks_lower_to_the_parent_s_modules(name, mode):
    modules = flash_mosaic_modules(**mode)
    assert len(modules) == 3
    assert hashlib.sha256("\n".join(modules).encode()).hexdigest() == MODULES_OF_THE_PARENT[name]


def test_the_fourth_mask_lowers_for_a_tpu_with_its_walk_prefetched():
    modules = flash_mosaic_modules(causal=False, block_diffusion=(128, 4))
    assert len(modules) == 3 and all("arith.cmpi" in module for module in modules)
    digest = lambda found: hashlib.sha256("\n".join(found).encode()).hexdigest()
    assert digest(modules) not in MODULES_OF_THE_PARENT.values()


# -- the noise ----------------------------------------------------------------
def test_the_noise_is_a_function_of_the_integer_alone():
    tokens = ids(seed=5, batch=2)
    draw = jax.jit(lambda tokens, noise: T.block_diffusion_noise(tokens, noise, MODEL))
    xt, m, t = draw(tokens, NOISE)
    again = draw(ids(seed=6, batch=2), NOISE)                    # other tokens, the same integers
    assert jnp.array_equal(m, again[1]) and jnp.array_equal(t, again[2])
    other = draw(tokens, NOISE + 2)
    assert not jnp.array_equal(m, other[1]) and not jnp.array_equal(t, other[2])
    assert not jnp.array_equal(m[0], m[1])                       # a sequence its own integer
    swapped = draw(tokens[::-1], NOISE[::-1])
    assert jnp.array_equal(swapped[1], m[::-1])                  # and nothing else of the batch
    assert jnp.array_equal(xt, jnp.where(m, 255, tokens)) and m.dtype == jnp.bool_
    by_block = np.asarray(t).reshape(2, SEQ // BLOCK, BLOCK)
    assert np.all(by_block == by_block[..., :1]) and np.unique(by_block[..., 0]).size == 2 * SEQ // BLOCK
    assert t.dtype == jnp.float32 and float(t.min()) > 1e-3 and float(t.max()) <= 1.0


def test_half_the_positions_are_masked_on_average():
    """``t ~ U(t_min, 1]`` a block and ``m ~ Bernoulli(t)``: the masked share
    is 0.5 and follows the levels."""
    model = dataclasses.replace(MODEL, max_seq=2048)
    tokens = jnp.zeros((16, 2048), jnp.int32)
    _, m, t = jax.jit(lambda n: T.block_diffusion_noise(tokens, n, model))(jnp.arange(16, dtype=jnp.int32))
    assert abs(float(m.mean()) - 0.5) < 0.02 and abs(float(t.mean()) - 0.5005) < 0.02
    facts = reference.noise_facts(tokens, {"xt": jnp.where(m, 255, tokens), "m": m, "t": t}, CONFIG)
    assert facts["masked_sigmas"] < 4.0 and facts["xt_is_masked_x0"] and facts["one_level_a_block"]
    # the masked share of the high-level blocks is high
    high = np.asarray(t) > 0.75
    assert float(np.asarray(m)[high].mean()) > 0.8 and float(np.asarray(m)[~high].mean()) < 0.45


# -- the model against the plain reference ------------------------------------
def _drawn(tokens):
    return jax.jit(lambda tokens: T.block_diffusion_noise(tokens, NOISE, MODEL))(tokens)


@pytest.mark.parametrize("attention", ["flash", "reference"])
def test_logits_and_routing_match_the_reference(params, attention):
    tokens = ids(seed=1)
    model = dataclasses.replace(MODEL, attention=attention)
    got, routing, drawn = jax.jit(
        lambda p, t: T.block_diffusion_forward(p, t, NOISE, model))(params, tokens)
    xt, m, t = _drawn(tokens)
    assert jnp.array_equal(drawn["xt"], xt) and jnp.array_equal(drawn["m"], m)
    want, routings = reference.logits(reference_weights(params), tokens, xt, dict(CONFIG))
    assert got.shape == (2, SEQ, 256) and got.dtype == jnp.float32      # the noised half's rows alone
    close(got, want, 5e-4)
    assert routing["experts"].shape == (2, 2 * 2 * SEQ, 2)              # the stream's 2 L rows a layer
    for layer, theirs in enumerate(routings):
        assert jnp.array_equal(jnp.sort(routing["experts"][layer]), jnp.sort(theirs["experts"]))


@pytest.mark.parametrize("remat", [None, "full"])
def test_loss_and_every_gradient_leaf_match_the_reference(params, remat):
    """The objective through the flash kernels: the value and every leaf's
    gradient are the reference's, handed the ``xt``, ``m`` and ``t`` the
    program drew."""
    tokens = ids(seed=2)
    xt, m, t = _drawn(tokens)
    config = dict(CONFIG)
    want, wanted = jax.jit(jax.value_and_grad(
        lambda w: reference.loss(w, tokens, xt, m, t, config)))(reference_weights(params))
    model = dataclasses.replace(MODEL, remat=remat)
    got, grads = jax.jit(jax.value_and_grad(
        lambda p: T.block_diffusion_loss_fn(p, tokens, NOISE, model)))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for published, own in {**family_module.ATTENTION, **family_module.MOE}.items():
        stacked = jnp.stack([layer[published] for layer in wanted["layers"]])
        close(grads["layers"][own], stacked, 2e-3, own)
        assert float(jnp.max(jnp.abs(stacked))) > 0, own
    for published, own in (("embed_tokens", "embed"), ("norm", "final_norm"), ("lm_head", "lm_head")):
        close(grads[own], wanted[published], 2e-3, own)
    # under a mask the mean is over its positions: the reference's over the same
    counted = jnp.zeros((2, SEQ), bool).at[:, -8:].set(True)
    masked = jax.jit(lambda p: T.block_diffusion_loss_fn(p, tokens, NOISE, model, mask=counted))(params)
    np.testing.assert_allclose(
        masked, reference.loss(reference_weights(params), tokens, xt, m, t, config, counted), rtol=1e-5)


def test_full_remat_does_not_run_the_flash_forward_twice(params):
    """PR 29's rule for the other masks: the layer checkpoint keeps the
    forward kernel's named ``out`` and ``lse``, so the step holds one forward
    call, one dq and one dkv a scanned layer."""
    tokens = ids(seed=2)
    model = dataclasses.replace(MODEL, remat="full")
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: T.block_diffusion_loss_fn(p, tokens, NOISE, model)))(params)

    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(inner)

    # the layer's nine grouped matmuls are pallas calls too: the flash ones take q of 4 heads
    flash_calls = [eqn for eqn in calls(jaxpr.jaxpr) if any(
        tuple(v.aval.shape) == (2 * 4, 2 * SEQ, 16) for v in eqn.invars)]
    assert len(flash_calls) == 3


def test_the_check_passes_on_the_program_and_counts_what_it_drew(params):
    tokens = ids(seed=7)
    program = jax.jit(FAMILY.forward)(params, tokens)
    result = FAMILY.check(program[:, -8:], params, tokens, last=8)
    assert result["ok"], result
    assert result["published"]["rel_rms"] < 1e-4 and result["loss_rel"] < 1e-5
    assert result["terms_rel_rms"] < 1e-4
    assert result["harness_rel_rms"] < 1e-6 and result["noise_ok"]
    assert result["flash_pairs"]["allowed_pairs"] == SEQ * SEQ + SEQ * BLOCK
    assert result["masked_targets_pct"] == result["noise"]["masked_targets"] / (2 * SEQ) * 100.0
    assert reference.checked_rows(SEQ, 8) == [slice(0, 8), slice(24, 32)]
    assert reference.checked_rows(SEQ, None) == [slice(0, SEQ)] and reference.checked_rows(12, 8) == [slice(0, 12)]


def test_the_noise_has_its_scope_inside_embed_and_the_kernels_stay_under_attention(params):
    """``noise`` is read by name (``benchmarks/layer_metrics/noise_ms.py``)
    and lies inside ``embed``, one of the six block scopes the coverage
    counts; the flash calls under the new mask keep the ``attention`` scope."""
    tokens = ids(seed=2)
    text = jax.jit(jax.grad(
        lambda p: T.block_diffusion_loss_fn(p, tokens, NOISE, MODEL))).lower(params).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    noise = [name for name in names if re.search(r"(?:^|[/(])noise(?=[/)]|$)", name)]
    # jax writes the outer scope inside its own wrapper: jvp(embed)/noise/...
    assert noise and all(re.search(r"[/(]embed\)?/noise[/)]|[/(]embed\)?/noise$", name) for name in noise)
    assert any("_uniform" in name for name in noise)                            # the draw itself
    assert "noise" not in T.SCOPES and T.SCOPES[0] == "embed"
    jaxpr = str(jax.make_jaxpr(lambda p: T.block_diffusion_loss_fn(p, tokens, NOISE, MODEL))(params))
    assert "_flash_forward" in jaxpr


# -- three properties of the mask, on the model --------------------------------
def test_what_a_noised_block_sees_and_what_the_clean_half_does_not(params):
    """Noised block ``b``'s logits do not move when ``x0`` changes in blocks
    ``>= b``; they move in block ``b`` alone when ``xt`` changes in block
    ``b``; and the clean half's hidden states do not depend on ``xt``."""
    tokens = ids(seed=9, batch=1)
    xt, m, _ = jax.tree.map(lambda a: a[:1], _drawn(jnp.concatenate([tokens, tokens])))
    length, b = SEQ, 3
    first, behind = b * BLOCK, (b + 1) * BLOCK
    positions = jnp.tile(jnp.arange(length, dtype=jnp.int32), 2)[None]

    @jax.jit
    def stream(x0, xt):
        """``(the noised half's logits, the clean half's hidden states)`` of
        ``[x0 ; xt]`` under the mask, ``xt`` handed in."""
        x, _ = T._hidden_with_routing(
            params, jnp.concatenate([x0, xt], axis=1), MODEL, positions,
            block_diffusion=(length, BLOCK))
        return T._head(params, x[:, length:], MODEL), x[:, :length]

    logits, clean = stream(tokens, xt)
    # (1) the clean tokens of block b and behind are not seen by noised block b (nor by those before it)
    later = tokens.at[:, first:].set((tokens[:, first:] + 7) % 250)
    moved, _ = stream(later, xt)
    assert jnp.array_equal(moved[:, :behind], logits[:, :behind])
    assert not jnp.allclose(moved[:, behind:], logits[:, behind:])       # the blocks behind b do see them
    # (2) the noised tokens of block b are seen by block b alone
    other = xt.at[:, first:behind].set((xt[:, first:behind] + 3) % 250)
    moved, same_clean = stream(tokens, other)
    changed = np.asarray(jnp.any(moved != logits, axis=-1))[0]
    assert changed[first:behind].all() and not changed[:first].any() and not changed[behind:].any()
    # (3) nothing of xt reaches the clean half
    assert jnp.array_equal(same_clean, clean)
    everything, still_clean = stream(tokens, (xt + 1) % 250)
    assert jnp.array_equal(still_clean, clean) and not jnp.allclose(everything, logits)
    # and a clean position sees the rest of its own block: a later token of block b moves its first
    inside = tokens.at[:, behind - 1].set((tokens[:, behind - 1] + 5) % 250)
    _, looked_ahead = stream(inside, xt)
    changed = np.asarray(jnp.any(looked_ahead != clean, axis=-1))[0]
    assert changed[first] and not changed[:first].any()


# -- what is not written refuses by name --------------------------------------
def test_what_block_diffusion_cannot_do_yet_is_refused_by_name(params):
    tokens = ids()
    q = jnp.zeros((1, 2, 64, 16))
    for given, named in (
        ({"causal": True}, "excludes causal"),
        ({"causal": True, "window": 8}, "excludes causal, window"),
        ({"causal": False, "selection": jnp.ones((1, 64, 64), jnp.int8)}, "excludes selection"),
    ):
        with pytest.raises(ValueError, match=named):
            flash_attention(q, q, q, block_diffusion=(32, 4), **given)
    with pytest.raises(ValueError, match="clean_len a multiple of block"):
        flash_attention(q, q, q, causal=False, block_diffusion=(32, 5))
    with pytest.raises(ValueError, match="seq_q == seq_k == 2 \\* clean_len"):
        flash_attention(q, q, q, causal=False, block_diffusion=(16, 4))
    with pytest.raises(ValueError, match="window=8 needs causal=True.*the four modes"):
        flash_attention(q, q, q, causal=False, window=8)
    bd = MODEL.block_diffusion
    linear = T.LinearAttentionConfig(num_key_heads=4, num_value_heads=4, key_head_dim=16, value_head_dim=16)
    for fields, named in (
        ({"latent": T.LatentAttentionConfig()}, "beside a latent mixer"),
        ({"sparse": T.SparseAttentionConfig(topk=8)}, "beside a sparse mixer"),
        ({"layer_pattern": ("window", "full"), "window": 8}, "beside a window mixer"),
        ({"layer_pattern": ("linear", "full"), "linear": linear}, "beside a linear mixer"),
        ({"layer_pattern": ("conv", "full")}, "beside a conv mixer"),
        ({"layer_pattern": ("ssm", "full"), "ssm": T.SSMConfig()}, "beside a ssm mixer"),
        ({"attention": lambda q, k, v, causal: q}, "callable attention="),
    ):
        with pytest.raises(NotImplementedError, match=named):
            T.TransformerConfig.tiny(block_diffusion=bd, **fields)
    with pytest.raises(ValueError, match="block_length 0"):
        T.BlockDiffusionConfig(block_length=0)
    whole_heads = dataclasses.replace(MODEL, dim=64)         # 4 heads of 16: no stated head_dim in the way
    with pytest.raises(NotImplementedError, match="denoises a block of block_length positions"):
        T.init_kv_cache(whole_heads, 1, 16)
    with pytest.raises(NotImplementedError, match="denoises a block of block_length positions"):
        T.decode_step(params, {}, ids(batch=1, seq=1), whole_heads)
    with pytest.raises(ValueError, match="train it through block_diffusion_loss_fn"):
        T.loss_fn(params, tokens, tokens, MODEL)
    assert np.isfinite(float(jax.jit(                         # the causal loss, asked for by name
        lambda p: T.loss_fn(p, tokens, tokens, MODEL, next_token=True))(params)))
    plain = dataclasses.replace(MODEL, block_diffusion=None)
    with pytest.raises(ValueError, match="needs a config with block_diffusion="):
        T.block_diffusion_loss_fn(params, tokens, NOISE, plain)
    with pytest.raises(ValueError, match="no multiple of block_length"):
        T.block_diffusion_noise(tokens[:, :30], NOISE, MODEL)
    with pytest.raises(ValueError, match="mask= .* or weights="):
        T.head_loss(params, jnp.zeros((2, SEQ, MODEL.dim)), tokens, MODEL,
                    mask=jnp.ones((2, SEQ)), weights=jnp.ones((2, SEQ)))


def _loss_over(mesh_axes, params, tokens, noise):
    """The objective traced under a mesh of ``mesh_axes``, the batch over its data axes."""
    from jax.sharding import NamedSharding, PartitionSpec
    from ray_tpu.parallel.mesh import LogicalRules, MeshSpec

    spec = MeshSpec(dict(mesh_axes))
    mesh = spec.build(jax.devices()[:spec.size])
    rows = NamedSharding(mesh, LogicalRules().spec(("batch", None), mesh))
    params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
    tokens = jax.device_put(tokens, rows)

    def loss(params, tokens, noise):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return T.block_diffusion_loss_fn(params, tokens, noise, MODEL)

    return float(jax.jit(loss)(params, tokens, noise))


def test_data_and_head_shards_give_the_one_device_s_loss_and_sp_is_refused(params):
    tokens = ids(seed=8, batch=4)
    noise = jnp.arange(4, dtype=jnp.int32) + 20
    one = float(jax.jit(lambda p: T.block_diffusion_loss_fn(p, tokens, noise, MODEL))(params))
    np.testing.assert_allclose(_loss_over({"dp": 2}, params, tokens, noise), one, rtol=2e-6)
    np.testing.assert_allclose(_loss_over({"dp": 2, "tp": 2}, params, tokens, noise), one, rtol=2e-5)
    with pytest.raises(NotImplementedError, match="block_diffusion over a mesh with sp > 1"):
        _loss_over({"dp": 2, "sp": 2}, params, tokens, noise)
