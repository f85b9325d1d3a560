"""The learned sparse attention (``ops/flash_attention.py``'s ``selection``,
``ops/sparse_index.py`` and ``models/transformer.py``'s "sparse" layers over
held experts: Keye-VL-2.0's shape) on the CPU at tiny widths with seeded
weights: the masked kernels (interpret mode) against the oracle, the mixer
and the loss with the scorer's term against the PLAIN REFERENCE of the
benchmark (``benchmarks/reference/sparse_gqa_moe_decoder.py``: float32, a
real top-k and a scatter, no kernel, no scan), values and gradients, where
the scorer's gradient comes from and where it goes, the held shares of an
expert layer, and every refusal.

Tolerances, each of the largest value compared: kernel outputs 2e-5 and
gradients 2e-4 (``tests/test_ops.py``'s), logits 5e-4, loss 1e-5, gradients
2e-3 (``tests/test_window_moe.py``'s and for its reasons: both sides float32,
sums in another order). A wrong term is off by far more.
"""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import sparse_gqa_moe_decoder as family_module
from benchmarks.reference import sparse_gqa_moe_decoder as reference
from ray_tpu.models import transformer as T
from ray_tpu.ops import sparse_index
from ray_tpu.ops.flash_attention import attention_reference, flash_attention

import model_helpers
from model_helpers import close, flash_mosaic_modules, listed, loss_and_grads

SEQ, TOPK = 32, 8
CONFIG = {
    "name": "tiny-sparse-moe", "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 48, "mlp_only_layers": [], "moe_intermediate_size": 24,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default", "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4, "indexer_num_kv_heads": 1,
                  "kv_chunk_size": 8, "q_chunk_size": 8, "topk": TOPK},
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 256, "torch_dtype": "float32", "first_expert_held": 4,
    "published": {"num_experts": 8},
}
TRAFFIC = {"seq_len": SEQ, "batch_size": 2, "remat": None}
FAMILY = family_module.build(CONFIG, TRAFFIC)
MODEL = FAMILY.model
SCORER = ("wq_index", "wk_index", "k_index_norm", "w_index")


@pytest.fixture(scope="module")
def params():
    """The program's initialiser's weights, every norm weight moved off 1."""
    params = jax.jit(lambda key: T.init_params(MODEL, key))(jax.random.PRNGKey(3))
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 16))
    layers = params["layers"]
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm", "k_index_norm"):
        layers[name] = layers[name] + 0.2 * jax.random.normal(next(keys), layers[name].shape)
    params["final_norm"] = params["final_norm"] + 0.2 * jax.random.normal(next(keys), (MODEL.dim,))
    return params


ids = functools.partial(model_helpers.ids, seq=SEQ)


def reference_weights(params):
    return listed(FAMILY.reference_weights(params))


# -- the masked kernels ----------------------------------------------------
def _operands(seq, topk, seed=0, batch=2, heads=4, kv_heads=2, dim=16, index=(4, 8)):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)
    q, k, v = normal(keys[0], batch, heads, seq, dim), normal(keys[1], batch, kv_heads, seq, dim), \
        normal(keys[2], batch, kv_heads, seq, dim)
    scorer = (
        normal(keys[3], batch, seq, *index), normal(keys[4], batch, seq, index[1]),
        0.3 * normal(keys[5], batch, seq, index[0]),
    )
    return q, k, v, scorer, sparse_index.index_select(*scorer, topk=topk, chunk=16)


@pytest.mark.parametrize("topk", [8, 64, 100], ids=["below", "at", "above"])
def test_the_masked_kernels_match_the_oracle(topk):
    """Value, ``lse`` and the three gradients under a selection that is
    data, at ``topk`` below, at and above the sequence (64); ``lse`` is handed
    out DETACHED: a loss through it moves no gradient."""
    q, k, v, _, selection = _operands(64, topk)
    assert int(selection.sum()) == 2 * reference.chosen_pairs(64, topk)
    repeat = lambda x: jnp.repeat(x, 2, axis=1)
    blocks = dict(block_q=16, block_k=32, precision=jax.lax.Precision.HIGHEST)

    def flash(q, k, v):
        out, lse = flash_attention(q, k, v, selection=selection, return_lse=True, **blocks)
        return jnp.sum(out ** 2) + jnp.sum(lse), (out, lse)

    def oracle(q, k, v):
        out, lse = attention_reference(
            q, repeat(k), repeat(v), selection=selection, return_lse=True)
        return jnp.sum(out ** 2) + jnp.sum(lse), (out, lse)

    ((_, got), grads), ((_, want), wanted) = (
        jax.value_and_grad(fn, argnums=(0, 1, 2), has_aux=True)(q, k, v) for fn in (flash, oracle)
    )
    close(got[0], want[0], 2e-5, "out")
    close(got[1], want[1], 2e-5, "lse")
    for got, want, name in zip(grads, wanted, "qkv"):
        close(got, want, 2e-4, f"d{name}")
    if topk >= 64:
        # every causal key chosen is no selection: bit for bit
        whole = lambda q, k, v: jnp.sum(flash_attention(q, k, v, **blocks) ** 2)
        plain = lambda q, k, v: jnp.sum(flash_attention(q, k, v, selection=selection, **blocks) ** 2)
        for got, want in zip(jax.grad(plain, (0, 1, 2))(q, k, v), jax.grad(whole, (0, 1, 2))(q, k, v)):
            assert jnp.array_equal(got, want)
    else:
        with pytest.raises(ValueError, match="selection"):
            flash_attention(q, k, v, selection=selection[:, :16])


# The three Mosaic modules of ``jax.grad(flash_attention)`` with NO selection
# (plain, under a window of 64, at a group of 1), lowered for a TPU, parsed
# and printed WITHOUT source locations. What the line holds: that a
# selection's arrival or change leaves the calls without one alone, not that
# their modules never change. PR 53 pinned what ITS parent lowered; PR 60
# changed the kernels on purpose (a tile a mask cuts is walked in sub-blocks),
# PR 61 again (every grid is its prefetched table of the tiles that run) and
# this is what its tree lowers. A change to the kernels that is meant changes
# this line; one that is not must not.
MODULES_WITHOUT_A_SELECTION = "68d0f130dedf4ed6d72ec57daf99a4cb6889d222595cdfe2cbbe9f02d22034cf"


def test_without_a_selection_the_mosaic_modules_are_the_parents():
    modules = flash_mosaic_modules() + flash_mosaic_modules(window=64) + flash_mosaic_modules(kv_heads=4)
    assert len(modules) == 9
    assert hashlib.sha256("\n".join(modules).encode()).hexdigest() == MODULES_WITHOUT_A_SELECTION


# -- the scorer, the selection, the term -------------------------------------
def test_the_selection_is_the_reference_top_k_and_ties_go_to_the_lower_key():
    _, _, _, scorer, selection = _operands(64, 16)
    scores = jnp.concatenate(
        [reference.index_scores_block(*scorer, start, 16) for start in range(0, 64, 16)], axis=1
    )
    close(sparse_index.index_scores(*scorer), scores, 1e-6)
    want = jnp.concatenate(
        [reference.select_block(scores[:, s:s + 16], s, 16) for s in range(0, 64, 16)], axis=1
    )
    assert jnp.array_equal(selection != 0, want)
    # exact zeros where every index head is cut by its ReLU: the first 16 keys win
    tied = sparse_index.index_select(scorer[0] * 0, *scorer[1:], topk=16, chunk=16)
    row = jnp.arange(64)
    assert jnp.array_equal(tied[0] != 0, row[None, :] < jnp.minimum(row[:, None] + 1, 16))
    # a chunk size changes nothing
    assert jnp.array_equal(selection, sparse_index.index_select(*scorer, topk=16, chunk=64))


def _unpacked(packed, keys):
    return jnp.concatenate([slab for _, slab in sparse_index._slabs(packed, keys)], axis=-1)


def _selection_case(name):
    """``(scores [1, rows, keys] float32, first row, topk)`` of one hard case
    of the selection; every row sees every key unless the case says
    otherwise."""
    rows, keys, topk = 8, 96, 16
    first_row = keys                                            # every key is causal
    random = np.random.default_rng(11)
    if name == "all_equal":
        scores = np.full((rows, keys), 0.5, np.float32)
    elif name == "exactly_k_distinct_values":
        scores = np.tile(np.arange(topk, dtype=np.float32), keys // topk)[None].repeat(rows, 0)
    elif name == "ties_straddle_the_threshold":
        # 10 above, then 20 equal entries of which 6 are taken, scattered over the row
        scores = np.stack([random.permutation(np.r_[np.arange(10) + 2.0, np.ones(20), -np.arange(66.0)])
                           for _ in range(rows)]).astype(np.float32)
    elif name == "zeros_of_both_signs_and_denormals":
        pool = np.array([0.0, -0.0, 1e-40, -1e-40, 1e-45, -1e-45, 3e-39, 1.0, -1.0], np.float32)
        scores = pool[random.integers(0, len(pool), (rows, keys))]
    elif name == "masked_keys_are_minus_infinity":
        # the chunk's own rows: row t sees t + 1 keys, some rows fewer than topk
        first_row, scores = 12, random.standard_normal((rows, keys)).astype(np.float32)
    elif name == "fewer_keys_than_topk":
        keys = first_row = 12
        scores = random.standard_normal((rows, keys)).astype(np.float32)
    elif name == "a_chunk_at_the_cell_s_shape":
        rows, keys, topk, first_row = 512, 16384, 2048, 16384 - 512
        scores = random.standard_normal((rows, keys)).astype(np.float32)
        scores = np.where(random.random((rows, keys)) < 0.1, 0.0, scores)
    return jnp.asarray(scores)[None], first_row, topk


@pytest.mark.parametrize("name", [
    "all_equal", "exactly_k_distinct_values", "ties_straddle_the_threshold",
    "zeros_of_both_signs_and_denormals", "masked_keys_are_minus_infinity", "fewer_keys_than_topk",
    "a_chunk_at_the_cell_s_shape",
])
def test_the_threshold_is_the_sorted_one_bit_for_bit_and_the_chosen_set_is_top_k_s(name):
    """``_kth_largest`` against a SORT of the floats' ordered bit patterns
    (the k-th largest entry itself, -0.0 one under +0.0, -inf where a row
    has fewer entries above it), bit for bit; and ``select_keys``' chosen set
    against ``jax.lax.top_k`` over the causal keys, whose equal values come
    lower index first: the parent's tie rule."""
    scores, first_row, topk = _selection_case(name)
    _, rows, keys = scores.shape
    causal = jnp.arange(keys)[None, :] <= first_row + jnp.arange(rows)[:, None]
    keyed = jnp.where(causal, scores, -jnp.inf)
    if keys > topk:
        bits = np.asarray(keyed).view(np.uint32)
        ordered = np.where(bits >> 31 == 1, ~bits, bits | np.uint32(1 << 31))
        kth = np.sort(ordered, axis=-1)[..., -topk][..., None]
        want = np.where(kth >> 31 == 1, kth & np.uint32(0x7FFFFFFF), ~kth).astype(np.uint32)
        got = np.asarray(jax.jit(sparse_index._kth_largest, static_argnums=1)(keyed, topk))
        assert np.array_equal(got.view(np.uint32), want)
    chosen = _unpacked(jax.jit(sparse_index.select_keys, static_argnums=2)(scores, first_row, topk), keys)
    # top_k over zeros of ONE sign: the selection compares floats, where -0.0 == 0.0
    best = jax.lax.top_k(keyed + 0.0, min(topk, keys))[1]
    want = jnp.zeros((1, rows, keys), bool).at[0, jnp.arange(rows)[:, None], best[0]].set(True) & causal
    assert chosen.dtype == jnp.int8 and jnp.array_equal(chosen != 0, want)
    seen = jnp.minimum(first_row + jnp.arange(rows) + 1, keys)
    assert jnp.array_equal(jnp.sum(chosen, axis=-1)[0], jnp.minimum(seen, topk))


@pytest.mark.parametrize("shape", [(1, 512, 16384), (2, 7, 1003), (1, 5, 3)],
                         ids=["a_chunk_at_the_cell_s_keys", "ragged_tail", "fewer_keys_than_bits"])
def test_packing_and_unpacking_give_the_mask_back(shape):
    """``_pack`` and its inverse ``_slabs``: eight keys a byte within one row,
    whole vregs of lanes a slab at the cell's 16,384 keys, a last slab cut
    short (or none at all) where 8 does not divide the keys."""
    mask = jax.random.bernoulli(jax.random.PRNGKey(shape[-1]), 0.3, shape)
    packed = jax.jit(sparse_index._pack)(mask)
    assert packed.dtype == jnp.uint8 and packed.shape == (*shape[:-1], -(-shape[-1] // 8))
    assert jnp.array_equal(_unpacked(packed, shape[-1]) != 0, mask)


def test_the_index_loss_and_its_gradient_are_the_reference_s():
    q, k, v, scorer, selection = _operands(64, 16)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, axis=1)) * 16 ** -0.5
    lse = jax.nn.logsumexp(jnp.where(selection[:, None] != 0, scores, -jnp.inf), axis=-1)
    by_token = lambda x: jnp.swapaxes(x, 1, 2)

    def plain(q_index, k_index, w):
        total = 0.0
        for start in range(0, 64, 16):
            scores = reference.index_scores_block(q_index, k_index, w, start, 16)
            mask = reference.select_block(jax.lax.stop_gradient(scores), start, 16)
            _, probs = reference.attention_block(by_token(q), by_token(k), by_token(v), mask, start, 16)
            total = total + reference.index_loss_block(scores, mask, probs)
        return total / (2 * 64)

    ours = lambda *scorer: sparse_index.index_loss(
        *scorer, q, k, selection, lse, scale=16 ** -0.5, block_q=16, block_k=16)
    (got, grads), (want, wanted) = (
        jax.jit(jax.value_and_grad(fn, (0, 1, 2)))(*scorer) for fn in (ours, plain)
    )
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for got, want in zip(grads, wanted):
        close(got, want, 2e-4)
    # nothing flows into what the term reads detached
    into_q = jax.grad(lambda q: sparse_index.index_loss(
        *scorer, q, k, selection, lse, scale=0.25, block_q=16, block_k=16))(q)
    assert float(jnp.max(jnp.abs(into_q))) == 0.0


@pytest.mark.parametrize("case", [
    dict(id="topk_or_fewer_keys", seq=32, topk=64, blocks=(16, 16)),
    dict(id="causal_tiles_skipped", seq=64, topk=16, blocks=(16, 16)),
    dict(id="row_blocks_wider_than_key_blocks", seq=64, topk=16, blocks=(32, 16)),
    dict(id="one_tile_one_row", seq=64, topk=16, blocks=(None, None), batch=1),
    dict(id="group_of_1", seq=64, topk=16, blocks=(16, 32), heads=2, kv_heads=2),
    dict(id="group_of_8", seq=64, topk=16, blocks=(16, 32), heads=8, kv_heads=1),
    dict(id="bfloat16_operands", seq=64, topk=16, blocks=(16, 16), dtype=jnp.bfloat16),
    dict(id="exponentials_underflow", seq=64, topk=16, blocks=(16, 16), sharpen=100.0),
], ids=lambda case: case["id"])
def test_the_term_s_kernels_and_their_three_gradients_are_the_reference_s(case):
    """``index_loss``'s two kernels (interpret mode) against the benchmark's
    plain reference, which knows no tile: the term and the gradients made in
    its forward, over block shapes that skip whole causal tiles, both group
    sizes, a batch of 2 and of 1, float32 operands at ``Precision.HIGHEST``
    (the file's tolerances) and bfloat16 ones (products exact in the float32
    accumulator, so the term holds to the float32 tolerance; ``dP`` and two of
    the results are rounded to bfloat16, 2^-8 a value: 1e-2 of the largest);
    attention so sharp that ``exp`` underflows for every head of a chosen
    pair, where ``xlogy(0, 0)`` is 0; and nothing reaches ``q``, ``k``,
    ``lse``."""
    seq, topk, dtype = case["seq"], case["topk"], case.get("dtype", jnp.float32)
    heads, kv_heads = case.get("heads", 4), case.get("kv_heads", 2)
    q, k, v, scorer, _ = _operands(
        seq, topk, seed=5, batch=case.get("batch", 2), heads=heads, kv_heads=kv_heads)
    rounded = lambda x: x.astype(dtype).astype(jnp.float32)
    q, k, v = rounded(q * case.get("sharpen", 1.0)), rounded(k), rounded(v)
    scorer = (rounded(scorer[0]), rounded(scorer[1]), scorer[2])
    selection = sparse_index.index_select(*scorer, topk=topk, chunk=16)
    batch, group = q.shape[0], heads // kv_heads
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, group, axis=1)) * 16 ** -0.5
    lse = jax.nn.logsumexp(jnp.where(selection[:, None] != 0, scores, -jnp.inf), axis=-1)
    by_token = lambda x: jnp.swapaxes(x, 1, 2)
    def plain(q_index, k_index, w):
        total, underflowed = 0.0, False
        for start in range(0, seq, 16):
            scores = reference.index_scores_block(q_index, k_index, w, start, 16)
            mask = reference.select_block(jax.lax.stop_gradient(scores), start, topk)
            _, probs = reference.attention_block(by_token(q), by_token(k), by_token(v), mask, start, 16)
            underflowed |= jnp.any(mask & (jnp.mean(probs, axis=1) == 0.0))
            total = total + reference.index_loss_block(scores, mask, probs)
        return total / (batch * seq), underflowed

    exact = dtype == jnp.float32
    static = dict(
        scale=16 ** -0.5, block_q=case["blocks"][0], block_k=case["blocks"][1],
        precision=jax.lax.Precision.HIGHEST if exact else None,
    )
    cast = lambda x: x.astype(dtype)

    def ours(q_index, k_index, w, q, k, lse):
        return sparse_index.index_loss(
            cast(q_index), cast(k_index), w, cast(q), cast(k), selection, lse, **static)

    (want, underflowed), wanted = jax.jit(jax.value_and_grad(plain, (0, 1, 2), has_aux=True))(*scorer)
    got, grads = jax.jit(jax.value_and_grad(ours, tuple(range(6))))(*scorer, q, k, lse)
    assert bool(underflowed) == ("sharpen" in case)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for got, want, name in zip(grads, wanted, ("dq_index", "dk_index", "dw")):
        close(got, want, 2e-4 if exact else 1e-2, name)
    assert all(float(jnp.max(jnp.abs(detached))) == 0.0 for detached in grads[3:])
    if case["blocks"] == (16, 16):
        from ray_tpu.ops.flash_attention import causal_tile_counts
        assert causal_tile_counts(seq, seq, 16, 16)["skipped"] == (seq // 16) * (seq // 16 - 1) // 2


# -- the model against the benchmark's reference -----------------------------
def test_logits_terms_and_selections_match_the_reference(params):
    """Through ``attention="reference"`` (the oracle in place of the kernels;
    the kernels' path is held by the loss and its gradients below), with the
    selections handed out."""
    tokens = ids()
    config = dict(CONFIG)
    want, terms = jax.jit(lambda w: (
        reference.logits(w, tokens, config)[0], reference.loss_terms(w, tokens, tokens, config)[1]
    ))(reference_weights(params))
    oracle = dataclasses.replace(MODEL, attention="reference")
    got, routing = jax.jit(
        lambda p, t: T.forward_with_routing(p, t, oracle, selections=True)
    )(params, tokens)
    close(got, want, 5e-4)
    np.testing.assert_allclose(routing["index_loss"], jnp.stack(terms), rtol=1e-4)
    assert set(routing) >= {"experts", "weights", "index_loss", "selection"}
    selection = routing["selection"]
    assert selection.shape == (2, 2, SEQ, SEQ) and selection.dtype == jnp.int8
    assert np.all(np.asarray(selection.sum(axis=(2, 3))) == reference.chosen_pairs(SEQ, TOPK))
    assert jnp.array_equal(selection, jnp.tril(selection))
    assert "selection" not in jax.eval_shape(lambda p: T.forward_with_routing(p, tokens, MODEL)[1], params)
    with pytest.raises(NotImplementedError, match="selections=True"):
        T.forward_with_routing(params, tokens, T.TransformerConfig.tiny(), selections=True)


def test_loss_and_every_gradient_leaf_match_the_reference(params):
    """Cross-entropy plus both layers' scorer terms through the flash kernels
    under full remat (the selection kept packed, the term's gradient made in
    its forward): the value and every leaf's gradient are the reference's,
    whose scorer reads a DETACHED input and whose term reads the attention
    detached: a cross-entropy that leaked into the scorer's four leaves, or a
    term that leaked into any other, would show here as that leaf's error."""
    tokens = ids(seed=2, seq=SEQ + 1)
    x, y = tokens[:, :-1], tokens[:, 1:]
    config = dict(CONFIG)
    want, wanted = jax.jit(jax.value_and_grad(lambda w: reference.loss(w, x, y, config)))(
        reference_weights(params)
    )
    model = dataclasses.replace(MODEL, remat="full")
    got, grads = loss_and_grads(model)(params, x, y)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for published, own in {**family_module.ATTENTION, **family_module.MOE}.items():
        stacked = jnp.stack([layer[published] for layer in wanted["layers"]])
        close(grads["layers"][own], stacked, 2e-3, own)
        assert float(jnp.max(jnp.abs(stacked))) > 0, own               # the scorer's leaves among them
    for published, own in (("embed_tokens", "embed"), ("norm", "final_norm"), ("lm_head", "lm_head")):
        close(grads[own], wanted[published], 2e-3, own)


def test_the_term_trains_the_scorer_alone_and_the_output_never(params):
    """One layer's mixer: its output's gradient reaches every leaf but the
    scorer's four, its term's gradient those four and nothing else, not the
    stream ``h`` either (the scorer reads it detached)."""
    layer = jax.tree.map(lambda leaf: leaf[1], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(6), (2, SEQ, MODEL.dim))
    oracle = dataclasses.replace(MODEL, attention="reference")
    mixer = lambda layer, h: T._sparse_mixer(h, layer, oracle, T._rope_tables(oracle), None, None)
    by_output, by_term = jax.jit(lambda layer, h: (
        jax.grad(lambda layer, h: jnp.sum(mixer(layer, h)[0] ** 2), (0, 1))(layer, h),
        jax.grad(lambda layer, h: mixer(layer, h)[1]["index_loss"], (0, 1))(layer, h),
    ))(layer, h)
    moved = lambda leaf: float(jnp.max(jnp.abs(leaf))) > 0.0
    mixers = SCORER + ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
    for name in mixers:
        assert moved(by_output[0][name]) == (name not in SCORER), name
        assert moved(by_term[0][name]) == (name in SCORER), name
    assert moved(by_output[1]) and not moved(by_term[1])


def _block_diffusion_family():
    """SDAR's family at this file's sizes: the same backbone under another
    objective (tests/test_block_diffusion.py), its expert layer cut the same way."""
    from benchmarks.families import block_diffusion_moe_decoder

    config = {k: v for k, v in CONFIG.items() if k != "sa_config"}
    config.update(rope_scaling=None, block_length=4, mask_token_id=255, t_min=1e-3)
    return block_diffusion_moe_decoder.build(config, TRAFFIC)


@pytest.mark.parametrize("family", ["sparse_gqa_moe_decoder", "block_diffusion_moe_decoder"])
def test_the_shares_add_up(params, family):
    """The parts of an expert layer that the two shares of its 8 experts
    give add up to what the uncut reference gives for the whole layer, for
    each configuration that holds a share of this backbone's experts (a
    block-diffusion stream's rows are rows like any other to the experts)."""
    MODEL = FAMILY.model if family == "sparse_gqa_moe_decoder" else _block_diffusion_family().model
    layer = jax.tree.map(lambda leaf: leaf[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(8), (2, SEQ, MODEL.dim))
    experts = {name: jax.random.normal(jax.random.PRNGKey(9 + i), (8, *layer[name].shape[1:])) * 0.1
               for i, name in enumerate(("w_gate", "w_up", "w_down"))}
    router = jax.random.normal(jax.random.PRNGKey(12), layer["router"].shape)
    whole = {
        "post_attention_layernorm": layer["mlp_norm"], "router": router,
        "gate": experts["w_gate"], "up": experts["w_up"], "down": experts["w_down"],
    }
    uncut = dict(CONFIG, num_experts=8, first_expert_held=0)
    want, _ = reference.moe_forward(x, whole, uncut)
    def share(first):
        model = dataclasses.replace(MODEL, moe=dataclasses.replace(MODEL.moe, held=(first, 4)))
        held = {**layer, "router": router, **{n: e[first:first + 4] for n, e in experts.items()}}
        return T._mlp_block(x, held, model, True)[0] - x

    parts = jax.jit(lambda: (share(0), share(4)))()
    close(parts[0] + parts[1], want - x, 5e-4)
    assert float(jnp.max(jnp.abs(parts[0]))) > 0 and float(jnp.max(jnp.abs(parts[1]))) > 0


# -- what is not written refuses by name ------------------------------------
def test_what_a_sparse_layer_cannot_do_yet_is_refused_by_name(params):
    whole_heads = dataclasses.replace(MODEL, dim=64)         # 4 heads of 16: no stated head_dim in the way
    with pytest.raises(NotImplementedError, match="index keys cached beside K and V"):
        T.init_kv_cache(whole_heads, 1, 16)
    with pytest.raises(NotImplementedError, match="index keys cached beside K and V"):
        T.decode_step(params, {}, ids(batch=1, seq=1), whole_heads)
    with pytest.raises(NotImplementedError, match="partition_stages over sparse layers"):
        T.partition_stages(params, whole_heads, 2)
    with pytest.raises(NotImplementedError, match="stage_forward over sparse layers"):
        T.stage_forward(params, ids(), whole_heads, first=True, last=True)
    with pytest.raises(NotImplementedError, match="callable attention="):
        dataclasses.replace(MODEL, attention=lambda q, k, v, causal: q)
    with pytest.raises(NotImplementedError, match="sparse= with first_dense_layers"):
        # the prefix's scan hands on the stream alone: its scorers would never train
        dataclasses.replace(MODEL, first_dense_layers=1)
    with pytest.raises(ValueError, match="not latent="):
        dataclasses.replace(MODEL, latent=T.LatentAttentionConfig())
    with pytest.raises(ValueError, match="exactly where sparse="):
        dataclasses.replace(MODEL, layer_pattern=("full", "full"))
    with pytest.raises(ValueError, match="RoPE turns pairs"):
        T.SparseAttentionConfig(index_head_dim=7)
    with pytest.raises(ValueError, match=">= 1"):
        T.SparseAttentionConfig(topk=0)


def _loss_over(model, mesh_axes, params, x):
    """``loss_fn`` traced under a mesh of ``mesh_axes``, the batch over its data axes."""
    from jax.sharding import NamedSharding, PartitionSpec
    from ray_tpu.parallel.mesh import LogicalRules, MeshSpec

    spec = MeshSpec(dict(mesh_axes))
    mesh = spec.build(jax.devices()[:spec.size])
    rows = NamedSharding(mesh, LogicalRules().spec(("batch", None), mesh))
    params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
    x = jax.device_put(x, rows)

    def loss(params, x):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return T.loss_fn(params, x[:, :-1], x[:, 1:], model)

    return float(jax.jit(loss)(params, x))


def test_data_shards_give_the_one_device_s_loss_and_tp_and_sp_are_refused(params):
    """Two data shards: each selects, attends and takes its term's mean over
    its own rows, the shards' terms averaged; a mesh that would cut a row's
    heads or its sequence is refused by name."""
    x = ids(seed=8, batch=4, seq=SEQ + 1)
    one = float(jax.jit(lambda p: T.loss_fn(p, x[:, :-1], x[:, 1:], MODEL))(params))
    np.testing.assert_allclose(_loss_over(MODEL, {"dp": 2}, params, x), one, rtol=2e-6)
    for axes in ({"tp": 2}, {"dp": 2, "sp": 2}):
        with pytest.raises(NotImplementedError, match="a sparse layer over a mesh with (tp|sp) > 1"):
            _loss_over(MODEL, axes, params, x)
