"""``reference/sparse_gqa_moe_decoder.py`` held to its own description on
tiny hand-checkable inputs, the family against it at a small size, gradients
included, and its comparison held to what must fail: a program that ignores
the selection and one whose scorer's operands are rounded each fail a stated
limit."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import sparse_gqa_moe_decoder
from benchmarks.harness import sparse_gqa_moe_controls as controls
from benchmarks.reference import sparse_gqa_moe_decoder as R
from benchmarks.tests.test_discovery_sparse_gqa_moe import TINY

TRAFFIC = {"seq_len": 96, "batch_size": 2, "remat": "full"}


@pytest.fixture(scope="module")
def family():
    return sparse_gqa_moe_decoder.build(TINY, TRAFFIC)


@pytest.fixture(scope="module")
def params(family):
    return jax.jit(family.init)(jax.random.PRNGKey(53))


def ids(seed=1, batch=2, seq=96):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0, 256)


def test_it_imports_nothing_of_the_program():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(R))
    imported = [
        (node.module or "") if isinstance(node, ast.ImportFrom) else alias.name
        for node in ast.walk(tree) if isinstance(node, (ast.Import, ast.ImportFrom))
        for alias in (node.names if isinstance(node, ast.Import) else [None])
    ]
    assert imported and not [m for m in imported if m.startswith("ray_tpu")]


def test_the_scores_and_the_choice_by_hand():
    """One index head of one dim: ``I[t, s] = w[t] relu(qI[t] kI[s])``. With
    qI = 1, w = 1 and kI = the key's own number (one negative), query t
    chooses its topk LARGEST earlier keys; the ReLU's zeros tie and the lower
    key wins."""
    seq, topk = 8, 3
    k_index = jnp.array([0.5, -2.0, 3.0, 1.0, 0.0, 7.0, 2.0, 4.0])[None, :, None]
    ones = jnp.ones((1, seq, 1, 1))
    scores = R.index_scores_block(ones, k_index, ones[..., 0], 0, seq)
    assert np.allclose(scores[0, 3], [0.5, 0.0, 3.0, 1.0, 0.0, 7.0, 2.0, 4.0])      # relu cut -2
    mask = np.asarray(R.select_block(scores, 0, topk))[0]
    assert mask[0].tolist() == [1, 0, 0, 0, 0, 0, 0, 0] and mask[1, :2].all() and mask[2, :3].all()
    assert np.flatnonzero(mask[3]).tolist() == [0, 2, 3]           # 3.0, 1.0, 0.5 of keys 0-3
    assert np.flatnonzero(mask[7]).tolist() == [2, 5, 7]           # 7.0, 4.0, 3.0
    assert mask.sum() == R.chosen_pairs(seq, topk) == 1 + 2 + 3 * 6
    # keys 1 and 4 score exactly 0 for every query: of such ties the lower key is chosen
    zeros = R.select_block(scores * 0, 0, topk)[0]
    assert np.flatnonzero(zeros[7]).tolist() == [0, 1, 2]
    # a negative weight turns the order: the smallest products win
    negative = R.index_scores_block(ones, k_index, -ones[..., 0], 0, seq)
    assert np.flatnonzero(R.select_block(negative, 0, topk)[0, 7]).tolist() == [0, 1, 4]
    assert R.chosen_pairs(16384, 2048) == 31_458_304


def test_attention_is_the_softmax_over_the_chosen_keys_alone_and_the_term_is_a_kl():
    seq = 6
    q = jnp.zeros((1, seq, 2, 4))
    v = jnp.eye(seq)[None, :, None, :]                             # one KV head, one-hot values
    mask = jnp.array([[1, 0, 0, 0, 0, 0], [1, 1, 0, 0, 0, 0], [0, 1, 1, 0, 0, 0], [1, 0, 0, 1, 0, 0],
                      [0, 0, 1, 1, 1, 0], [0, 1, 0, 0, 0, 1]], bool)[None]
    out, probs = R.attention_block(q, q[:, :, :1], v, mask, 0, seq)
    # equal scores: 1 / count on the chosen keys, nothing elsewhere, both heads alike
    want = mask[0] / mask[0].sum(-1, keepdims=True)
    assert np.allclose(out[0, :, 0], want) and np.allclose(probs[0, 1], want)
    # a scorer that says what the attention says pays nothing; any other pays the KL
    uniform = jnp.zeros((1, seq, seq))
    assert abs(float(R.index_loss_block(uniform, mask, probs))) < 1e-6
    tilted = uniform.at[0, 4, 2].set(jnp.log(2.0))                 # row 4: softmax (1/2, 1/4, 1/4)
    kl = (1 / 3) * (np.log((1 / 3) / 0.5) + 2 * np.log((1 / 3) / 0.25))
    assert float(R.index_loss_block(tilted, mask, probs)) == pytest.approx(kl, rel=1e-5)
    # what the mask leaves out adds nothing, whatever its score
    loud = tilted.at[0, 4, 0].set(50.0)
    assert float(R.index_loss_block(loud, mask, probs)) == pytest.approx(kl, rel=1e-5)


def test_an_absent_expert_adds_nothing_and_the_weights_are_renormalised():
    cfg = dict(TINY, first_expert_held=4)                          # this share: experts 4-7 of 8
    hidden, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    w = {
        "post_attention_layernorm": jnp.ones((hidden,)),
        "router": jax.random.normal(next(keys), (hidden, 8)),
        "gate": jax.random.normal(next(keys), (4, hidden, width)) * 0.1,
        "up": jax.random.normal(next(keys), (4, hidden, width)) * 0.1,
        "down": jax.random.normal(next(keys), (4, width, hidden)) * 0.1,
    }
    x = jax.random.normal(next(keys), (1, 16, hidden))
    _, routing = R.moe_forward(x, w, cfg)
    assert np.allclose(np.asarray(routing["weights"]).sum(-1), 1.0, atol=1e-6)      # norm_topk_prob
    absent = jnp.zeros((16, 2), jnp.int32).at[:, 1].set(1)         # experts 0 and 1: held are 4-7
    out, _ = R.moe_forward(x, w, cfg, forced=absent)
    assert np.array_equal(np.asarray(out), np.asarray(x))
    one = jnp.zeros((16, 2), jnp.int32).at[:, 1].set(5)            # expert 5 is held, 0 is not
    out, routing = R.moe_forward(x, w, cfg, forced=one)
    m = R.rms_norm(x, w["post_attention_layernorm"], 1e-6).reshape(16, hidden)
    silu = jax.nn.silu(m @ w["gate"][1]) * (m @ w["up"][1])
    want = routing["weights"][:, 1:2] * (silu @ w["down"][1])
    assert np.allclose(np.asarray(out - x)[0], np.asarray(want), atol=1e-5)


def test_the_family_matches_the_reference(family, params):
    tokens = ids()
    program = jax.jit(family.forward)(params, tokens)
    result = family.check(program, params, tokens)
    assert result["ok"] and result["published"]["rel_rms"] < 1e-4 and result["own"]["rel_rms"] < 1e-4
    assert result["picks_agree_pct"] == 100.0 and result["selection_ok"]
    weights = lambda: family.reference_weights(params)
    assert R.dense_gap(weights, tokens, family.config) > 100 * result["published"]["rel_rms"]
    tail = family.check(program[:, -32:], params, tokens, last=32)
    assert tail["ok"] and all(l["picks"] == 2 * 32 * 16 for l in tail["layers"])


def test_loss_and_gradients_match_the_reference(family, params):
    tokens = ids(seed=2, seq=97)
    batch = {"x": tokens[:, :-1], "y": tokens[:, 1:]}
    got, grads = jax.jit(jax.value_and_grad(family.loss))(params, batch)
    weights = family.reference_weights(params)
    weights = {**weights, "layers": list(weights["layers"])}
    config = dict(TINY)
    want, wanted = jax.jit(jax.value_and_grad(
        lambda w: R.loss(w, batch["x"], batch["y"], config)))(weights)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    names = {**sparse_gqa_moe_decoder.ATTENTION, **sparse_gqa_moe_decoder.MOE}
    for published, own in names.items():
        if own == "router":
            assert float(jnp.max(jnp.abs(grads["layers"][own]))) == 0.0      # held still
            continue
        stacked = jnp.stack([layer[published] for layer in wanted["layers"]])
        scale = float(jnp.max(jnp.abs(stacked)))
        assert float(jnp.max(jnp.abs(grads["layers"][own] - stacked))) <= 2e-3 * scale, own


@pytest.mark.parametrize("name", ["selection_ignored", "mask_not_applied", "scorer_operands_rounded"])
def test_a_control_is_not_correct(family, params, name):
    """At a small size in float32: the three controls each fail the limit
    ``harness/sparse_gqa_moe_controls.py`` names for them (the scorer's
    operands rounded to TWO bits here: float32 against float32 leaves no
    stream noise to hide five behind, and the scores are a tenth of the
    cell's)."""
    tokens = ids(seed=3)
    program = jax.jit(family.forward)(params, tokens)
    assert family.check(program, params, tokens)["ok"]
    model, traced_in = controls.control(name, family.model, mantissa_bits=2)
    reported = {}
    if name == "mask_not_applied":
        own = family._logits_and_routing(params, tokens)[1]["selection"]
        reported = {"selection": lambda routing: own}
    with traced_in:
        result = family.check(program, params, tokens, model=model, **reported)
    assert not result["ok"]
    if name == "selection_ignored":
        # every causal key counted, the logits far from the reference's under its own choice
        assert not result["selection_ok"] and result["selected_pairs_pct"] == 100.0
        assert result["own"]["rel_rms"] > R.OWN_TOLERANCE and result["published"]["ok"]
    elif name == "mask_not_applied":
        # the selections are the model's; the logits are not the ones they give
        assert result["selection_ok"] and result["layers"][0]["picks_agree_pct"] == 100.0
        assert result["published"]["rel_rms"] > R.TOLERANCE
    else:
        # the logits follow the selection handed over; the picks are what gives it away
        assert result["published"]["ok"] and result["selection_ok"]
        assert result["worst_pick_shortfall"] > R.PICK_MARGIN and result["picks_agree_pct"] < 100.0


def test_the_routers_are_zero_and_stay_there(family, params):
    """Zero routers: every token's two equal best scores are experts 0 and
    1, which the share holds (experts 0-3 of 8), in the program and in the
    reference alike; every (token, choice) pair of every layer is held."""
    assert not np.asarray(params["layers"]["router"]).any()
    _, routing = family._logits_and_routing(params, ids())
    assert np.array_equal(np.unique(np.asarray(routing["experts"])), [0, 1])
    assert np.all(np.asarray(routing["held_pairs"]) == 2 * 96 * 2)
    assert np.allclose(np.asarray(routing["weights"]), 0.5)
    layer = next(iter(family.reference_weights(params)["layers"]))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 48))
    _, plain = R.moe_forward(x, layer, dict(TINY))
    assert np.array_equal(np.asarray(plain["experts"]), np.tile([0, 1], (8, 1)))
    batch = {"x": ids()[:, :-1], "y": ids()[:, 1:]}
    assert not np.asarray(jax.jit(jax.grad(family.loss))(params, batch)["layers"]["router"]).any()
