"""``reference/window_moe_decoder.py`` held to its own description on tiny
hand-checkable inputs, the family against it at a small size, gradients
included, and its comparison held to what must fail: the window ignored,
RoPE on the global layer, the router fed the normed input, SiLU for ReLU and
the router's logits in bfloat16 each fail a stated limit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import window_moe_decoder
from benchmarks.harness import window_moe_controls
from benchmarks.reference import window_moe_decoder as R
from benchmarks.tests.test_discovery_window_moe import TINY

TRAFFIC = {"seq_len": 96, "batch_size": 2, "remat": "full"}


@pytest.fixture(scope="module")
def family():
    return window_moe_decoder.build(TINY, TRAFFIC)


@pytest.fixture(scope="module")
def params(family):
    return jax.jit(family.init)(jax.random.PRNGKey(45))


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


def test_the_window_is_the_keys_up_to_the_querys_own_by_hand():
    """One-hot values and equal scores: query i's output is 1 / count on the
    keys it sees: it holds key i - window + 1 and not key i - window."""
    seq, window = 12, 4
    q = jnp.zeros((1, seq, 2, 8))
    v = jnp.tile(jnp.eye(seq)[None, :, None, :], (1, 1, 1, 1))      # one KV head
    out = np.asarray(R.banded_attention(q, q[:, :, :1], v, window))[0, :, 1]
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    seen = (j <= i) & (j > i - window)
    assert np.array_equal(out > 0, seen)
    assert out[9, 6] == 0.25 and out[9, 5] == 0 and out[9, 10] == 0 and out[1, 0] == 0.5
    whole = np.asarray(R.banded_attention(q, q[:, :, :1], v, None))[0, :, 0]
    assert np.array_equal(whole > 0, j <= i)


def test_the_router_reads_what_it_is_given_and_weighs_by_the_chosen_logits():
    logits = jnp.array([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 3.0, 1.0]])
    r = R.route(jnp.eye(2), logits, None, top_k=2)
    assert np.asarray(r["own"]).tolist() == [[0, 1], [2, 3]]
    e = np.exp([2.0, 1.0])
    assert np.allclose(np.asarray(r["weights"][0]), e / e.sum(), rtol=1e-6)
    # the same numbers as a softmax over all four, its top two, divided by their sum
    every = np.asarray(jax.nn.softmax(logits[0]))
    assert np.allclose(np.asarray(r["weights"][0]), every[:2] / every[:2].sum(), rtol=1e-6)
    forced = jnp.array([[2, 3], [0, 1]])
    assert np.array_equal(np.asarray(R.route(jnp.eye(2), logits, forced, top_k=2)["experts"]), forced)


def test_an_absent_expert_adds_nothing_and_the_gate_is_relu():
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    d, m, tokens = 8, 4, 6
    w = {
        "post_attention_layernorm": jnp.ones(d), "router": jax.random.normal(ks[0], (d, 4)),
        "gate": jax.random.normal(ks[1], (2, d, m)), "up": jax.random.normal(ks[2], (2, d, m)),
        "down": jax.random.normal(ks[3], (2, m, d)),
    }
    cfg = {
        "rms_norm_eps": 1e-6, "moe_num_active_primary_experts": 2, "moe_num_primary_experts": 2,
        "first_expert_held": 2,
    }
    x, stream = jax.random.normal(ks[4], (1, tokens, d)), jax.random.normal(ks[5], (1, tokens, d))
    forced = jnp.tile(jnp.array([[0, 1]]), (tokens, 1))          # both absent here
    out, _ = R.moe_forward(x, stream, w, cfg, forced)
    assert np.array_equal(np.asarray(out), np.asarray(x))
    forced = jnp.tile(jnp.array([[2, 0]]), (tokens, 1))          # expert 2 is held expert 0
    out, routing = R.moe_forward(x, stream, w, cfg, forced)
    with jax.default_matmul_precision("highest"):
        h = np.asarray(R.rms_norm(x, jnp.ones(d), 1e-6))[0]
        one = (np.maximum(h @ np.asarray(w["gate"][0]), 0) * (h @ np.asarray(w["up"][0]))) @ np.asarray(w["down"][0])
    weight = np.asarray(routing["weights"])[:, 0]
    assert np.allclose(np.asarray(out - x)[0], weight[:, None] * one, rtol=1e-4, atol=1e-5)
    # the weights come from the STREAM's logits, not the normed input's
    want = np.asarray(jax.nn.softmax((stream[0] @ w["router"])[:, [2, 0]], axis=-1))
    assert np.allclose(np.asarray(routing["weights"]), want, rtol=1e-5)


def test_the_family_matches_the_reference(family, params):
    x = ids()
    program = jax.jit(family.forward)(params, x)
    result = family.check(program, params, x)
    assert result["ok"], result
    # float32 on both sides at this size: far inside the chip's limits
    assert result["published"]["rel_rms"] < 1e-5 and result["worst_position_rel_rms"] < 1e-4
    assert result["router"]["weights_rel_rms"] < 1e-6 and result["router"]["worst_shortfall"] < 1e-6
    assert all(l["counts_agree"] and l["held_pairs_agree"] for l in result["layers"])
    assert result["same_set_share"] > 0.99
    free, _ = R.logits(family.reference_weights(params), x, TINY)
    assert R.compare(program, free)["rel_rms"] < 1e-5           # its own choices are the program's


def test_loss_and_gradients_match_the_reference(family, params):
    from ray_tpu.models import transformer as T

    x, y = ids(), ids(seed=2)
    got, grads = jax.jit(jax.value_and_grad(lambda p: T.loss_fn(p, x, y, family.model)))(params)

    def reference_loss(params):
        weights = family.reference_weights(params)
        return R.loss(dict(weights, layers=list(weights["layers"])), x, y, TINY)

    want, want_grads = jax.value_and_grad(reference_loss)(params)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    mine, theirs = (jax.tree_util.tree_leaves_with_path(g) for g in (grads, want_grads))
    for (path, leaf), (_, wanted) in zip(mine, theirs):
        name = jax.tree_util.keystr(path)
        wanted, leaf = np.asarray(wanted), np.asarray(leaf)
        assert np.any(wanted), name                              # the routers' among them
        assert np.max(np.abs(leaf - wanted)) <= 2e-3 * np.max(np.abs(wanted)), name
    # the cell's loss is that loss with the routers' weights held still
    held_still, cell = jax.jit(jax.value_and_grad(lambda p: family.loss(p, {"x": x, "y": y})))(params)
    assert float(held_still) == float(got)
    for (path, leaf), (_, trained) in zip(jax.tree_util.tree_leaves_with_path(cell), mine):
        if "router" in jax.tree_util.keystr(path):
            assert not np.any(np.asarray(leaf))
        else:
            assert np.array_equal(np.asarray(leaf), np.asarray(trained)), jax.tree_util.keystr(path)


@pytest.mark.parametrize("control", [
    "window_ignored", "rope_on_the_global", "router_fed_normed", "silu_for_relu",
])
def test_a_program_with_another_models_term_is_not_correct(family, params, control):
    x = ids()
    program = jax.jit(family.forward)(params, x)
    model = window_moe_controls.models(family.model)[control]
    result = family.check(program, params, x, model=model)
    assert not result["ok"], control
    facts = window_moe_controls.readings(result)
    failed = {
        "rel_rms": facts["rel_rms"] > R.TOLERANCE,
        "position": facts["worst_position_rel_rms"] > R.POSITION_TOLERANCE,
        "margin": facts["worst_shortfall"] > R.MARGIN,
        "weights": facts["weights_rel_rms"] > R.WEIGHT_TOLERANCE,
    }
    assert any(failed.values()), (control, facts)
    if control == "router_fed_normed":
        assert failed["margin"] or failed["weights"]
    else:
        assert failed["rel_rms"]


def test_router_logits_in_bfloat16_are_not_correct(family, params):
    x = ids()
    program = jax.jit(family.forward)(params, x)
    route = window_moe_controls.routers(family, family.layer(params, 1))["logits_in_bfloat16"]
    result = family.check(program, params, x, route=route)
    assert not result["ok"] and not result["router"]["ok"]
    assert result["published"]["ok"]                             # the logits cannot see it
    assert result["router"]["weights_rel_rms"] > R.TOLERANCE_ROUTER


def test_the_absent_experts_router_columns_are_zero_and_the_routers_stay_where_they_are(params):
    """``Family.init``: the tokens choose among the held experts, so the held
    experts get every pair; ``Family.loss`` stops the routers' gradient, so
    AdamW moves them by its weight decay alone (a factor a step, zero stays
    zero) while everything else trains."""
    import optax

    from benchmarks.harness import LR

    # TINY holds experts 4-7 of 8: the absent block comes FIRST there
    for leaves in params["layers"].values():
        router = np.asarray(leaves["router"])
        assert not router[..., :4].any() and router[..., 4:].all()
    # the embedding's rows at unit scale: EMBED_SCALE times init_params' 0.02
    assert abs(float(np.std(np.asarray(params["embed"], np.float32))) - 1.0) < 0.05
    # 16 of 32 held, 2 a token: 17 of 65,536 tokens have fewer than 2 positive held logits
    family = window_moe_decoder.build(
        dict(TINY, moe_num_primary_experts=16, first_expert_held=0,
             published={"moe_num_primary_experts": 32}), TRAFFIC)
    fresh = jax.jit(family.init)(jax.random.PRNGKey(7))
    optimizer = optax.adamw(LR)
    weights, state = fresh, optimizer.init(fresh)
    tokens = ids(3)
    batch = {"x": tokens[:, :-1], "y": tokens[:, 1:]}

    @jax.jit
    def step(weights, state):
        loss, grads = jax.value_and_grad(family.loss)(weights, batch)
        updates, state = optimizer.update(grads, state, weights)
        return optax.apply_updates(weights, updates), state, loss

    losses = []
    for _ in range(4):
        weights, state, loss = step(weights, state)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    for kind, leaves in weights["layers"].items():
        router, was = np.asarray(leaves["router"]), np.asarray(fresh["layers"][kind]["router"])
        assert not router[..., 16:].any() and router[..., :16].all()
        assert np.allclose(router, was * (1 - LR * 1e-4) ** 4, rtol=1e-6, atol=0)
        assert not np.allclose(np.asarray(leaves["wq"]), np.asarray(fresh["layers"][kind]["wq"]))
    routing = family._logits_and_routing(weights, batch["x"])[1]
    pairs = batch["x"].size * 2
    assert np.asarray(routing["held_pairs"]).tolist() == [pairs] * 4
