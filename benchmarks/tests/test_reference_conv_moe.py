"""``reference/conv_moe_decoder.py`` held to its own description on tiny
hand-checkable inputs, the family against it at a small size, and its
comparison held to what must fail: a convolution summed in bfloat16 and a
router whose scores are rounded to bfloat16 each fail a stated limit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import conv_moe_decoder
from benchmarks.harness import conv_moe_controls, conv_moe_flops
from benchmarks.reference import conv_moe_decoder as R
from benchmarks.tests.test_discovery_conv_moe import TINY

TRAFFIC = {"seq_len": 192, "batch_size": 2, "remat": "full"}


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


def test_the_convolution_is_three_shifted_multiply_adds_by_hand():
    x = jnp.arange(1.0, 6.0).reshape(1, 5, 1)            # 1 2 3 4 5
    f = jnp.array([[100.0], [10.0], [1.0]])              # the LAST tap on the current token
    out = np.asarray(R.causal_conv(x, f)).reshape(5)
    assert out.tolist() == [1.0, 12.0, 123.0, 234.0, 345.0]   # zeros before the sequence
    two = jnp.concatenate([x, 10 * x], axis=-1)
    both = np.asarray(R.causal_conv(two, jnp.concatenate([f, f[::-1]], axis=-1)))
    assert both[0, :, 0].tolist() == out.tolist()              # depthwise: a filter a channel
    assert both[0, :, 1].tolist() == [1000.0, 2100.0, 3210.0, 4320.0, 5430.0]


def _route(bias, logits, forced=None):
    hidden = logits.shape[0]
    x = jnp.eye(hidden)[None]                                    # token t is unit vector t
    router = logits / jnp.sqrt(jnp.float32(hidden))              # undo the norm of a unit vector
    return R.route(
        x, jnp.ones(hidden), router, bias, forced, eps=0.0, top_k=2, norm_topk_prob=True, scaling=1.0,
    )[1]


def test_the_bias_chooses_and_does_not_weigh():
    logits = jnp.full((4, 4), -4.0).at[0].set(jnp.array([2.0, 1.0, 0.0, -1.0]))
    r = _route(jnp.zeros(4), logits)
    scores = np.asarray(r["scores"][0])
    assert np.allclose(scores, 1 / (1 + np.exp(-np.array([2.0, 1.0, 0.0, -1.0]))), rtol=1e-6)
    assert sorted(np.asarray(r["own"][0]).tolist()) == [0, 1]
    chosen = scores[[0, 1]]
    assert np.allclose(sorted(np.asarray(r["weights"][0])), sorted(chosen / (chosen.sum() + 1e-6)), rtol=1e-6)
    pushed = _route(jnp.zeros(4).at[3].set(5.0), logits)
    assert sorted(np.asarray(pushed["own"][0]).tolist()) == [0, 3]
    w = np.asarray(pushed["weights"][0])
    assert np.isclose(w.sum(), 1.0, atol=1e-5) and np.isclose(w.min(), scores[3] / (scores[0] + scores[3]), rtol=1e-5)
    forced = jnp.tile(jnp.array([[2, 3]]), (4, 1))
    assert np.array_equal(np.asarray(_route(jnp.zeros(4), logits, forced)["experts"]), np.asarray(forced))


def test_an_absent_expert_adds_nothing():
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    d, m, tokens = 8, 4, 6
    w = {
        "ffn_norm": jnp.ones(d), "router": jax.random.normal(ks[0], (d, 8)), "expert_bias": jnp.zeros(8),
        "w1": jax.random.normal(ks[1], (2, d, m)), "w3": jax.random.normal(ks[2], (2, d, m)),
        "w2": jax.random.normal(ks[3], (2, m, d)),
    }
    cfg = {
        "norm_eps": 1e-5, "num_experts_per_tok": 2, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts": 2, "first_expert_held": 2,
    }
    x = jax.random.normal(ks[4], (1, tokens, d))
    away = jnp.tile(jnp.array([[0, 7]]), (tokens, 1))            # neither is held (2, 3 are)
    out, _, h = R.moe_forward(x, w, cfg, forced=away)
    assert np.allclose(np.asarray(out), np.asarray(x))
    here = jnp.tile(jnp.array([[3, 0]]), (tokens, 1))            # expert 3 is held slot 1
    out, routing, h = R.moe_forward(x, w, cfg, forced=here)
    one = (jax.nn.silu(h @ w["w1"][1]) * (h @ w["w3"][1])) @ w["w2"][1]
    want = np.asarray(routing["weights"][:, :1]) * np.asarray(one)
    assert np.allclose(np.asarray(out - x).reshape(tokens, d), want, atol=1e-5)


@pytest.fixture(scope="module")
def family():
    return conv_moe_decoder.build(dict(TINY), dict(TRAFFIC))


@pytest.fixture(scope="module")
def params(family):
    params = jax.jit(family.init)(jax.random.PRNGKey(11))
    bias = params["layers"]["conv"]["router_bias"]
    params["layers"]["conv"]["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(12), bias.shape)
    return params


def test_the_family_against_the_reference_at_a_small_size(family, params):
    model = family.model
    assert (model.layer_pattern, model.first_dense_kind, model.periods) == (("full", "conv", "conv", "conv"), "conv", 1)
    assert model.tie_embeddings and model.qk_head_norm and model.moe.held == (4, 4) and model.moe.num_experts == 8
    assert family.parameters() == conv_moe_flops.parameters(family.config)
    assert family.parameters() == sum(int(x.size) for x in jax.tree.leaves(params))
    x = jax.random.randint(jax.random.PRNGKey(13), (2, 192), 0, 256)
    y = jax.random.randint(jax.random.PRNGKey(14), (2, 192), 0, 256)
    logits = jax.jit(family.forward)(params, x)
    check = family.check(logits[:, -64:], params, x, last=64)
    assert check["ok"] and check["published"]["rel_rms"] < 1e-4, check
    assert check["conv"]["rel_rms"] < 1e-6 and check["router"]["weights_rel_rms"] < 1e-5
    assert check["harness_rel_rms"] < 1e-6 and 0 < check["held_pairs_pct"] < 100
    assert family.kernel_needed(2, 192)["experts"] == conv_moe_flops.experts_needed(
        family.config, 2, 192, 4, rows=check["held_rows_per_layer"]
    )
    # loss and the gradient of every leaf, the table's through both its uses
    weights = family.reference_weights(params)
    weights = dict(weights, layers=list(weights["layers"]))
    want, want_grads = jax.value_and_grad(R.loss)(weights, x, y, family.config)
    got, grads = jax.jit(jax.value_and_grad(lambda p: family.loss(p, {"x": x, "y": y})))(params)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    named = family.reference_weights(grads)
    pairs = [(named["embed_tokens"], want_grads["embed_tokens"]), (named["embedding_norm"], want_grads["embedding_norm"])]
    for mine, theirs in zip(named["layers"], want_grads["layers"], strict=True):
        assert set(mine) == set(theirs)
        pairs += [(mine[k], theirs[k]) for k in mine if k != "expert_bias"]
    for mine, theirs in pairs:
        worst = float(jnp.max(jnp.abs(mine - theirs))) / float(jnp.max(jnp.abs(theirs)))
        assert worst <= 2e-3, worst


@pytest.mark.parametrize("conv,passes", [("program", True), ("taps_summed_in_bfloat16", False)])
def test_a_convolution_summed_in_bfloat16_fails_its_limit(family, params, conv, passes):
    x = jax.random.randint(jax.random.PRNGKey(13), (2, 192), 0, 256)
    fn = family.conv if conv == "program" else conv_moe_controls.taps_summed_in_bfloat16
    found = R.check_conv(fn, family.reference_weights(params), x, family.config, last=64)
    worst = max(found["rel_rms"], found["last_rel_rms"])
    assert found["ok"] == passes, found
    assert worst < R.TOLERANCE_CONV / 30 if passes else worst > 30 * R.TOLERANCE_CONV, found


@pytest.mark.parametrize("router,passes", [("program", True), ("scores_in_bfloat16", False)])
def test_a_router_with_bfloat16_scores_fails_its_limit(family, params, router, passes):
    x = jax.random.randint(jax.random.PRNGKey(13), (2, 192), 0, 256)
    normed = R.hidden(family.reference_weights(params), x, family.config)[2]
    named = R.first_expert_layer(family.reference_weights(params), family.config)
    layer = family.first_expert_layer(params)
    route = (
        (lambda h: family.route(layer, h)) if router == "program"
        else conv_moe_controls.scores_in_bfloat16(family, layer)
    )
    found = R.check_router(route, named, normed, family.config)
    assert found["ok"] == passes, found
    if not passes:
        assert found["weights_rel_rms"] > 1.5 * R.TOLERANCE_ROUTER, found


def test_a_changed_term_fails_the_check(family, params):
    from ray_tpu.models import transformer as T

    x = jax.random.randint(jax.random.PRNGKey(13), (2, 192), 0, 256)
    replace = T.dataclasses.replace
    for what, model in (
        ("other_block", replace(family.model, moe=replace(family.model.moe, held=(0, 4)))),
        ("no_renormalisation", replace(family.model, moe=replace(family.model.moe, norm_topk_prob=False))),
        ("no_per_head_norm", replace(family.model, qk_head_norm=False)),
    ):
        logits, routing = jax.jit(lambda p, t: T.forward_with_routing(p, t, model))(params, x)
        check = R.check(logits, routing, lambda: family.reference_weights(params), x, family.config)
        assert not check["ok"], what
