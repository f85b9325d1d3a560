"""``reference/gated_window_moe_decoder.py`` held to its own description on
tiny hand-checkable inputs, the family against it at a small size, gradients
included, and its comparison held to what must fail: the gate dropped, the
branch-output norms dropped, the embedding unscaled, the window ignored, RoPE
on the global layer and the router's scores in bfloat16 each fail a stated
limit; a bias rule with a flipped sign or no centring fails its own."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import gated_window_moe_decoder
from benchmarks.harness import gated_window_moe_controls
from benchmarks.reference import gated_window_moe_decoder as R
from benchmarks.tests.test_discovery_gated_window_moe import TINY

TRAFFIC = {"seq_len": 96, "batch_size": 2, "remat": "full"}


@pytest.fixture(scope="module")
def family():
    return gated_window_moe_decoder.build(TINY, TRAFFIC)


@pytest.fixture(scope="module")
def params(family):
    return jax.jit(family.init)(jax.random.PRNGKey(62))


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


def test_the_bias_rule_by_hand():
    """n = [4, 0, 2, 6], mean 3: signs [-1, 1, 1, -1], whose mean is 0; n = [9,
    1, 1, 1]: signs [-1, 1, 1, 1] less their mean 0.5; an expert AT the mean
    stays (sign(0) = 0) but for the centring."""
    rate = 0.001
    np.testing.assert_allclose(
        R.bias_rule(np.zeros(4), [4, 0, 2, 6], rate), [-rate, rate, rate, -rate], rtol=1e-6)
    np.testing.assert_allclose(
        R.bias_rule(np.ones(4), [9, 1, 1, 1], rate) - 1, rate * np.array([-1.5, 0.5, 0.5, 0.5]), rtol=1e-3)
    np.testing.assert_allclose(
        R.bias_rule(np.zeros(3), [3, 2, 1], rate), [-rate, 0.0, rate], atol=1e-12)
    assert R.bias_rule(np.zeros(4, np.float64), [1, 2, 3, 4], rate).dtype == np.float32


def test_the_routing_by_hand():
    """Scores under a bias: the bias chooses, the unbiased scores weigh."""
    m = jnp.eye(4, dtype=jnp.float32)[:2]                       # two tokens, hidden 4
    router = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 0.0, 0.0],
                          [0.0, 0.0, 0.0, 0.0], [0.0, 0.0, 0.0, 0.0]], jnp.float32)
    bias = jnp.asarray([-1.0, 0.0, 0.0, 1.0], jnp.float32)
    out = R.route(m, router, bias, None, top_k=2, norm=True, scale=2.0)
    s = 1 / (1 + np.exp(-np.array([2.0, 1.0, 0.0, -1.0])))
    # token 0: s + b = [-0.12, 0.73, 0.5, 1.27]: experts 3 and 1, weighed by s alone
    assert sorted(np.asarray(out["experts"][0]).tolist()) == [1, 3]
    chosen = np.asarray(out["experts"][0])
    np.testing.assert_allclose(np.asarray(out["weights"][0]), 2.0 * s[chosen] / s[[1, 3]].sum(), rtol=1e-6)
    # token 1: every score 0.5: the bias alone chooses 3 and then 1 (the first of the tie)
    assert np.asarray(out["experts"][1]).tolist() == [3, 1]
    np.testing.assert_allclose(np.asarray(out["weights"][1]), [1.0, 1.0], rtol=1e-6)


def test_the_family_matches_the_reference_logits_loss_and_gradients(family, params):
    tokens = ids()
    weights = lambda tree: dict(family.reference_weights(tree), layers=list(family.reference_weights(tree)["layers"]))
    want, routings = R.logits(weights(params), tokens, TINY)
    got = family.forward(params, tokens)
    assert R.compare(got, want, 1e-4)["ok"]
    assert len(routings) == 4 and routings[0]["experts"].shape == (192, 2)
    x, y = tokens[:, :-1], tokens[:, 1:]
    short = gated_window_moe_decoder.build(TINY, dict(TRAFFIC, seq_len=95))
    (loss, _moved), grads = jax.jit(jax.value_and_grad(short.loss, has_aux=True))(params, {"x": x, "y": y})
    want_loss, want_grads = jax.value_and_grad(lambda w: R.loss(w, x, y, TINY))(weights(params))
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    got_grads = weights(grads)
    for name in ("embed_tokens", "lm_head", "norm"):
        assert R.compare(got_grads[name], want_grads[name], 2e-3)["ok"], name
    for i, (g, w) in enumerate(zip(got_grads["layers"], want_grads["layers"], strict=True)):
        for name in w:
            if name == "expert_bias":
                assert not np.any(np.asarray(g[name]))
            elif float(jnp.max(jnp.abs(w[name]))) > 0:
                assert R.compare(g[name], w[name], 5e-3)["ok"], (i, name)


def test_the_check_passes_the_program_and_fails_every_control(family, params):
    tokens = ids(seed=3, batch=1)
    program = family.forward(params, tokens)[:, -32:]
    result = family.check(program, params, tokens, last=32)
    assert result["ok"] and result["router"]["ok"] and result["bias_rule"]["ok"], result
    assert result["held_pairs_pct"] == 100.0 and result["harness_rel_rms"] < 1e-6
    for name, model in gated_window_moe_controls.models(family.model).items():
        control = family.check(program, params, tokens, last=32, model=model)
        assert not control["ok"], name
        assert control["bias_rule"]["ok"]           # the rule is not what the control changed
    layer = family.first_expert_layer(params)
    rounded = gated_window_moe_controls.routers(family, layer)["scores_in_bfloat16"]
    control = family.check(program, params, tokens, last=32, route=rounded)
    assert not control["router"]["ok"] and not control["ok"]
    # on the CPU the default precision IS float32: that control is the program here
    assert family.check(program, params, tokens, last=32, route=gated_window_moe_controls.routers(
        family, layer)["logits_one_pass"])["router"]["ok"]


@pytest.mark.parametrize("wrong", ["flipped", "uncentred", "doubled"])
def test_a_wrong_rule_fails_the_rules_own_check(wrong):
    biases = [np.zeros(8, np.float32), np.full(8, -0.5, np.float32)]
    counts = [[40, 0, 0, 8, 8, 8, 0, 0], [9, 1, 1, 1, 20, 0, 0, 0]]
    rate = TINY["load_balance_coeff"]
    right = [R.bias_rule(b, n, rate) for b, n in zip(biases, counts)]
    assert R.check_bias_rule(right, biases, counts, TINY)["ok"]

    def step(b, n):
        n = np.asarray(n, np.float32)
        d = rate * np.sign(n.mean() - n)
        return {"flipped": b - (d - d.mean()), "uncentred": b + d, "doubled": b + 2 * (d - d.mean())}[wrong]

    got = R.check_bias_rule([step(b, n) for b, n in zip(biases, counts)], biases, counts, TINY)
    assert not got["ok"] and (got["worst_over_rate"] > 0.2 or not got["signs_agree"])
