"""``reference/hybrid_moe_decoder.py`` held to its own description on tiny
hand-checkable inputs, and its comparison held to what must fail: the plain
reference is the yardstick of the cell's ``correct``, so it is tested
without the program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import hybrid_moe_decoder as R


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


def test_layer_kinds_follow_the_published_rule_at_the_published_index():
    whole = {"layer_group_size": 6, "num_hidden_layers": 42}
    kinds = R.layer_kinds(whole)
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == [5, 11, 17, 23, 29, 35, 41]
    cut = R.layer_kinds({"layer_group_size": 6, "num_hidden_layers": 7, "layer_offset": 1})
    assert cut == kinds[1:8] == ["linear_attention"] * 4 + ["full_attention"] + ["linear_attention"] * 2


def test_the_recurrence_is_the_written_one():
    """Two tokens, one head, by hand: the decay scales the state's COLUMNS
    (one a key channel) before the read and the write."""
    q = jnp.array([[1.0, 0.0], [0.5, 2.0]]).reshape(1, 2, 1, 2)
    k = jnp.array([[1.0, 0.0], [0.6, 0.8]]).reshape(1, 2, 1, 2)
    v = jnp.array([[2.0], [3.0]]).reshape(1, 2, 1, 1)
    g = jnp.log(jnp.array([[0.5, 0.25], [0.5, 0.25]])).reshape(1, 2, 1, 2)
    beta = jnp.array([0.5, 1.0]).reshape(1, 2, 1)
    out = np.asarray(R.delta_rule(q, k, v, g, beta)).reshape(2)
    s1 = np.array([[1.0, 0.0]])                                  # 0.5 * 2 * k_1^T, [d_v, d_k]
    assert np.isclose(out[0], 1.0)
    decayed = s1 * np.array([0.5, 0.25])
    read = decayed @ np.array([0.6, 0.8])
    s2 = decayed + 1.0 * (3.0 - read)[:, None] * np.array([[0.6, 0.8]])
    assert np.isclose(out[1], (s2 @ np.array([0.5, 2.0]))[0], rtol=1e-6)
    # a scalar decay is the special case: equal channels
    same = jnp.broadcast_to(g[..., :1], g.shape)
    from benchmarks.reference import hybrid_decoder

    scalar = hybrid_decoder.delta_rule(q, k, v, jnp.exp(same[..., 0]), beta)
    assert np.allclose(np.asarray(R.delta_rule(q, k, v, same, beta)), np.asarray(scalar), rtol=1e-6)


def _route(biased_minus_scores, logits, forced=None, **changes):
    args = dict(eps=1e-6, top_k=2, n_group=4, topk_group=2, norm_topk_prob=True, scaling=2.5)
    args.update(changes)
    hidden = logits.shape[-1]
    x = jnp.eye(hidden)[None]                                    # token t is unit vector t
    # RMSNorm of a unit vector with weight 1 is sqrt(hidden) x it: undo that in the router
    router = logits / jnp.sqrt(jnp.float32(hidden))
    return R.route(x, jnp.ones(hidden), router, biased_minus_scores, forced, **args)[1]


def test_the_group_routine_by_hand():
    """8 experts in 4 groups of 2, the 2 best groups stay, 2 a token. Token 0:
    its single best expert (6) sits in a group whose other member is poor, so
    the group's top-2 sum loses to two middling groups and expert 6 is NOT
    chosen."""
    logits = jnp.full((8, 8), -4.0)
    logits = logits.at[0].set(jnp.array([1.0, 0.9, 0.8, 1.1, -4.0, -4.0, 2.0, -6.0]))
    r = _route(jnp.zeros(8), logits)
    scores = np.asarray(r["scores"][0])
    assert np.argmax(scores) == 6
    assert sorted(np.asarray(r["own"][0]).tolist()) == [0, 3]    # the best of groups 0 and 1
    groups = np.asarray(r["groups"][0])
    assert np.allclose(groups[0], scores[0] + scores[1]) and sorted(np.argsort(groups)[-2:].tolist()) == [0, 1]
    # weights: the chosen scores, renormalised, x 2.5
    chosen = scores[[3, 0]]
    assert np.allclose(sorted(np.asarray(r["weights"][0])), sorted(2.5 * chosen / chosen.sum()), rtol=1e-6)
    # the bias chooses but does not weigh
    biased = _route(jnp.zeros(8).at[7].set(3.0).at[6].set(1.0), logits)
    assert sorted(np.asarray(biased["own"][0]).tolist()) == [6, 7]
    w = np.asarray(biased["weights"][0])
    assert np.isclose(w.sum(), 2.5, rtol=1e-6) and w.min() < 0.1  # expert 7's own score is tiny
    # forced choices are used as given
    forced = jnp.tile(jnp.array([[4, 5]]), (8, 1))
    assert np.array_equal(np.asarray(_route(jnp.zeros(8), logits, forced)["experts"]), np.asarray(forced))


def test_an_absent_expert_adds_nothing_and_the_shared_expert_always_runs():
    key = jax.random.PRNGKey(0)
    d, m, tokens = 8, 4, 6
    ks = jax.random.split(key, 8)
    w = {
        "post_attention_layernorm": jnp.ones(d), "router": jax.random.normal(ks[0], (d, 8)),
        "e_score_correction_bias": jnp.zeros(8),
        "gate_proj": jax.random.normal(ks[1], (2, d, m)), "up_proj": jax.random.normal(ks[2], (2, d, m)),
        "down_proj": jax.random.normal(ks[3], (2, m, d)),
        "shared_gate_proj": jax.random.normal(ks[4], (d, m)),
        "shared_up_proj": jax.random.normal(ks[5], (d, m)),
        "shared_down_proj": jax.random.normal(ks[6], (m, d)),
    }
    cfg = {
        "rms_norm_eps": 1e-6, "num_experts_per_tok": 2, "n_group": 4, "topk_group": 2,
        "norm_topk_prob": True, "routed_scaling_factor": 2.5, "num_experts": 2,
        "first_expert_held": 2,
    }
    x = jax.random.normal(ks[7], (1, tokens, d))
    away = jnp.tile(jnp.array([[0, 7]]), (tokens, 1))            # neither is held (2, 3 are)
    out, routing = R.moe_forward(x, w, cfg, forced=away)
    h = R.rms_norm(x, jnp.ones(d), 1e-6).reshape(tokens, d)
    shared = (jax.nn.silu(h @ w["shared_gate_proj"]) * (h @ w["shared_up_proj"])) @ w["shared_down_proj"]
    assert np.allclose(np.asarray(out - x).reshape(tokens, d), np.asarray(shared), atol=1e-5)
    here = jnp.tile(jnp.array([[3, 0]]), (tokens, 1))            # expert 3 is held slot 1
    out, routing = R.moe_forward(x, w, cfg, forced=here)
    weight = np.asarray(routing["weights"][:, 0])
    one = (jax.nn.silu(h @ w["gate_proj"][1]) * (h @ w["up_proj"][1])) @ w["down_proj"][1]
    want = np.asarray(shared) + weight[:, None] * np.asarray(one)
    assert np.allclose(np.asarray(out - x).reshape(tokens, d), want, atol=1e-5)


@pytest.mark.parametrize("what,passes", [
    ("same", True), ("a_rounding_apart", True), ("below_the_line", False),
    ("a_group_too_many", False), ("a_poor_group", False), ("twice", False),
])
def test_routing_facts_hold_the_choice_to_the_reference(what, passes):
    """16 experts in 4 groups, 2 of them, 3 a token, one token."""
    scores = jnp.array([[
        0.90, 0.80, 0.10, 0.10,    0.70, 0.60, 0.595, 0.10,    0.50, 0.40, 0.10, 0.10,    0.2, 0.2, 0.1, 0.1,
    ]])
    groups = R.group_scores(scores, 4)
    assert np.allclose(np.asarray(groups), [[1.7, 1.3, 0.9, 0.4]])
    reference = {
        "biased": scores, "groups": groups, "own": jnp.array([[0, 1, 4]]),
        "weights": jnp.array([[0.9, 0.8, 0.7]]) / 2.4 * 2.5,
    }
    chosen = {
        "same": [0, 1, 4], "a_rounding_apart": [0, 1, 5],        # hmm: 0.60 against 0.70
        "below_the_line": [0, 1, 7], "a_group_too_many": [0, 4, 8], "a_poor_group": [0, 1, 8],
        "twice": [0, 0, 1],
    }[what]
    if what == "a_rounding_apart":
        chosen = [0, 4, 6]                                       # 0.595 where the line is 0.60... and 1
        reference["biased"] = scores.at[0, 1].set(0.60)
    experts = jnp.array([chosen])
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    weights = weights / weights.sum() * 2.5
    facts = R._routing_facts(experts, weights, reference, n_group=4, topk_group=2)
    ok = (
        float(facts["worst_shortfall"]) <= R.MARGIN
        and float(facts["worst_group_shortfall"]) <= R.GROUP_MARGIN
        and int(facts["most_groups"]) <= 2 and bool(facts["distinct"])
    )
    assert ok == passes, {k: np.asarray(v).tolist() for k, v in facts.items() if k != "tokens_per_expert"}
    assert int(np.asarray(facts["tokens_per_expert"]).sum()) == 3
