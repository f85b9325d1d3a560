"""``reference/kda_gqa_moe_decoder.py`` held to its own description on tiny
hand-checkable inputs, and its comparison held to what must fail: the plain
reference is the yardstick of the cell's ``correct``, so it is tested
without the program (the controls, which need the program's rule to be
wrong about, are held in ``tests/test_kda_gqa_moe.py``)."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import kda_gqa_moe_decoder as R


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


def test_layer_kinds_follow_the_published_list_at_the_published_index():
    listed = list(range(0, 48, 4))
    kinds = R.layer_kinds({"gqa_layers": listed, "num_hidden_layers": 48})
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == listed
    assert kinds.count("linear_attention") == 36
    cut = R.layer_kinds({"gqa_layers": listed, "num_hidden_layers": 4})
    assert cut == kinds[:4] == ["full_attention"] + ["linear_attention"] * 3
    moved = R.layer_kinds({"gqa_layers": listed, "num_hidden_layers": 4, "layer_offset": 2})
    assert moved == kinds[2:6]
    cfg = {"n_routed_experts": 8, "published": {"n_routed_experts": 320}, "first_expert_held": 16}
    assert (R.router_width(cfg), R.held_block(cfg)) == (320, (16, 8))
    assert R.router_width({"n_routed_experts": 320}) == 320


def _linear_weights(key, d=8, heads=2, d_k=4, rank=4):
    ks = iter(jax.random.split(key, 16))
    draw = lambda *shape: jax.random.normal(next(ks), shape) * shape[0] ** -0.5
    wide = heads * d_k
    return {
        "input_layernorm": jnp.ones(d), "q_proj": draw(d, wide), "k_proj": draw(d, wide),
        "v_proj": draw(d, wide), "f_a_proj": draw(d, rank), "f_b_proj": 8.0 * draw(rank, wide),
        "b_proj": draw(d, heads), "g_a_proj": draw(d, rank), "g_b_proj": draw(rank, wide),
        "q_conv1d": draw(4, wide), "k_conv1d": draw(4, wide), "v_conv1d": draw(4, wide),
        "A_log": jnp.log(jnp.array([4.0, 16.0])), "dt_bias": jnp.linspace(-2.0, 6.0, wide),
        "o_norm": jnp.ones(d_k), "o_proj": draw(wide, d),
    }


def test_the_gate_is_unbounded_and_beta_reaches_two():
    """The operands by hand: ``g = -exp(A_log) softplus((h W_fa) W_fb +
    dt_bias)`` with nothing under it, ``beta = 2 sigmoid(h W_b)``, q and k of
    unit length (q times ``d_k^-1/2``)."""
    w = _linear_weights(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, 8))
    q, k, v, g, beta = R.recurrence_operands(x, w, heads=2, d_k=4, eps=1e-5)
    h = np.asarray(R.rms_norm(x, jnp.ones(8), 1e-5), np.float64)
    raw = (h @ np.asarray(w["f_a_proj"], np.float64)) @ np.asarray(w["f_b_proj"], np.float64)
    raw = (raw + np.asarray(w["dt_bias"], np.float64)).reshape(1, 12, 2, 4)
    want = -np.array([4.0, 16.0])[:, None] * np.log1p(np.exp(raw))
    assert np.allclose(np.asarray(g), want, rtol=1e-5)
    assert float(g.max()) < 0.0 and float(g.min()) < -50.0      # far past any "safe" bound
    want_beta = 2.0 / (1.0 + np.exp(-(h @ np.asarray(w["b_proj"], np.float64))))
    assert np.allclose(np.asarray(beta), want_beta, rtol=1e-5) and float(beta.max()) > 1.0
    assert np.allclose(np.asarray(jnp.sum(k * k, -1)), 1.0, atol=1e-4)
    assert np.allclose(np.asarray(jnp.sum(q * q, -1)), 0.25, atol=1e-4)
    # the recurrence forgets what such a gate tells it to: finite, and token
    # t's output under a shut channel reads only what was written since
    out = R.delta_rule(q, k, v, g, beta)
    assert bool(jnp.all(jnp.isfinite(out)))
    opened = R.opened(w)
    assert np.allclose(np.asarray(opened["dt_bias"] - w["dt_bias"]), R.OPENED_BY)


def test_the_linear_mixer_gates_the_normed_output_through_the_rank():
    w = _linear_weights(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 6, 8))
    got = R.linear_mixer_forward(x, w, heads=2, d_k=4, eps=1e-5)
    h = R.rms_norm(x, jnp.ones(8), 1e-5)
    o = R.delta_rule(*R.recurrence_operands(x, w, heads=2, d_k=4, eps=1e-5))
    gate = jax.nn.sigmoid((h @ w["g_a_proj"]) @ w["g_b_proj"]).reshape(o.shape)
    y = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5) * gate
    assert np.allclose(np.asarray(got - x), np.asarray(y.reshape(1, 6, 8) @ w["o_proj"]), atol=1e-5)


def test_the_grouped_query_layer_carries_no_position_and_gates_by_element():
    ks = iter(jax.random.split(jax.random.PRNGKey(4), 8))
    d, heads, kv, hd = 8, 4, 2, 4
    draw = lambda *shape: jax.random.normal(next(ks), shape) * shape[0] ** -0.5
    w = {
        "input_layernorm": jnp.ones(d), "q_proj": draw(d, heads * hd), "k_proj": draw(d, kv * hd),
        "v_proj": draw(d, kv * hd), "g_proj": draw(d, heads * hd), "o_proj": draw(heads * hd, d),
    }
    x = jax.random.normal(next(ks), (1, 5, d))
    got = np.asarray(R.gqa_mixer_forward(x, w, heads=heads, kv_heads=kv, eps=1e-5) - x)
    h = np.asarray(R.rms_norm(x, jnp.ones(d), 1e-5), np.float64)[0]
    q = (h @ np.asarray(w["q_proj"], np.float64)).reshape(5, heads, hd)
    k = (h @ np.asarray(w["k_proj"], np.float64)).reshape(5, kv, hd)
    v = (h @ np.asarray(w["v_proj"], np.float64)).reshape(5, kv, hd)
    out = np.zeros((5, heads, hd))
    for head in range(heads):
        for t in range(5):
            scores = q[t, head] @ k[:t + 1, head // 2].T / 2.0   # KV head j serves heads 2j, 2j+1
            p = np.exp(scores - scores.max())
            out[t, head] = (p / p.sum()) @ v[:t + 1, head // 2]
    gate = 1.0 / (1.0 + np.exp(-(h @ np.asarray(w["g_proj"], np.float64))))
    want = (out.reshape(5, -1) * gate) @ np.asarray(w["o_proj"], np.float64)
    assert np.allclose(got[0], want, atol=1e-5)
    # no position: with the first two tokens exchanged, the LAST token's output is unchanged
    swapped = x[:, jnp.array([1, 0, 2, 3, 4])]
    again = np.asarray(R.gqa_mixer_forward(swapped, w, heads=heads, kv_heads=kv, eps=1e-5) - swapped)
    assert np.allclose(again[0, -1], got[0, -1], atol=1e-5)


def test_an_absent_expert_adds_nothing_and_the_shared_expert_always_runs():
    ks = jax.random.split(jax.random.PRNGKey(5), 8)
    d, m, tokens = 8, 4, 6
    w = {
        "post_attention_layernorm": jnp.ones(d), "router": jax.random.normal(ks[0], (d, 10)),
        "e_score_correction_bias": jnp.zeros(10),
        "gate_proj": jax.random.normal(ks[1], (2, d, m)), "up_proj": jax.random.normal(ks[2], (2, d, m)),
        "down_proj": jax.random.normal(ks[3], (2, m, d)),
        "shared_gate_proj": jax.random.normal(ks[4], (d, m)),
        "shared_up_proj": jax.random.normal(ks[5], (d, m)),
        "shared_down_proj": jax.random.normal(ks[6], (m, d)),
    }
    cfg = {
        "rms_norm_eps": 1e-5, "num_experts_per_tok": 2, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "n_routed_experts": 2, "first_expert_held": 2,
        "published": {"n_routed_experts": 10},
    }
    x = jax.random.normal(ks[7], (1, tokens, d))
    away = jnp.tile(jnp.array([[0, 9]]), (tokens, 1))            # neither is held (2, 3 are)
    out, routing = R.moe_forward(x, w, cfg, forced=away)
    assert routing["scores"].shape == (tokens, 10)               # the router keeps its width
    h = R.rms_norm(x, jnp.ones(d), 1e-5).reshape(tokens, d)
    shared = (jax.nn.silu(h @ w["shared_gate_proj"]) * (h @ w["shared_up_proj"])) @ w["shared_down_proj"]
    assert np.allclose(np.asarray(out - x).reshape(tokens, d), np.asarray(shared), atol=1e-5)
    here = jnp.tile(jnp.array([[3, 0]]), (tokens, 1))            # expert 3 is held slot 1
    out, routing = R.moe_forward(x, w, cfg, forced=here)
    weight = np.asarray(routing["weights"][:, 0])
    assert np.allclose(np.asarray(routing["weights"]).sum(-1), 1.0, atol=1e-6)   # renormalised, x 1
    one = (jax.nn.silu(h @ w["gate_proj"][1]) * (h @ w["up_proj"][1])) @ w["down_proj"][1]
    want = np.asarray(shared) + weight[:, None] * np.asarray(one)
    assert np.allclose(np.asarray(out - x).reshape(tokens, d), want, atol=1e-5)
    # its own choice: the top 2 of score + bias, the bias choosing but not weighing
    biased = dict(w, e_score_correction_bias=jnp.zeros(10).at[7].set(5.0))
    _, own = R.moe_forward(x, biased, cfg)
    assert bool(jnp.all(jnp.any(own["experts"] == 7, axis=-1)))
    assert np.allclose(np.asarray(own["weights"]).sum(-1), 1.0, atol=1e-6)


def test_steep_blocks_counts_where_a_split_at_the_first_row_overflows():
    g = jnp.zeros((1, 64, 2, 4))
    # head 0: block 1 has ONE channel whose |g| sums to 96 (16 x 6); block 2 one at 80
    g = g.at[0, 16:32, 0, 3].set(-6.0).at[0, 32:48, 0, 1].set(-5.0)
    # head 1: one token of -100 in block 3
    g = g.at[0, 50, 1, 0].set(-100.0)
    assert np.isclose(float(R.steep_blocks_pct(g)), 100.0 * 2 / 8)
    assert float(R.steep_blocks_pct(jnp.zeros((1, 40, 2, 4)))) == 0.0


def test_the_scan_check_reads_both_sets_of_gates_and_fails_a_wrong_rule():
    cfg = {
        "gqa_layers": [0], "num_hidden_layers": 2, "rms_norm_eps": 1e-5, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_experts_per_tok": 2, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "n_routed_experts": 2,
        "linear_attn_config": {"num_heads": 2, "head_dim": 4},
    }
    ks = iter(jax.random.split(jax.random.PRNGKey(6), 32))
    d, m = 8, 4
    draw = lambda *shape: jax.random.normal(next(ks), shape) * shape[0] ** -0.5
    moe = lambda: {
        "post_attention_layernorm": jnp.ones(d), "router": draw(d, 2),
        "e_score_correction_bias": jnp.zeros(2), "gate_proj": draw(2, d, m),
        "up_proj": draw(2, d, m), "down_proj": draw(2, m, d), "shared_gate_proj": draw(d, m),
        "shared_up_proj": draw(d, m), "shared_down_proj": draw(m, d),
    }
    full = {
        "input_layernorm": jnp.ones(d), "q_proj": draw(d, 16), "k_proj": draw(d, 8),
        "v_proj": draw(d, 8), "g_proj": draw(d, 16), "o_proj": draw(16, d), **moe(),
    }
    weights = {
        "embed_tokens": draw(32, d), "norm": jnp.ones(d), "lm_head": draw(d, 32),
        "layers": [full, {**_linear_weights(next(ks)), **moe()}],
    }
    tokens = jax.random.randint(next(ks), (1, 48), 0, 32)
    exact = lambda *operands: R.delta_rule(*operands)
    found = R.check_scan(exact, weights, tokens, cfg)
    assert found["ok"] and found["layer"] == 1 and found["own"]["rel_rms"] == 0.0
    assert found["opened"]["steep_blocks_pct"] >= found["own"]["steep_blocks_pct"]
    assert found["opened"]["steepest_log_decay"] < found["own"]["steepest_log_decay"] < 0.0
    clamped = lambda q, k, v, g, beta: R.delta_rule(q, k, v, jnp.maximum(g, -5.0), beta)
    small_beta = lambda q, k, v, g, beta: R.delta_rule(q, k, v, g, 0.5 * beta)
    for wrong in (clamped, small_beta):
        assert not R.check_scan(wrong, weights, tokens, cfg)["ok"]
    # logits of the whole model, forced to its own choices, are its own
    out, routings = R.logits(weights, tokens, cfg)
    again, _ = R.logits(weights, tokens, cfg, forced=[r["experts"] for r in routings])
    assert out.shape == (1, 48, 32) and np.allclose(np.asarray(out), np.asarray(again), atol=1e-6)
