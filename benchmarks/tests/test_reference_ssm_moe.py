"""``reference/ssm_moe_decoder.py`` held to its own description on tiny
hand-checkable inputs, and its comparison held to what must fail: the plain
reference is the yardstick of the cell's ``correct``, so it is tested without
the program (the controls, which need the program's scan to be wrong about,
are held in ``tests/test_ssm_moe.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import ssm_moe_decoder as R

SIZES = {"heads": 4, "width": 2, "state": 3, "groups": 2, "inner": 8, "eps": 1e-5}
KEY = tuple(sorted(SIZES.items()))


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


def test_layer_kinds_follow_the_pattern_and_the_share_the_file():
    cfg = {"hybrid_override_pattern": "MEMEMEMEM*E", "num_hidden_layers": 11}
    kinds = R.layer_kinds(cfg)
    assert kinds == ["mamba", "moe"] * 4 + ["mamba", "attention", "moe"]
    with pytest.raises(ValueError, match="11 kinds for 10 layers"):
        R.layer_kinds(dict(cfg, num_hidden_layers=10))
    cfg = {"n_routed_experts": 16, "published": {"n_routed_experts": 512}, "first_expert_held": 32}
    assert (R.router_width(cfg), R.held_block(cfg)) == (512, (32, 16))
    assert R.router_width({"n_routed_experts": 512}) == 512


def _mamba_weights(key, d=6):
    ks = iter(jax.random.split(key, 8))
    draw = lambda *shape: jax.random.normal(next(ks), shape) * shape[0] ** -0.5
    conv = SIZES["inner"] + 2 * SIZES["groups"] * SIZES["state"]
    return {
        "norm": jnp.ones(d), "in_proj": draw(d, 2 * SIZES["inner"] + 12 + SIZES["heads"]),
        "conv1d_weight": draw(4, conv), "conv1d_bias": draw(conv),
        "dt_bias": jnp.linspace(-3.0, 1.0, 4), "A_log": jnp.log(jnp.array([1.0, 2.0, 8.0, 16.0])),
        "D": jnp.array([1.0, 0.5, 0.0, -1.0]), "mixer_norm": 1.0 + 0.1 * draw(SIZES["inner"]),
        "out_proj": draw(SIZES["inner"], d),
    }


def test_the_recurrence_by_hand_a_head_reads_its_group():
    """Two tokens, by hand: ``S_1 = dt_1 x_1 B_1^T``, ``S_2 = exp(dt_2 A) S_1 +
    dt_2 x_2 B_2^T``, ``y_t = S_t C_t + D x_t``, heads 0-1 on group 0 and 2-3
    on group 1."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (1, 2, 4, 2))
    dt = jnp.array([[[0.1, 0.2, 0.3, 0.4], [0.5, 0.6, 0.7, 0.8]]])
    A, D = -jnp.array([1.0, 2.0, 3.0, 4.0]), jnp.array([1.0, 0.0, 2.0, -1.0])
    B, C = jax.random.normal(ks[1], (1, 2, 2, 3)), jax.random.normal(ks[2], (1, 2, 2, 3))
    got = np.asarray(R.recurrence(x, dt, A, B, C, D))
    x64, B64, C64 = (np.asarray(t, np.float64) for t in (x, B, C))
    for head in range(4):
        group = head // 2
        s1 = dt[0, 0, head] * np.outer(x64[0, 0, head], B64[0, 0, group])
        s2 = np.exp(float(dt[0, 1, head] * A[head])) * s1 + float(dt[0, 1, head]) * np.outer(
            x64[0, 1, head], B64[0, 1, group]
        )
        want1 = s1 @ C64[0, 0, group] + float(D[head]) * x64[0, 0, head]
        want2 = s2 @ C64[0, 1, group] + float(D[head]) * x64[0, 1, head]
        assert np.allclose(got[0, 0, head], want1, rtol=1e-5, atol=1e-6), head
        assert np.allclose(got[0, 1, head], want2, rtol=1e-5, atol=1e-6), head


def test_the_mixer_convolves_with_a_bias_gates_first_and_norms_by_group():
    w = _mamba_weights(jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 9, 6))
    x_, dt, A, B, C, D = R.ssm_operands(x, w, sizes=KEY)
    h = np.asarray(R.rms_norm(x, jnp.ones(6), 1e-5), np.float64)
    proj = h @ np.asarray(w["in_proj"], np.float64)
    # dt = softplus(dt~ + dt_bias) from the LAST heads columns; A = -exp(A_log)
    want_dt = np.log1p(np.exp(proj[..., -4:] + np.asarray(w["dt_bias"], np.float64)))
    assert np.allclose(np.asarray(dt), want_dt, rtol=1e-5)
    assert np.allclose(np.asarray(A), [-1.0, -2.0, -8.0, -16.0], rtol=1e-6)
    # the convolution: last tap on the current token, zeros before the sequence, bias, SiLU
    xbc = proj[..., 8:8 + 20]
    taps, bias = np.asarray(w["conv1d_weight"], np.float64), np.asarray(w["conv1d_bias"], np.float64)
    pre = sum(np.pad(xbc, ((0, 0), (3, 0), (0, 0)))[:, j:j + 9] * taps[j] for j in range(4)) + bias
    conv = pre / (1.0 + np.exp(-pre))
    assert np.allclose(np.asarray(x_).reshape(1, 9, 8), conv[..., :8], rtol=1e-4, atol=1e-6)
    assert np.allclose(np.asarray(B).reshape(1, 9, 6), conv[..., 8:14], rtol=1e-4, atol=1e-6)
    assert np.allclose(np.asarray(C).reshape(1, 9, 6), conv[..., 14:], rtol=1e-4, atol=1e-6)
    # the whole mixer: gate FIRST, then a norm over each of the 2 groups of 4 channels
    y = np.asarray(R.recurrence(x_, dt, A, B, C, D), np.float64).reshape(1, 9, 8)
    z = proj[..., :8]
    gated = (y * z / (1.0 + np.exp(-z))).reshape(1, 9, 2, 4)
    normed = gated / np.sqrt(np.mean(gated ** 2, axis=-1, keepdims=True) + 1e-5)
    want = np.asarray(x, np.float64) + (
        normed.reshape(1, 9, 8) * np.asarray(w["mixer_norm"], np.float64)
    ) @ np.asarray(w["out_proj"], np.float64)
    assert np.allclose(np.asarray(R.mamba_forward(x, w, sizes=KEY)), want, rtol=1e-4, atol=1e-5)
    for control in ("gate_after_norm", "no_conv_bias"):
        wrong = R.mamba_forward(x, w, sizes=KEY, control=control)
        assert float(jnp.max(jnp.abs(wrong - want))) > 1e-2, control


def _moe(key, d=6, latent=4, width=5, shared=7, experts=8, held=4):
    ks = iter(jax.random.split(key, 10))
    draw = lambda *shape: jax.random.normal(next(ks), shape) * shape[-2] ** -0.5
    weights = {
        "norm": jnp.ones(d), "router": draw(d, experts),
        "e_score_correction_bias": 0.1 * jax.random.normal(next(ks), (experts,)),
        "up_proj": draw(held, latent, width), "down_proj": draw(held, width, latent),
        "fc1_latent_proj": draw(d, latent), "fc2_latent_proj": draw(latent, d),
        "shared_up_proj": draw(d, shared), "shared_down_proj": draw(shared, d),
    }
    cfg = {
        "layer_norm_epsilon": 1e-5, "num_experts_per_tok": 3, "norm_topk_prob": True,
        "routed_scaling_factor": 5, "n_routed_experts": held, "first_expert_held": 2,
        "published": {"n_routed_experts": experts},
    }
    return weights, cfg


def test_the_experts_are_ungated_live_in_the_latent_and_only_the_held_add():
    w, cfg = _moe(jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 7, 6))
    out, routing = R.moe_forward(x, w, cfg)
    h = np.asarray(R.rms_norm(x, jnp.ones(6), 1e-5), np.float64).reshape(7, 6)
    scores = 1.0 / (1.0 + np.exp(-(h @ np.asarray(w["router"], np.float64))))
    chosen = np.argsort(-(scores + np.asarray(w["e_score_correction_bias"], np.float64)), -1)[:, :3]
    assert np.array_equal(np.sort(chosen, -1), np.sort(np.asarray(routing["experts"]), -1))
    assert np.allclose(np.asarray(routing["weights"]).sum(-1), 5.0, rtol=1e-5)   # renormalised x 5
    relu2 = lambda t: np.maximum(t, 0.0) ** 2
    u = h @ np.asarray(w["fc1_latent_proj"], np.float64)
    routed = np.zeros_like(u)
    for token in range(7):
        total = scores[token, chosen[token]].sum()
        for expert in chosen[token]:
            if 2 <= expert < 6:                                        # held here: experts 2-5
                up, down = (np.asarray(w[n][expert - 2], np.float64) for n in ("up_proj", "down_proj"))
                routed[token] += 5.0 * scores[token, expert] / total * (relu2(u[token] @ up) @ down)
    want = routed @ np.asarray(w["fc2_latent_proj"], np.float64) + relu2(
        h @ np.asarray(w["shared_up_proj"], np.float64)
    ) @ np.asarray(w["shared_down_proj"], np.float64)
    assert np.allclose(np.asarray(out - x).reshape(7, 6), want, rtol=1e-4, atol=1e-5)
    gated, _ = R.moe_forward(x, w, dict(cfg, control="gated_expert"))
    assert float(jnp.max(jnp.abs(gated - out))) > 1e-2


def test_a_wrong_scan_fails_check_scan_and_the_recurrence_itself_passes():
    cfg = {
        "hybrid_override_pattern": "M", "num_hidden_layers": 1, "mamba_num_heads": 4,
        "mamba_head_dim": 2, "ssm_state_size": 3, "n_groups": 2, "layer_norm_epsilon": 1e-5,
    }
    weights = {
        "embed_tokens": jax.random.normal(jax.random.PRNGKey(5), (16, 6)),
        "layers": [_mamba_weights(jax.random.PRNGKey(6))],
    }
    tokens = jax.random.randint(jax.random.PRNGKey(7), (1, 40), 0, 16)
    right = R.check_scan(R.recurrence, dict(weights), tokens, cfg)
    assert right["ok"] and right["layer"] == 0 and right["own"]["rel_rms"] == 0.0
    assert right["opened"]["steepest_log_decay"] == pytest.approx(-1.6, rel=1e-6)
    assert right["opened"]["mean_log_decay"] == pytest.approx(-1.6, rel=1e-6)
    next_group = lambda x, dt, A, B, C, D: R.recurrence(x, dt, A, jnp.roll(B, 1, 2), jnp.roll(C, 1, 2), D)
    slower = lambda x, dt, A, B, C, D: R.recurrence(x, dt, 0.999 * A, B, C, D)
    for scan in (next_group, slower):
        wrong = R.check_scan(scan, dict(weights), tokens, cfg)
        assert not wrong["ok"] and any(
            wrong[decays]["rel_rms"] > R.TOLERANCE_SCAN[decays] for decays in ("own", "opened")
        )
