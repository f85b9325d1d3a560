"""``reference/sambay_decoder.py`` held to its own description on tiny
hand-checkable inputs, the family against it at a small size, and its comparison
held to what must fail: a dropped ``lam`` term, a window off by one, a dropped
skip, the memory taken after the gate and a state carried in bfloat16 each fail
a stated limit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import sambay_decoder
from benchmarks.harness import sambay_controls
from benchmarks.reference import sambay_decoder as R
from benchmarks.tests.test_discovery_sambay import TINY

TRAFFIC = {"seq_len": 160, "batch_size": 1, "remat": "full"}


@pytest.fixture(scope="module")
def family():
    return sambay_decoder.build(TINY, TRAFFIC)


@pytest.fixture(scope="module")
def params(family):
    return jax.jit(family.init)(jax.random.PRNGKey(65))


def ids(seed=1, batch=1, seq=160):
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


def test_the_kinds_by_the_rule():
    kinds = R.layer_kinds(dict(TINY, num_hidden_layers=32))
    assert kinds[:16] == ["mamba", "window"] * 8 and kinds[16:18] == ["mamba", "full"]
    assert kinds[18:] == ["gmu", "cross"] * 7
    assert R.layer_kinds(dict(TINY, num_hidden_layers=12)) == (
        ["mamba", "window"] * 3 + ["mamba", "full"] + ["gmu", "cross"] * 2)
    for wrong in ({"num_hidden_layers": 10}, {"num_hidden_layers": 4}, {"mb_per_layer": 4}):
        with pytest.raises(ValueError):
            R.layer_kinds(dict(TINY, **wrong))
    assert R.lam_init(TINY, 0) == pytest.approx(0.2) and R.lam_init(TINY, 4) == pytest.approx(
        0.8 - 0.6 * np.exp(-4.8))


def test_the_recurrence_by_hand():
    """One channel, one state, three tokens: S = a S + dt B u with a = exp(dt A)."""
    u = jnp.array([[[1.0], [2.0], [3.0]]])
    dt = jnp.full((1, 3, 1), 0.5)
    A, B, C, D = jnp.array([[-2.0]]), jnp.ones((1, 3, 1)), jnp.array([[[1.0], [1.0], [2.0]]]), jnp.array([0.25])
    a = np.exp(-1.0)
    s1 = 0.5 * 1.0
    s2 = a * s1 + 0.5 * 2.0
    s3 = a * s2 + 0.5 * 3.0
    want = [s1 + 0.25, s2 + 0.5, 2 * s3 + 0.75]
    np.testing.assert_allclose(R.recurrence(u, dt, A, B, C, D)[0, :, 0], want, rtol=1e-6)


def test_the_convolution_is_causal_with_its_last_tap_on_the_token():
    x = jnp.zeros((1, 6, 1)).at[0, 2, 0].set(1.0)
    filters = jnp.array([[1.0], [2.0], [3.0], [4.0]])
    pre = R.short_conv(x, filters, jnp.zeros((1,)))[0, :, 0]
    silu = lambda v: v / (1 + np.exp(-v))
    np.testing.assert_allclose(pre, [0, 0, silu(4.0), silu(3.0), silu(2.0), silu(1.0)], rtol=1e-6)


def test_differential_attention_by_hand():
    """One pair, two positions: position 0 sees itself alone, so both softmaxes
    are 1 and o = (1 - lam) v_0 under the norm; the window's edge counts the
    query's own position."""
    rng = np.random.default_rng(0)
    q1, q2, k1, k2 = (jnp.asarray(rng.normal(size=(1, 4, 1, 2)), jnp.float32) for _ in range(4))
    v = jnp.asarray(rng.normal(size=(1, 4, 1, 4)), jnp.float32)
    w = {"lambda_q1": jnp.array([0.1, 0.2]), "lambda_k1": jnp.array([0.3, -0.1]),
         "lambda_q2": jnp.array([0.0, 0.1]), "lambda_k2": jnp.array([0.2, 0.2]),
         "subln_weight": jnp.ones((4,))}
    lam0 = 0.5
    lam = np.exp(0.1 * 0.3 - 0.2 * 0.1) - np.exp(0.1 * 0.2) + lam0
    out = R.differential_attention(q1, q2, k1, k2, v, w, lam0)[0]
    first = (1 - lam) * np.asarray(v[0, 0, 0])
    want = first / np.sqrt(np.mean(first**2) + 1e-5) * (1 - lam0)
    np.testing.assert_allclose(out[0], want, rtol=1e-5)
    # window 1: every query sees itself alone
    alone = R.differential_attention(q1, q2, k1, k2, v, w, lam0, window=1)[0]
    for t in range(4):
        row = (1 - lam) * np.asarray(v[0, t, 0])
        np.testing.assert_allclose(alone[t], row / np.sqrt(np.mean(row**2) + 1e-5) * 0.5, rtol=1e-5)
    # window 2 off by one sees three keys: another output from the third position on
    two = R.differential_attention(q1, q2, k1, k2, v, w, lam0, window=2)
    off = R.differential_attention(q1, q2, k1, k2, v, w, lam0, window=2, control="window_off_by_one")
    np.testing.assert_allclose(two[0, :2], off[0, :2], rtol=1e-6)
    assert float(jnp.max(jnp.abs(two[0, 2:] - off[0, 2:]))) > 1e-3


def test_the_program_agrees_and_every_control_fails(family, params):
    tokens = ids()
    program = jax.jit(family.forward)(params, tokens)[:, -32:]
    found = family.check(program, params, tokens, last=32)
    assert found["ok"] and found["published"]["rel_rms"] < 1e-4
    assert found["scan"]["ok"] and found["differential"]["ok"]
    assert found["scan_kept_gib"] > 0
    weights = lambda: family.reference_weights(params)
    for control in R.CONTROLS:
        wrong = R.check(program, weights, tokens, dict(TINY, control=control), last=32)
        assert not wrong["ok"], control
    for name in sambay_controls.SCAN_CONTROLS:
        wrong = R.check_scan(sambay_controls.scan_control(name), weights(), tokens, TINY)
        assert not wrong["ok"], name
    for name in sambay_controls.ATTEND_CONTROLS:
        wrong = R.check_differential(
            sambay_controls.attend_control(family, params, name), weights(), tokens, TINY)
        assert not wrong["ok"], name


def test_the_recurrence_in_kept_blocks_is_the_recurrence():
    """``kept_every``: the same values and the same gradients, a state a block kept."""
    rng = np.random.default_rng(1)
    u, B, C = (jnp.asarray(rng.normal(size=shape), jnp.float32) for shape in ((2, 24, 8), (2, 24, 4), (2, 24, 4)))
    dt = jax.nn.softplus(jnp.asarray(rng.normal(size=(2, 24, 8)), jnp.float32))
    A, D = -jnp.exp(jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)), jnp.ones((8,))
    operands = (u, dt, A, B, C, D)
    np.testing.assert_array_equal(R.recurrence(*operands), R.recurrence(*operands, kept_every=8))
    dy = jnp.asarray(rng.normal(size=u.shape), jnp.float32)
    grads = lambda **how: jax.vjp(lambda *o: R.recurrence(*o, **how), *operands)[1](dy)
    for got, want in zip(grads(kept_every=8), grads()):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_the_scans_backward_is_held_to_the_recurrences(family, params):
    """``check_scan``'s fourth reading: all six gradients of the program's scan
    against ``jax.vjp`` of the recurrence; a state carried in bfloat16 and a step
    rounded to bfloat16 each fail it, and so does a backward whose ``dA`` or
    ``dC`` alone is wrong."""
    tokens = ids()
    weights = lambda: family.reference_weights(params)
    found = R.check_scan(family.scan, weights(), tokens, TINY)["gradients"]
    assert found["ok"] and max(found[name] for name in R.GRADIENT_NAMES) < 1e-4, found
    for name in sambay_controls.SCAN_CONTROLS:
        wrong = R.check_scan(sambay_controls.scan_control(name), weights(), tokens, TINY)
        assert not wrong["gradients"]["ok"], (name, wrong["gradients"])

    def wrong_gradient(at):
        @jax.custom_vjp
        def scan(*operands):
            return family.scan(*operands)

        def back(operands, dy):
            grads = list(jax.vjp(family.scan, *operands)[1](dy))
            grads[at] = grads[at] * 1.05
            return tuple(grads)

        scan.defvjp(lambda *operands: (family.scan(*operands), operands), back)
        return scan

    for at in (2, 4):
        wrong = R.check_scan(wrong_gradient(at), weights(), tokens, TINY)
        assert wrong["own"]["ok"] and not wrong["gradients"]["ok"], R.GRADIENT_NAMES[at]
        assert wrong["gradients"][R.GRADIENT_NAMES[at]] > R.TOLERANCE_SCAN["gradients"]["float32"]


def test_the_differential_limit_stops_growing_as_lam_nears_one(family, params):
    """A full layer whose ``lam`` is within 0.001 of 1 is held to ``TOLERANCE_DIFF
    x CONDITIONING_CAP``, not to a limit that grows without end: the program
    passes, a dropped ``lam`` still fails, and the float32 witness passes."""
    at = R.layer_kinds(TINY).index("full")
    d = TINY["hidden_size"] // TINY["num_attention_heads"]
    lam0 = R.lam_init(TINY, at)
    flat = lambda value: lambda leaf: jnp.full_like(leaf, value)
    bridge = dict(params["layers"][at // 2][1])
    want = np.log(1.001 - lam0 + 1.0)                       # exp(lq1 . lk1) - exp(0) + lam0 = 1.001
    bridge.update(lq1=flat(np.sqrt(want / d))(bridge["lq1"]), lk1=flat(np.sqrt(want / d))(bridge["lk1"]),
                  lq2=flat(0.0)(bridge["lq2"]), lk2=flat(0.0)(bridge["lk2"]))
    layers = [list(segment) for segment in params["layers"]]
    layers[at // 2][1] = bridge
    near = {**params, "layers": layers}
    tokens = ids()
    weights = lambda: family.reference_weights(near)
    attend = lambda *layer: family.attend(near, *layer)
    found = R.check_differential(attend, weights(), tokens, TINY)
    assert found["full"]["conditioning"] > 1000
    assert found["full"]["limit"] == pytest.approx(R.TOLERANCE_DIFF * R.CONDITIONING_CAP)
    assert found["ok"], found
    dropped = R.check_differential(
        sambay_controls.attend_control(family, near, "lam_dropped"), weights(), tokens, TINY)
    assert not dropped["full"]["ok"], dropped
    witness = R.check_differential(sambay_controls.attend_float32(family, near), weights(), tokens, TINY)
    assert witness["ok"], witness


def test_loss_and_gradients_flow_to_every_leaf(family, params):
    tokens = ids(seq=48)
    weights = dict(family.reference_weights(params))
    weights["layers"] = list(weights["layers"])
    value, grads = jax.value_and_grad(lambda w: R.loss(w, tokens[:, :-1], tokens[:, 1:], TINY))(weights)
    assert np.isfinite(float(value))
    kinds = R.layer_kinds(TINY)
    for kind, layer in zip(kinds, grads["layers"]):
        for name in (*R.NORM_NAMES, *R.MIXER_NAMES[kind], *R.MLP_NAMES):
            assert float(jnp.max(jnp.abs(layer[name]))) > 0 or name == "Wqkv_bias", (kind, name)
