"""``reference/hybrid_decoder.py`` against hand-worked cases, and the
comparison's teeth: the program agrees in float32 and, visibly but inside
the tolerances, in bfloat16; a scan whose state is carried in bfloat16, or
whose decays are rounded to bfloat16, fails ``check_scan``. (The equations'
terms, one by one (the norm's placement, a rotary embedding, beta without
its 2, the convolution's taps reversed) fail in ``tests/test_hybrid_model.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import hybrid_decoder
from benchmarks.harness import tokens
from benchmarks.reference import hybrid_decoder as reference
from ray_tpu.ops import gated_delta_rule as G

PERIOD = ["linear_attention", "linear_attention", "linear_attention", "full_attention"]
TINY = {
    "name": "tiny", "family": "hybrid_decoder", "model_type": "olmo_hybrid", "hidden_size": 128,
    "intermediate_size": 320, "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 4, "layer_types": PERIOD * 2, "linear_num_key_heads": 4,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16, "linear_value_head_dim": 32,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True, "vocab_size": 256,
    "rope_parameters": {"rope_theta": None}, "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "tie_word_embeddings": False, "attention_bias": False, "torch_dtype": "float32",
}
TRAFFIC = {"seq_len": 512, "batch_size": 1, "remat": None}


def family(**changes):
    return hybrid_decoder.build(dict(TINY, **changes), TRAFFIC)


def ids(rows=1):
    spec = {"distribution": "zipf", "a": 1.1}
    return jnp.asarray(tokens.rows(spec, TINY["vocab_size"], 7, rows, TRAFFIC["seq_len"]))


# -- hand-worked ---------------------------------------------------------

def test_short_conv_by_hand():
    """Two channels, taps (1, 2, 3, 4) and (0, 0, 0, 1): the LAST tap is the
    current token's, earlier tokens are zero before the sequence starts."""
    x = jnp.array([[[1.0, 5.0], [2.0, 6.0], [3.0, 7.0]]])
    filters = jnp.array([[1.0, 0.0], [2.0, 0.0], [3.0, 0.0], [4.0, 1.0]])
    linear = np.array([[4.0, 5.0], [3.0 + 8.0, 6.0], [2.0 + 6.0 + 12.0, 7.0]])
    want = linear / (1.0 + np.exp(-linear))
    np.testing.assert_allclose(np.asarray(reference.short_conv(x, filters))[0], want, rtol=1e-6)


def test_delta_rule_by_hand():
    """One head, d_k = 2, d_v = 1, two tokens. S_1 = beta_1 v_1 k_1^T; S_2 =
    alpha_2 S_1 + beta_2 (v_2 - alpha_2 S_1 k_2) k_2^T; o_t = S_t q_t."""
    q = jnp.array([[[[1.0, 0.0]], [[1.0, 1.0]]]])
    k = jnp.array([[[[1.0, 0.0]], [[0.6, 0.8]]]])
    v = jnp.array([[[[2.0]], [[-1.0]]]])
    alpha, beta = jnp.array([[[0.5], [0.9]]]), jnp.array([[[1.5], [0.5]]])
    s1 = 1.5 * 2.0 * np.array([1.0, 0.0])
    decayed = 0.9 * s1
    s2 = decayed + 0.5 * (-1.0 - decayed @ np.array([0.6, 0.8])) * np.array([0.6, 0.8])
    want = np.array([s1 @ [1.0, 0.0], s2 @ [1.0, 1.0]])
    got = reference.delta_rule(q, k, v, alpha, beta)
    np.testing.assert_allclose(np.asarray(got)[0, :, 0, 0], want, rtol=1e-6)


def test_a_wiped_state_forgets_and_a_kept_state_remembers():
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k = (jax.random.normal(key, (1, 6, 1, 4)) for key in keys[:2])
    v = jax.random.normal(keys[2], (1, 6, 1, 3))
    beta = jnp.ones((1, 6, 1))
    wiped = reference.delta_rule(q, k, v, jnp.ones((1, 6, 1)).at[0, 3].set(0.0), beta)
    alone = reference.delta_rule(q[:, 3:], k[:, 3:], v[:, 3:], jnp.ones((1, 3, 1)), beta[:, 3:])
    np.testing.assert_allclose(wiped[:, 3:], alone, rtol=1e-5, atol=1e-6)


# -- the comparison's teeth ----------------------------------------------

@pytest.fixture(scope="module")
def seeded():
    fam = family()
    return fam, jax.jit(fam.init)(jax.random.PRNGKey(3)), ids()


def test_float32_agrees_with_room_to_spare(seeded):
    fam, params, x = seeded
    check = fam.check(jax.jit(fam.forward)(params, x)[:, -64:], params, x, last=64)
    assert check["ok"] and check["published"]["rel_rms"] < 1e-4
    assert check["worst_position_rel_rms"] < 1e-3
    assert check["scan"]["ok"] and check["scan"]["rel_rms"] < 1e-5 and check["scan"]["last_rel_rms"] < 1e-5


def test_bfloat16_shows_in_the_logits_and_not_in_the_scan(seeded):
    """At these widths (hidden 128, two periods of normed branches) bfloat16
    weights and activations move the logits by several percent: more than
    the 1.8e-2 the published widths read on the chip, which is why the
    logits' limits cannot see the scan's precision and ``check_scan`` is
    there. The scan is handed float32 operands either way."""
    _fam, _params, x = seeded
    fam = family(torch_dtype="bfloat16")
    params = jax.jit(fam.init)(jax.random.PRNGKey(3))
    assert params["layers"]["linear"]["wq"].dtype == jnp.bfloat16
    assert params["layers"]["linear"]["a_log"].dtype == jnp.float32
    check = fam.check(jax.jit(fam.forward)(params, x)[:, -64:], params, x, last=64)
    assert 1e-3 < check["published"]["rel_rms"] < 2e-1
    assert check["scan"]["ok"] and check["scan"]["rel_rms"] < 1e-5


@pytest.mark.parametrize("what", ["state_in_bfloat16", "decays_in_bfloat16"])
def test_a_lower_precision_scan_fails_the_scan_check(what, seeded, monkeypatch):
    """The nearest precision below the one the configuration's scan states
    (float32 state and decays): NOT correct by ``TOLERANCE_SCAN``, while
    the logits, through four layers' norms, stay inside theirs."""
    fam, params, x = seeded
    if what == "state_in_bfloat16":
        monkeypatch.setattr(G, "_STATE_DTYPE", jnp.bfloat16)
    else:
        prepare = G._prepare
        monkeypatch.setattr(G, "_prepare", lambda q, k, v, log_alpha, beta, chunk: prepare(
            q, k, v, jax.lax.reduce_precision(log_alpha, 8, 7), beta, chunk
        ))
    jax.clear_caches()
    try:
        scan = jax.jit(lambda *a: hybrid_decoder.Family.scan.__wrapped__(*a))
        found = reference.check_scan(scan, fam.reference_weights(params), x, fam.config, last=64)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not found["ok"] and max(found["rel_rms"], found["last_rel_rms"]) > 2 * reference.TOLERANCE_SCAN
