"""``reference/mla_moe_decoder.py`` against hand-worked cases, and the
routing-aware comparison's teeth: the program agrees in float32 and,
visibly but inside the tolerances, in bfloat16; a dropped token, a choice
below the margin and accumulation in bfloat16 fail. (The equations' terms,
one by one — rope over the whole head, the scale, the bias in the weights,
no renormalisation, no scaling factor, no shared branch, no latent norm, an
expert layer in place of layer 0, one expert fewer — fail in
``tests/test_mla_moe.py``.)"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import mla_moe_decoder
from benchmarks.harness import tokens
from benchmarks.reference import dense_decoder
from benchmarks.reference import mla_moe_decoder as reference
from ray_tpu.models import transformer as T

TINY = {
    "name": "tiny", "family": "mla_moe_decoder", "hidden_size": 128, "intermediate_size": 320,
    "moe_intermediate_size": 48, "num_attention_heads": 4, "num_key_value_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "q_lora_rank": None, "num_hidden_layers": 3, "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "n_routed_experts": 16, "num_experts_per_tok": 4, "n_shared_experts": 2, "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc", "scoring_func": "sigmoid", "norm_topk_prob": True,
    "routed_scaling_factor": 2.446, "aux_loss_alpha": 0.001, "seq_aux": True, "vocab_size": 256,
    "rope_theta": 50000, "rope_scaling": None, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "attention_bias": False, "num_nextn_predict_layers": 0, "hidden_act": "silu",
    "torch_dtype": "float32",
}
TRAFFIC = {"seq_len": 128, "batch_size": 2, "remat": None}


def family(**changes):
    return mla_moe_decoder.build(dict(TINY, **changes), TRAFFIC)


def seeded(fam):
    params = fam.init(jax.random.PRNGKey(3))
    bias = params["layers"]["router_bias"]
    params["layers"]["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(9), bias.shape)
    return params


def ids(rows=2):
    spec = {"distribution": "zipf", "a": 1.1}
    return jnp.asarray(tokens.rows(spec, TINY["vocab_size"], 7, rows, TRAFFIC["seq_len"]))


def checked(fam, params, x=None):
    x = ids() if x is None else x
    return reference.check(
        jax.jit(fam.forward)(params, x), fam.routing(params, x),
        lambda: fam.reference_weights(params), x, fam.config,
    )


# -- hand-worked ---------------------------------------------------------

def test_route_by_hand():
    """One token, four experts, two a token. Scores 0.25, 0.75, 0.5, 0.6;
    the bias lifts expert 0 to 0.85: chosen are 0 and 1 (unbiased it would
    be 1 and 3), weighed by 0.25 and 0.75 WITHOUT the bias, over their sum,
    times 2.446."""
    logits = jnp.array([math.log(1 / 3), math.log(3.0), 0.0, math.log(1.5)])
    x = jnp.array([[[1.0, -1.0, 1.0, -1.0]]])                     # rms 1: the norm leaves it
    router = jnp.zeros((4, 4)).at[0].set(logits)
    bias = jnp.array([0.6, 0.0, 0.0, 0.0])
    kw = dict(eps=1e-5, top_k=2, norm_topk_prob=True, scaling=2.446)
    _h, got = reference.route(x, jnp.ones(4), router, bias, None, **kw)
    np.testing.assert_allclose(got["scores"][0], [0.25, 0.75, 0.5, 0.6], atol=1e-5)
    assert sorted(np.asarray(got["experts"][0]).tolist()) == [0, 1]
    by_expert = dict(zip(np.asarray(got["experts"][0]).tolist(), np.asarray(got["weights"][0]).tolist()))
    assert by_expert[0] == pytest.approx(0.25 * 2.446, abs=1e-4)
    assert by_expert[1] == pytest.approx(0.75 * 2.446, abs=1e-4)
    _h, plain = reference.route(x, jnp.ones(4), router, jnp.zeros(4), None, **kw)
    assert sorted(np.asarray(plain["experts"][0]).tolist()) == [1, 3]
    # forced to other choices, the weights are the reference's own scores of THOSE experts
    _h, forced = reference.route(x, jnp.ones(4), router, bias, jnp.array([[2, 3]]), **kw)
    np.testing.assert_allclose(forced["weights"][0], np.array([0.5, 0.6]) / 1.1 * 2.446, atol=1e-4)


def test_balance_loss_by_hand():
    """One sequence of two tokens, two experts, one a token, both tokens
    choose expert 0: f = 2 / (1 x 2) x (2, 0); normalised scores (0.75,
    0.25) and (0.5, 0.5): P = (0.625, 0.375); loss 2 x 0.625. Two such
    sequences of which the second splits evenly (f = (1, 1), loss 1):
    their mean."""
    cfg = {"n_routed_experts": 2, "num_experts_per_tok": 1}
    scores = jnp.array([[0.6, 0.2], [0.3, 0.3]])
    one = {"scores": scores, "experts": jnp.array([[0], [0]])}
    assert float(reference.balance_loss([one], cfg, 1)) == pytest.approx(1.25)
    two = {"scores": jnp.concatenate([scores, scores]), "experts": jnp.array([[0], [0], [0], [1]])}
    assert float(reference.balance_loss([two], cfg, 2)) == pytest.approx((1.25 + 1.0) / 2)
    # the program's statistics say the same
    routing = {
        "experts": two["experts"][None],
        "prob_sum": jnp.array([[2 * 0.625 + 1 * 0.625, 0 * 0.375 + 1 * 0.375]]),
    }
    moe = T.MoEConfig(num_experts=2, top_k=1, scoring="sigmoid")
    assert float(T.load_balancing_loss(routing, moe)) == pytest.approx((1.25 + 1.0) / 2)


def test_attention_by_hand():
    """One head, two positions, no rope dims to speak of: position 0 sees
    itself alone (out = v_0); position 1 weighs v_0 and v_1 by the softmax
    of q_1 k^T x (q's dim)^-0.5, whatever v's dim."""
    q = jnp.array([[[[1.0, 0.0, 0.0]], [[0.0, 3.0, 0.0]]]])          # [b=1, s=2, H=1, d=3]
    k = jnp.array([[[[1.0, 0.0, 0.0]], [[0.0, 1.0, 0.0]]]])
    v = jnp.array([[[[1.0, 2.0]], [[3.0, 6.0]]]])                      # dv = 2
    out = reference.causal_attention(q, k, v)
    assert out.shape == (1, 2, 1, 2)
    np.testing.assert_allclose(out[0, 0, 0], [1.0, 2.0], atol=1e-6)
    w = 1 / (1 + math.exp(-3.0 * 3 ** -0.5))                           # softmax of (0, 3 / sqrt(3))
    np.testing.assert_allclose(out[0, 1, 0], [(1 - w) * 1 + w * 3, (1 - w) * 2 + w * 6], atol=1e-6)


# -- the comparison's teeth ----------------------------------------------

def test_reference_agrees_with_program_in_float32():
    fam = family()
    got = checked(fam, seeded(fam))
    assert got["ok"] and got["published"]["rel_rms"] < 2e-6, got
    assert got["worst_position_rel_rms"] < 1e-5 and got["same_set_share"] == 1.0
    assert len(got["layers"]) == 2                           # layers 1 and 2; layer 0 is dense
    for layer in got["layers"]:
        assert layer["worst_shortfall"] == 0.0 and layer["weights_rel_rms"] < 1e-6
        assert layer["counts_agree"] and layer["pairs"] == 2 * 128 * 4
        assert layer["tokens_per_expert_max"] >= layer["tokens_per_expert_mean"] == 64.0


def test_bfloat16_program_is_inside_the_tolerances_and_not_far_inside():
    # the cell's depth: the dense layer and one expert layer (a third layer's
    # roundings bring this tiny width to 1.25e-2)
    fam = family(torch_dtype="bfloat16", num_hidden_layers=2)
    got = checked(fam, seeded(fam))
    assert got["ok"], got
    assert 5e-4 < got["published"]["rel_rms"] < reference.TOLERANCE
    assert got["published"]["rel_rms"] < got["worst_position_rel_rms"] < reference.POSITION_TOLERANCE
    assert all(1e-4 < l["weights_rel_rms"] < reference.WEIGHT_TOLERANCE for l in got["layers"])
    assert 0.9 < got["same_set_share"] <= 1.0
    assert all(l["worst_shortfall"] <= reference.MARGIN for l in got["layers"])


def test_a_token_without_its_routed_experts_fails_at_its_position(monkeypatch):
    """One token of 256 gets no routed-expert output in the last layer: its
    own position is far outside POSITION_TOLERANCE, the average barely moves."""
    fam = family()
    params, x = seeded(fam), ids()
    real, calls = T._weighted_sum, []

    def drop(per_token, weights_):
        calls.append(1)
        return real(per_token, weights_).at[100].set(0)

    sound = fam.forward(params, x)
    monkeypatch.setattr(T, "_weighted_sum", drop)
    program = fam.forward(params, x)
    monkeypatch.undo()
    assert calls and float(jnp.abs(program - sound).max()) > 0
    got = reference.check(program, fam.routing(params, x), lambda: fam.reference_weights(params), x, fam.config)
    assert not got["ok"] and got["worst_position_at"] == 100, got
    assert got["worst_position_rel_rms"] > 2 * reference.POSITION_TOLERANCE
    assert got["published"]["rel_rms"] < got["worst_position_rel_rms"] / 10


def test_a_choice_below_the_margin_fails():
    """The program's routing with one token's last choice swapped for the
    expert the reference ranks LAST under ``s + b``: the logits comparison is
    forced to the same wrong choice and stays quiet; the margin speaks."""
    fam = family()
    params, x = seeded(fam), ids()
    routing = dict(fam.routing(params, x))
    _, own = reference.logits(fam.reference_weights(params), x, fam.config)
    worst = jnp.argmin(own[0]["biased"][5])
    routing["experts"] = routing["experts"].at[0, 5, -1].set(worst)
    program, _ = reference.logits(
        fam.reference_weights(params), x, fam.config, forced=list(routing["experts"])
    )
    got = reference.check(program, routing, lambda: fam.reference_weights(params), x, fam.config)
    assert got["published"]["rel_rms"] < 1e-6
    assert not got["ok"] and got["layers"][0]["worst_shortfall"] > reference.MARGIN
    assert not got["layers"][0]["counts_agree"]


def _accumulated(a, b, accumulator):
    """``a @ b`` with the sum over the contraction kept in ``accumulator``,
    element by element."""
    if accumulator == jnp.float32:
        return jnp.dot(a, b, preferred_element_type=jnp.float32)

    def body(acc, ab):
        return (acc + (ab[0] * ab[1]).astype(accumulator)).astype(accumulator), None

    out, _ = jax.lax.scan(
        body, jnp.zeros((a.shape[0], b.shape[1]), accumulator),
        (a.T[:, :, None].astype(accumulator), b[:, None, :].astype(accumulator)),
    )
    return out.astype(jnp.float32)


def test_bfloat16_accumulation_fails():
    """The precision below the configuration's (bfloat16 values, float32
    accumulation) is accumulation in bfloat16. At this model's own
    lengths, from bfloat16 inputs: ONE expert matmul (2048 into 1408, or
    1408 into 2048) and ONE row of ``P v`` over 8192 keys are each outside
    TOLERANCE alone; a forward pass chains a dozen. SYNTHETIC operands of
    the real lengths: the whole family in that precision was not run."""
    key = jax.random.PRNGKey(0)
    for length in (2048, 1408):
        x = jax.random.normal(key, (16, length), jnp.float32).astype(jnp.bfloat16)
        w = (jax.random.normal(jax.random.fold_in(key, 1), (length, 256)) * length ** -0.5).astype(jnp.bfloat16)
        sound = _accumulated(x, w, jnp.float32)
        got = dense_decoder.compare(_accumulated(x, w, jnp.bfloat16), sound, reference.TOLERANCE)
        assert not got["ok"] and got["rel_rms"] > 2 * reference.TOLERANCE, (length, got)
    # P v: 8 queries' probabilities over 8192 keys (a softmax of N(0, 1) scores), values N(0, 1)
    probs = jax.nn.softmax(jax.random.normal(key, (8, 8192)), axis=-1).astype(jnp.bfloat16)
    values = jax.random.normal(jax.random.fold_in(key, 2), (8192, 128)).astype(jnp.bfloat16)
    got = dense_decoder.compare(
        _accumulated(probs, values, jnp.bfloat16), _accumulated(probs, values, jnp.float32),
        reference.TOLERANCE,
    )
    assert not got["ok"] and got["rel_rms"] > 2 * reference.TOLERANCE, got
