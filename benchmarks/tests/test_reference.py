"""The plain reference against the program's forward at a tiny size, and
the tolerance's teeth: what must fail, fails."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import dense_decoder
from benchmarks.harness import tokens
from benchmarks.reference import dense_decoder as reference

TINY = {
    "name": "tiny", "family": "dense_decoder", "hidden_size": 64, "intermediate_size": 160,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 256, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "sliding_window": None, "tie_word_embeddings": False,
    "torch_dtype": "float32",
}
TRAFFIC = {"seq_len": 128, "batch_size": 2, "remat": None}


def family(**changes):
    return dense_decoder.build(dict(TINY, **changes), TRAFFIC)


def ids(fam, rows=2):
    spec = {"distribution": "zipf", "a": 1.1}
    return jnp.asarray(tokens.rows(spec, fam.config["vocab_size"], 7, rows, TRAFFIC["seq_len"]))


@pytest.fixture(scope="module")
def weights():
    return family().init(jax.random.PRNGKey(3))


def test_reference_agrees_with_program_in_float32(weights):
    fam = family()
    x = ids(fam)
    got = fam.check(jax.jit(fam.forward)(weights, x), weights, x)
    assert got["ok"] and got["published"]["rel_rms"] < 2e-6 and "as_computed" not in got, got


def test_last_positions_against_whole_context(weights):
    fam = family()
    x = ids(fam)
    whole = fam.reference_logits(weights, x)
    last = fam.reference_logits(weights, x, last=32)
    assert last.shape == (2, 32, 256)
    np.testing.assert_allclose(np.asarray(last), np.asarray(whole[:, -32:]), rtol=1e-6, atol=1e-6)


def test_query_blocks_give_the_same_attention():
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (1, 512, heads, 16))
        for i, heads in ((0, 4), (1, 2), (2, 2))
    )
    whole = reference.causal_attention(q, k, v)
    orig = reference.query_block
    reference.query_block = lambda *_a, **_k: 128
    try:
        blocked = reference.causal_attention(q, k, v)
    finally:
        reference.query_block = orig
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole), rtol=1e-5, atol=1e-6)
    assert reference.query_block(1, 32, 16384) == 512
    assert reference.query_block(1, 32, 4096) == 2048


def test_bfloat16_program_is_inside_the_tolerance_and_not_far_inside():
    """The program in bf16 against the float32 reference on the same
    (bf16-rounded) weights: inside 2e-2, but a visible error, so the
    tolerance is not orders of magnitude loose."""
    fam = family(torch_dtype="bfloat16")
    weights = fam.init(jax.random.PRNGKey(3))
    x = ids(fam)
    got = fam.check(jax.jit(fam.forward)(weights, x), weights, x)
    assert got["ok"] and 5e-4 < got["published"]["rel_rms"] < reference.TOLERANCE, got


@pytest.mark.parametrize(
    "what, change",
    [
        ("other rotary base", {"rope_theta": 100.0}),
        ("eps a thousand times larger", {"rms_norm_eps": 1e-3}),
        ("KV heads grouped otherwise", {"num_key_value_heads": 4}),
    ],
)
def test_a_changed_term_fails(weights, what, change):
    """The reference told another story than the program computes: the
    comparison must notice."""
    fam = family()
    x = ids(fam)
    program = jax.jit(fam.forward)(weights, x)
    if "num_key_value_heads" in change:
        # pair KV heads wrongly: repeat-interleave becomes tile
        w = fam.reference_weights(weights)
        layers = []
        for layer in w["layers"]:
            layers.append(dict(layer, k_proj=jnp.flip(layer["k_proj"].reshape(64, 2, 16), 1).reshape(64, 32)))
        w["layers"] = layers
        wrong = reference.logits(w, x, fam.config)
    else:
        wrong = reference.logits(fam.reference_weights(weights), x, dict(fam.config, **change))
    got = reference.compare(program, wrong)
    assert not got["ok"], (what, got)


def test_low_precision_accumulation_fails():
    """Dot products of the cells' lengths (hidden 4096, MLP 14336) from
    bf16 inputs: accumulated in float32 the error is nothing; in bfloat16,
    and in float16 over the MLP's length, one matmul alone is outside the
    tolerance. (One 4096-long float16 accumulation errs by 0.9e-2, just
    inside: a forward pass chains more than a dozen.)"""

    def error(length, accumulator):
        a = jax.random.normal(jax.random.PRNGKey(0), (32, length), jnp.float32).astype(jnp.bfloat16)
        b = jax.random.normal(jax.random.PRNGKey(1), (length, 32), jnp.float32).astype(jnp.bfloat16)
        exact = a.astype(jnp.float32) @ b.astype(jnp.float32)
        if accumulator == jnp.float32:
            got = jnp.dot(a, b, preferred_element_type=jnp.float32)
        else:
            def body(acc, ab):
                return (acc + (ab[0] * ab[1]).astype(accumulator)).astype(accumulator), None
            got, _ = jax.lax.scan(
                body, jnp.zeros((32, 32), accumulator),
                (a.T[:, :, None].astype(accumulator), b[:, None, :].astype(accumulator)),
            )
        return reference.compare(got, exact)

    assert error(4096, jnp.float32)["rel_rms"] < 1e-5
    assert not error(4096, jnp.bfloat16)["ok"]
    assert not error(14336, jnp.float16)["ok"]
    assert 5e-3 < error(4096, jnp.float16)["rel_rms"] < 1.5e-2
