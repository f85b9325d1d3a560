"""Flagship transformer model tests."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention as flash_mod
from ray_tpu.models import transformer as T
from ray_tpu.models.transformer import (
    MoEConfig, TransformerConfig, decode_step, forward, init_kv_cache,
    init_params, loss_fn, num_params,
)


def test_forward_shapes_and_finite():
    config = TransformerConfig.tiny()
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 256)
    logits = forward(params, tokens, config)
    assert logits.shape == (2, 32, 256)
    assert np.isfinite(np.asarray(logits)).all()


def test_grad_flows_everywhere():
    config = TransformerConfig.tiny()
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 256)
    _, grads = jax.value_and_grad(loss_fn)(params, tokens, tokens, config)
    for path, leaf in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert np.isfinite(np.asarray(leaf)).all(), path
        assert float(jnp.abs(leaf).max()) > 0, f"dead grad at {path}"


def test_causality():
    """Changing a future token must not affect earlier logits."""
    config = TransformerConfig.tiny()
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, 256)
    logits_a = forward(params, tokens, config)
    tokens_b = tokens.at[0, -1].set((tokens[0, -1] + 1) % 256)
    logits_b = forward(params, tokens_b, config)
    np.testing.assert_allclose(
        np.asarray(logits_a[0, :-1]), np.asarray(logits_b[0, :-1]), atol=1e-5
    )


def test_moe_forward_and_grad():
    config = TransformerConfig.tiny(moe=MoEConfig(num_experts=4, top_k=2))
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 256)
    loss, grads = jax.value_and_grad(loss_fn)(params, tokens, tokens, config)
    assert np.isfinite(float(loss))
    for name in ("router", "w_gate", "w_up", "w_down"):
        assert float(jnp.abs(grads["layers"][name]).max()) > 0, name


OLMOE_SHAPED = dict(
    n_kv_heads=4, qk_norm=True, rms_norm_eps=1e-5,
    moe=MoEConfig(num_experts=8, top_k=2, aux_loss_coef=0.01),
)


@pytest.mark.parametrize("overrides", [{}, OLMOE_SHAPED], ids=["dense", "olmoe"])
def test_decode_matches_forward(overrides):
    config = TransformerConfig.tiny(**overrides)
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 256)
    cache = init_kv_cache(config, 2, 16)
    for i in range(8):
        logits, cache = decode_step(params, cache, tokens[:, i : i + 1], config)
    full = forward(params, tokens, config)[:, -1]
    assert float(jnp.max(jnp.abs(logits - full))) < 1e-3


@pytest.mark.parametrize("overrides", [{}, OLMOE_SHAPED], ids=["dense", "olmoe"])
def test_full_remat_changes_no_bit_of_loss_or_gradient(overrides):
    """A checkpointed layer recomputes its activations and keeps the flash
    kernel's ``out`` and ``lse``: what is kept is what the recompute would
    have produced, so loss and every gradient leaf are the unchecked
    model's, bit for bit (interpreted kernels)."""
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256)
    results = {}
    for remat in (None, "full"):
        config = TransformerConfig.tiny(remat=remat, attention="flash", **overrides)
        params = init_params(config, jax.random.PRNGKey(0))
        results[remat] = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, tokens, tokens, config)
        ))(params)
    (loss, grads), (loss_remat, grads_remat) = results[None], results["full"]
    assert np.array_equal(np.asarray(loss), np.asarray(loss_remat))
    for (path, leaf), other in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree.leaves(grads_remat)
    ):
        assert np.array_equal(np.asarray(leaf), np.asarray(other)), path


def _tiny_chunks(rows: int):
    """``head_loss``'s chunk rule held to ``rows`` rows of the tiny
    vocabulary's float32 logits, so that 64 tokens walk in several chunks."""
    return mock.patch.object(T, "_LOGITS_CHUNK_BYTES", 4 * rows * 256)


@pytest.mark.parametrize("dtype,batch,seq,mask,chunks", [
    ("float32", 2, 64, None, 1),        # one chunk: the whole sequence
    ("float32", 2, 64, "some", 4),      # 4 chunks of 16, which divide 64
    ("float32", 1, 50, "some", 4),      # 4 chunks of 13: the last holds 11 tokens and padding
    ("float32", 2, 50, "none-set", 4),  # an all-zero mask: loss and gradients 0, not nan
    ("bfloat16", 2, 64, None, 4),
    ("bfloat16", 1, 50, "some", 4),
])
def test_head_loss_is_logits_loss_of_head(dtype, batch, seq, mask, chunks):
    """``head_loss`` against its plain definition ``logits_loss(_head(...))``:
    the value and the gradient of every input (``x``, ``final_norm``,
    ``lm_head``). float32 to 1e-5; bfloat16 to the spacing of its values
    (2**-7 of the value, for a gradient of its largest entry): the plain
    definition rounds the logits to bfloat16 before the softmax, the chunked
    one does not."""
    config = TransformerConfig.tiny(dtype=jnp.dtype(dtype))
    params = init_params(config, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, seq, config.dim)).astype(config.dtype)
    targets = jax.random.randint(jax.random.PRNGKey(2), (batch, seq), 0, config.vocab_size)
    mask = {
        None: None,
        "some": (jax.random.uniform(jax.random.PRNGKey(3), (batch, seq)) > 0.3).astype(jnp.float32),
        "none-set": jnp.zeros((batch, seq), jnp.float32),
    }[mask]

    def plain(params, x):
        return T.logits_loss(T._head(params, x, config), targets, mask)

    def chunked(params, x):
        return T.head_loss(params, x, targets, config, mask)

    with _tiny_chunks(batch * -(-seq // chunks)):
        assert T._head_chunks(batch, seq, config.vocab_size) == (chunks, -(-seq // chunks))
        want, want_grads = jax.value_and_grad(plain, argnums=(0, 1))(params, x)
        got, got_grads = jax.value_and_grad(chunked, argnums=(0, 1))(params, x)
    ulp = 1e-5 if dtype == "float32" else 2.0 ** -7
    assert abs(float(got) - float(want)) <= ulp * max(float(want), 1.0)
    for name, g, w in [("x", got_grads[1], want_grads[1])] + [
        (name, got_grads[0][name], want_grads[0][name]) for name in ("final_norm", "lm_head")
    ]:
        assert g.dtype == w.dtype and g.shape == w.shape, name
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= ulp * max(np.abs(w).max(), 1e-6), name
        if mask is None or float(mask.sum()):
            assert np.abs(g).max() > 0, name


def test_loss_fn_is_the_plain_loss_with_the_balancing_term():
    """``loss_fn`` through ``head_loss`` (in two chunks) on the tiny
    OLMoE-shaped model: cross-entropy of ``forward``'s logits plus
    ``aux_loss_coef`` times the balancing loss, value and every gradient."""
    config = TransformerConfig.tiny(**OLMOE_SHAPED)
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 256)
    mask = (jax.random.uniform(jax.random.PRNGKey(3), (2, 32)) > 0.3).astype(jnp.float32)

    def plain(params):
        logits, routing = T.forward_with_routing(params, tokens, config)
        balance = T.load_balancing_loss(routing, config.moe)
        return T.logits_loss(logits, tokens, mask) + config.moe.aux_loss_coef * balance

    with _tiny_chunks(2 * 16):
        want, want_grads = jax.value_and_grad(plain)(params)
        got, got_grads = jax.value_and_grad(loss_fn)(params, tokens, tokens, config, mask)
    assert abs(float(got) - float(want)) < 1e-5
    for (path, w), g in zip(
        jax.tree_util.tree_flatten_with_path(want_grads)[0], jax.tree.leaves(got_grads)
    ):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5, err_msg=str(path))


def test_residual_names_leave_nothing_behind_outside_a_checkpoint():
    """``flash_attention`` with no ``jax.checkpoint`` around it: the names
    on ``out`` and ``lse`` are identities, and the compiled gradient has as
    many instructions as with no names at all (the parent's)."""
    q = jnp.ones((1, 2, 64, 32), jnp.float32)

    def instruction_count():
        grads = jax.grad(
            lambda q, k, v: flash_mod.flash_attention(q, k, v).sum(), argnums=(0, 1, 2)
        )
        text = jax.jit(grads).lower(q, q, q).compile().as_text()
        assert "flash_out" not in text and "flash_lse" not in text
        return sum(" = " in line for line in text.splitlines())

    named = instruction_count()
    with mock.patch.object(flash_mod, "checkpoint_name", lambda x, _name: x):
        assert instruction_count() == named


def test_param_count_scales():
    small = num_params(init_params(TransformerConfig.tiny(), jax.random.PRNGKey(0)))
    bigger = num_params(
        init_params(TransformerConfig.tiny(n_layers=4), jax.random.PRNGKey(0))
    )
    assert bigger > small
