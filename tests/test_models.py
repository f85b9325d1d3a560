"""Flagship transformer model tests."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention as flash_mod
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
