"""The patterned decoder (``models/transformer.py`` with ``layer_pattern=``,
``linear=``, ``norm_placement="post"`` and ``rope_theta=None``: Olmo-Hybrid's
block) against the plain reference (``benchmarks/reference/hybrid_decoder.py``:
the per-token recurrence, explicit softmax attention, no layer scan), on
the CPU in float32 at tiny widths with seeded weights: TWO periods of
(linear, full). (One layer a kind a period: the scan's body is one period, so
the programs these cases compile grow with it, and a second or third linear
layer in a row claims nothing the first does not.
``test_the_tree_is_stacked_by_period_and_counted`` builds the published period
of four, which compiles no step.)

Tolerances, each of the largest value compared. Logits 5e-4 and loss 1e-5:
both sides compute in float32; what is left is the order of the sums (a
chunk at once against a token at a time) through post-norm layers,
which at these widths grows a 1e-6 difference about a hundredfold (a head of
16 key dims whose SiLU outputs are all near zero is normalised from
rounding). Gradients 2e-3: the same, through the backward. A wrong term (the
convolution's taps reversed, the norm on the branch's input, beta without
its 2, a rotary embedding) is off by 1e-1 or more: the last test holds the
comparison to that, term by term.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families import hybrid_decoder  # noqa: E402
from benchmarks.harness import hybrid_flops  # noqa: E402
from benchmarks.reference import hybrid_decoder as reference  # noqa: E402
from ray_tpu.models import transformer as T  # noqa: E402
from ray_tpu.parallel.mesh import MeshSpec  # noqa: E402
from ray_tpu.train import jax_utils  # noqa: E402

from model_helpers import (  # noqa: E402
    close, forward, ids, listed, loss_and_grads, trains_through_jax_trainer,
)

PUBLISHED_PERIOD = ["linear_attention", "linear_attention", "linear_attention", "full_attention"]
PERIOD = ["linear_attention", "full_attention"]
CFG = {
    "name": "tiny-hybrid", "family": "hybrid_decoder", "model_type": "olmo_hybrid",
    "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 4, "layer_types": PERIOD * 2,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 24, "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "vocab_size": 256, "rope_parameters": {"rope_theta": None}, "rms_norm_eps": 1e-6,
    "hidden_act": "silu", "tie_word_embeddings": False, "attention_bias": False,
    "torch_dtype": "float32",
}
TRAFFIC = {"seq_len": 40, "batch_size": 2, "remat": None}


def build(remat=None, **changes):
    return hybrid_decoder.build(dict(CFG, **changes), dict(TRAFFIC, remat=remat))


def seeded(fam, seed=3):
    """Weights from the program's initialiser, every norm weight moved off 1."""
    params = jax.jit(fam.init)(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))
    for kind, names in (("linear", ("attn_norm", "mlp_norm", "o_norm")),
                        ("full", ("attn_norm", "mlp_norm", "q_norm", "k_norm"))):
        for name in names:
            leaf = params["layers"][kind][name]
            params["layers"][kind][name] = leaf + 0.2 * jax.random.normal(next(keys), leaf.shape)
    params["final_norm"] = params["final_norm"] + 0.2 * jax.random.normal(next(keys), (64,))
    return params


@pytest.fixture(scope="module")
def fam():
    return build()


@pytest.fixture(scope="module")
def params(fam):
    return seeded(fam)


def test_the_tree_is_stacked_by_period_and_counted():
    """At the PUBLISHED period, three linear layers then a full one: the one
    case that is about the period itself, and it compiles no step."""
    fam = build(num_hidden_layers=8, layer_types=PUBLISHED_PERIOD * 2)
    model, params = fam.model, seeded(fam)
    assert model.layer_pattern == ("linear", "linear", "linear", "full") and model.periods == 2
    assert params["layers"]["linear"]["wq"].shape == (2, 3, 64, 64)
    assert params["layers"]["linear"]["conv_v"].shape == (2, 3, 4, 96)
    assert params["layers"]["full"]["wq"].shape == (2, 1, 64, 64)
    assert "wk" in params["layers"]["full"] and "conv_q" not in params["layers"]["full"]
    counted = T.config_num_params(model)
    assert counted == T.num_params(params) == hybrid_flops.parameters(fam.config) == fam.parameters()
    dims = T.param_logical_dims(model)
    is_dims = lambda x: isinstance(x, tuple)
    assert jax.tree.structure(dims, is_leaf=is_dims) == jax.tree.structure(params)
    for leaf, names in zip(jax.tree.leaves(params), jax.tree.leaves(dims, is_leaf=is_dims)):
        assert leaf.ndim == len(names)
    assert T.LINEAR_SCOPES == ("linear_attention", "short_conv", "delta_rule", "gate_norm")


def test_logits_match_the_reference(fam, params):
    x = ids()
    want = reference.logits(fam.reference_weights(params), x, fam.config)
    got = forward(fam.model)(params, x)              # what ``fam.forward`` is
    assert fam.model.layer_pattern == ("linear", "full") and fam.model.periods == 2
    close(got, want, 5e-4, "kernels")
    recurrence = T.forward(params, x, T.dataclasses.replace(fam.model, attention="reference"))
    close(recurrence, want, 5e-4, "the per-token recurrence")
    check = fam.check(got[:, -8:], params, x, last=8)
    assert check["ok"] and check["published"]["rel_rms"] < 1e-4
    # two linear layers keep [2, 4 heads, 48 (one padded chunk), 24] float32
    # and T's diagonal blocks, a chunk of 48 float32 a head and token
    assert check["linear_state_gib"] == 2 * 2 * 4 * 48 * (24 + 48) * 4 / 2**30


STEP = 0.5
_STEPPED = {}


def fused_step(params, remat=None):
    """``(loss, gradients)`` of one fused step (``build_sharded_train_step``:
    gradients and SGD in one jit, donated state) on the batch of
    ``ids(seed=5)`` over two data shards; the gradients are read back from
    the update. Once a ``remat``."""
    if remat not in _STEPPED:
        x = ids(seed=5)
        fam = build(remat=remat)
        optimizer = optax.sgd(STEP)
        setup = jax_utils.setup_sharded_training(
            lambda: jax.tree.map(jnp.copy, params), optimizer, logical_dims=fam.logical_dims,
            mesh=MeshSpec({"dp": 2}).build(jax.devices()[:2]),
        )
        step = jax_utils.build_sharded_train_step(fam.loss, optimizer, setup)
        batch = setup.shard_batch({"x": x[:, :-1], "y": x[:, 1:]})
        stepped, _state, loss = step(setup.params, setup.opt_state, batch)
        grads = jax.tree.map(lambda new, old: (old - new) / STEP, stepped, params)
        _STEPPED[remat] = (float(loss), grads)
    return _STEPPED[remat]


def test_loss_and_every_gradient_leaf_match_through_the_fused_step(fam, params):
    x = ids(seed=5)
    weights = listed(fam.reference_weights(params))
    want, want_grads = jax.value_and_grad(reference.loss)(weights, x[:, :-1], x[:, 1:], fam.config)
    got, grads = fused_step(params)
    assert abs(got - float(want)) <= 1e-5 * abs(float(want))
    theirs = listed(fam.reference_weights(grads))
    for name in ("embed_tokens", "norm", "lm_head"):
        close(theirs[name], want_grads[name], 2e-3, name)
    for number, (mine, ref) in enumerate(zip(theirs["layers"], want_grads["layers"], strict=True)):
        assert set(mine) == set(ref)
        for name in ref:
            close(mine[name], ref[name], 2e-3, f"layer {number} {name}")


def test_full_remat_gives_the_gradients_of_no_remat(params):
    """Under "full" the layer checkpoint keeps the carry and the kernels'
    named outputs alone; the same sums, fused otherwise: the tolerance of
    the gradients."""
    loss, grads = fused_step(params)
    again_loss, again = fused_step(params, remat="full")
    assert abs(again_loss - loss) <= 1e-6 * abs(loss)
    for got, want in zip(jax.tree.leaves(again), jax.tree.leaves(grads)):
        close(got, want, 2e-3)


def test_no_logit_before_a_changed_token_moves(fam, params):
    """The convolution reaches back three tokens and the scan carries
    everything before: neither may look ahead."""
    x = ids(seed=7)
    at = 17
    changed = x.at[:, at].set((x[:, at] + 1) % 256)
    before, after = forward(fam.model)(params, x), forward(fam.model)(params, changed)
    np.testing.assert_array_equal(np.asarray(before[:, :at]), np.asarray(after[:, :at]))
    assert float(jnp.max(jnp.abs(before[:, at:] - after[:, at:]))) > 1e-3


def test_the_compiled_step_names_the_linear_mixers_work(params):
    """What ``linear_attn_ms`` and its like read on the chip: every scope of
    ``LINEAR_SCOPES`` reaches the optimized program's ``op_name``s, forward,
    recomputed and transposed, and always inside ``attention``."""
    import re

    x = ids(seed=6)
    step = jax.jit(jax.grad(build(remat="full").loss))
    text = step.lower(params, {"x": x[:, :-1], "y": x[:, 1:]}).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    under = lambda scope: {n for n in names if re.search(r"(?:^|[/(])" + scope + r"(?:[/)]|$)", n)}
    whole = under("linear_attention")
    assert whole and whole <= under("attention")
    for scope in ("short_conv", "delta_rule", "gate_norm"):
        found = under(scope)
        assert found and found <= whole, scope
        assert any("transpose(" in n for n in found) and any("rematted_computation" in n for n in found)


def test_the_tiny_preset_trains_through_jax_trainer(ray_start_shared, tmp_path):
    """The normal path: JaxTrainer -> setup_sharded_training ->
    build_sharded_train_step -> loss_fn, over a dp 2 x fsdp 2 mesh (the scan
    kernels per data shard under shard_map)."""
    trains_through_jax_trainer(build(remat="full").model, "hybrid", tmp_path)


def test_what_a_patterned_model_cannot_do_yet_is_refused_by_name(fam, params):
    model = fam.model
    with pytest.raises(NotImplementedError, match="recurrent-state cache"):
        T.init_kv_cache(model, 1, 16)
    with pytest.raises(NotImplementedError, match="recurrent-state cache"):
        T.decode_step(params, {}, ids(batch=1, seq=1), model)
    with pytest.raises(NotImplementedError, match="partition_stages.*layer_pattern"):
        T.partition_stages(params, model, 2)
    with pytest.raises(NotImplementedError, match="stage_forward.*layer_pattern"):
        T.stage_forward(params, ids(), model, first=True, last=True)
    mesh = jax.sharding.AbstractMesh((2, 2), ("dp", "tp"))
    with jax.sharding.use_abstract_mesh(mesh), pytest.raises(NotImplementedError, match="tp > 1"):
        jax.eval_shape(lambda p, t: T.forward(p, t, model), params, ids())
    with pytest.raises(NotImplementedError, match="fewer key heads"):
        T.dataclasses.replace(model, linear=T.LinearAttentionConfig(num_key_heads=2, num_value_heads=4))
    # a pattern may sit over expert layers since PR 36; the reordered norm may not
    sparse = T.dataclasses.replace(model, moe=T.MoEConfig())
    with pytest.raises(NotImplementedError, match='norm_placement="post" over a mixture-of-experts'):
        jax.eval_shape(
            lambda key, t: T.forward(T.init_params(sparse, key), t, sparse),
            jax.random.PRNGKey(0), ids(),
        )
    with pytest.raises(ValueError, match="no multiple of the period"):
        T.dataclasses.replace(model, n_layers=5)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        build(tie_word_embeddings=True)


def _reversed_taps(params):
    params = jax.tree.map(lambda x: x, params)
    params["layers"]["linear"]["conv_k"] = params["layers"]["linear"]["conv_k"][:, :, ::-1]
    return params


@pytest.mark.parametrize("what", ["pre_norm", "rotary", "beta_below_one", "reversed_taps"])
def test_a_changed_term_fails_the_check(what, fam, params):
    x = ids()
    model = {
        "pre_norm": T.dataclasses.replace(fam.model, norm_placement="pre"),
        "rotary": T.dataclasses.replace(fam.model, rope_theta=10000.0),
        "beta_below_one": T.dataclasses.replace(
            fam.model, linear=T.dataclasses.replace(fam.model.linear, allow_neg_eigval=False)
        ),
        "reversed_taps": fam.model,
    }[what]
    weights = _reversed_taps(params) if what == "reversed_taps" else params
    changed = forward(model)(weights, x)
    check = fam.check(changed, params, x)
    assert not check["ok"] and check["published"]["rel_rms"] > 1e-1


# Every setting of the three mixer fields a configuration may combine
# (``LinearAttentionConfig``: a decay per channel needs the bound, and is
# refused without it): Olmo-Hybrid's is the first, Ling's the last.
MIXER_SETTINGS = [
    ("head", None, "silu"), ("head", None, "sigmoid"), ("head", -5.0, "silu"),
    ("head", -5.0, "sigmoid"), ("channel", -5.0, "silu"), ("channel", -5.0, "sigmoid"),
]


@pytest.mark.parametrize("decay,bound,gate", MIXER_SETTINGS)
def test_every_allowed_setting_of_the_mixer_matches_the_recurrence(decay, bound, gate):
    """The chunked kernels' path against ``attention="reference"`` (the
    per-token recurrence, XLA's convolution) on one set of weights: logits
    and every gradient leaf, for each combination of decay, bound and
    output gate that ``LinearAttentionConfig`` lets through."""
    linear = T.LinearAttentionConfig(
        num_key_heads=4, num_value_heads=4, key_head_dim=16, value_head_dim=16,
        decay=decay, gate_lower_bound=bound, output_gate=gate,
    )
    configs = {
        attention: T.TransformerConfig.tiny(
            n_layers=2, n_kv_heads=4, layer_pattern=("linear", "full"), linear=linear,
            attention=attention,
        )
        for attention in ("flash", "reference")
    }
    params = T.init_params(configs["flash"], jax.random.PRNGKey(5))
    width = 64 if decay == "channel" else 4
    assert params["layers"]["linear"]["wa"].shape == (1, 1, 64, width)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 81), 0, 256)

    def loss_and_logits(config):
        logits = forward(config)(params, tokens[:, :-1])
        loss, grads = loss_and_grads(config)(params, tokens[:, :-1], tokens[:, 1:])
        return logits, loss, grads

    got, want = (loss_and_logits(configs[a]) for a in ("flash", "reference"))
    np.testing.assert_allclose(got[0], want[0], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5)
    for (path, g), (_, w) in zip(
        jax.tree_util.tree_leaves_with_path(got[2]), jax.tree_util.tree_leaves_with_path(want[2])
    ):
        scale = float(jnp.abs(w).max()) + 1e-12
        assert float(jnp.abs(g - w).max()) <= 2e-3 * scale + 1e-6, jax.tree_util.keystr(path)
