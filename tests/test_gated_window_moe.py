"""The gated window / global decoder under four norms a layer over experts
whose selection bias a RULE moves (``models/transformer.py`` with
``norm_placement="both"``, ``output_gate="element"`` on "window" and "full"
layers alike, ``qk_head_norm``, ``embed_scale``, a dense prefix of kind
"window" ahead of a pattern, sigmoid-routed experts under ``router_bias`` with
one shared expert, and ``MoEConfig.bias_update_rate``: AFMoE as Trinity-Mini
configures it) against the benchmark's plain reference
(``benchmarks/reference/gated_window_moe_decoder.py``: float32 ``jax.numpy``,
an explicit mask, no kernel, no sort, no scan, the experts a loop; it imports
nothing from ``ray_tpu.models``), through the family that names the program's
leaves for it. On the CPU at tiny widths with seeded weights: a dense window
layer, then ONE period of (window, full) (one layer a kind a period: the
scan's body is one period and a second window layer in a row claims nothing
the first does not), 4 / 2 heads of 16 on a stream of 48, a window of 8 keys
over 40 positions, 8 experts of which 4 are held, 2 a token, NON-ZERO biases.
ONE compiled program a ``remat`` for what the cases share.

Tolerances, each of the largest value compared: logits 5e-4, loss 1e-5,
gradients 2e-3 (``tests/test_window_moe.py``'s and for its reasons: both sides
float32, sums in another order). A wrong term is off by far more.
"""

import dataclasses
import functools
import re
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.families import gated_window_moe_decoder as family_module
from benchmarks.reference import gated_window_moe_decoder as reference
from ray_tpu.models import transformer as T
from ray_tpu.train import jax_utils

from model_helpers import close, forward, forward_with_routing, ids, listed, trains_through_jax_trainer

SLIDING, FULL = "sliding_attention", "full_attention"
TINY = {
    "name": "tiny-gated-window-moe", "model_type": "afmoe", "head_dim": 16, "hidden_act": "silu",
    "hidden_size": 48, "intermediate_size": 96, "layer_types": [SLIDING, SLIDING, FULL],
    "load_balance_coeff": 0.001, "moe_intermediate_size": 24, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 4, "num_dense_layers": 1, "num_expert_groups": 1, "num_experts": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 3, "num_key_value_heads": 2,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-5, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
    "sliding_window": 8, "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 256,
    "torch_dtype": "float32", "first_expert_held": 2, "published": {"num_experts": 8},
}
RATE = TINY["load_balance_coeff"]


def built(remat=None, **changes):
    """The family at the tiny sizes (``changes`` to the configuration)."""
    return family_module.build(dict(TINY, **changes), {"seq_len": 40, "remat": remat})


FAMILY = built()
MODEL = FAMILY.model
# every expert held: the rule's home, and what the shares add up to
WHOLE = built(num_experts=8, first_expert_held=0).model


def seeded(model=MODEL, seed=3):
    """Weights from the program's initialiser, every norm weight moved off 1
    (a dropped or misplaced norm then differs by more than a scale) and every
    selection bias off 0 (a choice made without it differs)."""
    params = jax.jit(lambda key: T.init_params(model, key))(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    norms = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm", "q_norm", "k_norm")
    for tree in (params["dense_layers"], *params["layers"].values()):
        for name in norms:
            tree[name] = tree[name] + 0.2 * jax.random.normal(next(keys), tree[name].shape)
        if "router_bias" in tree:
            shape = tree["router_bias"].shape
            tree["router_bias"] = 0.3 * jax.random.normal(next(keys), shape, jnp.float32)
    params["final_norm"] = params["final_norm"] + 0.2 * jax.random.normal(next(keys), (model.dim,))
    return params


@functools.cache
def loss_and_grads(model):
    """``(params, x, y) -> ((loss, moved), grads)``, compiled once a model."""
    return jax.jit(jax.value_and_grad(lambda p, x, y: T.loss_fn(p, x, y, model), has_aux=True))


def reference_logits(params, tokens, fam=FAMILY):
    return reference.logits(listed(fam.reference_weights(params)), tokens, fam.config)[0]


def load(routing):
    """(token, choice) pairs an expert got, ``[layers, experts]``."""
    return np.asarray(jnp.sum(routing["counts"], axis=1))


# -- against the reference ------------------------------------------------------
def test_the_tree_has_four_norms_a_layer_the_gate_on_both_kinds_and_the_prefix_ahead():
    params = jax.eval_shape(lambda: T.init_params(MODEL, jax.random.PRNGKey(0)))
    assert (MODEL.first_dense_layers, MODEL.first_dense_kind) == (1, "window")
    assert MODEL.layer_pattern == ("window", "full") and MODEL.periods == 1
    for tree, lead in ((params["dense_layers"], (1,)), (params["layers"]["window"], (1, 1)),
                       (params["layers"]["full"], (1, 1))):
        for norm in ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm"):
            assert tree[norm].shape == (*lead, 48)
        assert tree["wg"].shape == (*lead, 48, 64) and tree["q_norm"].shape == (*lead, 16)
    assert "router" not in params["dense_layers"]
    assert params["layers"]["window"]["router_bias"].shape == (1, 1, 8)
    assert params["layers"]["window"]["router_bias"].dtype == jnp.float32
    assert params["layers"]["full"]["w_gate"].shape == (1, 1, 4, 48, 24)
    assert T.config_num_params(MODEL) == sum(x.size for x in jax.tree.leaves(params))
    dims = T.param_logical_dims(MODEL)
    assert dims["layers"]["full"]["attn_post_norm"] == ("layer", None, None)
    assert dims["dense_layers"]["wg"] == ("layer", "embed", "heads")
    # the full pattern of the published file, mid-period behind the prefix: no program compiled
    real = dataclasses.replace(MODEL, n_layers=5, layer_pattern=("window", "full", "window", "window"))
    assert jax.eval_shape(lambda: T.init_params(real, jax.random.PRNGKey(0)))[
        "layers"]["window"]["wg"].shape == (1, 3, 48, 64)


def test_logits_match_the_reference_under_nonzero_biases():
    params, tokens = seeded(), ids(seq=40)
    got, routing = forward_with_routing(MODEL)(params, tokens)
    close(got, reference_logits(params, tokens), 5e-4, "logits")
    # the biases decide: without them another choice, and other logits
    unbiased = jax.tree.map(lambda x: x, params)
    for tree in unbiased["layers"].values():
        tree["router_bias"] = jnp.zeros_like(tree["router_bias"])
    other = forward_with_routing(MODEL)(unbiased, tokens)[1]
    assert not np.array_equal(np.asarray(routing["experts"]), np.asarray(other["experts"]))
    # a token's weights are its own scores', renormalised and scaled: they sum to route_scale
    np.testing.assert_allclose(np.asarray(routing["weights"]).sum(-1), 2.826, rtol=1e-5)


@pytest.mark.parametrize("remat", [None, "full"])
def test_loss_and_gradients_match_the_reference(remat):
    fam = built(remat)
    params, tokens = seeded(fam.model), ids(seq=41)
    x, y = tokens[:, :-1], tokens[:, 1:]
    (loss, moved), grads = loss_and_grads(fam.model)(params, x, y)
    want, want_grads = jax.value_and_grad(
        lambda w: reference.loss(w, x, y, fam.config))(listed(fam.reference_weights(params)))
    close(loss, want, 1e-5, "loss")
    got_grads = listed(fam.reference_weights(grads))
    for name in ("embed_tokens", "norm", "lm_head"):
        close(got_grads[name], want_grads[name], 2e-3, name)
    for i, (got, wanted) in enumerate(zip(got_grads["layers"], want_grads["layers"], strict=True)):
        for name in wanted:
            if name == "expert_bias":      # no gradient reaches it, on either side
                assert not np.any(np.asarray(got[name])) and not np.any(np.asarray(wanted[name]))
                continue
            close(got[name], wanted[name], 2e-3, f"layer {i} {name}", floor=1e-7)
    # what the loss hands out beside its value: the biases alone, in the tree's own layout
    assert jax.tree.structure(moved) == jax.tree.structure(
        {"layers": {kind: {"router_bias": 0} for kind in params["layers"]}})


CONTROLS = {
    "no_gate": dict(output_gate=None),
    "no_post_norm": dict(norm_placement="pre"),
    "no_embed_scale": dict(embed_scale=None),
    "rope_on_full": dict(rope_kinds=None),
    "no_window": dict(window=40),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_model_without_one_of_its_terms_is_another_model(control):
    """Each control runs on the SAME leaves (it reads a subset) and lies far
    outside the tolerance the program meets."""
    params, tokens = seeded(), ids(seq=40)
    want = np.asarray(reference_logits(params, tokens))
    got = np.asarray(forward(dataclasses.replace(MODEL, **CONTROLS[control]))(params, tokens))
    assert np.max(np.abs(got - want)) > 2e-2 * np.max(np.abs(want)), control


def test_the_shares_routed_parts_add_up_to_the_uncut_layer():
    """Four chips' held blocks of 2 of 8 experts: their routed parts sum to
    the layer that holds all eight (the shared expert and the norms are whole
    on every chip, added once outside ``_moe_mlp``)."""
    params = seeded(WHOLE)
    layer = jax.tree.map(lambda leaf: leaf[0, 0], params["layers"]["window"])
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 40, 48), jnp.float32)
    whole, routing = jax.jit(lambda l, h: T._moe_mlp(h, l, WHOLE))(layer, h)
    total = 0.0
    for first in range(0, 8, 2):
        moe = dataclasses.replace(WHOLE.moe, held=(first, 2))
        share = {**layer, **{n: layer[n][first:first + 2] for n in ("w_gate", "w_up", "w_down")}}
        part, held = jax.jit(
            lambda l, h, moe=moe: T._moe_mlp(h, l, dataclasses.replace(WHOLE, moe=moe)))(share, h)
        assert np.array_equal(np.asarray(held["experts"]), np.asarray(routing["experts"]))
        total = total + part
    close(total, whole, 1e-5, "the shares' sum")


# -- the rule -------------------------------------------------------------------
def replayed(bias, counts):
    """The published rule in NumPy on one layer's ``[experts]``."""
    n = np.asarray(counts, np.float32)
    step = np.float32(RATE) * np.sign(n.mean(dtype=np.float32) - n).astype(np.float32)
    return np.asarray(bias, np.float32) + (step - step.mean(dtype=np.float32))


def test_the_rule_is_the_published_one():
    bias = jnp.asarray([[0.5, -0.25, 0.0, 0.125], [0.0, 0.0, 0.0, 0.0]], jnp.float32)
    counts = jnp.asarray([[[3, 0, 1, 2], [1, 0, 1, 4]], [[2, 2, 2, 2], [1, 1, 1, 1]]], jnp.int32)
    got = np.asarray(T.router_bias_update(bias, counts, RATE))
    # n = [4, 0, 2, 6], mean 3: signs [-1, +1, +1, -1], mean 0; and an even layer stays
    np.testing.assert_allclose(got[0] - np.asarray(bias[0]), [-RATE, RATE, RATE, -RATE], rtol=1e-4)
    np.testing.assert_array_equal(got[1], 0.0)
    # n = [9, 1, 1, 1], mean 3: signs [-1, 1, 1, 1] less their mean 0.5
    skew = np.asarray(T.router_bias_update(jnp.zeros(4), jnp.asarray([[9, 1, 1, 1]]), RATE))
    np.testing.assert_allclose(skew, RATE * np.asarray([-1.5, 0.5, 0.5, 0.5]), rtol=1e-5)
    np.testing.assert_allclose(skew, replayed(np.zeros(4), [9, 1, 1, 1]), rtol=1e-6)
    grad = jax.grad(lambda b: jnp.sum(T.router_bias_update(b, counts, RATE)))(bias)
    assert not np.any(np.asarray(grad))                  # no gradient passes


def test_the_fused_step_moves_the_bias_by_the_rule_and_by_nothing_else():
    """Five AdamW steps (weight decay on) of the real step against a NumPy
    replay of the rule on the counts of each step's own forward pass: the
    biases are the replay's to a rounding, their moments stay zero, and every
    other leaf trains."""
    optimizer = optax.adamw(3e-3, weight_decay=0.1)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    setup = jax_utils.setup_sharded_training(
        lambda: seeded(), optimizer, mesh=mesh, logical_dims=T.param_logical_dims(MODEL))
    step = jax_utils.build_sharded_train_step(
        lambda p, b: T.loss_fn(p, b["x"], b["y"], MODEL), optimizer, setup)
    params, opt_state = setup.params, setup.opt_state
    first_router = np.asarray(params["layers"]["window"]["router"])
    expected = {kind: np.asarray(tree["router_bias"])[0, 0] for kind, tree in params["layers"].items()}
    for i in range(5):
        tokens = ids(seed=20 + i, seq=41)
        batch = {"x": tokens[:, :-1], "y": tokens[:, 1:]}
        counts = load(forward_with_routing(MODEL)(params, batch["x"])[1])
        for number, kind in enumerate(MODEL.layer_pattern):
            expected[kind] = replayed(expected[kind], counts[number])
        params, opt_state, loss = step(params, opt_state, batch)
        assert np.isfinite(float(loss))
        for kind in MODEL.layer_pattern:
            got = np.asarray(params["layers"][kind]["router_bias"])[0, 0]
            np.testing.assert_allclose(got, expected[kind], rtol=0, atol=1e-7, err_msg=f"step {i} {kind}")
    adam = opt_state[0]
    for kind in MODEL.layer_pattern:
        assert not np.any(np.asarray(adam.mu["layers"][kind]["router_bias"]))
        assert not np.any(np.asarray(adam.nu["layers"][kind]["router_bias"]))
        assert np.any(np.asarray(adam.nu["layers"][kind]["router"]))
    assert not np.array_equal(np.asarray(params["layers"]["window"]["router"]), first_router)
    # the state survives a committed checkpoint, the bias with it
    checkpoint = jax_utils.save_sharded_state(params, opt_state, extra={"step": 5})
    try:
        back, back_opt, extra = jax_utils.restore_sharded_state(checkpoint, setup)
    finally:
        shutil.rmtree(checkpoint.path, ignore_errors=True)
    assert extra == {"step": 5}
    for kind in MODEL.layer_pattern:
        np.testing.assert_array_equal(
            np.asarray(back["layers"][kind]["router_bias"]), np.asarray(params["layers"][kind]["router_bias"]))
    assert jax.tree.structure(back_opt) == jax.tree.structure(opt_state)


def test_with_every_expert_held_the_rule_evens_a_skewed_load():
    """200 rule-only steps (no optimizer) on one skewed batch: the fullest
    expert's share of a layer's pairs falls towards the mean."""
    params, tokens = seeded(WHOLE, seed=7), ids(seed=9, batch=4, seq=40)
    for tree in params["layers"].values():               # a router that favours expert 0
        tree["router"] = tree["router"].at[..., 0].add(0.5)
        tree["router_bias"] = jnp.zeros_like(tree["router_bias"])

    @jax.jit
    def rule_only(params):
        routing = T.forward_with_routing(params, tokens, WHOLE)[1]
        moved = T.moved_router_biases(params, routing, WHOLE)
        return jax_utils._overlaid(params, moved), jnp.sum(routing["counts"], axis=1)

    ratios = []
    for _ in range(200):
        params, counts = rule_only(params)
        counts = np.asarray(counts, np.float64)
        ratios.append(float(np.max(counts.max(-1) / counts.mean(-1))))
    assert ratios[0] > 1.5 and ratios[-1] < 0.75 * ratios[0] and ratios[-1] < ratios[50], (
        ratios[0], ratios[50], ratios[-1])


def _instructions(compiled) -> int:
    return len(re.findall(r"^\s+(?:ROOT )?%?[\w.\-]+ = ", compiled.as_text(), re.M))


def test_a_frozen_bias_compiles_the_step_it_compiled_before():
    """``bias_update_rate`` 0: the fused step is a82330e's, instruction for
    instruction: the step as it was written there, built here beside the
    one ``build_sharded_train_step`` makes, and the count read there (7,346,
    this model on this CPU backend)."""
    moe = T.MoEConfig(num_experts=8, top_k=2, scoring="sigmoid", norm_topk_prob=True,
                      shared_experts=1, expert_dim=32)
    model = T.TransformerConfig.tiny(moe=moe, attention="reference")
    optimizer = optax.adamw(3e-4)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    setup = jax_utils.setup_sharded_training(
        lambda: T.init_params(model, jax.random.PRNGKey(0)), optimizer, mesh=mesh,
        logical_dims=T.param_logical_dims(model))
    loss = lambda p, b: T.loss_fn(p, b["x"], b["y"], model)
    x = jnp.zeros((4, 32), jnp.int32)
    batch = {"x": x, "y": x}
    step = jax_utils.build_sharded_train_step(loss, optimizer, setup)
    now = _instructions(step.lower(setup.params, setup.opt_state, batch).compile())

    def before(params, opt_state, batch):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            value, grads = jax.value_and_grad(loss)(params, batch)
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(grads, opt_state, params)
            new_params = jax.tree.map(lambda p, u: p + u.astype(p.dtype), params, updates)
        return new_params, new_opt, value

    then = jax.jit(
        before, out_shardings=(setup.param_shardings, setup.opt_shardings, None),
        donate_argnums=(0, 1),
    ).lower(setup.params, setup.opt_state, batch).compile()
    assert now == _instructions(then) == 7346
    # and the live rule adds to it
    live = dataclasses.replace(model, moe=dataclasses.replace(moe, bias_update_rate=RATE))
    live_step = jax_utils.build_sharded_train_step(
        lambda p, b: T.loss_fn(p, b["x"], b["y"], live), optimizer, setup)
    assert _instructions(live_step.lower(setup.params, setup.opt_state, batch).compile()) > now


def test_it_trains_through_jax_trainer(ray_start_shared, tmp_path):
    """The normal path: JaxTrainer -> setup_sharded_training ->
    build_sharded_train_step -> loss_fn over a dp 2 x fsdp 2 mesh: the loss
    that returns ``(loss, moved)`` through the fused step, the counts summed
    over the data shards, full remat."""
    trains_through_jax_trainer(dataclasses.replace(MODEL, remat="full"), "gated-window-moe", tmp_path, seq=41)


# -- what the shape cannot do stays an honest refusal ---------------------------
REFUSALS = {
    "decode_a_window_cache": (
        lambda: T.init_kv_cache(MODEL, 1, 8), NotImplementedError, "head_dim stated apart|window"),
    "decode_a_gate": (
        lambda: T.init_kv_cache(T.TransformerConfig.tiny(output_gate="element"), 1, 8),
        NotImplementedError, "no output gate"),
    "decode_step_a_gate": (
        lambda: T.decode_step({}, {}, jnp.zeros((1, 1), jnp.int32), T.TransformerConfig.tiny(output_gate="element")),
        NotImplementedError, "no output gate"),
    "decode_a_branch_output_norm": (
        lambda: T.init_kv_cache(T.TransformerConfig.tiny(norm_placement="both"), 1, 8),
        NotImplementedError, "norm_placement='both'"),
    "decode_window_layers": (
        lambda: T.init_kv_cache(
            T.TransformerConfig.tiny(layer_pattern=("window", "full"), window=8), 1, 8),
        NotImplementedError, "ring cache of `window` rows"),
    "pipeline_over_a_pattern": (
        lambda: T.partition_stages({}, dataclasses.replace(MODEL, head_dim=12), 2),
        NotImplementedError, "layer_pattern stacks"),
    "callable_attention_under_a_window": (
        lambda: dataclasses.replace(MODEL, attention=lambda q, k, v, causal: q),
        NotImplementedError, "window layer under a callable attention"),
    "a_rule_without_the_bias_it_moves": (
        lambda: T.MoEConfig(scoring="softmax", bias_update_rate=RATE), ValueError, "bias_update_rate"),
    "a_rule_over_layers_by_place": (
        lambda: T.TransformerConfig.tiny(
            n_layers=2, layer_pattern=("full", "mlp"),
            moe=T.MoEConfig(scoring="sigmoid", bias_update_rate=RATE)),
        NotImplementedError, "one-block layers"),
    "one_block_layers_under_both": (
        lambda: T.TransformerConfig.tiny(
            n_layers=2, layer_pattern=("full", "mlp"), norm_placement="both"),
        NotImplementedError, "one-block layers"),
    "an_unknown_placement": (
        lambda: T.TransformerConfig.tiny(norm_placement="sandwich"), ValueError, "norm_placement"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_is_not_written_refuses_by_name(what):
    call, error, match = REFUSALS[what]
    with pytest.raises(error, match=match):
        call()
