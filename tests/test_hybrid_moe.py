"""The patterned decoder over expert layers (``models/transformer.py`` with a
``layer_pattern`` whose linear layers carry a decay per channel, whose full
layers are gated latent attention, over group-routed experts of which a
block is HELD, behind a dense prefix with a linear mixer: Ling-3.0-flash-VL's
language model) against the plain reference (``benchmarks/reference/
hybrid_moe_decoder.py``: the per-token recurrence, explicit softmax, the
group routine written out, the experts a loop over the same held block), on
the CPU in float32 at tiny widths with seeded weights: a dense linear layer,
then TWO periods of (full, linear), 32 experts in 4 groups of which 2, 4 a
token, 8 held. (One layer a kind a period: the scan's body is one period, so
the programs these cases compile grow with it, and the second linear layer in
a row claims nothing the first does not.
``test_the_tree_is_stacked_by_period_and_counted`` builds the published
period of three, ``layer_group_size`` 3, which compiles no step.)

Tolerances, each of the largest value compared: logits 5e-4, loss 1e-5,
gradients 2e-3, ``tests/test_hybrid_model.py``'s and for its reasons (both
sides float32; a chunk at once against a token at a time). A wrong term is
off by far more: the last test holds the comparison to that, term by term.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families import hybrid_moe_decoder  # noqa: E402
from benchmarks.harness import hybrid_moe_flops  # noqa: E402
from benchmarks.reference import hybrid_moe_decoder as reference  # noqa: E402
from ray_tpu.models import transformer as T  # noqa: E402
from ray_tpu.ops.rmsnorm import rmsnorm_reference  # noqa: E402

from model_helpers import (  # noqa: E402
    close, forward_with_routing, ids, listed, loss_and_grads, trains_through_jax_trainer,
)

CFG = {
    "name": "tiny-hybrid-moe", "family": "hybrid_moe_decoder", "model_type": "bailing_hybrid",
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 5, "layer_offset": 0,
    "layer_group_size": 2, "first_k_dense_replace": 1, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rotary_dim": 8, "partial_rotary_factor": 0.5,
    "q_lora_rank": None, "rope_theta": 10000, "rms_norm_eps": 1e-6, "vocab_size": 256,
    "short_conv_kernel_size": 4, "kda_lower_bound": -5, "kda_safe_gate": True,
    "no_kda_lora": True, "use_kda_lora": False, "linear_silu": True, "use_qk_norm": True,
    "use_mla_nope": False, "use_nGPT": False, "scale_router_input": False, "value_norm": False,
    "up_proj_norm": False, "gated_attention_proj_granularity_type": "head_wise",
    "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
    "num_experts": 8, "first_expert_held": 8, "published": {"num_experts": 32},
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "score_function": "sigmoid",
    "moe_router_enable_expert_bias": True, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 32,
    "expert_swiglu_limit_list": [0] * 8, "share_expert_swiglu_limit_list": [0] * 8,
    "torch_dtype": "float32",
}
TRAFFIC = {"seq_len": 40, "batch_size": 2, "remat": None}
TOKENS, TOP_K = 80, 4


def build(remat=None, **changes):
    return hybrid_moe_decoder.build(dict(CFG, **changes), dict(TRAFFIC, remat=remat))


def seeded(fam, seed=3):
    """Weights from the program's initialiser, every norm weight moved off 1
    and the routers' biases off 0 (no gradient reaches them: seeded here)."""
    params = jax.jit(fam.init)(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 32))
    trees = [params["dense_layers"], params["layers"]["linear"], params["layers"]["full"]]
    for tree in trees:
        for name in ("attn_norm", "mlp_norm", "o_norm", "kv_norm"):
            if name in tree:
                tree[name] = tree[name] + 0.2 * jax.random.normal(next(keys), tree[name].shape)
        if "router_bias" in tree:
            shape = tree["router_bias"].shape
            tree["router_bias"] = 0.1 * jax.random.normal(next(keys), shape)
    params["final_norm"] = params["final_norm"] + 0.2 * jax.random.normal(next(keys), (64,))
    return params


@pytest.fixture(scope="module")
def fam():
    return build()


@pytest.fixture(scope="module")
def params(fam):
    return seeded(fam)


def test_the_tree_is_stacked_by_period_and_counted():
    """At the PUBLISHED period, a latent layer then two linear ones behind the
    dense prefix: the one case that is about the period itself, and it
    compiles no step."""
    fam = build(num_hidden_layers=7, layer_offset=1, layer_group_size=3)
    model, params = fam.model, seeded(fam)
    assert reference.layer_kinds(fam.config) == [
        "linear_attention", "full_attention", "linear_attention", "linear_attention",
        "full_attention", "linear_attention", "linear_attention",
    ]
    assert model.layer_pattern == ("full", "linear", "linear") and model.periods == 2
    assert (model.first_dense_layers, model.first_dense_kind) == (1, "linear")
    assert model.moe.num_experts == 32 and model.moe.held == (8, 8) and model.moe.num_held == 8
    assert params["dense_layers"]["wa"].shape == (1, 64, 64)          # a decay per channel
    assert params["dense_layers"]["dt_bias"].shape == (1, 64)
    assert params["dense_layers"]["a_log"].shape == (1, 4)
    assert params["dense_layers"]["w_gate"].shape == (1, 64, 96)      # the dense SwiGLU
    assert params["layers"]["linear"]["w_gate"].shape == (2, 2, 8, 64, 32)   # the HELD experts
    assert params["layers"]["linear"]["router"].shape == (2, 2, 64, 32)      # all are scored
    assert params["layers"]["full"]["wg_head"].shape == (2, 1, 64, 4)
    assert "conv_q" not in params["layers"]["full"] and "wkv_a" not in params["layers"]["linear"]
    counted = T.config_num_params(model)
    assert counted == T.num_params(params) == hybrid_moe_flops.parameters(fam.config)
    assert counted == fam.parameters()
    # five linear layers: the rule's output and T's diagonal blocks (a padded chunk of 48)
    assert T.linear_state_bytes(model, 2, 40) == 5 * T.kept_bytes(2, 4, 40, 16, 4)
    assert T.kept_bytes(2, 4, 40, 16, 4) == 2 * 4 * 48 * (16 * 4 + 48 * 4)
    dims = T.param_logical_dims(model)
    is_dims = lambda x: isinstance(x, tuple)
    assert jax.tree.structure(dims, is_leaf=is_dims) == jax.tree.structure(params)
    for leaf, names in zip(jax.tree.leaves(params), jax.tree.leaves(dims, is_leaf=is_dims)):
        assert leaf.ndim == len(names)


def test_logits_and_routing_match_the_reference(fam, params):
    x = ids()
    want, routings = reference.logits(fam.reference_weights(params), x, fam.config)
    got, routing = forward_with_routing(fam.model)(params, x)
    close(got, want, 5e-4, "kernels")
    assert fam.model.layer_pattern == ("full", "linear") and fam.model.periods == 2
    assert routing["experts"].shape == (4, TOKENS, TOP_K)
    for i, r in enumerate(routings):
        assert np.array_equal(np.sort(routing["experts"][i], -1), np.sort(r["experts"], -1)), i
        held = np.sum((np.asarray(r["experts"]) >= 8) & (np.asarray(r["experts"]) < 16))
        assert int(routing["held_pairs"][i]) == held
    recurrence = T.forward(params, x, T.dataclasses.replace(fam.model, attention="reference"))
    close(recurrence, want, 5e-4, "the per-token recurrence")
    check = fam.check(jax.jit(fam.forward)(params, x)[:, -8:], params, x, last=8)
    assert check["ok"], check
    assert check["scan"]["ok"] and check["scan"]["rel_rms"] < 1e-5
    assert check["linear_state_gib"] == T.linear_state_bytes(fam.model, 2, 40) / 2**30
    assert 0 < check["held_pairs_pct"] < 100
    assert fam.kernel_needed(2, 40)["experts"] == hybrid_moe_flops.experts_needed(
        fam.config, 2, 40, 4, rows=check["held_rows_per_layer"]
    )


def test_a_best_expert_outside_the_kept_groups_is_not_chosen(fam, params):
    """The group routine decides: some token's best ``s + b`` lies in a group
    that is not among its two best groups, and neither side chooses it."""
    x = ids()
    _, routings = reference.logits(fam.reference_weights(params), x, fam.config)
    _, routing = forward_with_routing(fam.model)(params, x)
    outside = 0
    for i, r in enumerate(routings):
        best = np.argmax(np.asarray(r["biased"]), axis=-1)                     # [T]
        kept = np.argsort(np.asarray(r["groups"]), axis=-1)[:, -2:]            # [T, 2]
        lost = ~np.any(kept == (best // 8)[:, None], axis=-1)
        outside += int(lost.sum())
        chosen = np.asarray(routing["experts"][i])
        assert not np.any(chosen[lost] == best[lost][:, None])
        assert all(set(c // 8) <= set(k) for c, k in zip(chosen, kept))
    assert outside > 0


def test_loss_and_every_gradient_leaf_match_the_reference(fam, params):
    x, y = ids(), ids(seed=2)
    want, want_grads = jax.value_and_grad(reference.loss)(
        listed(fam.reference_weights(params)), x, y, fam.config
    )
    for remat in (None, "full"):
        model = T.dataclasses.replace(fam.model, remat=remat)
        got, grads = loss_and_grads(model)(params, x, y)
        assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want)), remat
        named = listed(fam.reference_weights(grads))
        for name in ("embed_tokens", "norm", "lm_head"):
            close(named[name], want_grads[name], 2e-3, name)
        assert len(named["layers"]) == len(want_grads["layers"]) == 5
        for i, (mine, theirs) in enumerate(zip(named["layers"], want_grads["layers"])):
            assert set(mine) == set(theirs), i
            for name in mine:
                if name == "e_score_correction_bias":     # a buffer: no gradient on either side
                    assert not np.any(np.asarray(mine[name])) and not np.any(np.asarray(theirs[name]))
                else:
                    close(mine[name], theirs[name], 2e-3, (remat, i, name))


def _one_layer(fam, params, held):
    """Layer ``linear[0, 0]``'s expert leaves as a model holding ``held``
    would store them: the routed experts are those of the fixture's block
    (8-15) REPEATED over every block, so all 32 experts have weights."""
    layer = {k: v[0, 0] for k, v in params["layers"]["linear"].items()}
    key = jax.random.PRNGKey(11)
    full = {
        name: jax.random.normal(jax.random.fold_in(key, n), (32, *layer[name].shape[1:]))
        * layer[name].shape[1] ** -0.5
        for n, name in enumerate(("w_gate", "w_up", "w_down"))
    }
    first, count = held
    share = dict(layer, **{name: full[name][first:first + count] for name in full})
    moe = T.dataclasses.replace(fam.model.moe, held=held)
    return share, full, T.dataclasses.replace(fam.model, moe=moe)


def test_the_shares_add_up(fam, params):
    """The routed parts of all four shares of 8 experts, plus the shared
    expert counted ONCE, equal the uncut reference layer."""
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 40, 64))
    layer, full, _ = _one_layer(fam, params, (0, 8))
    h = rmsnorm_reference(x, layer["mlp_norm"], eps=1e-6)
    routed, held_pairs = 0.0, 0
    for first in (0, 8, 16, 24):
        share, _, model = _one_layer(fam, params, (first, 8))
        out, routing = jax.jit(lambda h, l: T._moe_mlp(h, l, model))(h, share)
        routed = routed + out
        held_pairs += int(routing["held_pairs"])
    assert held_pairs == TOKENS * TOP_K                  # every pair is some share's
    shared = T._dense_mlp(h, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
    uncut = dict(CFG, num_experts=32, first_expert_held=0)
    weights = {
        "post_attention_layernorm": layer["mlp_norm"], "router": layer["router"],
        "e_score_correction_bias": layer["router_bias"],
        "gate_proj": full["w_gate"], "up_proj": full["w_up"], "down_proj": full["w_down"],
        "shared_gate_proj": layer["shared_gate"], "shared_up_proj": layer["shared_up"],
        "shared_down_proj": layer["shared_down"],
    }
    want, _ = reference.moe_forward(x, weights, uncut)
    close(routed + shared, want - x, 2e-5, "four shares and the shared expert once")
    # the whole block under the layer's own path agrees too, and one share is NOT the layer
    share, _, model = _one_layer(fam, params, (8, 8))
    one, _ = T._mlp_block(x, share, model, True)
    assert np.max(np.abs(np.asarray(one - want))) > 1e-2 * np.max(np.abs(np.asarray(want - x)))


@pytest.mark.parametrize("bias,pairs", [(+10.0, TOKENS * TOP_K), (-10.0, 0)])
def test_routed_wholly_here_or_wholly_away_is_exact_under_one_trace(fam, params, bias, pairs):
    """Two routings, ONE compiled program: a bias that sends every pair to
    the held block (group 1, experts 8-15), and one that sends none."""
    x, y = ids(), ids(seed=2)
    step = _value_and_grad(fam)

    def biased(params):
        params = jax.tree.map(lambda leaf: leaf, params)
        for kind in ("linear", "full"):
            params["layers"][kind]["router_bias"] = (
                params["layers"][kind]["router_bias"].at[..., 8:16].add(bias)
            )
        return params

    changed = biased(params)
    (got, routing), grads = step(changed, x, y)
    assert step._cache_size() == 1
    assert [int(n) for n in routing["held_pairs"]] == [pairs] * 4
    want, want_grads = jax.value_and_grad(reference.loss)(
        listed(fam.reference_weights(changed)), x, y, fam.config
    )
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    named = listed(fam.reference_weights(grads))
    for i, (mine, theirs) in enumerate(zip(named["layers"], want_grads["layers"])):
        for name in ("gate_proj", "down_proj", "router", "shared_up_proj", "f_proj", "q_proj"):
            if name not in mine:
                continue
            if not np.any(np.asarray(theirs[name])):
                assert not np.any(np.asarray(mine[name])), (i, name)   # absent: exactly nothing
            else:
                close(mine[name], theirs[name], 2e-3, (i, name))


_STEPS = {}


def _value_and_grad(fam):
    """One jitted loss-and-gradient for the whole module: the two routings
    of the test above must share its ONE trace."""
    if id(fam) not in _STEPS:
        def loss(params, x, y):
            hidden, routing = T._hidden_with_routing(params, x, fam.model)
            return T.head_loss(params, hidden, y, fam.model), routing

        _STEPS[id(fam)] = jax.jit(jax.value_and_grad(loss, has_aux=True))
    return _STEPS[id(fam)]


def test_the_tiny_preset_trains_through_jax_trainer(ray_start_shared, tmp_path):
    """The normal path: JaxTrainer -> setup_sharded_training ->
    build_sharded_train_step -> loss_fn, over a dp 2 x fsdp 2 mesh (the
    convolutions, the scan kernels and the held experts' block per data
    shard under shard_map), full remat."""
    trains_through_jax_trainer(build(remat="full").model, "hybrid-moe", tmp_path)


def test_what_this_model_cannot_do_yet_is_refused_by_name(fam, params):
    model = fam.model
    with pytest.raises(NotImplementedError, match="recurrent-state cache"):
        T.init_kv_cache(model, 1, 16)
    with pytest.raises(NotImplementedError, match="recurrent-state cache"):
        T.decode_step(params, {}, ids(batch=1, seq=1), model)
    with pytest.raises(NotImplementedError, match="partition_stages.*layer_pattern"):
        T.partition_stages(params, model, 2)
    with pytest.raises(NotImplementedError, match="stage_forward.*layer_pattern"):
        T.stage_forward(params, ids(), model, first=True, last=True)
    for axis in ("tp", "sp"):
        mesh = jax.sharding.AbstractMesh((2, 2), ("dp", axis))
        with jax.sharding.use_abstract_mesh(mesh), pytest.raises(
            NotImplementedError, match=f"{axis} > 1"
        ):
            jax.eval_shape(lambda p, t: T.forward(p, t, model), params, ids())
    post = T.dataclasses.replace(model, norm_placement="post")
    with pytest.raises(NotImplementedError, match='norm_placement="post" over a mixture-of-experts'):
        jax.eval_shape(lambda p, t: T.forward(p, t, post), params, ids())
    # what PR 48 lifted: a decay per channel needs no bound, and a bound too
    # steep for the bounded preparation (15 x 6 > 88) takes the halving one
    from ray_tpu.ops.gated_delta_rule import carries_bound
    assert T.LinearAttentionConfig(decay="channel").gate_lower_bound is None
    assert T.LinearAttentionConfig(decay="channel", gate_lower_bound=-6.0).gate_lower_bound == -6.0
    assert carries_bound(-5.0) and not carries_bound(-6.0) and not carries_bound(None)
    with pytest.raises(ValueError, match="scoring='sigmoid'"):
        T.MoEConfig(num_experts=32, n_group=4, topk_group=2)
    with pytest.raises(ValueError, match="no block of 32 experts"):
        T.MoEConfig(num_experts=32, held=(28, 8))
    with pytest.raises(ValueError, match="no multiple of the period"):
        T.dataclasses.replace(model, n_layers=6)
    with pytest.raises(ValueError, match="kda_safe_gate"):
        build(kda_safe_gate=False)
    with pytest.raises(ValueError, match="clamps a kept layer"):
        build(expert_swiglu_limit_list=[0, 0, 0, 4, 0, 0, 0, 0])


def _without_head_gate(params):
    params = jax.tree.map(lambda x: x, params)
    del params["layers"]["full"]["wg_head"]
    return params


@pytest.mark.parametrize("what", [
    "gate_bound", "silu_output_gate", "beta_times_two", "no_head_gate", "no_groups",
    "no_scaling", "other_block",
])
def test_a_changed_term_fails_the_check(what, fam, params):
    x = ids()
    model, replace = fam.model, T.dataclasses.replace
    changed = {
        "gate_bound": replace(model, linear=replace(model.linear, gate_lower_bound=-2.0)),
        "silu_output_gate": replace(model, linear=replace(model.linear, output_gate="silu")),
        "beta_times_two": replace(model, linear=replace(model.linear, allow_neg_eigval=True)),
        "no_head_gate": replace(model, latent=replace(model.latent, output_gate=None)),
        "no_groups": replace(model, moe=replace(model.moe, n_group=1, topk_group=1)),
        "no_scaling": replace(model, moe=replace(model.moe, routed_scaling=1.0)),
        "other_block": replace(model, moe=replace(model.moe, held=(16, 8))),
    }[what]
    weights = _without_head_gate(params) if what == "no_head_gate" else params
    logits, routing = jax.jit(lambda p, t: T.forward_with_routing(p, t, changed))(weights, x)
    check = reference.check(
        logits, routing, lambda: fam.reference_weights(params), x, fam.config
    )
    assert not check["ok"], what
    off = check["published"]["rel_rms"] / reference.TOLERANCE
    if what == "no_groups":
        # the choice itself is off: experts of groups the reference does not keep
        assert max(l["worst_group_shortfall"] for l in check["layers"]) > reference.GROUP_MARGIN
    elif what == "no_scaling":
        assert max(l["weights_rel_rms"] for l in check["layers"]) > 10 * reference.WEIGHT_TOLERANCE
    elif what == "other_block":
        assert not all(l["held_pairs_agree"] for l in check["layers"]) and off > 1.5
    else:
        assert off > 1.5, (what, off)


@functools.cache
def _at_192_tokens():
    """The family at four chunks of 48 and its fresh weights, drawn once for the four cases below."""
    fam = hybrid_moe_decoder.build(dict(CFG), dict(TRAFFIC, seq_len=192))
    return fam, jax.jit(fam.init)(jax.random.PRNGKey(11))


@pytest.mark.parametrize("name", [
    "program", "head_mean_decay", "log_decay_bfloat16", "chunk_operands_bfloat16",
])
def test_a_wrong_decay_or_a_lower_precision_fails_the_scan_check(name):
    """``harness/scan_controls.py``: the delta rule with the head's mean
    decay in every channel, or with the chunk operands rounded to bfloat16,
    is NOT correct by ``check_scan``'s limit once the checked layer's gates
    are open (``open_gates``: where the initialisation leaves them nearly
    shut the output hardly reads the decay), and the program's own scan is,
    with room. The log-decay rounded to bfloat16 moves the reading a hundredfold
    and stays under the limit: what the limit cannot see. (The preparation's
    products at default precision are the chip's to show: a CPU computes
    both alike.)"""
    from benchmarks.harness import scan_controls

    fam, params = _at_192_tokens()
    x = ids(seed=12, seq=192)
    scan = fam.scan if name == "program" else scan_controls.control(name)
    weights = scan_controls.open_gates(fam.reference_weights(params))
    found = reference.check_scan(scan, weights, x, fam.config, last=64)
    worst = max(found["rel_rms"], found["last_rel_rms"])
    if name == "program":
        assert found["ok"] and worst < reference.TOLERANCE_SCAN / 100, found
    elif name == "log_decay_bfloat16":
        assert found["ok"] and worst > reference.TOLERANCE_SCAN / 10, found
    else:
        assert not found["ok"] and worst > 1.5 * reference.TOLERANCE_SCAN, found
