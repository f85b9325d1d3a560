"""The patterned decoder whose linear layers are Kimi Delta Attention under
its own UNBOUNDED gate with the gates through a rank, whose full layers are
element-gated grouped-query attention with no position, over sigmoid-routed
experts of which a block is HELD beside one shared (``models/transformer.py``:
Solar-Open2's language model) against the plain reference
(``benchmarks/reference/kda_gqa_moe_decoder.py``: the per-token recurrence,
explicit softmax, the experts a loop over the same held block), on the CPU in
float32 at tiny widths with seeded weights: TWO periods of (full, linear),
4 / 2 heads of 16, 40 experts of which 8 held, 4 a token. (One layer a kind a
period: the scan's body is one period, so the programs these cases compile
grow with it, and a second or third linear layer in a row claims nothing the
first does not. ``test_the_tree_is_the_tables_and_counted`` builds the
published period of four, which compiles no step.)

Tolerances, each of the largest value compared: logits 5e-4, loss 1e-5,
gradients 2e-3, ``tests/test_hybrid_moe.py``'s and for its reasons (both
sides float32; a chunk at once against a token at a time). A wrong term is
off by far more: the last test holds the comparison to that, term by term.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families import kda_gqa_moe_decoder  # noqa: E402
from benchmarks.harness import kda_gqa_moe_controls as controls  # noqa: E402
from benchmarks.reference import kda_gqa_moe_decoder as reference  # noqa: E402
from ray_tpu.models import transformer as T  # noqa: E402
from ray_tpu.ops.rmsnorm import rmsnorm_reference  # noqa: E402

from model_helpers import close, forward_with_routing, ids, listed, loss_and_grads  # noqa: E402

CFG = {
    "name": "tiny-kda-gqa-moe", "family": "kda_gqa_moe_decoder", "model_type": "solar_open2",
    "linear_attn_config": {
        "short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4, "num_kv_heads": None,
    },
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 4, "layer_offset": 0,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "moe_intermediate_size": 32, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "tie_word_embeddings": False, "first_k_dense_replace": 0, "use_rope": False,
    "gqa_interval": 1, "gqa_layers": [0, 2, 4], "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "n_routed_experts": 8, "first_expert_held": 8, "published": {"n_routed_experts": 40},
    "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 4, "torch_dtype": "float32",
}
TRAFFIC = {"seq_len": 40, "batch_size": 2, "remat": None}
TOKENS, TOP_K = 80, 4


def build(remat=None, **changes):
    return kda_gqa_moe_decoder.build(dict(CFG, **changes), dict(TRAFFIC, remat=remat))


def seeded(fam, seed=3):
    """Weights from the program's initialiser, every norm weight moved off 1
    and the routers' biases off 0 (no gradient reaches them: seeded here)."""
    params = jax.jit(fam.init)(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 32))
    for tree in params["layers"].values():
        for name in ("attn_norm", "mlp_norm", "o_norm"):
            if name in tree:
                tree[name] = tree[name] + 0.2 * jax.random.normal(next(keys), tree[name].shape)
        tree["router_bias"] = 0.1 * jax.random.normal(next(keys), tree["router_bias"].shape)
    params["final_norm"] = params["final_norm"] + 0.2 * jax.random.normal(next(keys), (64,))
    return params


@pytest.fixture(scope="module")
def fam():
    return build()


@pytest.fixture(scope="module")
def params(fam):
    return seeded(fam)


def test_the_tree_is_the_tables_and_counted():
    """At the PUBLISHED period, a grouped-query layer then three of Kimi Delta
    Attention: the one case that is about the period itself, and it compiles
    no step."""
    fam = build(num_hidden_layers=8, gqa_interval=3, gqa_layers=[0, 4, 8])
    model, params = fam.model, seeded(fam)
    assert reference.layer_kinds(fam.config) == ["full_attention"] + ["linear_attention"] * 3 + [
        "full_attention"] + ["linear_attention"] * 3
    assert model.layer_pattern == ("full", "linear", "linear", "linear") and model.periods == 2
    assert (model.linear.gate_rank, model.linear.gate_lower_bound) == (16, None)
    assert model.full_gate == "element" and model.rope_theta is None
    assert (model.moe.num_experts, model.moe.held, model.moe.shared_experts) == (40, (8, 8), 1)
    linear, full = params["layers"]["linear"], params["layers"]["full"]
    assert "wa" not in linear and "wg" not in linear                 # through the rank
    assert linear["wa_down"].shape == (2, 3, 64, 16) and linear["wa_up"].shape == (2, 3, 16, 64)
    assert linear["wg_down"].shape == (2, 3, 64, 16) and linear["wg_up"].shape == (2, 3, 16, 64)
    assert linear["dt_bias"].shape == (2, 3, 64) and linear["a_log"].shape == (2, 3, 4)
    assert full["wg"].shape == (2, 1, 64, 64) and full["wk"].shape == (2, 1, 64, 32)
    assert "wg_head" not in full and full["w_gate"].shape == (2, 1, 8, 64, 32)
    dims = T.param_logical_dims(model)["layers"]
    assert dims["linear"]["wa_down"] == ("layer", None, "embed", None)
    assert dims["linear"]["wa_up"] == ("layer", None, None, "heads")
    assert dims["full"]["wg"] == ("layer", None, "embed", "heads") == dims["full"]["wq"]
    assert T.num_params(params) == T.config_num_params(model) == fam.parameters()


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
    close(recurrence, want, 5e-4, "attention='reference': the recurrence and XLA's softmax")
    check = fam.check(jax.jit(fam.forward)(params, x), params, x)
    assert check["ok"], check
    assert check["scan"]["layer"] == 1 and check["scan"]["own"]["rel_rms"] < 1e-5
    assert check["scan"]["opened"]["rel_rms"] < 1e-5
    assert set(check["steep_blocks_pct"]) == {"own", "opened"}
    assert check["steep_blocks_pct"]["opened"] > check["steep_blocks_pct"]["own"]
    assert 0.0 <= check["held_pairs_pct"] <= 100.0 and check["harness_rel_rms"] == 0.0


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
        assert len(named["layers"]) == len(want_grads["layers"]) == 4
        for i, (mine, theirs) in enumerate(zip(named["layers"], want_grads["layers"])):
            assert set(mine) == set(theirs), i
            for name in mine:
                if name == "e_score_correction_bias":     # a buffer: no gradient on either side
                    assert not np.any(np.asarray(mine[name])) and not np.any(np.asarray(theirs[name]))
                else:
                    close(mine[name], theirs[name], 2e-3, (remat, i, name))


def test_the_routers_weights_are_held_still_and_the_rest_is_loss_fn(fam, params):
    x, y = ids(), ids(seed=2)
    batch = {"x": x, "y": y}
    whole_loss, whole = loss_and_grads(fam.model)(params, x, y)
    held_loss, held = jax.jit(jax.value_and_grad(fam.loss))(params, batch)
    assert float(held_loss) == float(whole_loss)
    for kind in ("full", "linear"):
        assert np.any(np.asarray(whole["layers"][kind]["router"]))
        assert not np.any(np.asarray(held["layers"][kind]["router"]))
        for name in ("wo", "w_down", "shared_up", "attn_norm"):
            close(held["layers"][kind][name], whole["layers"][kind][name], 1e-6, (kind, name))
    close(held["embed"], whole["embed"], 1e-6, "embed")


def _one_layer(fam, params, held):
    """Layer ``linear[0, 0]``'s expert leaves as a model holding ``held``
    would store them, all 40 experts drawn."""
    layer = {k: v[0, 0] for k, v in params["layers"]["linear"].items()}
    key = jax.random.PRNGKey(11)
    full = {
        name: jax.random.normal(jax.random.fold_in(key, n), (40, *layer[name].shape[1:]))
        * layer[name].shape[1] ** -0.5
        for n, name in enumerate(("w_gate", "w_up", "w_down"))
    }
    first, count = held
    share = dict(layer, **{name: full[name][first:first + count] for name in full})
    moe = T.dataclasses.replace(fam.model.moe, held=held)
    return share, full, T.dataclasses.replace(fam.model, moe=moe)


def test_the_shares_add_up(fam, params):
    """The routed parts of all FIVE shares of 8 experts, plus the shared
    expert counted ONCE, equal the uncut reference layer."""
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 40, 64))
    layer, full, _ = _one_layer(fam, params, (0, 8))
    h = rmsnorm_reference(x, layer["mlp_norm"], eps=1e-5)
    routed, held_pairs = 0.0, 0
    for first in (0, 8, 16, 24, 32):
        share, _, model = _one_layer(fam, params, (first, 8))
        out, routing = jax.jit(lambda h, l: T._moe_mlp(h, l, model))(h, share)
        routed = routed + out
        held_pairs += int(routing["held_pairs"])
    assert held_pairs == TOKENS * TOP_K                  # every pair is some share's
    shared = T._dense_mlp(h, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
    uncut = dict(CFG, n_routed_experts=40, first_expert_held=0)
    weights = {
        "post_attention_layernorm": layer["mlp_norm"], "router": layer["router"],
        "e_score_correction_bias": layer["router_bias"],
        "gate_proj": full["w_gate"], "up_proj": full["w_up"], "down_proj": full["w_down"],
        "shared_gate_proj": layer["shared_gate"], "shared_up_proj": layer["shared_up"],
        "shared_down_proj": layer["shared_down"],
    }
    want, _ = reference.moe_forward(x, weights, uncut)
    close(routed + shared, want - x, 2e-5, "five shares and the shared expert once")
    share, _, model = _one_layer(fam, params, (8, 8))
    one, _ = T._mlp_block(x, share, model, True)
    assert np.max(np.abs(np.asarray(one - want))) > 1e-2 * np.max(np.abs(np.asarray(want - x)))


def test_what_this_model_cannot_do_yet_is_refused_by_name(fam, params):
    model = fam.model
    with pytest.raises(NotImplementedError, match="recurrent-state cache"):
        T.init_kv_cache(model, 1, 16)
    with pytest.raises(NotImplementedError, match="recurrent-state cache"):
        T.decode_step(params, {}, ids(batch=1, seq=1), model)
    with pytest.raises(NotImplementedError, match="partition_stages.*layer_pattern"):
        T.partition_stages(params, model, 2)
    for axis in ("tp", "sp"):
        mesh = jax.sharding.AbstractMesh((2, 2), ("dp", axis))
        with jax.sharding.use_abstract_mesh(mesh), pytest.raises(
            NotImplementedError, match=f"{axis} > 1"
        ):
            jax.eval_shape(lambda p, t: T.forward(p, t, model), params, ids())
    # a gated grouped-query layer with no pattern: decode computes no gate, and says so
    gated = T.TransformerConfig.tiny(output_gate="element")
    with pytest.raises(NotImplementedError, match="computes no output gate"):
        T.init_kv_cache(gated, 1, 16)
    with pytest.raises(ValueError, match="output_gate 'element' beside latent's 'head'"):
        T.TransformerConfig.tiny(
            output_gate="element", latent=T.LatentAttentionConfig(output_gate="head")
        )
    with pytest.raises(ValueError, match="unknown output_gate"):
        T.TransformerConfig.tiny(output_gate="row")
    with pytest.raises(ValueError, match="no rank"):
        T.LinearAttentionConfig(gate_rank=0)
    with pytest.raises(ValueError, match="kda_use_full_proj"):
        build(kda_use_full_proj=True)
    with pytest.raises(ValueError, match="use_rope"):
        build(use_rope=True)


def _whole_gates(params):
    """The linear layers' gate pairs multiplied out: ``gate_rank=None``'s leaves."""
    params = jax.tree.map(lambda x: x, params)
    linear = params["layers"]["linear"]
    for name in ("wa", "wg"):
        linear[name] = linear.pop(f"{name}_down") @ linear.pop(f"{name}_up")
    return params


@pytest.mark.parametrize("what", [
    "gate_bound", "beta_in_0_1", "silu_output_gate", "no_element_gate", "head_gate",
    "rope_on_the_full_layers", "other_block", "whole_gate_matrices",
])
def test_a_changed_term_fails_the_check(what, fam, params):
    x = ids()
    model, replace = fam.model, T.dataclasses.replace
    changed = {
        "gate_bound": replace(model, linear=replace(model.linear, gate_lower_bound=-5.0)),
        "beta_in_0_1": replace(model, linear=replace(model.linear, allow_neg_eigval=False)),
        "silu_output_gate": replace(model, linear=replace(model.linear, output_gate="silu")),
        "no_element_gate": replace(model, output_gate=None),
        "head_gate": replace(model, output_gate="head"),
        "rope_on_the_full_layers": replace(model, rope_theta=10000.0),
        "other_block": replace(model, moe=replace(model.moe, held=(16, 8))),
        "whole_gate_matrices": replace(model, linear=replace(model.linear, gate_rank=None)),
    }[what]
    weights = jax.tree.map(lambda x: x, params)
    if what == "no_element_gate":
        del weights["layers"]["full"]["wg"]
    elif what == "head_gate":
        weights["layers"]["full"]["wg_head"] = weights["layers"]["full"].pop("wg")[..., ::16]
    elif what == "whole_gate_matrices":
        weights = _whole_gates(params)
    logits, routing = jax.jit(lambda p, t: T.forward_with_routing(p, t, changed))(weights, x)
    check = reference.check(
        logits, routing, lambda: fam.reference_weights(params), x, fam.config
    )
    off = check["published"]["rel_rms"] / reference.TOLERANCE
    if what == "whole_gate_matrices":
        # the same mathematics in one product: the positive control of the changes above
        assert check["ok"] and check["published"]["rel_rms"] < 1e-5, off
    elif what == "other_block":
        assert not check["ok"] and not all(l["held_pairs_agree"] for l in check["layers"])
    else:
        assert not check["ok"] and off > 1.5, (what, off)


@pytest.mark.parametrize("name", ("program",) + controls.CONTROLS)
def test_a_bounded_gate_a_small_beta_or_a_lower_precision_fails_the_scan_check(name, fam, params):
    """``harness/kda_gqa_moe_controls.py``'s wrong scans at a tiny size: each
    must read NOT correct on at least one set of gates and the program's own
    correct on both. (On a CPU the chunk operands' rounding is what a chip's
    is; the cell's real sizes are read on the chip, PERF.md section 6.)"""
    x = ids(seq=96)
    weights = listed(fam.reference_weights(params))
    scan = fam.scan if name == "program" else controls.control(name)
    found = reference.check_scan(scan, weights, x, fam.config)
    if name == "program":
        assert found["ok"] and max(found[g]["rel_rms"] for g in ("own", "opened")) < 1e-5, found
    else:
        assert not found["ok"], (name, found)
        worst = max(found[g]["rel_rms"] for g in ("own", "opened"))
        assert worst > 3 * reference.TOLERANCE_SCAN, (name, found)
    if name == "gate_clamped":
        # the weights' own gates hardly pass the clamp: only the opened ones show it
        assert found["opened"]["steep_blocks_pct"] > 50.0 > found["own"]["steep_blocks_pct"]
        assert not found["opened"]["ok"]
