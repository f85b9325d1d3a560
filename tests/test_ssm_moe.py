"""The patterned decoder of ONE-BLOCK layers: Mamba-2 mixers, grouped-query
attention with no position, and a LATENT mixture of un-gated ReLU^2 experts of
which a block is HELD beside one shared (``models/transformer.py``:
Nemotron-3-Super's language model) against the plain reference
(``benchmarks/reference/ssm_moe_decoder.py``: the recurrence a token at a time,
the convolution as shifted sums, explicit softmax, the experts a loop over the
same held block), on the CPU in float32 at tiny widths with seeded weights:
TWO periods of ``M*E``, 8 state-space heads of 4 on a state of 8 in 2 groups,
4 / 2 attention heads of 8, 16 experts of width 24 in a latent of 16 of which
4 held, 3 a token scaled by 5. (One layer a kind a period: the scan's body is
one period, so the programs these cases compile grow with it, and the second
Mamba-2 and the second expert layer of ``MEM*E`` claim nothing the first do not.
``test_every_layer_is_one_block_with_one_norm`` builds ``MEM*E`` twice, the
published order, which compiles no step.)

Tolerances, each of the largest value compared: logits 5e-4, loss 1e-5,
gradients 2e-3, ``tests/test_kda_gqa_moe.py``'s and for its reasons (both
sides float32; a chunk at once against a token at a time). A wrong term is off
by far more: the last test holds the comparison to that, term by term.
"""

import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families import ssm_moe_decoder  # noqa: E402
from benchmarks.harness import ssm_moe_controls as controls  # noqa: E402
from benchmarks.reference import ssm_moe_decoder as reference  # noqa: E402
from ray_tpu.models import transformer as T  # noqa: E402
from ray_tpu.ops.rmsnorm import rmsnorm_reference  # noqa: E402

import model_helpers  # noqa: E402
from model_helpers import close, forward_with_routing, listed, loss_and_grads  # noqa: E402

CFG = {
    "name": "tiny-ssm-moe", "family": "ssm_moe_decoder", "model_type": "nemotron_h",
    "hidden_size": 32, "expand": 1, "mamba_num_heads": 8, "mamba_head_dim": 4,
    "ssm_state_size": 8, "n_groups": 2, "conv_kernel": 4, "chunk_size": 8, "use_conv_bias": True,
    "mamba_hidden_act": "silu", "mamba_proj_bias": False, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 1e-4,
    "num_hidden_layers": 6, "hybrid_override_pattern": "M*EM*E", "layer_offset": 0,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8, "attention_bias": False,
    "rope_theta": 10000, "vocab_size": 64, "intermediate_size": 24, "layer_norm_epsilon": 1e-5,
    "mlp_hidden_act": "relu2", "mlp_bias": False, "use_bias": False,
    "moe_intermediate_size": 24, "moe_latent_size": 16, "moe_shared_expert_intermediate_size": 40,
    "moe_shared_expert_overlap": False, "n_routed_experts": 4, "first_expert_held": 4,
    "published": {"n_routed_experts": 16}, "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 5, "num_experts_per_tok": 3,
    "residual_in_fp32": False, "tie_word_embeddings": False, "sliding_window": None,
    "torch_dtype": "float32",
}
TRAFFIC = {"seq_len": 24, "batch_size": 2, "remat": None}
TOKENS, TOP_K = 48, 3


def build(remat=None, **changes):
    return ssm_moe_decoder.build(dict(CFG, **changes), dict(TRAFFIC, remat=remat))


def seeded(fam, seed=3):
    """Weights from the program's initialiser, every norm weight moved off 1,
    the routers' biases off 0 (no gradient reaches them: seeded here) and the
    skip ``D`` off 1."""
    params = jax.jit(fam.init)(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 32))
    for tree in params["layers"]:
        for name in ("attn_norm", "mlp_norm", "y_norm", "d_skip"):
            if name in tree:
                tree[name] = tree[name] + 0.2 * jax.random.normal(next(keys), tree[name].shape)
        if "router_bias" in tree:
            tree["router_bias"] = 0.1 * jax.random.normal(next(keys), tree["router_bias"].shape)
    params["final_norm"] = params["final_norm"] + 0.2 * jax.random.normal(next(keys), (32,))
    return params


ids = functools.partial(model_helpers.ids, seq=24, vocab=64)


@pytest.fixture(scope="module")
def fam():
    return build()


@pytest.fixture(scope="module")
def params(fam):
    return seeded(fam)


@pytest.fixture(scope="module")
def logits(fam, params):
    """The family's own forward on ``ids()``, compiled and run once for the cases that read it."""
    return jax.jit(fam.forward)(params, ids())


def test_every_layer_is_one_block_with_one_norm():
    """At ``MEM*E`` twice, the published order of a period: the one case that
    is about the period itself, and it compiles no step."""
    fam = build(num_hidden_layers=10, hybrid_override_pattern="MEM*EMEM*E")
    model, params = fam.model, seeded(fam)
    assert reference.layer_kinds(fam.config) == ["mamba", "moe", "mamba", "attention", "moe"] * 2
    assert model.layer_pattern == ("ssm", "mlp", "ssm", "full", "mlp") and model.periods == 2
    assert model.one_block and model.rope_theta is None
    moe = model.moe
    assert (moe.num_experts, moe.held, moe.top_k, moe.routed_scaling) == (16, (4, 4), 3, 5.0)
    assert (moe.activation, moe.gated, moe.latent_dim, moe.shared_dim) == ("relu2", False, 16, 40)
    # the layers by PLACE in the period, a tree of [periods, ...] leaves a place
    assert isinstance(params["layers"], list) and len(params["layers"]) == 5
    ssm, mlp, _, full, _ = params["layers"]
    # a mixer's layer: its mixer, ONE norm, no MLP leaf; an "mlp" layer: the other way round
    assert set(ssm) == {
        "attn_norm", "w_z", "w_xbc", "w_dt", "conv", "conv_bias", "dt_bias", "a_log", "d_skip",
        "y_norm", "w_out",
    }
    assert set(full) == {"attn_norm", "wq", "wk", "wv", "wo"}
    assert set(mlp) == {
        "mlp_norm", "router", "router_bias", "w_up", "w_down", "latent_down", "latent_up",
        "shared_up", "shared_down",
    }                                                                # no gate anywhere
    assert ssm["w_xbc"].shape == (2, 32, 64) and ssm["conv_bias"].shape == (2, 64)
    assert ssm["a_log"].dtype == ssm["dt_bias"].dtype == ssm["d_skip"].dtype == jnp.float32
    assert mlp["w_up"].shape == (2, 4, 16, 24) and mlp["w_down"].shape == (2, 4, 24, 16)
    assert mlp["shared_up"].shape == (2, 32, 40) and mlp["latent_down"].shape == (2, 32, 16)
    assert set(params["layers"][2]) == set(ssm) and set(params["layers"][4]) == set(mlp)
    # the cell's own weights steer every token to the same top_k experts, two of them held
    fresh = jax.jit(fam.init)(jax.random.PRNGKey(0))["layers"]
    steered = np.asarray(fresh[1]["router_bias"])
    assert steered.shape == (2, 16) and np.all(steered[0] == steered[1])
    assert steered[0].sum() == 3 and list(np.nonzero(steered[0])[0]) == [6, 7, 8]   # held: 4-7
    fresh = fresh[0]
    steps = np.asarray(jax.nn.softplus(fresh["dt_bias"]))
    assert steps.min() >= 1e-4 - 1e-7 and steps.max() <= 0.1 + 1e-6
    rates = np.exp(np.asarray(fresh["a_log"]))
    assert rates.min() >= 1.0 and rates.max() <= 16.0 and np.all(np.asarray(fresh["d_skip"]) == 1)
    dims = T.param_logical_dims(model)["layers"]
    assert dims[0]["w_xbc"] == ("layer", "embed", "heads")
    assert dims[1]["w_up"] == ("layer", "expert", "embed", "mlp")
    assert dims[1]["latent_up"] == ("layer", "mlp", "embed")
    assert T.num_params(params) == T.config_num_params(model) == fam.parameters()
    # the older shapes keep two norms and an MLP a layer
    both = T._stacks(T.TransformerConfig.tiny(layer_pattern=("full",)))["layers"]["full"]
    assert {"attn_norm", "mlp_norm"} <= set(both[1]) and set(both[2]) == {"w_gate", "w_up", "w_down"}


def test_logits_and_routing_match_the_reference(fam, params, logits):
    x = ids()
    want, routings = reference.logits(fam.reference_weights(params), x, fam.config)
    got, routing = forward_with_routing(fam.model)(params, x)
    close(got, want, 5e-4, "kernels")
    assert fam.model.layer_pattern == ("ssm", "full", "mlp") and fam.model.periods == 2
    # a routing from the layers that route, and from those alone
    assert routing["experts"].shape == (2, TOKENS, TOP_K) and len(routings) == 2
    for i, r in enumerate(routings):
        assert np.array_equal(np.sort(routing["experts"][i], -1), np.sort(r["experts"], -1)), i
        close(jnp.sum(routing["weights"][i], -1), np.full(TOKENS, 5.0), 1e-5, "scaled by 5")
        held = np.sum((np.asarray(r["experts"]) >= 4) & (np.asarray(r["experts"]) < 8))
        assert int(routing["held_pairs"][i]) == held
    recurrence = T.forward(params, x, T.dataclasses.replace(fam.model, attention="reference"))
    close(recurrence, want, 5e-4, "attention='reference': the recurrence and XLA's forms")
    check = fam.check(logits, params, x)
    assert check["ok"], check
    assert check["scan"]["layer"] == 0 and check["scan"]["own"]["rel_rms"] < 1e-5
    assert check["scan"]["opened"]["rel_rms"] < 1e-5
    assert check["scan"]["opened"]["steepest_log_decay"] == pytest.approx(-1.6, rel=1e-5)
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
        for name in ("embed_tokens", "norm_f", "lm_head"):
            close(named[name], want_grads[name], 2e-3, name)
        assert len(named["layers"]) == len(want_grads["layers"]) == 6
        for i, (mine, theirs) in enumerate(zip(named["layers"], want_grads["layers"])):
            assert set(mine) == set(theirs), i
            for name in mine:
                if name == "e_score_correction_bias":     # a buffer: no gradient on either side
                    assert not np.any(np.asarray(mine[name])) and not np.any(np.asarray(theirs[name]))
                else:
                    close(mine[name], theirs[name], 2e-3, (remat, i, name))
    # the cell's loss: the routers' weights held still, everything else loss_fn's
    held = jax.jit(jax.grad(fam.loss))(params, {"x": x, "y": y})
    assert np.any(np.asarray(grads["layers"][2]["router"]))
    assert not np.any(np.asarray(held["layers"][2]["router"]))
    for place, name in ((2, "w_down"), (2, "latent_down"), (0, "a_log"), (1, "wo")):
        close(held["layers"][place][name], grads["layers"][place][name], 1e-5, (place, name))


def test_the_new_scopes_name_forward_and_backward(fam, params):
    """``ssm_mixer`` inside ``attention``, ``ssd`` inside it (forward AND the
    custom VJP's backward, which opens it itself), ``moe_latent`` inside
    ``mlp``, the convolution under ``short_conv``: what the four new readers
    search a device trace for."""
    x, y = ids(), ids(seed=2)
    model = T.dataclasses.replace(fam.model, remat="full")
    lowered = loss_and_grads(model).lower(params, x, y)
    names = set(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))
    under = lambda *path: [n for n in names if "/".join(path) in n]
    assert under("attention", "ssm_mixer", "ssd") and under("attention", "ssm_mixer", "short_conv")
    # the forward kernel's call and the backward's two (the chunk-start states, the gradients), under the same path
    assert under("attention", "ssm_mixer", "ssd", "jit(_ssd_forward)")
    assert under("ssm_mixer", "ssd", "jit(_ssd_states)") and under("ssm_mixer", "ssd", "jit(_ssd_backward)")
    assert under("mlp", "moe_latent", "dot_general") and under("mlp", "moe_latent", "transpose")
    assert under("mlp", "shared") and under("mlp", "router") and under("mlp", "experts")
    assert not [n for n in under("ssd/") if "ssm_mixer/ssd/" not in n]


def _one_layer(fam, params, held):
    """The first expert layer's leaves as a model holding ``held`` would
    store them, all 16 experts drawn."""
    layer = {k: v[0] for k, v in params["layers"][2].items()}
    key = jax.random.PRNGKey(11)
    full = {
        name: jax.random.normal(jax.random.fold_in(key, n), (16, *layer[name].shape[1:]))
        * layer[name].shape[1] ** -0.5
        for n, name in enumerate(("w_up", "w_down"))
    }
    first, count = held
    share = dict(layer, **{name: full[name][first:first + count] for name in full})
    moe = T.dataclasses.replace(fam.model.moe, held=held)
    return share, full, T.dataclasses.replace(fam.model, moe=moe)


def test_the_shares_add_up(fam, params):
    """The held blocks of all FOUR shares of 4 experts, each through the
    latent up-projection, plus the shared expert counted ONCE, equal the uncut
    reference layer: the up-projection is linear, so the shares' parts add up
    after it as before it."""
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 32))
    layer, full, _ = _one_layer(fam, params, (0, 4))
    h = rmsnorm_reference(x, layer["mlp_norm"], eps=1e-5)
    blocks, shared, held_pairs = 0.0, None, 0
    for first in (0, 4, 8, 12):
        share, _, model = _one_layer(fam, params, (first, 4))
        out, routing = jax.jit(lambda x, l: T._mlp_block(x, l, model, True))(x, share)
        mine = T._dense_mlp(h, None, share["shared_up"], share["shared_down"], T._relu2)
        shared = mine if shared is None else shared
        close(mine, shared, 1e-6, "every chip computes the shared expert alike")
        blocks = blocks + (out - x - mine)               # the block's part, through W_up
        held_pairs += int(routing["held_pairs"])
    assert held_pairs == TOKENS * TOP_K                  # every pair is some share's
    uncut = dict(CFG, n_routed_experts=16, first_expert_held=0)
    weights = {
        "norm": layer["mlp_norm"], "router": layer["router"],
        "e_score_correction_bias": layer["router_bias"],
        "up_proj": full["w_up"], "down_proj": full["w_down"],
        "fc1_latent_proj": layer["latent_down"], "fc2_latent_proj": layer["latent_up"],
        "shared_up_proj": layer["shared_up"], "shared_down_proj": layer["shared_down"],
    }
    want, _ = reference.moe_forward(x, weights, uncut)
    close(blocks + shared, want - x, 2e-5, "four shares and the shared expert once")
    share, _, model = _one_layer(fam, params, (4, 4))
    one, _ = T._mlp_block(x, share, model, True)
    assert np.max(np.abs(np.asarray(one - want))) > 1e-2 * np.max(np.abs(np.asarray(want - x)))


def test_what_this_model_cannot_do_yet_is_refused_by_name(fam, params):
    model = fam.model
    with pytest.raises(NotImplementedError, match='"ssm" layers'):
        T.init_kv_cache(model, 1, 8)
    with pytest.raises(NotImplementedError, match="ssm, full, mlp"):
        T.partition_stages(params, model, 2)
    with pytest.raises(ValueError, match="exactly where ssm="):
        T.TransformerConfig.tiny(layer_pattern=("ssm", "mlp"))
    with pytest.raises(NotImplementedError, match="one-block layers"):
        T.dataclasses.replace(model, first_dense_layers=3)
    with pytest.raises(ValueError, match="kinds are"):
        T.dataclasses.replace(model, first_dense_kind="mlp", layer_pattern=("full", "mlp"), ssm=None)
    mesh = jax.sharding.AbstractMesh((1, 2), ("dp", "tp"))
    with jax.sharding.use_abstract_mesh(mesh):
        with pytest.raises(NotImplementedError, match="linear, conv or ssm layers"):
            jax.eval_shape(lambda p, t: T.forward(p, t, model), params, ids())


@pytest.mark.parametrize("what", reference.CONTROLS)
def test_a_changed_term_fails_the_check(what, fam, params, logits):
    """Each wrong model of ``reference.CONTROLS`` moves the logits past the
    tolerance the cell holds them to (and far past this file's)."""
    wrong, _ = reference.logits(fam.reference_weights(params), ids(), dict(fam.config, control=what))
    assert reference.compare(logits, wrong)["rel_rms"] > 2 * reference.TOLERANCE, what


@pytest.mark.parametrize("name", ("program",) + controls.CONTROLS)
def test_a_lower_precision_or_the_wrong_group_fails_the_scan_check(name, fam, params):
    """``harness/ssm_moe_controls.py``'s wrong scans, each NOT correct on at
    least one of the three readings; the program's own correct on all."""
    scan = fam.scan if name == "program" else controls.control(name, fam.model.ssm.chunk)
    # eight chunks: a state that has something to carry
    result = reference.check_scan(scan, fam.reference_weights(params), ids(seq=64), fam.config)
    assert result["ok"] == (name == "program"), (name, result)


@pytest.mark.parametrize("name", ("program", "decay_bfloat16"))
def test_the_timed_reading_runs_the_scan_in_the_files_dtype(name):
    """Under a bfloat16 file the third reading hands ``x``, ``B`` and ``C`` over
    in bfloat16 (the instantiation the timed step compiles) and holds the
    program's rounding under its limit, and running sums kept in bfloat16 not
    (at the published chunk of 128: a sum of eight tokens' ``dt A`` is too
    small for its rounding to show)."""
    fam = build(chunk_size=128)
    params, handed = seeded(fam), []

    def scan(*operands):
        handed.append([t.dtype for t in operands])
        run = fam.scan if name == "program" else controls.control(name, fam.model.ssm.chunk)
        return run(*operands)

    cfg = dict(fam.config, torch_dtype="bfloat16")
    result = reference.check_scan(scan, fam.reference_weights(params), ids(seq=256), cfg)
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert handed[:2] == [[f32] * 6] * 2 and handed[2] == [bf16, f32, f32, bf16, bf16, f32]
    timed = result["timed"]
    assert timed["ok"] == (name == "program"), (name, timed)
    assert timed["rel_rms"] > 10 * result["own"]["rel_rms"] or name != "program"


@pytest.mark.parametrize("name", ("program", "weights_bfloat16") + controls.ROUTER_CONTROLS)
def test_a_wrong_router_fails_the_routing_limits(name, fam, params):
    """``harness/ssm_moe_controls.py``'s wrong routers through
    ``reference.check``: a bias left out fails ``MARGIN``, weights not
    renormalised or not scaled fail ``WEIGHT_TOLERANCE``; the program's own
    pass both, and so do its weights rounded to bfloat16 (the reading that is
    no control)."""
    found = controls.router_reading(name, fam, params, ids())
    assert found["ok"] == (name not in controls.ROUTER_CONTROLS), (name, found)
    by_margin = found["worst_shortfall"] > reference.MARGIN
    by_weights = found["weights_rel_rms"] > reference.WEIGHT_TOLERANCE
    # (a later layer's choice follows the stream a wrong weight has moved)
    assert {"bias_not_applied": by_margin, "not_renormalised": by_weights, "not_scaled": by_weights}.get(
        name, not (by_margin or by_weights)
    ), (name, found)


def test_the_unsteered_choice_of_22_in_512_at_the_published_widths():
    """The cell's own weights steer every token to the same 22 experts
    (``Family.init``), so its check reads a choice that cannot differ. Here the
    router alone decides: the first two layers of the published file (``ME``:
    the family builds no model without a Mamba-2 layer; a 4096-wide
    stream, 512 sigmoid scores, 22 a token scaled by 5, a latent of 1024, the
    shared expert of 5376) on ``init_params``' weights, whose correction bias is
    zeros, in bfloat16 as the cell runs it, through ``reference.check``. 512
    fresh scores lie 1e-3 apart at the 22nd, so the program's choice may differ
    from the float32 reference's, and every choice must be within ``MARGIN`` of
    it, with its weights within ``WEIGHT_TOLERANCE``."""
    import json

    with open(os.path.join(ROOT, "benchmarks/configs/nemotron-3-super-120b-a12b.json")) as f:
        published = json.load(f)
    cfg = dict(
        published, num_hidden_layers=2, hybrid_override_pattern="ME", n_routed_experts=2,
        vocab_size=512,
    )
    one = ssm_moe_decoder.build(cfg, {"seq_len": 128, "batch_size": 1, "remat": None})
    moe = one.model.moe
    assert (moe.num_experts, moe.top_k, moe.held, one.model.dim) == (512, 22, (0, 2), 4096)
    weights = jax.jit(lambda key: T.init_params(one.model, key))(jax.random.PRNGKey(11))
    assert not np.any(np.asarray(weights["layers"][1]["router_bias"]))
    tokens = jax.random.randint(jax.random.PRNGKey(12), (1, 128), 0, 512)
    found = one.check(jax.jit(one.forward)(weights, tokens), weights, tokens)
    layer, = found["layers"]
    assert found["ok"] and layer["distinct"] and layer["counts_agree"], found
    assert layer["pairs"] == 128 * 22 and layer["held_pairs_agree"]
    assert 0.0 < layer["worst_shortfall"] <= reference.MARGIN        # a choice that did differ
    assert layer["same_set_share"] < 1.0
    assert 0.0 < layer["weights_rel_rms"] <= reference.WEIGHT_TOLERANCE
