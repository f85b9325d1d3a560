"""DeepSeek-V3's block as Moonlight configures it (``models/transformer.py``
with ``latent=``, ``first_dense_layers=`` and a sigmoid-scored ``moe=``)
against the plain reference (``benchmarks/reference/mla_moe_decoder.py``:
explicit softmax attention, a Python loop over the experts), on the CPU in
float32 at tiny widths with seeded weights and a NON-ZERO router bias, so
that choosing by ``s + b`` and weighing by ``s`` are both exercised.

Tolerance 1e-4 of the largest value, as ``tests/test_moe.py``: both sides
compute in float32, what is left is summation order. A wrong term is off
by 1e-2 or more: the last test holds the comparison to that, term by term.
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

from benchmarks.families import mla_moe_decoder  # noqa: E402
from benchmarks.harness import mla_moe_flops  # noqa: E402
from benchmarks.reference import mla_moe_decoder as reference  # noqa: E402
from ray_tpu.models import transformer as T  # noqa: E402
from ray_tpu.ops.flash_attention import attention_reference, flash_attention  # noqa: E402

import model_helpers  # noqa: E402
from model_helpers import close, forward_with_routing, listed, loss_and_grads  # noqa: E402

CFG = {
    "name": "tiny-moonlight", "family": "mla_moe_decoder", "hidden_size": 64,
    "intermediate_size": 160, "moe_intermediate_size": 24, "num_attention_heads": 4,
    "num_key_value_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "q_lora_rank": None, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "moe_layer_freq": 1, "n_routed_experts": 8, "num_experts_per_tok": 3, "n_shared_experts": 2,
    "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "norm_topk_prob": True, "routed_scaling_factor": 2.446, "aux_loss_alpha": 0.001,
    "seq_aux": True, "vocab_size": 256, "rope_theta": 50000, "rope_scaling": None,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False, "attention_bias": False,
    "num_nextn_predict_layers": 0, "hidden_act": "silu", "torch_dtype": "float32",
}
TRAFFIC = {"seq_len": 64, "batch_size": 2, "remat": None}
TOL = 1e-4


def build(**changes):
    return mla_moe_decoder.build(dict(CFG, **changes), TRAFFIC)


def seeded(fam, seed=3):
    """Weights from the program's initialiser, every norm weight moved off
    1 and the router bias off 0 (about a tenth of the scores' spread: it
    changes choices without taking them over)."""
    params = fam.init(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))
    for stack in {"dense_layers", "layers"} & set(params):
        for name in ("attn_norm", "mlp_norm", "kv_norm"):
            leaf = params[stack][name]
            params[stack][name] = leaf + 0.2 * jax.random.normal(next(keys), leaf.shape)
    bias = params["layers"]["router_bias"]
    params["layers"]["router_bias"] = 0.05 * jax.random.normal(next(keys), bias.shape)
    params["final_norm"] = params["final_norm"] + 0.2 * jax.random.normal(next(keys), (64,))
    return params


ids = functools.partial(model_helpers.ids, seq=64)


def test_logits_and_routing_match_the_reference():
    fam = build()
    params, x = seeded(fam), ids()
    want, routings = reference.logits(fam.reference_weights(params), x, fam.config)
    got, routing = forward_with_routing(fam.model)(params, x)    # ``fam.forward`` and ``fam.routing`` in one
    close(got, want, TOL)
    assert routing["experts"].shape == (2, 128, 3)          # the two EXPERT layers
    for i, theirs in enumerate(routings):
        assert np.array_equal(np.sort(routing["experts"][i], -1), np.sort(theirs["experts"], -1))
        close(jnp.sort(routing["weights"][i], -1), jnp.sort(theirs["weights"], -1), TOL)
        # renormalised, then scaled
        assert np.allclose(np.asarray(jnp.sum(routing["weights"][i], -1)), 2.446, atol=1e-5)
        # the bias took part in the choice: without it some tokens choose otherwise
        unbiased = jax.lax.top_k(theirs["scores"], 3)[1]
        assert not np.array_equal(np.sort(unbiased, -1), np.sort(theirs["experts"], -1))


def test_loss_has_the_balance_term_and_matches():
    fam, plain = build(), build(aux_loss_alpha=0.0)
    params, x, y = seeded(fam), ids(), ids(2)
    want = reference.loss(listed(fam.reference_weights(params)), x, y, fam.config)
    got, _ = loss_and_grads(fam.model)(params, x, y)             # what ``fam.loss`` is
    close(got, want, TOL)
    # 0.001 x a loss that is 1 for a perfectly even router and more here
    aux = float(got - loss_and_grads(plain.model)(params, x, y)[0])
    assert 0.001 * 1.0 <= aux < 0.001 * 8.0
    # one sequence at a time: the loss is sequence-wise, the mean of the two
    one = jax.jit(fam.loss)
    alone = [float(one(params, {"x": x[i:i + 1], "y": y[i:i + 1]})) for i in (0, 1)]
    close(got, sum(alone) / 2, TOL)


def test_every_gradient_leaf_matches():
    fam = build()
    params, x, y = seeded(fam), ids(), ids(2)
    _, got = loss_and_grads(fam.model)(params, x, y)
    want = jax.grad(reference.loss)(listed(fam.reference_weights(params)), x, y, fam.config)
    names = {**mla_moe_decoder.ATTENTION, **mla_moe_decoder.MLP}
    assert set(got["dense_layers"]) == set(names.values())
    assert set(got["layers"]) == set({**names, **mla_moe_decoder.MOE}.values())
    for i, layer in enumerate(want["layers"]):
        stack, at = ("dense_layers", i) if i == 0 else ("layers", i - 1)
        for published, own in {**names, **({} if i == 0 else mla_moe_decoder.MOE)}.items():
            if own == "router_bias":
                # a buffer: no gradient reaches it (the reference differentiates
                # through a top_k's indices, which carry none either)
                assert float(jnp.abs(got[stack][own][at]).max()) == 0.0
                continue
            close(got[stack][own][at], layer[published], TOL, (i, published))
            assert float(jnp.abs(got[stack][own][at]).max()) > 0, (i, published)
    for published, own in (("embed_tokens", "embed"), ("norm", "final_norm"), ("lm_head", "lm_head")):
        close(got[own], want[published], TOL, published)


def test_one_compiled_program_serves_two_routings():
    fam = build()
    params = seeded(fam)
    step = jax.jit(jax.value_and_grad(fam.loss))
    counts = []
    for seed in (1, 5):
        x = ids(seed)
        step(params, {"x": x, "y": ids(seed + 1)})
        counts.append(np.asarray(jnp.sum(fam.routing(params, x)["counts"], axis=1)))
    assert step._cache_size() == 1
    assert not np.array_equal(counts[0], counts[1])
    assert counts[0].sum() == counts[1].sum() == 2 * 128 * 3


def test_parameter_count_from_shapes():
    fam = build()
    params = fam.init(jax.random.PRNGKey(0))
    attention = 64 * 4 * 24 + 64 * (32 + 8) + 32 * 4 * (16 + 16) + 4 * 16 * 64 + 2 * 64 + 32
    dense = attention + 3 * 64 * 160
    sparse = attention + 64 * 8 + 8 + (8 + 2) * 3 * 64 * 24
    by_hand = dense + 2 * sparse + 2 * 256 * 64 + 64
    assert T.num_params(params) == T.config_num_params(fam.model) == by_hand
    assert mla_moe_flops.parameters(fam.config) == by_hand
    assert params["dense_layers"]["w_gate"].shape == (1, 64, 160)
    assert params["layers"]["w_gate"].shape == (2, 8, 64, 24)
    assert params["layers"]["shared_down"].shape == (2, 48, 64)
    assert params["layers"]["wkv_b"].shape == (2, 32, 4 * 32)


def test_the_published_sizes_count_what_the_issue_counted():
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs", "moonlight-16b-a3b.json")) as f:
        cfg = json.load(f)
    w = mla_moe_flops.matmul_weights(cfg)
    assert w["attn_per_layer"] == 6_291_456 + 1_179_648 + 2_097_152 + 4_194_304
    assert w["dense_mlp_per_layer"] == 3 * 2048 * 11264 == 69_206_016
    assert w["experts_stored_per_layer"] == 64 * 3 * 2048 * 1408 == 553_648_128
    assert w["shared_per_layer"] == 17_301_504
    fam = mla_moe_decoder.build(cfg, {"seq_len": 8192, "batch_size": 1, "remat": None})
    assert T.config_num_params(fam.model) == mla_moe_flops.parameters(cfg) == 1_338_911_808
    # at the published depth: 16 B stored
    assert 15.9e9 < mla_moe_flops.parameters(dict(cfg, num_hidden_layers=27)) < 16.1e9


def test_what_this_model_cannot_do_yet_is_refused_in_one_sentence():
    model = build().model
    with pytest.raises(NotImplementedError, match="latent KV cache"):
        T.init_kv_cache(model, 1, 64)
    with pytest.raises(NotImplementedError, match="latent KV cache"):
        T.decode_step({}, {}, jnp.zeros((1, 1), jnp.int32), model)
    with pytest.raises(NotImplementedError, match="first_dense_layers"):
        T.partition_stages({}, model, 1)
    with pytest.raises(NotImplementedError, match="first_dense_layers"):
        T.stage_forward({}, jnp.zeros((1, 8), jnp.int32), model, first=True, last=True)


@pytest.mark.parametrize("qk_dim, v_dim, seq, block", [(48, 32, 256, 64), (192, 128, 256, 128)])
def test_flash_kernels_with_two_head_dims(qk_dim, v_dim, seq, block):
    """q / k of one dim, v / out / dO of another, interpreted: forward,
    dq, dk, dv against ``attention_reference``, over several causal tiles."""
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(key, (1, 2, seq, qk_dim)) for key in keys[:2])
    v = jax.random.normal(keys[2], (1, 2, seq, v_dim))
    weight = jax.random.normal(keys[3], (1, 2, seq, v_dim))
    highest = jax.lax.Precision.HIGHEST

    def flash(q, k, v):
        out = flash_attention(q, k, v, block_q=block, block_k=block, precision=highest)
        return jnp.sum(out * weight), out

    def plain(q, k, v):
        out = attention_reference(q, k, v)
        return jnp.sum(out * weight), out

    with jax.default_matmul_precision("highest"):
        got, got_out = jax.grad(flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        want, want_out = jax.grad(plain, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    assert got_out.shape == (1, 2, seq, v_dim)
    close(got_out, want_out, TOL, "out")
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == b.shape
        close(a, b, TOL, name)
    # the default scale is q's dim's, not v's
    scaled = flash_attention(q, k, v, scale=v_dim ** -0.5, block_q=block, block_k=block, precision=highest)
    assert float(jnp.abs(scaled - want_out).max()) > 1e-2


# -- what must FAIL: the program against a reference told another story ------

def _whole_head_rope(x, w, *, heads, rank, nope, theta, eps):
    """``reference.attention_forward`` with RoPE over the WHOLE q and k."""
    with jax.default_matmul_precision("highest"):
        batch, seq, _ = x.shape
        h = reference.rms_norm(x, w["input_layernorm"], eps)
        q = (h @ w["q_proj"]).reshape(batch, seq, heads, -1)
        kv_a = h @ w["kv_a_proj_with_mqa"]
        c = reference.rms_norm(kv_a[..., :rank], w["kv_a_layernorm"], eps)
        kv = (c @ w["kv_b_proj"]).reshape(batch, seq, heads, -1)
        k_rope = jnp.broadcast_to(kv_a[:, :, None, rank:], (batch, seq, heads, kv_a.shape[-1] - rank))
        k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
        attn = reference.causal_attention(
            reference.rotary(q, theta), reference.rotary(k, theta), kv[..., nope:]
        )
        return x + attn.reshape(batch, seq, -1) @ w["o_proj"]


def _scale_of_the_nope_dims(q, k, v, plain=reference.causal_attention):
    return plain(q * (q.shape[-1] / CFG["qk_nope_head_dim"]) ** 0.5, k, v)


def _no_latent_norm(x, weight, eps, plain=reference.rms_norm):
    return x if x.shape[-1] == CFG["kv_lora_rank"] else plain(x, weight, eps)


def _bias_in_the_weights(*args, plain=reference.route.__wrapped__, **kw):
    h, routing = plain(*args, **kw)
    weights = jnp.take_along_axis(routing["biased"], routing["experts"], axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20) * kw["scaling"]
    return h, dict(routing, weights=weights)


def _dense_layer_from_the_shared_experts(weights):
    """The reference's weights for a program whose layer 0 is an EXPERT
    layer: told that layer 0 is dense, it runs layer 0's shared experts as
    the dense MLP and none of the routed ones."""
    first = weights["layers"][0]
    weights["layers"][0] = dict(first, **{
        name: first["shared_" + name] for name in ("gate_proj", "up_proj", "down_proj")
    })
    return weights


WRONG = {
    # name: (changes to the PROGRAM's configuration, to what the reference is TOLD, patches of the reference)
    "rope over the whole head": ({}, {}, {"attention_forward": _whole_head_rope}),
    "scale 128^-0.5": ({}, {}, {"causal_attention": _scale_of_the_nope_dims}),
    "the bias used in the weights": ({}, {}, {"route": _bias_in_the_weights}),
    "no renormalisation": ({}, {"norm_topk_prob": False}, {}),
    "no 2.446": ({}, {"routed_scaling_factor": 1.0}, {}),
    "no shared branch": ({}, {"n_shared_experts": 0}, {}),
    "no latent norm": ({}, {}, {"rms_norm": _no_latent_norm}),
    "an expert layer in place of layer 0": ({"first_k_dense_replace": 0, "num_hidden_layers": 2}, {}, {}),
    "top-5 (one expert fewer a token)": ({"num_experts_per_tok": 2}, {}, {}),
}


@pytest.mark.parametrize("what", WRONG)
def test_a_changed_term_fails_the_check(what, monkeypatch):
    """Each is the program (or the reference, where the program offers no
    such switch) computing something else than the published equations:
    ``reference.check``, in float32 where agreement is 1e-6, must say no,
    and by a margin that the chip's bfloat16 tolerances of 1.2e-2 keep."""
    program, told, patches = WRONG[what]
    fam = build(**program)
    params, x = seeded(fam), ids()
    logits, routing = forward_with_routing(fam.model)(params, x)
    weights_fn = lambda: listed(fam.reference_weights(params))   # noqa: E731
    if not program:
        sound = reference.check(logits, routing, weights_fn, x, fam.config)
        assert sound["ok"] and sound["published"]["rel_rms"] < 1e-5, sound
    cfg = dict(fam.config, **told)
    if "first_k_dense_replace" in program:
        # the reference is told the published pattern: layer 0 dense, one expert layer
        cfg = dict(cfg, first_k_dense_replace=1)
        routing = {name: leaf[1:] for name, leaf in routing.items()}
        weights_fn = lambda: _dense_layer_from_the_shared_experts(   # noqa: E731
            listed(fam.reference_weights(params)))
    if "num_experts_per_tok" in program:
        cfg = dict(cfg, num_experts_per_tok=CFG["num_experts_per_tok"])
    for name, wrong in patches.items():
        monkeypatch.setattr(reference, name, wrong)
    with jax.disable_jit():      # the reference's jitted pieces look their helpers up again
        got = reference.check(logits, routing, weights_fn, x, cfg)
    assert not got["ok"], (what, got)
    if "published" in got:
        worst = max(
            got["published"]["rel_rms"] / reference.TOLERANCE,
            max(l["weights_rel_rms"] for l in got["layers"]) / reference.WEIGHT_TOLERANCE,
        )
        assert worst > 1.5, (what, got)
