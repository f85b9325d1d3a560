"""The dropless mixture-of-experts block (``models/transformer.py::_moe_mlp``,
q/k norms, the balancing loss) against the plain reference
(``benchmarks/reference/moe_decoder.py``: a Python loop over the experts,
each applied densely to all tokens), on the CPU in float32 at tiny widths
with seeded weights.

Tolerance 1e-4 of the largest value, everywhere: both sides compute in
float32 (the CPU's float32 matmul is exact to rounding, the reference
asks for "highest"), so what is left is summation order — a grouped
matmul over sorted rows against 8 dense matmuls, 1e-6 to 1e-5 through two
layers and their backward. A wrong term (a dropped pair, a renormalised
weight, a norm in the wrong place) is off by 1e-2 or more.
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

from benchmarks.families import moe_decoder  # noqa: E402
from benchmarks.reference import moe_decoder as reference  # noqa: E402
from ray_tpu.models import transformer as T  # noqa: E402

import model_helpers  # noqa: E402
from model_helpers import close, forward, listed, loss_and_grads  # noqa: E402

CFG = {
    "name": "tiny-olmoe", "family": "moe_decoder", "hidden_size": 64, "intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "rope_theta": 10000, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "clip_qkv": None, "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": False,
    "router_aux_loss_coef": 0.01, "torch_dtype": "float32",
}
TRAFFIC = {"seq_len": 64, "batch_size": 2, "remat": None}
TOL = 1e-4


def build(**changes):
    return moe_decoder.build(dict(CFG, **changes), TRAFFIC)


def seeded(fam, seed=3):
    """Weights from the program's initialiser, every norm weight moved off
    1 so that a misplaced or missing norm weight shows."""
    params = fam.init(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 8))
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        leaf = params["layers"][name]
        params["layers"][name] = leaf + 0.2 * jax.random.normal(next(keys), leaf.shape)
    params["final_norm"] = params["final_norm"] + 0.2 * jax.random.normal(next(keys), (64,))
    return params


ids = functools.partial(model_helpers.ids, seq=64)


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_logits_match_the_reference(norm_topk_prob):
    fam = build(norm_topk_prob=norm_topk_prob)
    params, x = seeded(fam), ids()
    want, routings = reference.logits(fam.reference_weights(params), x, fam.config)
    close(forward(fam.model)(params, x), want, TOL)          # what ``fam.forward`` is
    # the top-k weights: renormalised they sum to 1, published they do not
    weights = fam.routing(params, x)["weights"]
    assert np.allclose(np.asarray(jnp.sum(weights, axis=-1)), 1.0, atol=1e-6) == norm_topk_prob
    close(weights[1], routings[1]["weights"], TOL)


def test_loss_has_the_balancing_term_and_matches():
    fam, plain = build(), build(router_aux_loss_coef=0.0)
    params, x, y = seeded(fam), ids(), ids(2)
    batch = {"x": x, "y": y}
    want = reference.loss(listed(fam.reference_weights(params)), x, y, fam.config)
    got, _ = loss_and_grads(fam.model)(params, x, y)           # what ``fam.loss`` is
    assert float(got) == float(jax.jit(fam.loss)(params, batch))
    close(got, want, TOL)
    # 0.01 x a balancing loss that is 2 for a perfectly even router and more here
    aux = float(got - loss_and_grads(plain.model)(params, x, y)[0])
    assert 0.01 * 2.0 <= aux < 0.01 * 8.0


def test_every_gradient_leaf_matches():
    fam = build()
    params, x, y = seeded(fam), ids(), ids(2)
    _, got = loss_and_grads(fam.model)(params, x, y)
    want = jax.grad(reference.loss)(listed(fam.reference_weights(params)), x, y, fam.config)
    assert set(got["layers"]) == set(moe_decoder.NAMES.values())
    for i, layer in enumerate(want["layers"]):
        for published, own in moe_decoder.NAMES.items():
            close(got["layers"][own][i], layer[published], TOL, (i, published))
            assert float(jnp.abs(got["layers"][own][i]).max()) > 0, (i, published)
    for published, own in (("embed_tokens", "embed"), ("norm", "final_norm"), ("lm_head", "lm_head")):
        close(got[own], want[published], TOL, published)


def test_the_balancing_loss_reaches_the_router_only_through_the_probabilities():
    fam, plain = build(), build(router_aux_loss_coef=0.0)
    params, x, y = seeded(fam), ids(), ids(2)
    with_aux = loss_and_grads(fam.model)(params, x, y)[1]["layers"]
    without = loss_and_grads(plain.model)(params, x, y)[1]["layers"]
    assert float(jnp.abs(with_aux["router"] - without["router"]).max()) > 1e-6
    # the LAST layer's experts see the cross-entropy alone (the first
    # layer's feed the second's router)
    close(with_aux["w_down"][-1], without["w_down"][-1], TOL)
    assert float(jnp.abs(with_aux["w_down"][0] - without["w_down"][0]).max()) > 0


def one_expert_layer(config):
    """Router weights under which EVERY token of a positive input picks
    experts 0 and 1: two full groups and six empty ones."""
    d, hidden, experts = config.dim, config.hidden_dim, config.moe.num_experts
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    router = jnp.full((d, experts), -1.0).at[:, 0].set(1.0).at[:, 1].set(0.5)
    return {
        "mlp_norm": jnp.ones((d,)),
        "router": router,
        "w_gate": jax.random.normal(keys[0], (experts, d, hidden)) * 0.1,
        "w_up": jax.random.normal(keys[1], (experts, d, hidden)) * 0.1,
        "w_down": jax.random.normal(keys[2], (experts, hidden, d)) * 0.1,
    }


def test_a_batch_routed_entirely_to_one_expert_keeps_every_token():
    """What a capacity of 1.25 x the mean would have dropped: 128 tokens
    all choosing experts 0 and 1 of 8 (4 x the mean each)."""
    fam = build()
    layer = one_expert_layer(fam.model)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (2, 64, 64))) + 0.1
    got, routing = T._mlp_block(x, layer, fam.model, True)
    assert np.asarray(routing["counts"]).tolist() == [[128] + [0] * 7, [0, 128] + [0] * 6]
    assert set(np.asarray(routing["experts"]).reshape(-1).tolist()) == {0, 1}
    want, _ = reference.moe_forward(
        x, {"post_attention_layernorm": layer["mlp_norm"], "router": layer["router"],
            "gate_proj": layer["w_gate"], "up_proj": layer["w_up"], "down_proj": layer["w_down"]},
        fam.config,
    )
    close(got, want, TOL)
    # every token got both its experts: none equals the residual alone
    assert float(jnp.min(jnp.max(jnp.abs(got - x), axis=-1))) > 1e-3


def test_two_routings_run_one_compiled_program():
    fam = build()
    params = seeded(fam)
    traces = []

    @jax.jit
    def step(params, x):
        traces.append(1)
        logits, routing = T.forward_with_routing(params, x, fam.model)
        return jnp.sum(logits), routing["counts"]

    _, first = step(params, ids(1))
    _, second = step(params, ids(2))
    assert len(traces) == 1 and step._cache_size() == 1
    assert np.asarray(first).tolist() != np.asarray(second).tolist()
    for counts in (first, second):     # [layers, top_k, experts]: every pair counted
        assert np.asarray(counts).sum(axis=(1, 2)).tolist() == [2 * 64 * 2] * 2


def test_qk_norm_is_over_the_whole_projection_before_the_heads():
    """``qk_norm`` in the dense block too: forward against the same
    attention written out, and not against a per-head norm."""
    config = T.TransformerConfig.tiny(qk_norm=True, rms_norm_eps=1e-5, attention="reference")
    params = T.init_params(config, jax.random.PRNGKey(0))
    for name in ("q_norm", "k_norm"):
        leaf = params["layers"][name]
        params["layers"][name] = leaf + 0.3 * jax.random.normal(jax.random.PRNGKey(9), leaf.shape)
    assert params["layers"]["q_norm"].shape == (2, 64) and params["layers"]["k_norm"].shape == (2, 32)
    assert T.param_logical_dims(config)["layers"]["q_norm"] == ("layer", None)
    layer = jax.tree.map(lambda leaf: leaf[0], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))
    q, k, _v = T._qkv(h, layer, config)

    def whole(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-5) * w

    want_q = whole(h @ layer["wq"], layer["q_norm"]).reshape(2, 16, 4, 16).transpose(0, 2, 1, 3)
    want_k = whole(h @ layer["wk"], layer["k_norm"]).reshape(2, 16, 2, 16).transpose(0, 2, 1, 3)
    close(q, want_q, TOL)
    close(k, want_k, TOL)
    plain = T.TransformerConfig.tiny(attention="reference")
    x = ids(seq=32)
    logits = T.forward(params, x, config)
    assert np.isfinite(np.asarray(logits)).all()
    assert float(jnp.abs(logits - T.forward(params, x, plain)).max()) > 1e-3


def test_parameter_count_from_shapes():
    config = build().model
    assert T.config_num_params(config) == T.num_params(T.init_params(config, jax.random.PRNGKey(0)))
    assert not hasattr(config.moe, "capacity_factor")


def test_rms_norm_eps_comes_from_the_config():
    """1e-6 stays the default (the dense programs are unchanged); a
    configuration's eps reaches every norm of the model."""
    assert T.TransformerConfig().rms_norm_eps == 1e-6
    params = T.init_params(T.TransformerConfig.tiny(), jax.random.PRNGKey(0))
    params["embed"] = params["embed"] * 0.05     # a small variance, where eps shows
    x = ids(seq=32)
    a = T.forward(params, x, T.TransformerConfig.tiny(attention="reference"))
    b = T.forward(params, x, T.TransformerConfig.tiny(attention="reference", rms_norm_eps=1e-5))
    assert float(jnp.abs(a - b).max()) > 1e-3


# Group sizes against a 512-row tile (ops.grouped_matmul.ROW_TILE, which the
# tile count picks at these 2,048 rows): groups that end inside a tile, on a
# tile's edge, span three tiles, are empty, or are one row. What the kernel
# computed, not what the router counted.
RAGGED = {
    "straddling": [500, 30, 1006, 0, 1, 511],
    "on_the_edges": [512, 0, 1024, 512, 0, 0],
    "one_expert_takes_all": [0, 0, 2048, 0, 0, 0],
    "one_row_each_then_the_rest": [1, 1, 1, 1, 1, 2043],
}


@pytest.mark.parametrize("sizes", list(RAGGED.values()), ids=list(RAGGED))
def test_grouped_matmul_is_each_row_against_its_own_expert(sizes):
    """``ops.grouped_matmul`` row by row against a dense loop over the
    experts, forward and both gradients: a row lost or given to a
    neighbouring expert at a tile boundary shows here, where the
    benchmark's check only counts what the router chose."""
    from ray_tpu.ops.grouped_matmul import ROW_TILE, _tiling, grouped_matmul

    m, k, n = sum(sizes), 128, 256
    # four row tiles in all three calls, so a group spans several and tiles straddle
    assert m == 4 * ROW_TILE and all(
        _tiling(m, *sides, len(sizes), weight_grad, itemsize=4)[0] == ROW_TILE
        for *sides, weight_grad in ((k, n, False), (n, k, False), (k, n, True)))
    keys = jax.random.split(jax.random.PRNGKey(len(sizes) + sizes[0]), 3)
    lhs = jax.random.normal(keys[0], (m, k), jnp.float32)
    rhs = jax.random.normal(keys[1], (len(sizes), k, n), jnp.float32)
    weigh = jax.random.normal(keys[2], (m, n), jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    owner = np.repeat(np.arange(len(sizes)), sizes)

    def dense(lhs, rhs):
        with jax.default_matmul_precision("highest"):
            every = jnp.einsum("mk,gkn->gmn", lhs, rhs)
        return every[owner, np.arange(m)]

    got = jax.jit(grouped_matmul)(lhs, rhs, group_sizes)
    want = dense(lhs, rhs)
    rows = np.max(np.abs(np.asarray(got) - np.asarray(want)), axis=1)
    assert rows.max() <= TOL * np.max(np.abs(want)), f"worst row {rows.argmax()} of expert {owner[rows.argmax()]}"
    got_grads = jax.jit(jax.grad(lambda a, b: jnp.sum(grouped_matmul(a, b, group_sizes) * weigh), (0, 1)))(lhs, rhs)
    want_grads = jax.grad(lambda a, b: jnp.sum(dense(a, b) * weigh), (0, 1))(lhs, rhs)
    close(got_grads[0], want_grads[0], TOL, "d lhs")
    close(got_grads[1], want_grads[1], TOL, "d rhs")   # an empty group's gradient is zero, not stale memory


# A layer scan's stack of expert weights, and which layer reads it. The
# routings: two of four experts get no row; one expert gets them all.
IN_STACK = [(depth, layer) for depth in (1, 2, 3) for layer in range(depth)]
ROUTINGS = {"empty_groups": [700, 0, 836, 0], "one_full_group": [0, 1536, 0, 0]}


@pytest.mark.parametrize("sizes", list(ROUTINGS.values()), ids=list(ROUTINGS))
@pytest.mark.parametrize("depth,layer", IN_STACK, ids=[f"layer{l}of{d}" for d, l in IN_STACK])
def test_grouped_matmul_reads_a_layer_where_the_stack_holds_it(depth, layer, sizes):
    """``grouped_matmul(..., within=(stack, layer))`` IS the per-layer call
    on ``stack[layer]``: values, input gradient and weight gradient bit for
    bit (the same tiles in the same grid steps), with the weights read from
    the stack (``rhs`` is handed zeros: only its gradient is its own). And
    through ``jax.grad`` of a scan over the stack, as ``_scan_layers``
    builds it (the stack closed over under ``stop_gradient``, the layer's
    number scanned beside its slice), a layer's weight gradient is its own
    and a layer whose output the loss does not read gets exactly zero:
    nothing is added up across the other layers' windows."""
    from ray_tpu.ops.grouped_matmul import grouped_matmul

    m, k, n = sum(sizes), 128, 256
    keys = jax.random.split(jax.random.PRNGKey(7 * depth + layer), 3)
    lhs = jax.random.normal(keys[0], (m, k), jnp.float32)
    stack = jax.random.normal(keys[1], (depth, len(sizes), k, n), jnp.float32)
    weigh = jax.random.normal(keys[2], (m, n), jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)

    def alone(lhs, rhs):
        out = grouped_matmul(lhs, rhs, group_sizes)
        return jnp.sum(out * weigh), out

    def in_stack(lhs, rhs, stack, layer):
        out = grouped_matmul(lhs, rhs, group_sizes, within=(stack, layer))
        return jnp.sum(out * weigh), out

    grads = lambda f: jax.jit(jax.value_and_grad(f, (0, 1), has_aux=True))  # noqa: E731
    (_, want), (want_dlhs, want_drhs) = grads(alone)(lhs, stack[layer])
    (_, got), (got_dlhs, got_drhs) = grads(in_stack)(
        lhs, jnp.zeros_like(stack[layer]), stack, jnp.int32(layer))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got_dlhs, want_dlhs)
    np.testing.assert_array_equal(got_drhs, want_drhs)

    def scanned(stack):
        held = jax.lax.stop_gradient(stack)

        def body(carry, scanned):
            index, rhs = scanned
            return carry, grouped_matmul(lhs, rhs, group_sizes, within=(held, index))

        _, outs = jax.lax.scan(body, 0.0, (jnp.arange(depth, dtype=jnp.int32), stack))
        return jnp.sum(outs[layer] * weigh)

    dstack = np.asarray(jax.jit(jax.grad(scanned))(stack))
    np.testing.assert_array_equal(dstack[layer], want_drhs)
    assert not np.delete(dstack, layer, axis=0).any()
