"""The patterned decoder with window layers (``models/transformer.py`` with
a ``layer_pattern`` of one global "full" layer and three "window" layers, a
``head_dim`` stated apart from the stream's width, the rotary embedding on
the window layers alone (``rope_kinds``), over softmax-routed ReLU experts
(``MoEConfig.activation``) whose router reads the LAYER's input
(``router_input="layer_input"``) and of which a block is HELD:
SmallThinker-21BA3B's shape) against an oracle WRITTEN HERE: the same
mathematics in plain ``jax.numpy`` on the program's own parameter tree,
float32, an explicit ``(j <= i) & (j > i - window)`` mask, no kernel, no
sort, no scan, the experts a loop. On the CPU at tiny widths with seeded
weights: TWO periods of (full, window), 4 / 2 heads of 16 on a stream of 48
(q is 64 wide), a window of 8 keys over 40 positions, 8 experts of which 4 are
held, 2 a token. (One layer a kind a period: the scan's body is one period, so
the programs these cases compile grow with it, and a second or third window
layer in a row claims nothing the first does not.
``test_the_tree_is_stacked_by_period_and_counted`` builds the published period
of four, which compiles no step.)

Tolerances, each of the largest value compared: logits 5e-4, loss 1e-5,
gradients 2e-3 (``tests/test_conv_moe.py``'s and for its reasons: both sides
float32, sums in another order). A wrong term is off by far more.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import transformer as T
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.train import jax_utils

from model_helpers import (
    close, forward, forward_with_routing, ids, layers_in_order, loss_and_grads,
    trains_through_jax_trainer,
)

EPS, THETA, HELD, WINDOW = 1e-6, 1.5e6, (0, 4), 8
MODEL = T.TransformerConfig(
    vocab_size=256, dim=48, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16, hidden_dim=96,
    max_seq=40, rope_theta=THETA, rope_kinds=("window",), window=WINDOW, rms_norm_eps=EPS,
    dtype=jnp.float32, layer_pattern=("full", "window"),
    moe=T.MoEConfig(
        num_experts=8, top_k=2, norm_topk_prob=True, expert_dim=24, held=HELD,
        activation="relu", router_input="layer_input",
    ),
)
TOKENS = 80


def seeded(model=MODEL, seed=3):
    """Weights from the program's initialiser, every norm weight moved off 1
    (a router fed the normed input then differs by more than a scale)."""
    params = jax.jit(lambda key: T.init_params(model, key))(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 32))
    for tree in params["layers"].values():
        for name in ("attn_norm", "mlp_norm"):
            tree[name] = tree[name] + 0.2 * jax.random.normal(next(keys), tree[name].shape)
    params["final_norm"] = params["final_norm"] + 0.2 * jax.random.normal(next(keys), (model.dim,))
    return params


# -- the oracle ---------------------------------------------------------------
def _norm(x, weight):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * weight


def _rope(x):
    """Rotate-half over the whole head. x: [batch, seq, heads, head]."""
    seq, head = x.shape[1], x.shape[3]
    inv = THETA ** (-jnp.arange(0, head, 2, dtype=jnp.float32) / head)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., head // 2:], x[..., :head // 2]], axis=-1)
    return x * cos + turned * sin


def _oracle_attention(h, layer, model, window, rope):
    """Grouped-query attention at the stated head size; ``window`` None: the
    whole context; ``rope``: whether q and k are turned."""
    batch, seq, _ = h.shape
    heads, kv, hd = model.n_heads, model.n_kv_heads, model.head_dim
    q = (h @ layer["wq"]).reshape(batch, seq, heads, hd)
    k = (h @ layer["wk"]).reshape(batch, seq, kv, hd)
    v = (h @ layer["wv"]).reshape(batch, seq, kv, hd)
    if rope:
        q, k = _rope(q), _rope(k)
    k, v = (jnp.repeat(t, heads // kv, axis=2) for t in (k, v))   # KV head j: query heads j * group ..
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    i, j = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    visible = j <= i
    if window is not None:
        visible &= j > i - window
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(batch, seq, heads * hd)
    return out @ layer["wo"]


def _oracle_experts(h, routed_by, layer, moe, act=jax.nn.relu):
    """The HELD experts' part of the weighted sum: the router reads
    ``routed_by``, the experts ``h``; softmax over the chosen logits; a loop,
    every expert applied to all tokens, weight 0 where it was not chosen."""
    logits = routed_by @ layer["router"]
    top, chosen = jax.lax.top_k(logits, moe.top_k)
    weights = jax.nn.softmax(top, axis=-1)
    first, count = moe.held or (0, moe.num_experts)
    out = 0.0
    for e in range(count):
        weight = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        mlp = (act(h @ layer["w_gate"][e]) * (h @ layer["w_up"][e])) @ layer["w_down"][e]
        out = out + weight[..., None] * mlp
    return out


def oracle_logits(params, tokens, model=MODEL, *, window_on_full=None, rope_on_full=False,
                  window=WINDOW, normed_router=False, act=jax.nn.relu):
    """The keyword arguments are the CONTROLS: each computes another model."""
    x = params["embed"][tokens]
    for kind, layer in layers_in_order(params, model):
        layer_input = x
        h = _norm(x, layer["attn_norm"])
        if kind == "window":
            x = x + _oracle_attention(h, layer, model, window, True)
        else:
            x = x + _oracle_attention(h, layer, model, window_on_full, rope_on_full)
        h = _norm(x, layer["mlp_norm"])
        x = x + _oracle_experts(h, h if normed_router else layer_input, layer, model.moe, act)
    return _norm(x, params["final_norm"]) @ params["lm_head"]


def oracle_loss(params, tokens, targets, model=MODEL):
    logp = jax.nn.log_softmax(oracle_logits(params, tokens, model), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


@pytest.fixture(scope="module")
def params():
    return seeded()


# -- the model ----------------------------------------------------------------
def test_the_tree_is_stacked_by_period_and_counted():
    """At the PUBLISHED period, one global layer then three window layers: the
    one case that is about the period itself, and it compiles no step."""
    model = dataclasses.replace(MODEL, layer_pattern=("full", "window", "window", "window"))
    params = seeded(model)
    assert (model.periods, model.head_dim, model.n_heads * model.head_dim) == (1, 16, 64)
    assert params["layers"]["full"]["wq"].shape == (1, 1, 48, 64)      # q wider than the stream
    assert params["layers"]["window"]["wq"].shape == (1, 3, 48, 64)
    assert params["layers"]["window"]["wk"].shape == (1, 3, 48, 32)
    assert params["layers"]["window"]["wo"].shape == (1, 3, 64, 48)
    assert params["layers"]["window"]["w_gate"].shape == (1, 3, 4, 48, 24)  # the HELD experts
    assert params["layers"]["window"]["router"].shape == (1, 3, 48, 8)      # all are scored
    assert params["layers"]["window"]["router"].dtype == jnp.float32
    assert "router_bias" not in params["layers"]["window"]                   # softmax routing
    assert T.config_num_params(model) == T.num_params(params)
    dims = T.param_logical_dims(model)
    is_dims = lambda x: isinstance(x, tuple)
    assert jax.tree.structure(dims, is_leaf=is_dims) == jax.tree.structure(params)


def test_logits_match_the_oracle_on_both_paths(params):
    x = ids()
    with jax.default_matmul_precision("highest"):
        want = oracle_logits(params, x)
    got = {}
    for attention in ("flash", "reference"):
        model = dataclasses.replace(MODEL, attention=attention)
        got[attention], routing = forward_with_routing(model)(params, x)
        close(got[attention], want, 5e-4, attention)
        assert model.periods == 2
        assert routing["experts"].shape == (4, TOKENS, 2)
        assert all(0 < int(n) < TOKENS * 2 for n in routing["held_pairs"])
    close(got["flash"], got["reference"], 2e-5, "kernel path against attention='reference'")


def test_loss_and_every_gradient_leaf_match_the_oracle(params):
    x, y = ids(), ids(seed=2)
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(oracle_loss)(params, x, y)
    for attention, remat in (("flash", None), ("flash", "full"), ("reference", None)):
        model = dataclasses.replace(MODEL, attention=attention, remat=remat)
        got, grads = loss_and_grads(model)(params, x, y)
        assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want)), (attention, remat)
        assert jax.tree.structure(grads) == jax.tree.structure(want_grads)
        mine, theirs = (jax.tree_util.tree_leaves_with_path(g) for g in (grads, want_grads))
        for (path, leaf), (_, wanted) in zip(mine, theirs):
            name = jax.tree_util.keystr(path)
            assert np.any(np.asarray(wanted)), name           # the router's weight among them
            close(leaf, wanted, 2e-3, (attention, remat, name))


def test_the_routers_gradient_reaches_the_stream_and_no_norm_weight(params):
    """One expert layer alone: with the router on the layer's input, the
    gradient of the routing weights goes to ``layer_input`` and leaves the
    block's norm weight what the experts alone give it."""
    layer = jax.tree.map(lambda leaf: leaf[0, 0], params["layers"]["window"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 40, 48))
    stream = jax.random.normal(jax.random.PRNGKey(6), (2, 40, 48))

    def block(layer, x, stream):
        return jnp.sum(T._mlp_block(x, layer, MODEL, True, stream)[0] ** 2)

    grads, dstream = jax.grad(block, argnums=(0, 2))(layer, x, stream)
    assert np.any(np.asarray(dstream)) and np.any(np.asarray(grads["router"]))
    with jax.default_matmul_precision("highest"):
        def oracle(layer, x, stream):
            h = _norm(x, layer["mlp_norm"])
            return jnp.sum((x + _oracle_experts(h, stream, layer, MODEL.moe)) ** 2)
        want, want_stream = jax.grad(oracle, argnums=(0, 2))(layer, x, stream)
    close(dstream, want_stream, 2e-3, "the stream's gradient is the router's alone")
    close(grads["mlp_norm"], want["mlp_norm"], 2e-3, "mlp_norm")
    close(grads["router"], want["router"], 2e-3, "router")
    with pytest.raises(ValueError, match="layer's input"):
        T._mlp_block(x, layer, MODEL, True)


def _expert_layer(params, held):
    """One window layer's expert leaves for a model that holds ``held`` of the
    SAME 8 experts (None: all): the whole layer drawn once, shares sliced."""
    whole_model = dataclasses.replace(MODEL, moe=dataclasses.replace(MODEL.moe, held=None))
    whole = jax.jit(lambda key: T.init_params(whole_model, key))(jax.random.PRNGKey(9))
    layer = jax.tree.map(lambda leaf: leaf[1, 0], whole["layers"]["window"])
    if held is not None:
        first, count = held
        layer = {
            name: leaf[first:first + count] if name in T._EXPERT_WEIGHTS else leaf
            for name, leaf in layer.items()
        }
    return layer, dataclasses.replace(MODEL, moe=dataclasses.replace(MODEL.moe, held=held))


def test_the_shares_add_up(params):
    """Held (0, 4) + held (4, 4) of 8 equal the uncut layer; there is no
    shared expert, so nothing is counted twice."""
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 40, 48))
    stream = jax.random.normal(jax.random.PRNGKey(6), (2, 40, 48))
    routed, held_pairs = 0.0, 0
    for held in ((0, 4), (4, 4)):
        share, model = _expert_layer(params, held)
        out, routing = jax.jit(lambda h, r, l: T._moe_mlp(h, l, model, r))(h, stream, share)
        with jax.default_matmul_precision("highest"):
            close(out, _oracle_experts(h, stream, share, model.moe), 2e-5, held)
        routed, held_pairs = routed + out, held_pairs + int(routing["held_pairs"])
    assert held_pairs == TOKENS * 2                      # every pair is some share's
    whole, model = _expert_layer(params, None)
    uncut, routing = jax.jit(lambda h, r, l: T._moe_mlp(h, l, model, r))(h, stream, whole)
    assert "held_pairs" not in routing
    close(routed, uncut, 2e-5, "two shares")
    with jax.default_matmul_precision("highest"):
        close(routed, _oracle_experts(h, stream, whole, model.moe), 2e-5, "the uncut oracle")
    assert np.max(np.abs(np.asarray(out - uncut))) > 1e-2 * np.max(np.abs(np.asarray(uncut)))


CONTROLS = {
    "the router fed the normed input": dict(normed_router=True),
    "SiLU for ReLU": dict(act=jax.nn.silu),
    "RoPE on the global layer": dict(rope_on_full=True),
    "the window ignored on the window layers": dict(window=None),
    "a window on the global layer": dict(window_on_full=WINDOW),
}


@pytest.mark.parametrize("what", list(CONTROLS))
def test_a_changed_term_moves_the_logits(params, what):
    """What the comparison above would catch, term by term: the PROGRAM
    against the oracle computing another model reads over twenty tolerances."""
    x = ids()
    got = forward(MODEL)(params, x)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(oracle_logits(params, x, **CONTROLS[what]))
    off = np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want))
    assert off > 20 * 5e-4, (what, off)


def test_the_programs_own_switches_are_the_oracles(params):
    """The same terms from the program's side: each field set to the other
    model's value gives the oracle's control, within the comparison's limit."""
    x, replace = ids(), dataclasses.replace
    for model, control in (
        (replace(MODEL, moe=replace(MODEL.moe, router_input="normed")), dict(normed_router=True)),
        (replace(MODEL, moe=replace(MODEL.moe, activation="silu")), dict(act=jax.nn.silu)),
        (replace(MODEL, rope_kinds=None), dict(rope_on_full=True)),
        (replace(MODEL, window=40), dict(window=None)),
    ):
        got = forward(model)(params, x)
        with jax.default_matmul_precision("highest"):
            close(got, oracle_logits(params, x, **control), 5e-4, control)


def test_what_this_model_cannot_do_yet_is_refused_by_name(params):
    with pytest.raises(NotImplementedError, match="head_dim stated apart"):
        T.init_kv_cache(MODEL, 1, 16)
    whole_heads = dataclasses.replace(MODEL, head_dim=12)
    with pytest.raises(NotImplementedError, match="ring cache of `window` rows"):
        T.init_kv_cache(whole_heads, 1, 16)
    with pytest.raises(NotImplementedError, match="ring cache of `window` rows"):
        T.decode_step(params, {}, ids(batch=1, seq=1), whole_heads)
    with pytest.raises(NotImplementedError, match="partition_stages.*head_dim stated apart"):
        T.partition_stages(params, MODEL, 2)
    with pytest.raises(NotImplementedError, match="partition_stages.*layer_pattern"):
        T.partition_stages(params, whole_heads, 2)
    plain = T.TransformerConfig.tiny(head_dim=8)
    with pytest.raises(NotImplementedError, match="decode with a head_dim stated apart"):
        T.init_kv_cache(plain, 1, 16)
    with pytest.raises(ValueError, match="window="):
        dataclasses.replace(MODEL, window=None)
    with pytest.raises(NotImplementedError, match="callable attention="):
        dataclasses.replace(MODEL, attention=lambda q, k, v, causal: q)
    with pytest.raises(ValueError, match="rope_kinds"):
        dataclasses.replace(MODEL, rope_kinds=("conv",))
    with pytest.raises(ValueError, match="activation"):
        T.MoEConfig(activation="gelu")
    with pytest.raises(ValueError, match="router_input"):
        T.MoEConfig(router_input="embedding")


def _two_steps(model, mesh_axes, x):
    """The losses of two AdamW steps of ``model`` on ``x`` over a mesh of
    ``mesh_axes``, through the sharded training step."""
    from ray_tpu.parallel.mesh import MeshSpec

    mesh, optimizer = MeshSpec(dict(mesh_axes)), optax.adamw(1e-3)
    setup = jax_utils.setup_sharded_training(
        lambda: T.init_params(model, jax.random.PRNGKey(0)), optimizer,
        mesh=mesh.build(jax.devices()[:mesh.size]), logical_dims=T.param_logical_dims(model),
    )
    step = jax_utils.build_sharded_train_step(
        lambda params, batch: T.loss_fn(params, batch["x"], batch["y"], model), optimizer, setup
    )
    batch = setup.shard_batch({"x": x[:, :-1], "y": x[:, 1:]})
    params, opt_state, first = step(setup.params, setup.opt_state, batch)
    return float(first), float(step(params, opt_state, batch)[2])


@functools.cache
def _one_devices_two_steps():
    """What both meshes below are held to, stepped once."""
    return _two_steps(MODEL, {"dp": 1}, np.asarray(ids(seed=8, batch=4, seq=41)))


@pytest.mark.parametrize("axes", [{"fsdp": 2, "tp": 2}, {"dp": 2, "sp": 2}])
def test_the_step_is_the_one_devices_over_a_tp_and_an_sp_mesh(axes):
    """A pattern of full and window layers has no kernel that refuses a mesh
    axis: flash runs per (batch, heads) shard with and without a window
    (heads over tp; under sp the kernel still sees the whole sequence: the
    window through ``parallel/``'s sequence-parallel attention is what is NOT
    written), and two steps give the one-device losses."""
    x = np.asarray(ids(seed=8, batch=4, seq=41))
    mesh = _two_steps(MODEL, axes, x)
    assert mesh[1] < mesh[0]
    np.testing.assert_allclose(mesh, _one_devices_two_steps(), rtol=2e-6)


@pytest.mark.parametrize("kv_heads", [2, 1])
def test_flash_and_the_oracle_agree_on_grouped_heads_over_tp(kv_heads, monkeypatch):
    """K and V reach the flash kernels at ``n_kv_heads`` and are cut over
    ``tp`` as q's heads are: with 2 KV heads over tp 2 a shard holds one
    beside its two query heads; ONE KV head is not divisible and is repeated
    by the least factor that makes it (2, not the group's 4). The oracle is
    handed K and V repeated to the query heads. Two steps' losses agree."""
    x = np.asarray(ids(seed=9, batch=2, seq=41))
    per_shard = set()

    def recording(q, k, v, **kwargs):
        per_shard.add((q.shape[1], k.shape[1], v.shape[1]))
        return flash_attention(q, k, v, **kwargs)

    monkeypatch.setattr(T, "flash_attention", recording)
    losses = {
        attention: _two_steps(dataclasses.replace(MODEL, n_kv_heads=kv_heads, attention=attention), {"tp": 2}, x)
        for attention in ("flash", "reference")
    }
    assert per_shard == {(2, 1, 1)}      # a shard's kernels: two query heads on one KV head
    assert losses["flash"][1] < losses["flash"][0]
    np.testing.assert_allclose(losses["flash"], losses["reference"], rtol=1e-5)


def test_the_tiny_preset_trains_through_jax_trainer(ray_start_shared, tmp_path):
    """The normal path: JaxTrainer -> setup_sharded_training ->
    build_sharded_train_step -> loss_fn, over a dp 2 x fsdp 2 mesh (flash
    with and without a window and the held experts' block per data shard
    under shard_map, the router's second stream sharded as the first), full
    remat."""
    trains_through_jax_trainer(dataclasses.replace(MODEL, remat="full"), "window-moe", tmp_path, seq=41)
