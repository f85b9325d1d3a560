"""The held experts' rows in a buffer sized for what held experts get
(``models/transformer.py::_moe_mlp`` under ``MoEConfig.held``): where a
layer's held pairs fit ``held_row_bound`` the block runs through buffers of
that many rows (``_by_held_pair``); a routing with more takes the worst
case's own path, a loop over the held experts, each dense over every token
(``_by_held_expert``), whose trip count is the device's own verdict: no trip
for a routing that fits, and ``routing["overflow"]`` says that it ran.

Held to ``_parent_moe_mlp``, the block's formula written out below in plain
``jax.numpy`` (every pair's own expert by index and ``einsum``, no sort, no
gather by pair, none of the program's dispatch helpers): output, routing and
every gradient leaf, over routings on both sides of the bound, the loop
forced as well as chosen; per data shard under a mesh; and by the shapes in
the jaxpr. On the CPU in float32, 80 tokens, 32 experts of which 3 are held,
2 a token: 160 pairs, 15 of them an even routing's share, a bound of 120 rows.

Where the bound is the worst case's ``tokens * top_k`` rows (no expert absent,
or an eighth of them held and more) the block is ``_by_every_pair``: held to
the same oracle over ``top_k`` 2 to 8, by its jaxpr (no ``cond``, no loop, no
array ``[tokens, top_k, d]``), and with the rows no tile writes poisoned.
"""

import dataclasses
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as T

from model_helpers import close

DIM, WIDTH, EXPERTS, TOP_K, HELD = 64, 48, 32, 2, (4, 3)
BATCH, SEQ = 2, 40
TOKENS = BATCH * SEQ
PAIRS = TOKENS * TOP_K
BOUND = 120
MODEL = T.TransformerConfig.tiny(
    dim=DIM, dtype=jnp.float32,
    moe=T.MoEConfig(
        num_experts=EXPERTS, top_k=TOP_K, norm_topk_prob=True, renorm_eps=1e-6,
        expert_dim=WIDTH, scoring="sigmoid", held=HELD,
    ),
)
# held pairs of a routing -> routing["overflow"]: the loop's first trip comes between 120 and 121
ROUTINGS = {0: 0, 50: 0, BOUND: 0, BOUND + 1: 1, PAIRS: 1}


def _parent_moe_mlp(h, layer, config):
    """What ``_moe_mlp`` computes, the plain way: the router as the program
    has it, then every (token, choice) pair through ITS expert's gated MLP,
    the weights found by index, an absent pair's output zero, and the
    weighted sum over a token's choices. Autodiff does the rest."""
    moe = config.moe
    batch, seq, d = h.shape
    tokens = batch * seq
    ht = h.reshape(tokens, d)
    logits = ht.astype(jnp.float32) @ layer["router"].astype(jnp.float32)
    if moe.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)                      # [T, E]
        biased = scores + jax.lax.stop_gradient(layer["router_bias"])
        if moe.n_group > 1:
            biased = T._within_best_groups(biased, moe)
        _, experts = jax.lax.top_k(biased, moe.top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    else:
        scores = jax.nn.softmax(logits, axis=-1)             # [T, E]
        weights, experts = jax.lax.top_k(scores, moe.top_k)  # [T, K]
    chosen = experts[:, :, None] == jnp.arange(moe.num_experts, dtype=experts.dtype)
    if moe.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + moe.renorm_eps)
    if moe.routed_scaling != 1.0:
        weights = weights * moe.routed_scaling
    counts = jnp.sum(chosen, axis=0, dtype=jnp.int32)        # [K, E]
    if moe.scoring == "sigmoid":
        share = (scores / jnp.sum(scores, axis=-1, keepdims=True)).reshape(batch, seq, -1)
        chose = jnp.sum(chosen.reshape(batch, seq, moe.top_k, -1), axis=(1, 2))
        prob_sum = jnp.sum(chose * jnp.mean(share, axis=1), axis=0)
    else:
        prob_sum = jnp.sum(scores, axis=0)
    routing = {"prob_sum": prob_sum, "counts": counts, "experts": experts, "weights": weights}
    first, held = moe.held or (0, moe.num_experts)
    here = (experts >= first) & (experts < first + held)             # [T, K]
    if moe.held:
        routing["held_pairs"] = jnp.sum(here, dtype=jnp.int32)
    mine = jnp.clip(experts - first, 0, held - 1)
    gate = jnp.einsum("td,tkdf->tkf", ht, layer["w_gate"][mine])
    up = jnp.einsum("td,tkdf->tkf", ht, layer["w_up"][mine])
    per_pair = jnp.einsum("tkf,tkfd->tkd", jax.nn.silu(gate) * up, layer["w_down"][mine])
    per_pair = jnp.where(here[:, :, None], per_pair, 0).astype(jnp.float32)
    out = jnp.sum(per_pair * weights.astype(h.dtype).astype(jnp.float32)[:, :, None], axis=1)
    return out.astype(h.dtype).reshape(batch, seq, d), routing


def _weights(held=HELD):
    """One expert layer's leaves, seeded: what ``_moe_mlp`` reads."""
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    count = held[1] if held else EXPERTS
    return {
        "router": jax.random.normal(keys[0], (DIM, EXPERTS)) * DIM ** -0.5,
        "router_bias": jnp.zeros(EXPERTS),
        "w_gate": jax.random.normal(keys[1], (count, DIM, WIDTH)) * DIM ** -0.5,
        "w_up": jax.random.normal(keys[2], (count, DIM, WIDTH)) * DIM ** -0.5,
        "w_down": jax.random.normal(keys[3], (count, WIDTH, DIM)) * WIDTH ** -0.5,
    }


H = jax.random.normal(jax.random.PRNGKey(5), (BATCH, SEQ, DIM))
PROBE = jax.random.normal(jax.random.PRNGKey(6), (BATCH, SEQ, DIM))


def _held_pairs(layer, bias, h=H):
    scores = jax.nn.sigmoid(np.asarray(h, np.float64).reshape(-1, DIM) @ np.asarray(layer["router"], np.float64))
    chosen = np.argsort(-(scores + bias), axis=-1, kind="stable")[:, :TOP_K]
    return int(np.sum((chosen >= HELD[0]) & (chosen < HELD[0] + HELD[1])))


def routed(held_pairs: int) -> dict:
    """The seeded layer under a router bias that sends exactly
    ``held_pairs`` of the 160 pairs to the held block: one number on the
    held experts' bias, found by bisection (the count is monotone in it and
    moves a pair at a time)."""
    layer = _weights()
    bias = np.zeros(EXPERTS)
    low, high = -4.0, 4.0
    for _ in range(200):
        bias[HELD[0]:HELD[0] + HELD[1]] = middle = (low + high) / 2
        found = _held_pairs(layer, bias)
        if found == held_pairs:
            return dict(layer, router_bias=jnp.asarray(bias, jnp.float32))
        low, high = (middle, high) if found < held_pairs else (low, middle)
    raise AssertionError(f"no bias sends {held_pairs} pairs to the held block (last: {found})")


def _forced(past):
    """Either path whatever the count: the worst case's loop over the held
    experts (and the bounded path's weights zeroed), or the bounded path
    alone."""
    return mock.patch.object(T, "_past", lambda bound, sorting: jnp.bool_(past))


def value_and_grads(moe_mlp, layer, h=H):
    """``(out, routing)`` and the gradient of ``sum(out * PROBE)`` in
    ``h`` and every leaf of ``layer``."""
    def probed(h, layer):
        out, routing = moe_mlp(h, layer, MODEL)
        return jnp.sum(out * PROBE), (out, routing)

    (_, (out, routing)), grads = jax.jit(
        jax.value_and_grad(probed, argnums=(0, 1), has_aux=True)
    )(h, layer)
    return out, routing, grads


def agrees(got, want, overflow=None):
    out, routing, (dh, dlayer) = got
    want_out, want_routing, (want_dh, want_dlayer) = want
    close(out, want_out, 1e-5, "out")
    assert set(routing) == set(want_routing) | {"overflow"}
    for name, value in want_routing.items():
        if value.dtype.kind == "f":
            close(routing[name], value, 1e-6, name)
        else:
            assert np.array_equal(np.asarray(routing[name]), np.asarray(value)), name
    if overflow is not None:
        assert int(routing["overflow"]) == overflow
    close(dh, want_dh, 1e-5, "dh")
    assert set(dlayer) == set(want_dlayer)
    for name in want_dlayer:
        if name != "router_bias":                # behind stop_gradient: zeros
            close(dlayer[name], want_dlayer[name], 1e-5, name)


def test_the_bound_is_a_rule_of_the_shapes():
    """Eight times an even routing's held pairs: Ling's cell 32,768 of
    131,072 rows (16 of 512 held); this file's 120 of 160; an eighth of the
    experts held or more (LFM2's 16 of 32, every expert): the worst case
    itself, and ``_moe_mlp`` then has one path."""
    assert T.held_row_bound(16384, 8, 16, 512) == 32768
    assert T.held_row_bound(TOKENS, TOP_K, HELD[1], EXPERTS) == BOUND
    assert T.held_row_bound(16384, 4, 16, 32) == 16384 * 4
    assert T.held_row_bound(16384, 8, 64, 512) == 16384 * 8
    assert T.held_row_bound(16384, 8, 64, 64) == 16384 * 8
    for tokens, top_k, held, experts in ((16384, 8, 16, 512), (4096, 2, 3, 70), (40, 2, 3, 32)):
        bound = T.held_row_bound(tokens, top_k, held, experts)
        assert bound % (512 if bound > 512 else 8) == 0 and bound < tokens * top_k


@pytest.mark.parametrize("row", [16, 64, 1024])
@pytest.mark.parametrize("held_share", [0.0, 0.3, 1.0])
def test_the_order_without_a_sort_of_every_pair(row, held_share):
    """``_first_of_the_order`` against the stable sort of all the pairs it
    stands for: the held pairs' entries are the sort's, whatever the rows'
    length (1024 does not divide 192 pairs: one row), with no pair, some
    and every pair held."""
    held, pairs, bound = 5, 192, 128
    key = jax.random.PRNGKey(row)
    chosen = jax.random.randint(key, (pairs,), 0, held)
    absent = jax.random.uniform(jax.random.fold_in(key, 1), (pairs,)) >= held_share
    sort_by = jnp.where(absent, held, chosen).astype(jnp.int32)
    want = jax.lax.sort((sort_by, jnp.arange(pairs, dtype=jnp.int32)), num_keys=1, is_stable=True)[1]
    with mock.patch.object(T, "_SORTED_ROW", row):
        got = jax.jit(lambda s: T._first_of_the_order(s, held, bound))(sort_by)
    here = min(int(jnp.sum(~absent)), bound)
    assert got.shape == (bound,) and np.array_equal(np.asarray(got[:here]), np.asarray(want[:here]))
    assert np.all((np.asarray(got) >= 0) & (np.asarray(got) < pairs))


@pytest.mark.parametrize("held_pairs,via", [
    (held_pairs, via) for held_pairs in ROUTINGS for via in ("counted", "fits", "overflows")
    if via != "fits" or held_pairs <= BOUND          # the bounded path holds no more than its bound
])
def test_both_paths_are_the_parents_formula(held_pairs, via):
    """Output, routing and every gradient leaf, for routings with 0, some,
    exactly the bound's, one more and all of the pairs held: as the count
    chooses, and with either path forced where it holds the routing (the
    bounded one up to the bound, the worst case's always)."""
    layer = routed(held_pairs)
    want = value_and_grads(_parent_moe_mlp, layer)
    assert int(want[1]["held_pairs"]) == held_pairs
    if via == "counted":
        agrees(value_and_grads(T._moe_mlp, layer), want, overflow=ROUTINGS[held_pairs])
    else:
        with _forced(via == "overflows"):
            agrees(value_and_grads(T._moe_mlp, layer), want)


@pytest.mark.parametrize("held_pairs", ROUTINGS)
def test_both_paths_are_the_parents_formula_per_data_shard(held_pairs):
    """The same under ``_moe_over_mesh`` on a mesh of two data shards: each
    shard of 40 tokens takes its own branch by its own count against its
    own bound (56 of 80 rows), and ``overflow`` is the number of shards
    that took the worst case's."""
    layer = routed(held_pairs)
    want = value_and_grads(_parent_moe_mlp, layer)
    mesh = jax.make_mesh((2,), ("dp",), devices=jax.devices()[:2])
    h = jax.device_put(H, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("dp")))

    def over_mesh(h, layer, config):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return T._moe_over_mesh(h, layer, config)

    experts = np.asarray(want[1]["experts"]).reshape(2, -1)
    by_shard = np.sum((experts >= HELD[0]) & (experts < HELD[0] + HELD[1]), axis=1)
    bound = T.held_row_bound(SEQ, TOP_K, HELD[1], EXPERTS)
    assert bound == 56
    agrees(value_and_grads(over_mesh, layer, h), want, overflow=int(np.sum(by_shard > bound)))


def _equations(jaxpr, but=("pallas_call",)):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold,
    but a kernel's own (interpreted here, they are full of ``cond``s)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name not in but:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _equations(sub, but)


def _wide_arrays(jaxpr):
    """Shapes among ``jaxpr``'s operands and results, its ``cond``s' and
    loops' too, with a row for every (token, choice) pair and more than one
    column: ``[160, >1]`` or ``[80, 2, .]``."""
    shapes = [
        getattr(v.aval, "shape", ()) for eqn in _equations(jaxpr) for v in (*eqn.invars, *eqn.outvars)
    ]
    return [
        s for s in shapes
        if (len(s) > 1 and s[0] == PAIRS and s[1] > 1)
        # [80, 2, 48] is the worst case's gate and up of one expert, side by side
        or (len(s) > 2 and s[:2] == (TOKENS, TOP_K) and s[2] != WIDTH)
    ]


def test_no_array_under_held_has_a_row_for_every_pair():
    """The jaxpr of ``_moe_mlp``'s value-and-gradient under ``held``: no
    ``cond`` (the worst case's guard is its loop's trip count: two ``while``s
    of a dynamic bound, the forward's and the backward's, a held expert a
    trip, dense over the tokens), and nowhere, in the loops or outside, does
    an operand or result have ``tokens * top_k``
    (160) rows and more than one column, nor is one ``[tokens, top_k, d]``:
    every array of ``d`` or expert-width columns has the bound's 120 rows or
    the tokens' 80. With an eighth of the experts held the bound is the worst
    case's and the jaxpr is full of such rows, which is what the search would
    find."""
    layer = routed(50)
    probed = lambda h, layer: jnp.sum(T._moe_mlp(h, layer, MODEL)[0] * PROBE)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(probed, argnums=(0, 1)))(H, layer).jaxpr
    outside = [eqn.primitive.name for eqn in _equations(jaxpr, but=("pallas_call", "while"))]
    assert "cond" not in outside and outside.count("while") == 2
    loops = [eqn for eqn in _equations(jaxpr) if eqn.primitive.name == "while"]
    assert len(loops) == 2
    for loop in loops:       # a trip: one held expert's matmuls (gate and up as one) over every token
        inside = [e.primitive.name for e in _equations(loop.params["body_jaxpr"].jaxpr)]
        assert inside.count("dot_general") >= 2 and "gather" not in inside, inside
    # what is left with a row a pair: the router's [80, 2, 32] one-hot, the sort's [160] vectors
    assert {shape[-1] for shape in _wide_arrays(jaxpr)} <= {1, EXPERTS}
    rows = {
        v.aval.shape[0] for eqn in _equations(jaxpr) for v in eqn.outvars
        if len(getattr(v.aval, "shape", ())) == 2 and v.aval.shape[1] in (DIM, WIDTH)
    }
    assert {BOUND, TOKENS} <= rows and max(rows) == BOUND, rows         # the rest: weights
    found = _wide_arrays(_traced(T._moe_mlp, _every_pair_model(held=(4, 8)), _weights(held=(4, 8))).jaxpr)
    assert {(PAIRS, DIM), (PAIRS, WIDTH)} <= set(found)


def _traced(moe_mlp, model, layer):
    probed = lambda h, layer: jnp.sum(moe_mlp(h, layer, model)[0] * PROBE)
    return jax.make_jaxpr(jax.value_and_grad(probed, argnums=(0, 1)))(H, layer)


def _every_pair_model(held, top_k=TOP_K):
    """``MODEL`` with ``held`` (None: every expert here) and ``top_k``; an
    eighth of the 32 experts held, or more, is ``_by_every_pair``'s."""
    model = dataclasses.replace(MODEL, moe=dataclasses.replace(MODEL.moe, held=held, top_k=top_k))
    assert held is None or T.held_row_bound(TOKENS, top_k, held[1], EXPERTS) == TOKENS * top_k
    return model


def _held_to_the_oracle(model, layer):
    """Output, routing and every gradient leaf of ``_moe_mlp`` under
    ``model`` against the oracle's."""
    run = lambda moe_mlp: value_and_grads(lambda h, layer, _config: moe_mlp(h, layer, model), layer)
    got, want = run(T._moe_mlp), run(_parent_moe_mlp)
    assert ("overflow" in got[1]) == bool(model.moe.held)      # a counter only where experts are absent
    got[1].setdefault("overflow", 0)
    agrees(got, want, overflow=0)


def _on_the_every_pair_path(model, layer):
    """No ``cond`` and no loop; the value-and-gradient jaxpr has the worst
    case's row buffers and NO variable ``[tokens, top_k, d]``, of any dtype:
    a token's rows by choice are ``[top_k, tokens, d]``, summed over the
    leading axis. Returns the jaxpr's primitives by name."""
    jaxpr = _traced(T._moe_mlp, model, layer).jaxpr
    names = [eqn.primitive.name for eqn in _equations(jaxpr)]
    assert not [name for name in names if name in ("cond", "while")]
    shapes = {
        getattr(v.aval, "shape", ()) for eqn in _equations(jaxpr) for v in (*eqn.invars, *eqn.outvars)
    }
    top_k = model.moe.top_k
    assert {(TOKENS * top_k, DIM), (top_k, TOKENS, DIM)} <= shapes
    assert not [s for s in shapes if len(s) == 3 and s[:2] == (TOKENS, top_k) and s[2] in (DIM, WIDTH)]
    return names


def test_with_every_expert_held_the_block_is_the_oracles():
    """``held=None``: no ``cond`` and no loop, no ``overflow`` counter, no
    array ``[tokens, top_k, d]``; output, routing and every gradient leaf
    are the oracle's."""
    model, layer = _every_pair_model(held=None), _weights(held=None)
    _on_the_every_pair_path(model, layer)
    _held_to_the_oracle(model, layer)


def test_with_an_eighth_of_the_experts_held_the_block_is_the_oracles():
    """8 of 32 held: the bound is the worst case's rows, so there is no
    bounded path, no loop and no kept residual: ``_by_every_pair`` with its
    two selects, and the counter (``held_pairs > bound``, which is 0 here
    whatever the routing)."""
    model, layer = _every_pair_model(held=(4, 8)), _weights(held=(4, 8))
    assert "name" not in _on_the_every_pair_path(model, layer)
    _held_to_the_oracle(model, layer)


@pytest.mark.parametrize("held", [None, (4, 4), (8, 16)], ids=["all", "an-eighth", "half"])
@pytest.mark.parametrize("top_k", [2, 4, 6, 8])
def test_every_pair_is_the_oracles_formula(top_k, held):
    """``_by_every_pair`` over ``top_k`` 2 to 8 (the chain over a token's
    choices is ``top_k`` long; 4 and 6 are the two a ``[tokens, top_k, d]``
    layout paid most for), with every expert here, an eighth and half of them
    held: output, routing and every gradient leaf."""
    model, layer = _every_pair_model(held, top_k), _weights(held=held)
    _held_to_the_oracle(model, layer)


def _spoil(rows, held_pairs):
    """``NaN`` in the rows behind the last held group."""
    return jnp.where(jnp.arange(rows.shape[0])[:, None] >= held_pairs, jnp.nan, rows)


@jax.custom_vjp
def _spoiled_cotangent(rows, held_pairs):
    return rows


_spoiled_cotangent.defvjp(
    lambda rows, held_pairs: (rows, held_pairs),
    lambda held_pairs, g: (_spoil(g, held_pairs), None),
)


def _poisoned(real):
    """``_expert_mlps`` with ``NaN`` behind the last held group: in its
    output forward, and backward in the cotangent it hands back for its
    input rows. No tile writes those rows on the chip, so what they hold is
    whatever the buffer held."""
    def expert_mlps(gate_mul, rows, experts, group_sizes, stacks):
        held_pairs = jnp.sum(group_sizes)
        out = real(gate_mul, _spoiled_cotangent(rows, held_pairs), experts, group_sizes, stacks)
        return _spoil(out, held_pairs)
    return expert_mlps


@pytest.mark.parametrize("top_k", [2, 6])
def test_the_rows_no_tile_writes_may_hold_anything(top_k):
    """Half of the experts held, so about half of the pairs are absent and
    their rows lie behind the last held group: with ``NaN`` there in the
    experts' output and in the grouped matmuls' input cotangent, output and
    every gradient are finite and equal to the unpoisoned run's. Two selects,
    one in each sum over a token's rows, are enough."""
    model, layer = _every_pair_model((8, 16), top_k), _weights(held=(8, 16))
    run = lambda: value_and_grads(lambda h, layer, _config: T._moe_mlp(h, layer, model), layer)
    want = run()
    assert 0 < int(want[1]["held_pairs"]) < TOKENS * top_k
    with mock.patch.object(T, "_expert_mlps", _poisoned(T._expert_mlps)):
        got = run()
    assert np.all(np.isfinite(np.asarray(got[0])))
    agrees(got, want, overflow=0)
