"""The decoder in SEGMENTS whose later layers read what one earlier layer made
(``models/transformer.py`` with ``segments=``: Mamba-1 / window pairs, the
bridge, gated memory units / cross attention; ``differential`` attention;
``norm="layer"``; ``attention_bias``; a tied head: SambaY as
Phi-4-mini-flash-reasoning configures it) and the selective scan
(``ops/selective_scan.py``) against the benchmark's plain reference
(``benchmarks/reference/sambay_decoder.py``: float32 ``jax.numpy``, the
recurrence a token at a time, each softmax under an explicit mask, the
subtraction written out, K / V and the memory simply reused; it imports nothing
from ``ray_tpu.models``), through the family that names the program's leaves
for it. On the CPU at tiny widths with seeded weights: 8 layers by the model's
own rule (``m w m w | m f | g c``), 4 / 2 heads of 16 on a stream of 64, 128
channels of 16 states, a window of 8 keys over 40 positions. ONE compiled
program a case for what the cases share; the kernels run in the interpreter.

Tolerances, each of the largest value compared: logits 5e-4, loss 1e-5,
gradients 2e-3 (``tests/test_gated_window_moe.py``'s and for its reasons: both
sides float32, sums in another order). A wrong term is off by far more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import sambay_decoder as family_module
from benchmarks.reference import sambay_decoder as reference
from ray_tpu.models import transformer as T
from ray_tpu.ops.selective_scan import kept_bytes, selective_scan, selective_scan_reference

from model_helpers import close, ids, listed, trains_through_jax_trainer

TINY = {
    "name": "tiny-sambay", "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 96, "layer_norm_eps": 1e-5, "mb_per_layer": 2, "model_type": "phi4flash",
    "num_attention_heads": 4, "num_hidden_layers": 8, "num_key_value_heads": 2, "resid_pdrop": 0,
    "sliding_window": 8, "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
    "vocab_size": 256, "torch_dtype": "float32", "attention_bias": True,
    "differential_attention": True, "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 4, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "published_layer_index": [0, 1, 2, 3, 16, 17, 18, 19],
}


def built(remat=None, attention="flash", **changes):
    """The family at the tiny sizes, in the cell's layout (every pair of layers
    a segment of one period)."""
    family = family_module.build(dict(TINY, **changes), {"seq_len": 40, "remat": remat})
    assert family.model.segments == T.sambay_segments(TINY["num_hidden_layers"])
    family.model = dataclasses.replace(family.model, attention=attention)
    return family


FAMILY = built()
MODEL = FAMILY.model
REFERENCE_MODEL = dataclasses.replace(MODEL, attention="reference")


def seeded(model=MODEL, seed=3):
    """Weights from the program's initialiser, every norm weight moved off 1
    and every bias off 0 (a dropped bias or norm then differs by more than a
    scale), ``D`` off 1."""
    params = jax.jit(lambda key: T.init_params(model, key))(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 256))
    moved = ("norm", "bias", "bq", "bk", "bv", "bo", "d_skip")
    shake = lambda name, leaf: (
        leaf + 0.2 * jax.random.normal(next(keys), leaf.shape, leaf.dtype)
        if any(part in name for part in moved) and name != "dt_bias" else leaf
    )
    params["layers"] = [
        [{name: shake(name, leaf) for name, leaf in place.items()} for place in segment]
        for segment in params["layers"]
    ]
    for name in ("final_norm", "final_norm_bias"):
        params[name] = shake(name, params[name])
    return params


PARAMS = seeded()
TOKENS = ids()


def the_rule(n):
    s = n // 2
    return [
        ("mamba" if i <= s else "gmu") if i % 2 == 0 else
        ("window" if i < s else "full" if i == s + 1 else "cross")
        for i in range(n)
    ]


@pytest.mark.parametrize("n", [8, 12, 32])
def test_the_layer_kinds_follow_the_rule(n):
    kinds = [kind for pattern, periods in T.sambay_segments(n) for kind in pattern * periods]
    cfg = dict(TINY, num_hidden_layers=n)
    assert kinds == reference.layer_kinds(cfg) == the_rule(n)
    assert kinds.count("full") == 1 and kinds[n // 2:n // 2 + 2] == ["mamba", "full"]
    assert kinds.count("mamba") == n // 4 + 1 and kinds.count("gmu") == kinds.count("cross") == n // 4 - 1


def test_the_tree_is_a_segment_a_pair_by_place():
    assert MODEL.segments == (
        (("mamba", "window"), 1), (("mamba", "window"), 1), (("mamba", "full"), 1), (("gmu", "cross"), 1),
    )
    layers = PARAMS["layers"]
    assert [len(segment) for segment in layers] == [2, 2, 2, 2]
    assert layers[0][0]["a_log"].shape == (1, 128, 16) and layers[2][0]["w_in"].shape == (1, 64, 256)
    assert layers[3][0]["w_in"].shape == (1, 64, 128)                    # a gated memory unit's W_1
    assert "wk" in layers[2][1] and "wk" not in layers[3][1] and "wv" not in layers[3][1]
    assert "lm_head" not in PARAMS and "final_norm_bias" in PARAMS
    assert T.num_params(PARAMS) == T.config_num_params(MODEL)
    dims = T.param_logical_dims(MODEL)
    assert jax.tree.structure(dims, is_leaf=lambda d: isinstance(d, tuple)) == jax.tree.structure(PARAMS)
    assert [kind for kind, _ in T.layer_order(PARAMS, MODEL)] == the_rule(8)


@pytest.mark.parametrize("attention", ["flash", "reference"])
def test_logits_loss_and_every_gradient_match_the_reference(attention):
    model = dataclasses.replace(MODEL, attention=attention, remat="full")
    family = built(remat="full", attention=attention)
    x, y = TOKENS[:, :-1], TOKENS[:, 1:]
    logits = jax.jit(lambda p, t: T.forward(p, t, model))(PARAMS, x)
    want = reference.logits(family.reference_weights(PARAMS), x, TINY)
    close(logits, want, 5e-4, "logits")
    loss, grads = jax.jit(jax.value_and_grad(lambda p: T.loss_fn(p, x, y, model)))(PARAMS)
    # the reference's gradient with respect to the PROGRAM's leaves, through the family's names
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: reference.loss(listed(family.reference_weights(p)), x, y, TINY)
    )(PARAMS)
    close(loss, ref_loss, 1e-5, "loss")
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), wanted in zip(flat, jax.tree.leaves(ref_grads)):
        name = jax.tree_util.keystr(path)
        # a key bias moves every score of a row alike: its gradient is a rounding of zero
        floor = 1e-6 if name.endswith("['bk']") else 0.0
        close(got, wanted, 2e-3, name, floor=floor)


def test_segments_of_several_periods_are_the_same_model():
    """``segments=`` with a pattern's pairs stacked under ONE period scan (a leaf
    of ``[periods, ...]`` a place) computes what the cell's layout computes
    from the same layers (a leaf a layer, no loop)."""
    # (both through XLA's plain forms: the layout is what differs, not the kernels)
    stacked = dataclasses.replace(
        REFERENCE_MODEL,
        segments=((("mamba", "window"), 2), (("mamba", "full"), 1), (("gmu", "cross"), 1)),
    )
    own = [leaves for _, leaves in T.layer_order(PARAMS, MODEL)]
    periods = lambda *layers: jax.tree.map(lambda *leaves: jnp.stack(leaves), *layers)
    params = {**PARAMS, "layers": [
        [periods(own[0], own[2]), periods(own[1], own[3])],
        [periods(own[4]), periods(own[5])], [periods(own[6]), periods(own[7])],
    ]}
    assert jax.tree.structure(params) == jax.tree.structure(T.init_params(stacked, jax.random.PRNGKey(0)))
    x, y = TOKENS[:, :-1], TOKENS[:, 1:]
    loss, grads = jax.jit(jax.value_and_grad(lambda p: T.loss_fn(p, x, y, stacked)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(lambda p: T.loss_fn(p, x, y, REFERENCE_MODEL)))(PARAMS)
    close(loss, want, 1e-6, "loss")
    regrouped = [leaves for _, leaves in T.layer_order(want_grads, MODEL)]
    for (_, got), wanted in zip(T.layer_order(grads, stacked), regrouped):
        for name in got:
            close(got[name], wanted[name], 1e-4, name, floor=1e-7)


def test_the_bridge_gets_the_sum_of_its_readers_gradients():
    """``W_k`` / ``W_v`` of layer ``s + 1`` and what lies behind ``M`` are read by
    the bridge's own layers AND by the segment behind it: their gradient is
    the reference's, and it is NOT what the bridge alone would give."""
    x, y = TOKENS[:, :-1], TOKENS[:, 1:]
    grads = jax.jit(jax.grad(lambda p: T.loss_fn(p, x, y, REFERENCE_MODEL)))(PARAMS)
    ref = jax.grad(lambda p: reference.loss(listed(FAMILY.reference_weights(p)), x, y, TINY))(PARAMS)
    bridge, ref_bridge = grads["layers"][2], ref["layers"][2]
    for place, names in ((1, ("wk", "wv", "bv")), (0, ("w_in", "w_x", "w_dt", "a_log", "conv"))):
        for name in names:
            close(bridge[place][name], ref_bridge[place][name], 2e-3, name)
    # without the readers: the same six layers with no segment behind the bridge
    silent = dataclasses.replace(
        REFERENCE_MODEL, segments=MODEL.segments[:3], n_layers=6, depth_index=MODEL.depth_index[:6])
    alone = jax.jit(jax.grad(lambda p: T.loss_fn(p, x, y, silent)))({**PARAMS, "layers": PARAMS["layers"][:3]})
    assert float(jnp.max(jnp.abs(alone["layers"][2][1]["wk"] - bridge[1]["wk"]))) > 1e-3 * float(
        jnp.max(jnp.abs(bridge[1]["wk"])))


def scan_operands(key, batch, seq, channels, states, dtype=jnp.float32):
    ks = jax.random.split(key, 7)
    u = jax.random.normal(ks[0], (batch, seq, channels), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, seq, channels)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (channels, states), minval=-1.0, maxval=2.0))
    b = jax.random.normal(ks[3], (batch, seq, states)).astype(dtype)
    c = jax.random.normal(ks[4], (batch, seq, states)).astype(dtype)
    d = jax.random.normal(ks[5], (channels,))
    return (u, dt, a, b, c, d), jax.random.normal(ks[6], (batch, seq, channels))


def test_the_scan_kernels_match_the_recurrence_forward_and_all_six_gradients():
    """At a length that is no multiple of the chunk (200 of 128: two chunks, the
    second padded) and two batch rows, two lane tiles of channels."""
    operands, g = scan_operands(jax.random.PRNGKey(0), 2, 200, 256, 16)
    got = selective_scan(*operands, interpret=True)
    want = selective_scan_reference(*operands)
    close(got, want, 1e-5, "y")
    grads = lambda scan: jax.grad(lambda *o: jnp.sum(scan(*o) * g), argnums=tuple(range(6)))(*operands)
    for name, a, b in zip(("u", "dt", "A", "B", "C", "D"), grads(
        lambda *o: selective_scan(*o, interpret=True)), grads(selective_scan_reference)
    ):
        close(a, b, 1e-5, name)
    # what the forward keeps: the output and a state at each chunk's start, float32
    assert kept_bytes(2, 200, 256, 16, 4) == 2 * 256 * (200 * 4 + 2 * 16 * 4)


def test_the_scans_state_is_float32_whatever_the_operands():
    """bfloat16 operands: the kernel's state, decays and sums stay float32 (its
    distance from the float32 recurrence on the same values is the output's own
    rounding), and a bfloat16 STATE fails the check's tolerance."""
    operands, _ = scan_operands(jax.random.PRNGKey(1), 1, 256, 128, 16, jnp.bfloat16)
    f32 = tuple(t.astype(jnp.float32) for t in operands)
    want = reference.recurrence(*f32)
    got = selective_scan(*operands, interpret=True)
    assert got.dtype == jnp.bfloat16
    assert reference.compare(got, want, reference.TOLERANCE_SCAN["timed"])["ok"]
    # ... and on the same values in float32 the kernel is the recurrence to
    # rounding, where a state carried in bfloat16 is a hundred times off
    assert reference.compare(selective_scan(*f32, interpret=True), want, reference.TOLERANCE_SCAN["own"])["ok"]
    rounded = reference.recurrence(*f32, state_dtype=jnp.bfloat16)
    found = reference.compare(rounded, want, reference.TOLERANCE_SCAN["own"])
    assert not found["ok"] and found["rel_rms"] > 50 * reference.TOLERANCE_SCAN["own"]


def test_the_checks_scan_part_holds_a_float32_state_and_the_skip():
    weights = lambda: FAMILY.reference_weights(PARAMS)
    x = TOKENS[:1, :-1]
    assert reference.check_scan(FAMILY.scan, weights(), x, TINY)["ok"]
    bf16_state = lambda *o: reference.recurrence(
        *(t.astype(jnp.float32) for t in o), state_dtype=jnp.bfloat16)
    found = reference.check_scan(bf16_state, weights(), x, TINY)
    assert not found["ok"] and not found["own"]["ok"] and not found["opened"]["ok"]
    # a dropped skip term moves the logits far past their tolerance
    program = jax.jit(lambda p, t: T.forward(p, t, MODEL))(PARAMS, x)
    assert reference.check(program, weights, x, TINY)["ok"]
    for control in reference.CONTROLS:
        assert not reference.check(program, weights, x, dict(TINY, control=control))["ok"], control


@pytest.mark.parametrize("kind", ["window", "full", "cross"])
def test_differential_attention_is_the_written_out_form(kind):
    at = the_rule(8).index(kind)
    found, leaves = FAMILY.layer(PARAMS, at)
    assert found == kind
    layer = list(FAMILY.reference_weights(PARAMS)["layers"])[at]
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 40, 64), jnp.float32)
    lam0 = reference.lam_init(TINY, at)
    assert lam0 == pytest.approx(MODEL.lam_init(at)) == pytest.approx(
        0.8 - 0.6 * np.exp(-0.3 * TINY["published_layer_index"][at]))
    heads = dict(heads=4, lam0=lam0)
    if kind == "cross":
        shared = tuple(jax.random.normal(jax.random.PRNGKey(6 + i), shape) for i, shape in enumerate(
            ((2, 40, 1, 16), (2, 40, 1, 16), (2, 40, 1, 32))))
        want = reference.cross_attention(h, {n: layer[n] for n in reference.CROSS_NAMES}, shared, **heads)
        handed = dict(zip(("k1", "k2", "v"), (jnp.swapaxes(t, 1, 2) for t in shared)))
        mixer = lambda leaves, h: T._cross_mixer(
            h, {**leaves, "lam_init": jnp.float32(lam0), "shared": handed}, MODEL, None, None,
            T._attention_impl(MODEL))
        got = jax.jit(mixer)(leaves, h)
    else:
        window = TINY["sliding_window"] if kind == "window" else None
        want, _ = reference.self_attention(
            h, {n: layer[n] for n in reference.ATTENTION_NAMES}, kv_heads=2, window=window, **heads)
        got = FAMILY.attend(PARAMS, kind, at, h, lam0)
        # a window off by one, or no lam term, is another output
        for control in ("window_off_by_one", "no_lambda"):
            wrong, _ = reference.self_attention(
                h, {n: layer[n] for n in reference.ATTENTION_NAMES}, kv_heads=2, window=window,
                control=control, **heads)
            moved = float(jnp.max(jnp.abs(wrong - want))) / float(jnp.max(jnp.abs(want)))
            assert moved > 1e-2 or (control == "window_off_by_one" and kind == "full"), control
    close(got, want, 5e-4, kind)


def test_it_trains_through_jax_trainer(ray_start_shared, tmp_path):
    """The normal path: JaxTrainer -> setup_sharded_training ->
    build_sharded_train_step -> loss_fn over a dp 2 x fsdp 2 mesh: the scans,
    the convolutions and the flash calls per data shard, full remat."""
    trains_through_jax_trainer(dataclasses.replace(MODEL, remat="full"), "sambay", tmp_path, seq=41)


# -- what the shape cannot do stays an honest refusal ---------------------------
def _under_mesh(axes, model):
    from ray_tpu.parallel.mesh import MeshSpec

    mesh = MeshSpec(axes).build(jax.devices()[: int(np.prod(list(axes.values())))])
    params = jax.eval_shape(lambda key: T.init_params(model, key), jax.random.PRNGKey(0))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        return jax.eval_shape(lambda p, t: T.forward(p, t, model), params, TOKENS)


REFUSALS = {
    "decode_segments": (lambda: T.init_kv_cache(MODEL, 1, 8), NotImplementedError, "decode over segments"),
    "decode_step_segments": (
        lambda: T.decode_step({}, {}, jnp.zeros((1, 1), jnp.int32), MODEL), NotImplementedError,
        "selective scan's state"),
    "pipeline_over_segments": (
        lambda: T.partition_stages({}, MODEL, 2), NotImplementedError, "shared operands"),
    "stage_forward_over_segments": (
        lambda: T.stage_forward({}, TOKENS, MODEL, first=True, last=True), NotImplementedError,
        "shared operands"),
    "callable_attention": (
        lambda: dataclasses.replace(MODEL, attention=lambda q, k, v, causal: q),
        NotImplementedError, "callable attention"),
    "differential_outside_segments": (
        lambda: T.TransformerConfig.tiny(differential=True, rope_theta=None), NotImplementedError,
        "outside segments"),
    "differential_under_rope": (
        lambda: dataclasses.replace(MODEL, rope_theta=10000.0), NotImplementedError, "rotary embedding"),
    "differential_beside_a_gate": (
        lambda: dataclasses.replace(MODEL, output_gate="element"), NotImplementedError, "output gate"),
    "segments_beside_a_pattern": (
        lambda: dataclasses.replace(MODEL, layer_pattern=("full",)), NotImplementedError,
        "segments beside a layer_pattern"),
    "segments_beside_experts": (
        lambda: dataclasses.replace(MODEL, moe=T.MoEConfig()), NotImplementedError, "moe="),
    "segments_of_another_depth": (
        lambda: dataclasses.replace(MODEL, n_layers=10), ValueError, "are not n_layers=10"),
    "a_reader_without_its_bridge": (
        lambda: dataclasses.replace(MODEL, segments=((("mamba", "window"), 3), (("gmu", "cross"), 1))),
        ValueError, "the bridge"),
    "mamba_layers_without_mamba": (
        lambda: dataclasses.replace(MODEL, mamba=None), ValueError, "need mamba="),
    "the_rule_at_ten_layers": (lambda: T.sambay_segments(10), ValueError, "multiple of 4"),
    "a_mamba_mixer_of_no_channel": (lambda: T.MambaConfig(inner_dim=0), ValueError, "sizes >= 1"),
    "layer_norm_on_a_branch_output": (
        lambda: T.TransformerConfig.tiny(norm="layer", norm_placement="both"), NotImplementedError,
        'norm="layer" on a branch'),
    "an_unknown_norm": (lambda: T.TransformerConfig.tiny(norm="batch"), ValueError, "norm 'batch'"),
    "a_mamba_layer_over_tp": (
        lambda: _under_mesh({"tp": 2}, MODEL), NotImplementedError, "a mamba layer over a mesh with tp"),
    "differential_over_tp": (
        lambda: _under_mesh({"tp": 2}, dataclasses.replace(
            MODEL, segments=((("window", "full"), 4),), mamba=None)),
        NotImplementedError, "differential attention over a mesh with tp"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_is_not_written_refuses_by_name(what):
    call, error, match = REFUSALS[what]
    with pytest.raises(error, match=match):
        call()
