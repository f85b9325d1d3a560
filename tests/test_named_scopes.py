"""The fused train step names its own work.

``models/transformer.py`` and ``build_sharded_train_step`` open
``jax.named_scope`` around the model's blocks and the optimizer
(``transformer.SCOPES``); jax writes the scope, with ``jvp`` /
``transpose(`` / ``rematted_computation`` around it, into every
instruction's ``op_name``. The benchmark reads device time per scope from
those names (``benchmarks/harness/scopes.py``), so a matmul nobody named,
a renamed scope, or a scope that changed the compiled program is caught
here, on the CPU, from ``compiled.as_text()``.
"""

import contextlib
import functools
import re
from unittest import mock

import jax
import jax.numpy as jnp
import optax
import pytest

from ray_tpu.models import transformer as T
from ray_tpu.parallel.mesh import MeshSpec
from ray_tpu.train import jax_utils

POLICIES = (None, "full")
BLOCKS = re.compile(r"(?:^|[/(])(" + "|".join(T.SCOPES) + r")(?:[/)]|$)")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OPERATION = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


MOE = re.compile(r"(?:^|[/(])(" + "|".join(T.MOE_SCOPES) + r")(?:[/)]|$)")
OLMOE_SHAPED = dict(
    n_kv_heads=4, qk_norm=True, rms_norm_eps=1e-5,
    moe=T.MoEConfig(num_experts=4, top_k=2, aux_loss_coef=0.01),
)


LATENT = re.compile(r"(?:^|[/(])(" + "|".join(T.LATENT_SCOPES) + r")(?:[/)]|$)")
MOONLIGHT_SHAPED = dict(
    n_layers=3, n_kv_heads=4, hidden_dim=160, rms_norm_eps=1e-5, first_dense_layers=1,
    latent=T.LatentAttentionConfig(
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16),
    moe=T.MoEConfig(
        num_experts=4, top_k=2, norm_topk_prob=True, aux_loss_coef=0.001, expert_dim=32,
        shared_experts=2, scoring="sigmoid", routed_scaling=2.446),
)


# A patterned model over expert layers (Ling-3.0-flash-VL's shape): a dense
# prefix with a linear mixer, then (linear, full) over group-routed experts of
# which a block is held; what it names beyond the vocabularies above.
NEW_SCOPES = ("decay_prepare", "attn_gate", "conv_mixer", "window_attention", "window_flash")
NEW = re.compile(r"(?:^|[/(])(" + "|".join(NEW_SCOPES) + r")(?:[/)]|$)")
LINEAR = re.compile(r"(?:^|[/(])(" + "|".join(T.LINEAR_SCOPES) + r")(?=[/)]|$)")
LING_SHAPED = dict(
    n_layers=3, hidden_dim=160, first_dense_layers=1, first_dense_kind="linear",
    layer_pattern=("linear", "full"),
    linear=T.LinearAttentionConfig(
        num_key_heads=4, num_value_heads=4, key_head_dim=16, value_head_dim=16,
        allow_neg_eigval=False, decay="channel", gate_lower_bound=-5.0, output_gate="sigmoid"),
    latent=T.LatentAttentionConfig(
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        output_gate="head"),
    moe=T.MoEConfig(
        num_experts=64, top_k=2, norm_topk_prob=True, expert_dim=32, shared_experts=1,
        scoring="sigmoid", routed_scaling=2.5, n_group=4, topk_group=2, held=(4, 4)),
)


# Under ``held`` the worst case's path is a loop inside ``dispatch`` (a held
# expert a trip, ``_held_experts``): its matmuls lie under ``dispatch`` first.
_WORST_CASE = re.compile(r"/mlp/dispatch/while/body/")


def _bounded_matmuls_scopes(matmuls):
    """The expert scopes of the matmuls outside the worst case's loop."""
    return {
        MOE.search(n).group(1) for n in matmuls if MOE.search(n) and not _WORST_CASE.search(n)
    }


ONE_DEVICE = (("dp", 1),)
MESH_2X2 = (("fsdp", 2), ("tp", 2))


# Gated short convolutions over held experts (LFM2-8B-A1B's shape): a dense
# prefix with a conv mixer, then (full, conv) with per-head q / k norms, a tied
# head; what it names is ``conv_mixer`` and, inside it, ``short_conv``.
LFM2_SHAPED = dict(
    n_layers=3, hidden_dim=160, first_dense_layers=1, first_dense_kind="conv",
    layer_pattern=("full", "conv"), qk_head_norm=True, tie_embeddings=True, rms_norm_eps=1e-5,
    moe=T.MoEConfig(
        num_experts=8, top_k=2, norm_topk_prob=True, renorm_eps=1e-6, expert_dim=32,
        scoring="sigmoid", held=(0, 4)),
)


# A window layer beside a global layer that carries no rotary embedding (one
# layer a kind, as the two shapes above: a second and third window layer would
# name nothing the first does not), a head size stated apart from the stream's
# width, ReLU experts routed from the layer's input, half of them held; what it
# names is ``window_attention`` and, inside it, ``window_flash``.
WINDOW_SHAPED = dict(
    dim=48, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16, hidden_dim=96,
    layer_pattern=("full", "window"), window=16, rope_kinds=("window",),
    moe=T.MoEConfig(
        num_experts=8, top_k=2, norm_topk_prob=True, expert_dim=32, held=(0, 4),
        activation="relu", router_input="layer_input"),
)


# A decoder in segments: a Mamba-1 / window pair, the bridge and a gated memory
# unit / cross pair under differential attention, LayerNorm and a tied head;
# what it names: ``mamba_mixer`` and inside it ``selective_scan`` (and
# ``short_conv``), ``gmu``, ``cross_attention`` and, wherever a pair's two
# outputs meet, ``diff_attention``.
SAMBAY_SHAPED = dict(
    dim=64, n_layers=8, n_heads=4, n_kv_heads=2, hidden_dim=96, rope_theta=None, window=16,
    differential=True, attention_bias=True, norm="layer", tie_embeddings=True,
    segments=T.sambay_segments(8),
    mamba=T.MambaConfig(inner_dim=128, state_dim=16, dt_rank=4, conv_kernel=4),
)
SAMBAY_SCOPES = ("mamba_mixer", "selective_scan", "gmu", "cross_attention", "diff_attention")
SAMBAY = re.compile(r"(?:^|[/(])(" + "|".join(SAMBAY_SCOPES) + r")(?=[/)]|$)")


@functools.lru_cache(maxsize=None)
def instructions(remat, scoped=True, moe=False, axes=ONE_DEVICE, keep_flash=True, latent=False,
                 ling=False, lfm2=False, window=False, sambay=False):
    """``[(operation, op_name)]`` of the tiny configuration's compiled fused
    step on one device; ``scoped=False`` compiles the same step with every
    ``jax.named_scope`` of the program turned into a no-op; ``moe`` the
    OLMoE-shaped tiny configuration (q/k norms, a dropless expert layer,
    the balancing loss); ``axes`` the mesh, over as many of the virtual CPU
    devices; ``keep_flash=False`` puts the layer checkpoint's policy back
    to ``nothing_saveable``, what it was before it kept the flash
    kernel's residuals; ``latent`` the Moonlight-shaped tiny configuration
    (latent attention, a dense first layer, sigmoid-routed experts with
    shared experts); ``ling`` the patterned one over held experts; ``lfm2``
    the one with conv mixers under a tied head; ``window`` the one with
    window layers; ``sambay`` the decoder in segments."""
    shaped = (
        SAMBAY_SHAPED if sambay else WINDOW_SHAPED if window else LFM2_SHAPED if lfm2 else LING_SHAPED if ling else MOONLIGHT_SHAPED if latent
        else OLMOE_SHAPED if moe else {}
    )
    config = T.TransformerConfig.tiny(remat=remat, **shaped)
    assert config.attention == "flash"
    optimizer = optax.adamw(1e-3)
    mesh = MeshSpec(dict(axes))
    with contextlib.ExitStack() as patches:
        if not scoped:
            patches.enter_context(mock.patch.object(
                jax, "named_scope", lambda _name: contextlib.nullcontext()
            ))
        if not keep_flash:
            patches.enter_context(mock.patch.object(
                T, "_remat_policy",
                lambda _remat: jax.checkpoint_policies.nothing_saveable,
            ))
        setup = jax_utils.setup_sharded_training(
            lambda: T.init_params(config, jax.random.PRNGKey(0)), optimizer,
            mesh=mesh.build(jax.devices()[:mesh.size]),
            logical_dims=T.param_logical_dims(config),
        )
        step = jax_utils.build_sharded_train_step(
            lambda p, b: T.loss_fn(p, b["x"], b["y"], config), optimizer, setup
        )
        ids = jnp.zeros((2, 64), jnp.int32)
        batch = setup.shard_batch({"x": ids, "y": ids})
        text = step.lower(setup.params, setup.opt_state, batch).compile().as_text()
    out = []
    for line in text.splitlines():
        match = _INSTRUCTION.match(line)
        operation = match and _OPERATION.search(match.group(2))
        if operation:
            name = _OP_NAME.search(line)
            out.append((operation.group(1), name.group(1) if name else ""))
    return out


@pytest.mark.parametrize("remat", POLICIES)
def test_every_matmul_is_under_a_block_scope(remat):
    matmuls = [n for op, n in instructions(remat) if op in ("dot", "convolution")]
    assert len(matmuls) >= 10   # q k v o gate up down head, forward and backward
    unnamed = [n for n in matmuls if not BLOCKS.search(n)]
    assert not unnamed, unnamed


@pytest.mark.parametrize("remat", POLICIES)
@pytest.mark.parametrize("marker", T.SCOPES + ("transpose(", "rematted_computation"))
def test_scope_or_phase_occurs(remat, marker):
    names = [n for _op, n in instructions(remat)]
    if marker in T.SCOPES:
        assert any(m.group(1) == marker for n in names for m in [BLOCKS.search(n)] if m)
    else:
        # rematted_computation without a layer policy: _silu_mul, _rmsnorm_ckpt
        assert any(marker in n for n in names)


@pytest.mark.parametrize("remat", POLICIES)
def test_backward_and_recompute_keep_the_block(remat):
    """The forms the benchmark's classifier tells apart: a backward matmul
    is ``transpose(jvp(...))`` and still carries its block; full remat runs
    the blocks' matmuls again under ``rematted_computation``, the ever-on
    checkpoints of ``_silu_mul`` / ``_rmsnorm_ckpt`` recompute no matmul."""
    matmuls = [n for op, n in instructions(remat) if op == "dot"]
    backward = [n for n in matmuls if "transpose(" in n and "rematted_computation" not in n]
    recompute = [n for n in matmuls if "rematted_computation" in n]
    for block in ("attention", "mlp", "head"):
        assert any(BLOCKS.search(n).group(1) == block for n in backward), block
    blocks = {BLOCKS.search(n).group(1) for n in recompute}
    assert blocks == ({"attention", "mlp"} if remat == "full" else set())


@pytest.mark.parametrize("axes", [ONE_DEVICE, MESH_2X2], ids=["one-device", "fsdp2-tp2"])
def test_head_loss_names_its_forward_and_its_backward(axes):
    """``head_loss`` is a ``custom_vjp``: its forward (the chunk loop: norm,
    logits, the softmax statistics and ``dlogits``) carries ``head`` /
    ``loss`` and no ``transpose(``; its backward, which jax traces apart
    from the forward, opens ``head`` again around its two matmuls. With the
    chunk rule held to 32 rows the loop is a ``while`` of four steps."""
    rows = 32 // dict(axes).get("fsdp", 1)
    columns = 256 // dict(axes).get("tp", 1)
    with mock.patch.object(T, "_LOGITS_CHUNK_BYTES", 4 * rows * columns):
        named = instructions.__wrapped__(None, axes=axes)

    def of(block):
        return [(op, n) for op, n in named if BLOCKS.search(n) and BLOCKS.search(n).group(1) == block]

    head_dots = [n for op, n in of("head") if op == "dot"]
    forward = [n for n in head_dots if "transpose(" not in n]
    backward = [n for n in head_dots if "transpose(" in n]
    assert len(forward) == 1 and "while/body" in forward[0], forward    # the logits, in the chunk loop
    assert len(backward) == 2, backward                                  # dx and lm_head's gradient
    assert not [n for n in head_dots if "rematted_computation" in n]
    loss = of("loss")
    assert {"exponential", "reduce"} <= {op for op, _n in loss}
    # the softmax is taken in the forward and nowhere else
    softmax = [n for op, n in named if op == "exponential" and BLOCKS.search(n) and BLOCKS.search(n).group(1) == "loss"]
    assert softmax and not [n for n in softmax if "transpose(" in n], softmax


def _recomputed_flash_forward(named):
    """Instructions of the forward kernel (interpreted here: its ops carry
    the jit's name) that run again in the backward."""
    return [n for _op, n in named if "rematted_computation" in n and "_flash_forward" in n]


@pytest.mark.parametrize("axes", [ONE_DEVICE, MESH_2X2], ids=["one-device", "fsdp2-tp2"])
@pytest.mark.parametrize("remat", ["full", "dots"])
def test_layer_remat_keeps_the_flash_residuals(remat, axes):
    """What a checkpointed layer recomputes in the backward and what it
    keeps: the flash kernel's ``out`` and ``lse`` are saved by name
    (``flash_attention.RESIDUAL_NAMES``), so no instruction of the forward
    kernel is under ``rematted_computation``, per shard under the mesh's
    ``shard_map`` as on one device. The rest of the attention
    block still is: under "full" its projections (``dot_general``), under
    "dots", which keeps matmul outputs, what is elementwise around them."""
    named = instructions(remat, axes=axes)
    assert [n for _op, n in named if "_flash_forward" in n]     # the forward pass runs it
    assert not _recomputed_flash_forward(named)
    attention = [
        (op, n) for op, n in named
        if "rematted_computation" in n and BLOCKS.search(n) and BLOCKS.search(n).group(1) == "attention"
    ]
    assert attention
    projections = [n for op, n in attention if op == "dot" and "dot_general" in n]
    assert bool(projections) == (remat == "full"), projections


@pytest.mark.parametrize("axes", [ONE_DEVICE, MESH_2X2], ids=["one-device", "fsdp2-tp2"])
@pytest.mark.parametrize("remat", ["full", "dots"])
def test_without_the_names_the_forward_kernel_runs_twice(remat, axes):
    """Guards the test above: with the policy put back to
    ``nothing_saveable`` the same search finds the recomputed kernel."""
    assert _recomputed_flash_forward(instructions(remat, axes=axes, keep_flash=False))


@pytest.mark.parametrize("remat", POLICIES)
def test_scopes_change_names_never_the_program(remat):
    scoped, plain = instructions(remat), instructions(remat, scoped=False)
    assert [op for op, _ in scoped] == [op for op, _ in plain]
    assert not any(BLOCKS.search(n) for _op, n in plain)


@pytest.mark.parametrize("remat", POLICIES)
def test_every_moe_matmul_is_under_mlp_and_its_own_scope(remat):
    """The expert matmuls (the grouped-matmul kernel is interpreted here:
    its dots carry the ``pallas_call``'s name) under ``mlp`` AND
    ``experts``, the router's under ``mlp`` AND ``router``, forward, backward
    (``transpose(``) and, under full remat, recompute; nothing of the MoE
    block outside ``mlp``."""
    named = instructions(remat, moe=True)
    matmuls = [n for op, n in named if op in ("dot", "convolution")]
    assert not [n for n in matmuls if not BLOCKS.search(n)]
    in_mlp = [n for n in matmuls if BLOCKS.search(n).group(1) == "mlp"]
    assert in_mlp and not [n for n in in_mlp if not MOE.search(n)], in_mlp
    by_scope = {scope: [n for n in in_mlp if MOE.search(n).group(1) == scope] for scope in T.MOE_SCOPES}
    assert not by_scope["dispatch"]           # gathers and sums, no matmul
    for scope in ("router", "experts"):
        forward = [n for n in by_scope[scope] if "transpose(" not in n]
        backward = [n for n in by_scope[scope] if "transpose(" in n and "rematted_computation" not in n]
        recompute = [n for n in by_scope[scope] if "rematted_computation" in n]
        assert forward and backward, scope
        assert bool(recompute) == (remat == "full"), (scope, recompute)
    # every op that carries a MoE scope lies inside the mlp block
    assert not [n for _op, n in named if MOE.search(n) and BLOCKS.search(n).group(1) != "mlp"]


@pytest.mark.parametrize("scope", T.MOE_SCOPES)
def test_moe_scope_occurs_forward_and_backward(scope):
    names = [n for _op, n in instructions(None, moe=True) if MOE.search(n) and MOE.search(n).group(1) == scope]
    assert [n for n in names if "transpose(" not in n], scope
    assert [n for n in names if "transpose(" in n], scope


def test_moe_scopes_change_names_never_the_program():
    scoped, plain = instructions(None, moe=True), instructions(None, scoped=False, moe=True)
    assert [op for op, _ in scoped] == [op for op, _ in plain]
    assert not any(MOE.search(n) for _op, n in plain)


@pytest.mark.parametrize("remat", POLICIES)
def test_latent_and_shared_matmuls_are_under_their_scopes(remat):
    """``latent`` lies inside ``attention`` and holds ``W_kv_a`` and
    ``W_kv_b`` (forward, and two gradients each) but not ``W_q`` / ``W_o``;
    ``shared`` lies inside ``mlp`` beside the experts' scopes and holds the
    shared experts' three matmuls; the dense first layer's MLP carries
    neither. Both scans (the dense prefix, the expert layers) name their
    work: no matmul without a block."""
    named = instructions(remat, latent=True)
    matmuls = [n for op, n in named if op in ("dot", "convolution")]
    assert not [n for n in matmuls if not BLOCKS.search(n)]
    for scope, block in (("latent", "attention"), ("shared", "mlp")):
        mine = [n for n in matmuls if LATENT.search(n) and LATENT.search(n).group(1) == scope]
        assert mine and {BLOCKS.search(n).group(1) for n in mine} == {block}, scope
        assert [n for n in mine if "transpose(" not in n] and [n for n in mine if "transpose(" in n]
        assert bool([n for n in mine if "rematted_computation" in n]) == (remat == "full")
    attention = [n for n in matmuls if BLOCKS.search(n).group(1) == "attention"]
    assert [n for n in attention if not LATENT.search(n)]        # W_q, W_o
    mlp = [n for n in matmuls if BLOCKS.search(n).group(1) == "mlp"]
    assert [n for n in mlp if not LATENT.search(n) and not MOE.search(n)]   # layer 0's dense MLP
    assert [n for n in mlp if MOE.search(n) and MOE.search(n).group(1) == "experts"]
    # no op carries both an expert scope and ``shared``
    assert not [n for _op, n in named if LATENT.search(n) and MOE.search(n)]


def test_latent_scopes_change_names_never_the_program():
    scoped, plain = instructions(None, latent=True), instructions(None, scoped=False, latent=True)
    assert [op for op, _ in scoped] == [op for op, _ in plain]
    assert not any(LATENT.search(n) for _op, n in plain)


@pytest.mark.parametrize("remat", POLICIES)
@pytest.mark.parametrize("scope,inside", [
    ("decay_prepare", ("attention", "linear_attention", "delta_rule")),
    ("attn_gate", ("attention",)),
])
def test_a_patterned_model_over_experts_names_its_new_work(remat, scope, inside):
    """``decay_prepare`` (the chunk preparation under a decay per channel:
    ``ops/gated_delta_rule.py``'s ``_channel_prepare_forward`` / ``_backward``)
    lies inside ``delta_rule`` inside ``linear_attention`` inside
    ``attention``, ``attn_gate`` (the latent layers' head-wise gate) inside
    ``attention`` and outside the linear mixer: forward and, through the
    custom VJP and the layer checkpoint, backward; every matmul of both
    scans and of the period's two kinds of layer has a block."""
    named = instructions(remat, ling=True)
    # the scan kernels run interpreted here, and the interpreter's own dots
    # carry no name at all (on a chip they are inside the Mosaic call)
    matmuls = [n for op, n in named if op in ("dot", "convolution") and n]
    assert not [n for n in matmuls if not BLOCKS.search(n)]
    mine = [n for _op, n in named if NEW.search(n) and NEW.search(n).group(1) == scope]
    assert [n for n in mine if "transpose(" not in n], scope
    assert [n for n in mine if "transpose(" in n], scope
    for n in mine:
        assert BLOCKS.search(n).group(1) == "attention", n
        assert set(LINEAR.findall(n)) >= set(inside[1:]), n
        if scope == "attn_gate":
            assert not LINEAR.search(n), n
    if scope == "decay_prepare":
        # the preparation is a kernel pair since PR 41 (interpreted here: the
        # interpreter's instructions carry the scope, on a chip the two Mosaic
        # calls do, which is what ``decay_prepare_ms`` reads): the forward
        # kernel forward and once more in the backward, its transpose there alone
        forward = [n for n in mine if "jit(_channel_prepare_forward)" in n]
        assert [n for n in forward if "transpose(" not in n]
        assert [n for n in forward if "transpose(" in n]
        backward = [n for n in mine if "jit(_channel_prepare_backward)" in n]
        assert backward and all("transpose(" in n for n in backward)
        # and nothing of the scan kernels is under it
        assert not [n for n in mine if "_delta_rule_" in n]
    # the experts' scopes and ``shared`` are there under the pattern too
    assert _bounded_matmuls_scopes(matmuls) == {"router", "experts"}
    assert [n for n in matmuls if LATENT.search(n) and LATENT.search(n).group(1) == "shared"]


@pytest.mark.parametrize("remat", POLICIES)
def test_a_conv_layer_names_its_mixer(remat):
    """``conv_mixer`` lies inside ``attention`` and holds the mixer's two
    matmuls (``W_in``, ``W_out``) forward and backward; the convolution
    inside it is under ``short_conv`` too; the attention layer of the same
    period and the experts carry neither; the tied head's matmuls are under
    ``head``."""
    named = instructions(remat, lfm2=True)
    matmuls = [n for op, n in named if op in ("dot", "convolution") and n]
    assert not [n for n in matmuls if not BLOCKS.search(n)]
    mine = [n for _op, n in named if NEW.search(n)]
    assert mine and {NEW.search(n).group(1) for n in mine} == {"conv_mixer"}
    assert [n for n in mine if "transpose(" not in n] and [n for n in mine if "transpose(" in n]
    assert all(BLOCKS.search(n).group(1) == "attention" for n in mine)
    assert len([n for n in matmuls if NEW.search(n)]) >= 2 * 3     # two layers x (fwd, dx, dw)
    inside = [n for n in mine if LINEAR.search(n)]
    assert inside and {LINEAR.search(n).group(1) for n in inside} == {"short_conv"}
    assert all(NEW.search(n) for _op, n in named if LINEAR.search(n))
    assert _bounded_matmuls_scopes(matmuls) == {"router", "experts"}
    assert [n for n in matmuls if BLOCKS.search(n).group(1) == "head"]
    scoped = instructions(None, lfm2=True)
    plain = instructions(None, scoped=False, lfm2=True)
    assert [op for op, _ in scoped] == [op for op, _ in plain]


@pytest.mark.parametrize("remat", POLICIES)
def test_a_window_layer_names_its_attention_and_its_kernels(remat):
    """``window_attention`` lies inside ``attention`` and holds a window
    layer's four projections forward and backward; the kernel calls inside it
    are under ``window_flash`` too, forward and backward, and nothing else is;
    the global layer of the same period carries neither; the router reads the
    layer's input under ``mlp`` / ``router`` all the same."""
    named = instructions(remat, window=True)
    matmuls = [n for op, n in named if op in ("dot", "convolution") and n]
    assert not [n for n in matmuls if not BLOCKS.search(n)]
    mine = [n for _op, n in named if NEW.search(n)]
    assert mine and {NEW.search(n).group(1) for n in mine} == {"window_attention"}
    assert all(BLOCKS.search(n).group(1) == "attention" for n in mine)
    assert [n for n in mine if "transpose(" not in n] and [n for n in mine if "transpose(" in n]
    kernels = [n for n in mine if "window_flash" in n]
    assert kernels and all("/window_attention/window_flash/" in n for n in kernels)
    assert [n for n in kernels if "_flash_forward" in n]
    assert [n for n in kernels if "_flash_backward" in n]
    # the global layer's kernels are the same jitted functions, outside the scope
    assert [n for _op, n in named if "_flash_forward" in n and not NEW.search(n)]
    projections = [n for n in matmuls if NEW.search(n) and "window_flash" not in n]
    assert len(projections) >= 4 * 2           # q k v o, forward and backward
    assert _bounded_matmuls_scopes(matmuls) == {"router", "experts"}
    scoped = instructions(None, window=True)
    plain = instructions(None, scoped=False, window=True)
    assert [op for op, _ in scoped] == [op for op, _ in plain]
    assert not any(NEW.search(n) for _op, n in plain)


@pytest.mark.parametrize("remat", POLICIES)
def test_the_worst_case_in_its_loop_names_its_work(remat):
    """Under ``held`` (4 of 64 here: row buffers of half the pairs) the rows
    behind the bound go through a loop of as many trips as the routing asks
    for (none where it fits), forward and again in the backward of
    ``_held_experts``: no conditional in ``mlp``, and every instruction of
    the loops lies under ``dispatch``, their matmuls (``experts`` inside it)
    and their own sums too: the benchmark's ``harness/moe_scopes.classify``
    reads the outermost scope, so a trace that shows a trip reads as
    dispatch time. The bounded path, outside the loops, keeps ``dispatch``
    and ``experts`` apart, forward and backward. Half of the experts held
    (the conv model's 4 of 8) is the worst case's one path: no loop."""
    named = instructions(remat, ling=True)
    assert not [n for op, n in named if op == "conditional" and n.endswith("/mlp/cond")]
    in_loop = [n for _op, n in named if _WORST_CASE.search(n)]
    matmuls = [n for op, n in named if op == "dot" and _WORST_CASE.search(n)]
    assert matmuls and all("experts" in n for n in matmuls)
    assert [n for n in in_loop if "transpose(" not in n]
    assert [n for n in in_loop if "transpose(" in n]
    assert {MOE.search(n).group(1) for n in in_loop} == {"dispatch"}
    outside = [n for _op, n in named if MOE.search(n) and not _WORST_CASE.search(n)]
    assert {MOE.search(n).group(1) for n in outside} == set(T.MOE_SCOPES)
    for scope in ("dispatch", "experts"):         # the bounded path: forward and backward
        mine = [n for n in outside if MOE.search(n).group(1) == scope]
        assert [n for n in mine if "transpose(" not in n], scope
        assert [n for n in mine if "transpose(" in n], scope
    kernels = [n for n in outside if "jit(gmm)" in n or "jit(tgmm)" in n]
    assert kernels and {MOE.search(n).group(1) for n in kernels} == {"experts"}
    assert not [n for _op, n in instructions(remat, lfm2=True) if _WORST_CASE.search(n)]


@pytest.mark.parametrize("remat", POLICIES)
def test_a_decoder_in_segments_names_its_mixers(remat):
    """``mamba_mixer``, ``gmu`` and ``cross_attention`` lie inside ``attention``
    and hold their mixers' matmuls forward and backward; ``selective_scan`` (and
    ``short_conv``) lie inside ``mamba_mixer``; ``diff_attention`` lies inside
    ``attention`` on window, full and cross layers (inside ``window_attention``
    and ``cross_attention`` there) and holds no matmul; every matmul has a block."""
    named = instructions(remat, sambay=True)
    matmuls = [n for op, n in named if op in ("dot", "convolution") and n]
    assert not [n for n in matmuls if not BLOCKS.search(n)]
    for scope in SAMBAY_SCOPES:
        mine = [n for _op, n in named if scope in SAMBAY.findall(n)]
        assert [n for n in mine if "transpose(" not in n], scope
        assert [n for n in mine if "transpose(" in n], scope
        assert {BLOCKS.search(n).group(1) for n in mine} == {"attention"}, scope
    scans = [n for _op, n in named if "selective_scan" in SAMBAY.findall(n)]
    assert all("mamba_mixer" in SAMBAY.findall(n) for n in scans)
    assert [n for n in scans if "_selective_scan_forward" in n] and [
        n for n in scans if "_selective_scan_backward" in n]
    convs = [n for _op, n in named if LINEAR.search(n)]
    assert convs and all("mamba_mixer" in SAMBAY.findall(n) for n in convs)
    diffs = [n for _op, n in named if "diff_attention" in SAMBAY.findall(n)]
    assert [n for n in diffs if NEW.search(n) and NEW.search(n).group(1) == "window_attention"]
    assert [n for n in diffs if "cross_attention" in SAMBAY.findall(n)]
    assert [n for n in diffs if not NEW.search(n) and "cross_attention" not in SAMBAY.findall(n)]
    assert not [n for n in matmuls if "diff_attention" in SAMBAY.findall(n)]
    for scope, least in (("mamba_mixer", 4 * 3), ("gmu", 2 * 3), ("cross_attention", 2 * 3)):
        assert len([n for n in matmuls if scope in SAMBAY.findall(n)]) >= least, scope
    assert [n for n in matmuls if BLOCKS.search(n).group(1) == "head"]


def test_the_segments_scopes_change_names_never_the_program():
    scoped = instructions(None, sambay=True)
    plain = instructions(None, scoped=False, sambay=True)
    assert [op for op, _ in scoped] == [op for op, _ in plain]
    assert not any(SAMBAY.search(n) for _op, n in plain)


def test_the_new_scopes_change_names_never_the_program():
    scoped, plain = instructions(None, ling=True), instructions(None, scoped=False, ling=True)
    assert [op for op, _ in scoped] == [op for op, _ in plain]
    assert not any(NEW.search(n) for _op, n in plain)


def test_vocabulary():
    # benchmarks/harness/scopes.py repeats it: a rename renames metrics.
    assert T.SCOPES == ("embed", "attention", "mlp", "head", "loss", "optimizer")
    # benchmarks/harness/moe_scopes.py repeats these; none is a block's name
    assert T.MOE_SCOPES == ("router", "dispatch", "experts")
    assert not set(T.MOE_SCOPES) & set(T.SCOPES)
    # benchmarks/harness/latent_scopes.py repeats these
    assert T.LATENT_SCOPES == ("latent", "shared")
    assert not set(T.LATENT_SCOPES) & (set(T.SCOPES) | set(T.MOE_SCOPES))
    # benchmarks/harness/linear_scopes.py repeats these; the two names PR 36
    # added, PR 39's ``conv_mixer`` and PR 45's ``window_attention`` and
    # ``window_flash`` (read by name: harness/named_scope.py) are in none of
    # the four
    assert NEW_SCOPES[-2:] == ("window_attention", "window_flash")
    assert T.LINEAR_SCOPES == ("linear_attention", "short_conv", "delta_rule", "gate_norm")
    assert not set(NEW_SCOPES) & (
        set(T.SCOPES) | set(T.MOE_SCOPES) | set(T.LATENT_SCOPES) | set(T.LINEAR_SCOPES)
    )
    # PR 65's five, read by name too (``mamba_mixer_ms``, ``selective_scan_ms``,
    # ``gmu_ms``, ``cross_attn_ms``, ``diff_attn_ms``)
    assert SAMBAY_SCOPES == ("mamba_mixer", "selective_scan", "gmu", "cross_attention", "diff_attention")
    assert not set(SAMBAY_SCOPES) & (
        set(T.SCOPES) | set(T.MOE_SCOPES) | set(T.LATENT_SCOPES) | set(T.LINEAR_SCOPES) | set(NEW_SCOPES)
    )
