"""The expert layers' kernels and what stands round them: the grouped matmul
at the cells' sizes, a MoE and a latent-attention MoE step over a mesh of four,
the kernels reading the layer stack in place, and the dispatch by pairs.

Compiled for a TPU v5e that is described, not attached: nothing executes,
so these say what the chip's compiler accepts and nothing about results or
times. One of the ``test_chip_compile_*`` files, a kernel family each:
``tests/test_chip_compile_flash.py`` says why and how.
"""

import contextlib
import functools
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from model_helpers import custom_calls, loss_and_grads_text, one_chip_step


def _grouped_loss(lhs, rhs, group_sizes, tile=None):
    import ray_tpu.ops.grouped_matmul as gm

    forced = mock.patch.object(gm, "_tiling", lambda *shape, **kind: tile)
    with forced if tile else contextlib.nullcontext():
        out = gm.grouped_matmul(lhs, rhs, group_sizes, interpret=False)
    return jnp.sum(out.astype(jnp.float32))


@pytest.mark.parametrize("rows,k,n,groups", [
    (65536, 2048, 1024, 64), (65536, 1024, 2048, 64),     # OLMoE
    (49152, 2048, 1408, 64), (49152, 1408, 2048, 64),     # Moonlight: 1408 = 11 x 128
    (65536, 2048, 1792, 16), (65536, 1792, 2048, 16),     # LFM2: 1792 = 2 x 896
    (98304, 2560, 768, 32), (98304, 768, 2560, 32),       # SmallThinker: 2560 = 2 x 1280
    (131072, 2048, 768, 16), (131072, 768, 2048, 16),     # Keye, sdar
    (131072, 2048, 1024, 16), (131072, 1024, 2048, 16),   # Trinity
    (45056, 1024, 2688, 16), (45056, 2688, 1024, 16),     # Nemotron: 2688 = 3 x 896
    (6656, 4096, 1280, 8), (6656, 1280, 4096, 8),         # Solar
    (32768, 2560, 768, 16), (32768, 768, 2560, 16),       # Ling: the bounded buffer
])
def test_grouped_matmul_compiles_for_v5e(one_chip, rows, k, n, groups):
    """The expert matmuls at the benchmark cells' sizes, gate / up and down:
    the forward call and both gradients (the input's is ``gmm`` on the
    transposed experts, the weights' is ``tgmm``), each at the tile
    ``grouped_matmul`` counts out for it. What fits a v5e's VMEM is the
    compiler's to say: ``_fits`` is a fit to its verdicts, and this holds
    every tile the rule picks at a cell's shape to the compiler's own word."""
    import ray_tpu.ops.grouped_matmul as gm

    shapes = (
        jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((groups, k, n), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=one_chip),
    )
    calls = ((rows, k, n, groups), (rows, n, k, groups), (rows, k, n, groups, True))
    assert all(gm._fits(gm._tiling(*call), weight_grad=len(call) == 5) for call in calls)
    both = jax.value_and_grad(_grouped_loss, argnums=(0, 1))
    text = jax.jit(both).lower(*shapes).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    assert len(re.findall(r"%\S*tgmm\S* = \S+ custom-call", text)) == 1


def test_grouped_matmul_in_float32_compiles_for_v5e(one_chip):
    """``_fits`` was fitted on bfloat16 and scales its sum by the element's
    width, which over-counts the float32 accumulator: the float32 tiles it
    admits at OLMoE's widths are ones the compiler takes, in all three calls."""
    import ray_tpu.ops.grouped_matmul as gm

    rows, k, n, groups = 16384, 2048, 1024, 8
    shapes = (
        jax.ShapeDtypeStruct((rows, k), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((groups, k, n), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=one_chip),
    )
    assert gm._tiling(rows, k, n, groups, itemsize=4) != gm._tiling(rows, k, n, groups)
    both = jax.value_and_grad(_grouped_loss, argnums=(0, 1))
    assert jax.jit(both).lower(*shapes).compile().as_text().count("tpu_custom_call") == 3


def test_grouped_matmul_tile_too_large_for_vmem_is_refused(one_chip):
    """Why the row tile stops at 512: at Keye's forward call the rule's
    (512, 2048, 768) with 1024 rows needs more VMEM than a kernel may use on
    a v5e, as ``_fits`` says of it (the chip refused 1024 x 2048 x 1024 too:
    my chip run, PR 26)."""
    import ray_tpu.ops.grouped_matmul as gm

    shapes = (
        jax.ShapeDtypeStruct((131072, 2048), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((16, 2048, 768), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip),
    )
    assert gm._tiling(131072, 2048, 768, 16) == (512, 2048, 768)
    assert not gm._fits((1024, 2048, 768))
    big = functools.partial(_grouped_loss, tile=(1024, 2048, 768))
    with pytest.raises(Exception, match="(?i)vmem|memory"):
        jax.jit(big).lower(*shapes).compile()


@pytest.mark.parametrize("axes", [
    {"dp": 4}, {"dp": 2, "ep": 2}, {"fsdp": 2, "tp": 2},
], ids=lambda axes: "-".join(f"{k}{v}" for k, v in axes.items()))
def test_moe_step_compiles_for_a_v5e_mesh(topo, axes):
    """A MoE model's loss and gradients across four chips: GSPMD refuses to
    partition the Mosaic grouped matmuls ("wrap the call in a shard_map"),
    which the CPU, interpreting them as plain HLO, never shows. Traced
    under the mesh as ``build_sharded_train_step`` traces it, the block
    runs per data shard (``transformer._moe_over_mesh``), data parallel
    alone, with the experts sharded over ep, and under fsdp x tp."""
    from ray_tpu.models import transformer as T

    config = T.TransformerConfig(
        vocab_size=512, dim=256, n_layers=2, n_heads=2, n_kv_heads=2,
        hidden_dim=128, max_seq=512, qk_norm=True, attention="flash",
        moe=T.MoEConfig(num_experts=4, top_k=2, aux_loss_coef=0.01),
    )
    text = loss_and_grads_text(topo, config, axes, batch=4, seq=512)
    # three flash kernels; gate / up / down forward, input and weight gradients
    assert text.count("tpu_custom_call") == 12
    assert len(re.findall(r"%\S*tgmm\S* = \S+ custom-call", text)) == 3


@pytest.mark.parametrize("axes", [
    {"dp": 2, "ep": 2}, {"fsdp": 2, "tp": 2},
], ids=lambda axes: "-".join(f"{k}{v}" for k, v in axes.items()))
def test_latent_attention_moe_step_compiles_for_a_v5e_mesh(topo, axes):
    """DeepSeek-V3's block across four chips: the two-dim flash kernels per
    (batch, head) shard under ``shard_map`` (tp shards ``W_q``, ``W_kv_b``
    and ``W_o`` by whole heads, the latent and the shared rope key stay
    whole), a dense first layer in a scan of its own, then the expert layer
    per data shard with its shared experts outside the per-shard call, where
    GSPMD shards them as a dense MLP."""
    from ray_tpu.models import transformer as T

    config = T.TransformerConfig(
        vocab_size=512, dim=256, n_layers=2, n_heads=2, n_kv_heads=2,
        hidden_dim=384, max_seq=512, rms_norm_eps=1e-5, attention="flash",
        latent=T.LatentAttentionConfig(
            kv_lora_rank=128, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
        first_dense_layers=1,
        moe=T.MoEConfig(
            num_experts=4, top_k=2, norm_topk_prob=True, aux_loss_coef=0.001, expert_dim=128,
            shared_experts=2, scoring="sigmoid", routed_scaling=2.446),
    )
    text = loss_and_grads_text(topo, config, axes, batch=4, seq=512)
    # three flash kernels in each of the two scans; gate / up / down forward,
    # input and weight gradients in the expert layer's
    assert text.count("tpu_custom_call") == 15
    assert len(re.findall(r"%\S*tgmm\S* = \S+ custom-call", text)) == 3


def test_expert_kernels_read_the_layer_stack_in_place(topo):
    """``olmoe-seq4k-ingest``'s step (hidden 2048, 16 heads with q/k norm,
    64 experts of width 1024, 8 a token, vocabulary 50304, depth cut to 2;
    2 x 4096 tokens, no remat): the six ``gmm`` calls, forward and input
    gradient of gate / up / down, take the layer STACK seen as
    ``[layers x experts, k, n]`` (a bitcast of the loop's invariant), so no
    instruction of either scan's body, slice or copy, produces an
    ``[experts, k, n]`` array: only the three ``tgmm`` calls, whose results
    the weight gradients are, and the parameters of the fusions that stack
    those. The parent had six ``dynamic-slice_bitcast_fusion`` copies of 268
    MB in the loop bodies, each run once a layer. Twelve Mosaic calls as
    the parent, and no more of the chip than the parent's step needed
    (``parent_gib``: the cell's ``hbm_step_gib``, ledger, PR 30)."""
    from benchmarks.harness import described
    from ray_tpu.models import transformer as T

    parent_gib = 14.118
    layers, experts, dim, width = 2, 64, 2048, 1024
    config = T.TransformerConfig(
        vocab_size=50304, dim=dim, n_layers=layers, n_heads=16, n_kv_heads=16,
        hidden_dim=width, max_seq=4096, rope_theta=1e4, rms_norm_eps=1e-5, qk_norm=True,
        moe=T.MoEConfig(num_experts=experts, top_k=8, aux_loss_coef=0.01),
        attention="flash",
    )
    compiled = one_chip_step(topo, config, batch=2, seq=4096)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 12
    in_place = rf"bf16\[{layers * experts},({dim},{width}|{width},{dim})\]"
    gmm = re.findall(r"%gmm\S* = \S+ custom-call\(.*?operand_layout_constraints=(.*?)frontend_attributes", text)
    assert len(gmm) == 6 and all(re.search(in_place, operands) for operands in gmm)
    one_layer = rf"= bf16\[{experts},(?:{dim},{width}|{width},{dim})\]\S* ([\w-]+)\("
    assert sorted(re.findall(one_layer, text)) == ["custom-call"] * 3 + ["parameter"] * 3
    assert described.step_memory(compiled)["total_bytes"] / 2**30 <= parent_gib


def _fusions_that_only_select(text: str, rows: str) -> list[str]:
    """Fused computations of ``text`` that produce a ``[rows]`` array and whose
    only work is a ``select``: a pass over the buffer that does nothing but
    zero some of it."""
    idle = {"parameter", "constant", "broadcast", "bitcast", "convert", "compare", "iota",
            "tuple", "reshape", "select"}
    found = []
    for block in re.split(r"\n(?=%?fused_computation[\w.\-]* \()", text):
        head = re.match(r"%?(fused_computation[\w.\-]*) \(.*?\) -> (.*?) \{\n", block)
        if head and rows in head.group(2):
            ops = set(re.findall(r"= \S+\s+([\w-]+)\(", block.split("\n}")[0]))
            if "select" in ops and ops <= idle:
                found.append(head.group(1))
    return found


@pytest.mark.parametrize("dim,top_k,held,experts,width,parent_mib", [
    (2560, 6, 32, 64, 768, 2431),      # smallthinker-seq16k-fixed's expert layer
    (2048, 4, 16, 32, 1792, 1429),     # lfm2-moe-seq16k-fixed's
], ids=["top6-of-2560", "top4-of-2048"])
def test_every_pair_dispatch_has_no_array_with_top_k_second_minor(
    one_chip, dim, top_k, held, experts, width, parent_mib
):
    """Value and gradient of ONE expert layer (``_moe_mlp``) at the two dear
    cells' shapes, 16,384 tokens, half of the experts held, so the block is
    ``_by_every_pair``: the pairs are numbered choice-major, so the compiled
    program has no array ``[tokens, top_k, d]`` in any dtype (with ``top_k``
    second-minor the TPU's (8, 128) tile pads 6 to 8 or is swapped for a
    (4, 128) one: the parent's ``reshape f32[16384,6,2560]`` and its
    ``broadcast`` were physical copies, 1.34 GB each), a token's rows by
    choice are a BITCAST of the gathered ``[tokens x top_k, d]`` buffer, the
    entry computation holds no float32 array of the buffer's size (the
    backward stays in expert order: the cotangent's rows are gathered from
    the ``[tokens, d]`` array), and no fusion's only work is a ``select`` over
    the buffer (the held selects ride in the sums). Nine Mosaic calls as the
    parent. Temporaries against the parent's (``parent_mib``: the same
    function at commit 8d93ff2, compiled the same way; both printed): a sixth
    less at ``top_k`` 6, where the padded copies were; at ``top_k`` 4 this
    function ALONE reads 1 % over (1,446 against 1,429 MiB: the backward keeps
    the experts' output beside the gathered cotangent for one fusion), while
    the cell's whole step needs 8.56 GiB where the parent's needs 9.23
    (PERF.md section 6, PR 46)."""
    import ray_tpu.ops.grouped_matmul as gm
    from ray_tpu.models import transformer as T

    tokens, pairs = 16384, 16384 * top_k
    config = T.TransformerConfig(
        vocab_size=512, dim=dim, n_layers=1, n_heads=2, n_kv_heads=2, hidden_dim=width,
        max_seq=tokens,
        moe=T.MoEConfig(
            num_experts=experts, top_k=top_k, norm_topk_prob=True, expert_dim=width,
            scoring="sigmoid", held=(0, held)),
    )
    shaped = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    layer = {
        "router": shaped(dim, experts), "router_bias": shaped(experts, dtype=jnp.float32),
        "w_gate": shaped(held, dim, width), "w_up": shaped(held, dim, width),
        "w_down": shaped(held, width, dim),
    }
    h = shaped(1, tokens, dim)

    def probed(h, layer, probe):
        out, _ = T._moe_mlp(h, layer, config)
        return jnp.sum(out.astype(jnp.float32) * probe.astype(jnp.float32))

    with mock.patch.object(gm, "resolve_interpret", lambda _i: False):
        compiled = jax.jit(jax.value_and_grad(probed, argnums=(0, 1))).lower(h, layer, h).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 9
    assert not re.findall(rf"\w+\[{tokens},{top_k},{dim}\]", text)
    by_choice = rf"\[{top_k},{tokens},{dim}\]"
    entry = text[text.index("ENTRY"):]
    # forward and in the first gather's transpose: the gathered rows seen by choice, for free
    assert len(re.findall(rf"= bf16{by_choice}\S* bitcast\(", entry)) == 2
    assert not re.findall(rf"= f32(?:{by_choice}|\[{pairs},{dim}\])", entry)
    assert _fusions_that_only_select(text, f"[{pairs},{dim}]") == []
    temporaries = compiled.memory_analysis().temp_size_in_bytes / 2**20
    print(f"temporaries {temporaries:.0f} MiB, the parent's {parent_mib} MiB")
    assert temporaries <= 1.02 * parent_mib
