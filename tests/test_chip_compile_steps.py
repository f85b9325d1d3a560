"""Whole training steps at published widths: the sparse step against its
parent's memory, a patterned step over held experts on one chip and on four,
full remat running the forward kernel once, and the head's loss keeping no
float32 logits of the whole batch.

Compiled for a TPU v5e that is described, not attached: nothing executes,
so these say what the chip's compiler accepts and nothing about results or
times. One of the ``test_chip_compile_*`` files, a kernel family each:
``tests/test_chip_compile_flash.py`` says why and how.
"""

import math
import pathlib
import re
from unittest import mock

import jax
import pytest

from model_helpers import loss_and_grads_text, mosaic_calls, one_chip_step, unfused_instructions


def test_the_sparse_step_needs_no_more_of_the_chip_than_its_parent_s(topo):
    """``keye-vl2-seq16k-fixed``'s whole step (the benchmark's own
    configuration and traffic: six sparse layers over 16 held experts, 1 x
    16384, full remat) with the term as kernels: one call of each a layer's
    forward, none in its backward (the gradients are kept by name), no KV
    group's ``[8, 512, keys]`` float32 probabilities left. The selection is
    packed in the chunk that makes it and unpacked by slabs written in place
    (PR 58): under scope ``index_select`` nothing copies, transposes,
    broadcasts or reshapes an array of a chunk's rows by a row group's keys
    or more (the parent broadcast and reshaped ``u8[16384, 8, 2048]`` twice a
    layer), no int8 mask of a chunk's rows by the sequence's keys is copied
    anywhere in the step (the parent transposed one a chunk inside the
    loop), and the step needs no more of the chip than it landed on, 12.385
    GiB (compile for a described v5e, PR 58; the parent's 12.513), and 2 %."""
    import importlib

    import ray_tpu.ops.grouped_matmul as gm
    from benchmarks.harness import described
    from benchmarks.harness.manifest import Manifest
    from ray_tpu.ops import sparse_index

    manifest = Manifest(str(pathlib.Path(__file__).resolve().parents[1]))
    cell = manifest.cell("keye-vl2-seq16k-fixed")
    config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    family = importlib.import_module(f"benchmarks.families.{config['family']}").build(config, traffic)
    with mock.patch.object(gm, "resolve_interpret", lambda _i: False):
        compiled = described.compile_step(
            family, topo.devices, config["mesh_axes"], traffic["batch_size"], traffic["seq_len"])[1]
    calls = mosaic_calls(compiled.as_text())
    assert calls.count("_index_loss_lse") == 1 and calls.count("_index_loss_terms") == 1
    assert sum("_flash" in name for name in calls) == 3
    text = compiled.as_text()
    assert "f32[1,8,512," not in text
    chunk, seq = family.model.sparse.score_chunk, traffic["seq_len"]
    group_keys = sum(sparse_index._row_groups(seq, chunk)[0])   # the first row group's
    relaid = []
    for line in unfused_instructions(text):
        found = re.match(r"\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(", line)
        if not found or found[4] not in ("copy", "transpose", "broadcast", "reshape"):
            continue
        name, dtype, dims, op = found.groups()
        elements = math.prod(int(d) for d in dims.split(",") if d)
        scope = re.search(r'op_name="[^"]*[/(]index_select[/)"]', line)
        if (scope and elements >= chunk * group_keys) or (dtype == "s8" and elements >= chunk * seq):
            relaid.append(f"{op} {dtype}[{dims}] {name}")
    assert not relaid
    assert described.step_memory(compiled)["total_bytes"] / 2**30 <= 12.385 * 1.02


@pytest.mark.parametrize("axes", [
    {"dp": 1}, {"dp": 2, "fsdp": 2},
], ids=lambda axes: "-".join(f"{k}{v}" for k, v in axes.items()))
def test_patterned_step_over_held_experts_compiles_for_a_v5e_mesh(topo, axes):
    """A pattern over expert layers (Ling-3.0-flash-VL's shape at lane
    widths) on one chip and across four under data parallelism: a dense
    prefix with a linear mixer, then (linear, full) whose linear layers carry
    a decay per channel and whose full layers are gated latent attention,
    over 8 group-routed experts of which 4 are held. The convolutions, the
    scan kernels and the expert layer run per data shard under ``shard_map``;
    the grouped matmuls run over the held experts' groups alone."""
    from ray_tpu.models import transformer as T
    from ray_tpu.ops import gated_delta_rule as G, short_conv as S

    config = T.TransformerConfig(
        vocab_size=512, dim=256, n_layers=3, n_heads=2, n_kv_heads=2, hidden_dim=384,
        max_seq=512, attention="flash", remat="full",
        latent=T.LatentAttentionConfig(
            kv_lora_rank=128, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            output_gate="head"),
        first_dense_layers=1, first_dense_kind="linear", layer_pattern=("linear", "full"),
        linear=T.LinearAttentionConfig(
            num_key_heads=2, num_value_heads=2, key_head_dim=128, value_head_dim=128,
            allow_neg_eigval=False, decay="channel", gate_lower_bound=-5.0,
            output_gate="sigmoid"),
        moe=T.MoEConfig(
            num_experts=8, top_k=2, norm_topk_prob=True, expert_dim=128, shared_experts=1,
            scoring="sigmoid", routed_scaling=2.5, n_group=4, topk_group=2, held=(4, 4)),
    )
    with mock.patch.object(G, "resolve_interpret", lambda _i: False), \
            mock.patch.object(S, "resolve_interpret", lambda _i: False):
        text = loss_and_grads_text(topo, config, axes, batch=4, seq=512)
    calls = mosaic_calls(text)
    # two linear layers (one in each scan): the scan's forward, the forward
    # again for the chunk-start states, the backward
    assert calls.count("_delta_rule_forward") == 4 and calls.count("_delta_rule_backward") == 2
    # per channel: the preparation's own pair, not the scalar rule's
    assert not [c for c in calls if c.startswith("_delta_prepare")]
    assert calls.count("_channel_prepare_forward") == 4
    assert calls.count("_channel_prepare_backward") == 2
    assert calls.count("_flash_forward") == 1
    # two expert layers: nine grouped matmuls and the recompute's three forward ones
    assert len(re.findall(r"%\S*tgmm\S* = \S+ custom-call", text)) == 6
    assert len([c for c in calls if c.startswith("_short_conv")]) >= 12
    # the held experts' stack, [periods x count, held, k, n] flattened: 4 of the 8
    assert "bf16[4,256,128]" in text and "bf16[8,256,128]" not in text


@pytest.mark.parametrize("axes,batch", [
    ({"dp": 1}, 1), ({"fsdp": 2, "tp": 2}, 2),
], ids=["one-chip", "fsdp2-tp2"])
def test_full_remat_runs_the_forward_kernel_once(topo, axes, batch):
    """Two scanned layers at the 16k cell's attention shape (a device's
    call is ``[1, 32, 16384, 128]`` on one chip, hidden 4096) under
    ``remat="full"``: the layer checkpoint keeps the kernel's ``out`` and
    ``lse`` by name, so loss and gradients hold exactly three Mosaic calls
    (forward, dq, dkv; per shard under ``shard_map`` on the 2 x 2 mesh) and
    not a fourth, the forward again in the backward."""
    from ray_tpu.models import transformer as T

    config = T.TransformerConfig(
        vocab_size=512, dim=4096, n_layers=2, n_heads=32, n_kv_heads=8,
        hidden_dim=1024, max_seq=16384, attention="flash", remat="full",
    )

    def custom_calls():
        return loss_and_grads_text(topo, config, axes, batch, 16384).count("tpu_custom_call")

    assert custom_calls() == 3
    with mock.patch.object(
        T, "_remat_policy", lambda _r: jax.checkpoint_policies.nothing_saveable
    ):
        assert custom_calls() == 4   # what the names are for


def _mistral_7b_step(topo, batch, seq, remat):
    """At the benchmark's Mistral-7B widths (hidden 4096, 32 / 8 heads, MLP
    14336, vocabulary 32768, depth cut to 2)."""
    from ray_tpu.models import transformer as T

    config = T.TransformerConfig(
        vocab_size=32768, dim=4096, n_layers=2, n_heads=32, n_kv_heads=8,
        hidden_dim=14336, max_seq=seq, rope_theta=1e6, attention="flash", remat=remat,
    )
    return one_chip_step(topo, config, batch, seq)


@pytest.mark.parametrize("batch,seq,remat,parent_gib", [
    (2, 4096, None, 11.5391),      # mistral7b-seq4k-ingest
    (1, 16384, "full", 11.1715),   # mistral7b-seq16k-fixed
], ids=["seq4k", "seq16k"])
def test_head_loss_keeps_no_float32_logits_of_the_whole_batch(topo, batch, seq, remat, parent_gib):
    """``loss_fn`` ends in ``head_loss``: in the optimized step no float32
    array with the vocabulary as its last dimension has ``batch * seq``
    rows (what there is of float32 at that width is a chunk's, inside the
    loop's fusions, and AdamW's update of ``lm_head``, 4096 rows both);
    what is kept for the backward is the bfloat16 ``dlogits``, chunks of
    4096 rows in both cells. The step needs no more of the chip than its
    parent's did (``parent_gib``: the cell's ``hbm_step_gib``, ledger, PR
    28, where ``loss_fn`` was ``logits_loss(_head(...))``; arguments +
    temporaries + outputs - aliased)."""
    import math

    compiled = _mistral_7b_step(topo, batch, seq, remat)
    tokens, vocab = batch * seq, 32768
    of_vocab = {
        (dtype, dims) for dtype, dims in (
            (dtype, tuple(int(d) for d in dims.split(",")))
            for dtype, dims in re.findall(r"\b(f32|bf16)\[([\d,]+)\]", compiled.as_text())
        ) if len(dims) > 1 and dims[-1] == vocab
    }
    assert not [dims for dtype, dims in of_vocab if dtype == "f32" and math.prod(dims[:-1]) >= tokens]
    assert ("bf16", (tokens // 4096, 4096, vocab)) in of_vocab      # dlogits, by chunk
    from benchmarks.harness import described

    assert described.step_memory(compiled)["total_bytes"] / 2**30 <= parent_gib
