"""Compile ``lfm2-moe-seq16k-fixed``'s REAL training step for a TPU v5e
that is described, not attached, as ``test_compile_v5e_ling.py`` does for its
cell: the compiler's verdict, its memory analysis and the kernels in the
program, at published widths, at no chip time. Nothing executes.

The sizing it decides (ISSUE 39): one leading dense layer, one period of
four expert layers and a quarter of the vocabulary with 16 of 32 experts
held a layer need 9.23 GiB, 58.6 % of the chip: under the 92 % rule and over
the 25 % floor. All 32 held need 14.09 GiB, 89.5 %: ISSUE 39 counted them
over the rule (11.7 GiB "before any activation", with the gradients at
rest, which the fused step never holds at once); they are under it by 0.39
GiB, less room than any cell of the benchmark keeps (PERF.md section 6,
PR 39, says why the cell stays at 16). Run with ``-s`` to see the figures.

``python -m pytest benchmarks/tests`` is one process, so this file shares
the one load of the TPU's library with the other ``test_compile_v5e*``.
"""

import contextlib
import importlib
import json
import os
from unittest import mock

import jax
import pytest

from benchmarks.harness import described
from benchmarks.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "lfm2-moe-seq16k-fixed"
BYTES_LIMIT = int(15.75 * 2**30)   # a v5e chip's memory_stats()['bytes_limit'] (my chip run, PR 21)
FITS = 0.92                        # of bytes_limit, the rule of test_compile_v5e.py
FLOOR = 0.25


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def compile_cell(topo, **config_changes):
    from ray_tpu.ops import grouped_matmul, short_conv

    manifest = Manifest(ROOT)
    cell = manifest.cell(CELL)
    config = dict(manifest.config(cell["config"]), **config_changes)
    traffic = manifest.traffic(cell["traffic"])
    family = importlib.import_module(f"benchmarks.families.{config['family']}").build(config, traffic)
    # described.compile_step steers the flash module off the interpreter;
    # the other kernels' modules ask the platform rule under their own names
    with contextlib.ExitStack() as compiled_for_the_chip:
        for module in (grouped_matmul, short_conv):
            compiled_for_the_chip.enter_context(
                mock.patch.object(module, "resolve_interpret", lambda _i: False)
            )
        _lowered, compiled = described.compile_step(
            family, topo.devices, config["mesh_axes"], traffic["batch_size"], traffic["seq_len"]
        )
    memory = described.step_memory(compiled)
    text = compiled.as_text()
    print(json.dumps({
        "cell": CELL, **config_changes, "memory_analysis": memory,
        "share_of_bytes_limit": memory["total_bytes"] / BYTES_LIMIT,
        "tpu_custom_calls": text.count("tpu_custom_call"),
    }))
    return family, cell, traffic, memory, text


def test_cell_step_compiles_fits_and_has_its_kernels(topo):
    family, cell, traffic, memory, text = compile_cell(topo)
    assert cell["chips"] == 1 and cell["traffic"] == "seq16k-fixed"
    assert (traffic["batch_size"], traffic["seq_len"], traffic["remat"]) == (1, 16384, "full")
    assert family.config["num_experts"] == 16 and "16 held" in cell["why"]
    assert text.count("tpu_custom_call") >= family.expected_custom_calls == 3 + 36 + 8
    lines = [l.strip() for l in text.splitlines()]
    conv, flash, experts = (family.kernels[k] for k in ("short_conv", "flash", "experts"))
    # four conv layers (the dense one in a scan of its own): the forward, the
    # forward again in the backward's recompute (full remat keeps no
    # convolution's result), the backward
    assert len([l for l in lines if conv["fwd"].search(l)]) == 8
    assert len([l for l in lines if conv["bwd"].search(l)]) == 4
    assert [len([l for l in lines if flash[k].search(l)]) for k in ("fwd", "dq", "dkv")] == [1, 1, 1]
    # four expert layers: gate / up / down forward, forward again in the
    # recompute, their input gradients; three weight gradients
    assert len([l for l in lines if experts["gmm"].search(l)]) == 36
    assert len([l for l in lines if experts["tgmm"].search(l)]) == 12
    # the convolution at the cell's size, 3 taps padded to a sublane tile
    forward = [l for l in lines if conv["fwd"].search(l)]
    assert all("bf16[1,16384,2048]" in l and "f32[8,2048]" in l for l in forward)
    # the one attention layer's flash calls at head size 64: not padded to 128
    calls = [l for l in lines if flash["fwd"].search(l)]
    assert all("bf16[32,16384,64]" in l and "bf16[32,16384,128]" not in l for l in calls)
    # the grouped matmuls read the period's stack of HELD experts in place:
    # three conv layers x 16 as one [48, ...] stack, the attention layer's 16; never 32
    reads = [l for l in lines if experts["gmm"].search(l)]
    assert any("bf16[48,2048,1792]" in l for l in reads) and any("bf16[16,2048,1792]" in l for l in reads)
    assert not [l for l in reads if "bf16[32,2048" in l]
    assert FLOOR * BYTES_LIMIT < memory["total_bytes"] <= FITS * BYTES_LIMIT
    # weights and both AdamW moments (arguments) at 6 bytes a parameter; the
    # tied table is there ONCE
    assert family.parameters() == 860_141_824
    assert 6 * family.parameters() <= memory["argument_bytes"] < 6.2 * family.parameters()
    assert not [op for op in ("all-reduce(", "all-gather(", "all-to-all(") if f" {op}" in text]


def test_all_thirty_two_experts_held_leave_a_tenth_of_the_chip(topo):
    """With all 32 experts of a layer held (nothing cut but the depth and
    the vocabulary) the step compiles and needs 89.5 % of the chip: under the
    92 % rule, by less than any cell keeps free (OLMoE 86.5, Ling 87.1 %)."""
    config = Manifest(ROOT).config("lfm2-8b-a1b")
    assert (config["num_experts"], config["published"]["num_experts"]) == (16, 32)
    _f, _c, _t, memory, _text = compile_cell(topo, num_experts=32)
    assert 0.88 * BYTES_LIMIT < memory["total_bytes"] <= FITS * BYTES_LIMIT
