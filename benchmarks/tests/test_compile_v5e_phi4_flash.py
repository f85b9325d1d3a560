"""Compile ``phi4-flash-seq16k-fixed``'s REAL training step for a TPU v5e that
is described, not attached, as ``test_compile_v5e_trinity.py`` does for its
cell: the compiler's verdict, its memory analysis and the kernels in the
program, at published widths, at no chip time. Nothing executes.

The sizing it decides (ISSUE 65): 12 of 32 layers by the model's own rule (``m w
m w m w | m f | g c g c``) and an eighth of the vocabulary, one sequence of
16,384 tokens under full rematerialisation; under the 92 % rule and over the
25 % floor. And the layout: with the pairs of one pattern stacked under a
period scan the step read 17.32 GiB (a scan's stacked gradient is whole only
when the loop ends, and the fused step then holds every gradient beside 7.4 GiB
of activations); with every pair a segment of its own, walked in line, 13.43
(``sambay_segments``: the one layout it builds). Run with ``-s`` to see the
figures.

``python -m pytest benchmarks/tests`` is one process, so this file shares
the one load of the TPU's library with the other ``test_compile_v5e*``.
"""

import contextlib
import importlib
import json
import os
import re
from unittest import mock

import jax
import pytest

from benchmarks.harness import described
from benchmarks.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "phi4-flash-seq16k-fixed"

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
    from ray_tpu.ops import selective_scan, short_conv

    manifest = Manifest(ROOT)
    cell = manifest.cell(CELL)
    config = dict(manifest.config(cell["config"]), **config_changes)
    traffic = manifest.traffic(cell["traffic"])
    family = importlib.import_module(f"benchmarks.families.{config['family']}").build(config, traffic)
    # described.compile_step steers the flash module off the interpreter;
    # the other kernels' modules ask the platform rule under their own names
    with contextlib.ExitStack() as compiled_for_the_chip:
        for module in (selective_scan, short_conv):
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
        "hbm_step_gib": memory["total_bytes"] / 2**30,
        "parameters": family.parameters(),
        "tpu_custom_calls": text.count("tpu_custom_call"),
    }))
    return family, cell, traffic, memory, text


def test_cell_step_compiles_fits_and_has_its_kernels(topo):
    family, cell, traffic, memory, text = compile_cell(topo)
    assert cell["chips"] == 1 and cell["traffic"] == "seq16k-fixed"
    assert (traffic["batch_size"], traffic["seq_len"], traffic["remat"]) == (1, 16384, "full")
    model = family.model
    # every period a segment of its own, walked in line: what fits (the module docstring)
    assert model.segments == (
        *((("mamba", "window"), 1),) * 3, (("mamba", "full"), 1), *((("gmu", "cross"), 1),) * 2,
    )
    assert model.differential and model.norm == "layer" and model.tie_embeddings
    assert model.depth_index == (0, 1, 2, 3, 4, 5, 16, 17, 18, 19, 20, 21)
    assert (model.mamba.inner_dim, model.mamba.state_dim, model.mamba.dt_rank) == (5120, 16, 160)
    assert text.count("tpu_custom_call") >= family.expected_custom_calls == 4 * 4 + 6 * 6
    lines = [l.strip() for l in text.splitlines()]
    flash = family.kernels["flash"]
    # TWO calls a layer and kernel, three window layers, the full layer and two
    # cross layers, all in line; full remat keeps the forward's out and lse
    assert [len([l for l in lines if flash[k].search(l)]) for k in ("fwd", "dq", "dkv")] == [12, 12, 12]
    # 20 query heads of 64 on 10 key-value heads, V and the output 128 wide
    for kernel in ("fwd", "dq", "dkv"):
        call = next(l for l in lines if flash[kernel].search(l))
        assert "bf16[20,16384,64]" in call and "bf16[10,16384,128]" in call
    # no mask or score array of the context's square, and no state a token, in the step
    assert not re.search(r"\[(?:\d+,)*16384,16384[,\]]", text)
    assert not re.search(r"\[(?:\d+,)*16384,(?:5120,16|16,5120)[,\]]", text)
    # the scans' two kernels, four Mamba-1 layers: the forward ONCE a layer (full
    # remat keeps its output and states), the backward; the kept chunk-start
    # states are float32, one every 128 tokens
    scans = [l for l in lines if "selective_scan" in l and "tpu_custom_call" in l]
    assert len(scans) == 8 and "f32[1,128,16,5120]" in text
    assert FLOOR * BYTES_LIMIT < memory["total_bytes"] <= FITS * BYTES_LIMIT
    # weights and both AdamW moments (arguments) at 6 bytes a parameter (the float32 leaves on top)
    assert family.parameters() == 1_330_162_944
    assert 6 * family.parameters() <= memory["argument_bytes"] < 6.2 * family.parameters()
    assert not [op for op in ("all-reduce(", "all-gather(", "all-to-all(") if f" {op}" in text]
