"""Compile ``smallthinker-seq16k-fixed``'s REAL training step for a TPU v5e
that is described, not attached, as ``test_compile_v5e_lfm2.py`` does for its
cell: the compiler's verdict, its memory analysis and the kernels in the
program, at published widths, at no chip time. Nothing executes.

The sizing it decides (ISSUE 45): one period of four layers and an eighth of
the vocabulary with 32 of 64 experts held a layer; under the 92 % rule and
over the 25 % floor. Run with ``-s`` to see the figures.

``python -m pytest benchmarks/tests`` is one process, so this file shares
the one load of the TPU's library with the other ``test_compile_v5e*``.
"""

import importlib
import json
import os
from unittest import mock

import jax
import pytest

from benchmarks.harness import described
from benchmarks.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "smallthinker-seq16k-fixed"

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
    from ray_tpu.ops import grouped_matmul

    manifest = Manifest(ROOT)
    cell = manifest.cell(CELL)
    config = dict(manifest.config(cell["config"]), **config_changes)
    traffic = manifest.traffic(cell["traffic"])
    family = importlib.import_module(f"benchmarks.families.{config['family']}").build(config, traffic)
    # described.compile_step steers the flash module off the interpreter;
    # the grouped matmuls' module asks the platform rule under its own name
    with mock.patch.object(grouped_matmul, "resolve_interpret", lambda _i: False):
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
    assert family.config["moe_num_primary_experts"] == 32 and "32 held" in cell["why"]
    assert family.model.moe.held == (0, 32) and family.model.moe.num_experts == 64
    assert text.count("tpu_custom_call") >= family.expected_custom_calls == 12 + 36
    lines = [l.strip() for l in text.splitlines()]
    flash, experts = (family.kernels[k] for k in ("flash", "experts"))
    # the period's body holds the global layer's three calls and the three
    # window layers' nine: full remat keeps each forward's out and lse
    assert [len([l for l in lines if flash[k].search(l)]) for k in ("fwd", "dq", "dkv")] == [4, 4, 4]
    # q of 28 heads of 128 on a stream of 2560; K and V repeated to the 28
    calls = [l for l in lines if flash["fwd"].search(l)]
    assert all("bf16[28,16384,128]" in l for l in calls)
    # four expert layers: gate / up / down forward, forward again in the
    # recompute, their input gradients; three weight gradients
    assert len([l for l in lines if experts["gmm"].search(l)]) == 36
    assert len([l for l in lines if experts["tgmm"].search(l)]) == 12
    # the grouped matmuls read the period's stack of HELD experts in place:
    # three window layers x 32 as one [96, ...] stack, the global layer's 32; never 64
    reads = [l for l in lines if experts["gmm"].search(l)]
    assert any("bf16[96,2560,768]" in l for l in reads) and any("bf16[32,2560,768]" in l for l in reads)
    assert not [l for l in reads if "bf16[64,2560" in l]
    # the worst case's row buffers: half held is over an eighth, so the block is _by_every_pair
    assert any("bf16[98304,2560]" in l for l in reads)
    assert FLOOR * BYTES_LIMIT < memory["total_bytes"] <= FITS * BYTES_LIMIT
    # weights and both AdamW moments (arguments) at 6 bytes a parameter (the routers' float32 on top)
    assert family.parameters() == 936_778_240
    assert 6 * family.parameters() <= memory["argument_bytes"] < 6.2 * family.parameters()
    assert not [op for op in ("all-reduce(", "all-gather(", "all-to-all(") if f" {op}" in text]
