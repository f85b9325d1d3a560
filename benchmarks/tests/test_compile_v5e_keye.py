"""Compile ``keye-vl2-seq16k-fixed``'s REAL training step for a TPU v5e that
is described, not attached, as ``test_compile_v5e_smallthinker.py`` does for
its cell: the compiler's verdict, its memory analysis and the kernels in the
program, at published widths, at no chip time. Nothing executes.

The sizing it decides (ISSUE 53): six layers and an eighth of the vocabulary
with 16 of 128 experts held a layer; under the 92 % rule and over the 25 %
floor. Run with ``-s`` to see the figures.

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
CELL = "keye-vl2-seq16k-fixed"

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
    assert family.config["num_experts"] == 16 and "16 held" in cell["why"]
    assert family.model.moe.held == (0, 16) and family.model.moe.num_experts == 128
    assert family.model.sparse.topk == 2048 and family.model.layer_kind == "sparse"
    assert text.count("tpu_custom_call") >= family.expected_custom_calls == 12
    lines = [l.strip() for l in text.splitlines()]
    flash, experts = (family.kernels[k] for k in ("flash", "experts"))
    # the scanned layer's three calls: full remat keeps the forward's out and lse
    assert [len([l for l in lines if flash[k].search(l)]) for k in ("fwd", "dq", "dkv")] == [1, 1, 1]
    # q of 32 heads of 128, K and V at their own 4, the selection one int8 tile a pair
    for kernel in ("fwd", "dq", "dkv"):
        call = next(l for l in lines if flash[kernel].search(l))
        assert "bf16[32,16384,128]" in call and "bf16[4,16384,128]" in call
        assert "s8[1,16384,16384]" in call
    # no [heads, seq, seq] array and no gathered K / V anywhere in the step
    assert "16384,16384,128]" not in text and "[32,16384,16384]" not in text
    assert "2048,4,128]" not in text
    assert len([l for l in lines if experts["gmm"].search(l)]) == 9
    assert len([l for l in lines if experts["tgmm"].search(l)]) == 3
    reads = [l for l in lines if experts["gmm"].search(l)]
    assert any("bf16[96,2048,768]" in l for l in reads)          # six layers' 16 held, in place
    assert any("bf16[131072,2048]" in l for l in reads)          # every pair's row: _by_every_pair
    assert FLOOR * BYTES_LIMIT < memory["total_bytes"] <= FITS * BYTES_LIMIT
    # weights and both AdamW moments (arguments) at 6 bytes a parameter (the routers' float32 on top)
    assert family.parameters() == 659_189_632
    assert 6 * family.parameters() <= memory["argument_bytes"] < 6.2 * family.parameters()
    assert not [op for op in ("all-reduce(", "all-gather(", "all-to-all(") if f" {op}" in text]
