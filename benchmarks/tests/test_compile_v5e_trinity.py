"""Compile ``trinity-mini-seq16k-ingest``'s REAL training step for a TPU v5e
that is described, not attached, as ``test_compile_v5e_sdar.py`` does for its
cell: the compiler's verdict, its memory analysis and the kernels in the
program, at published widths, at no chip time. Nothing executes.

The sizing it decides (ISSUE 62): published layers 1-5 (the dense window
layer, then one period of window, global, window, window) and an eighth of
the vocabulary with 16 of 128 experts held a layer, one sequence of 16,384
tokens under full rematerialisation; under the 92 % rule and over the 25 %
floor. Run with ``-s`` to see the figures.

``python -m pytest benchmarks/tests`` is one process, so this file shares
the one load of the TPU's library with the other ``test_compile_v5e*``.
"""

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
CELL = "trinity-mini-seq16k-ingest"

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
            family, topo.devices, config["mesh_axes"], traffic["batch_size"], traffic["seq_len"],
        )
    memory = described.step_memory(compiled)
    text = compiled.as_text()
    print(json.dumps({
        "cell": CELL, **config_changes, "memory_analysis": memory,
        "share_of_bytes_limit": memory["total_bytes"] / BYTES_LIMIT,
        "hbm_step_gib": memory["total_bytes"] / 2**30,
        "tpu_custom_calls": text.count("tpu_custom_call"),
    }))
    return family, cell, traffic, memory, text


def test_cell_step_compiles_fits_and_has_its_kernels(topo):
    family, cell, traffic, memory, text = compile_cell(topo)
    assert cell["chips"] == 1 and cell["traffic"] == "seq16k-ingest"
    assert (traffic["batch_size"], traffic["seq_len"], traffic["remat"]) == (1, 16384, "full")
    model = family.model
    assert family.config["num_experts"] == 16 and "16 held" in cell["why"]
    assert model.moe.held == (0, 16) and model.moe.num_experts == 128
    assert model.moe.bias_update_rate == 0.001 and model.norm_placement == "both"
    assert (model.first_dense_layers, model.first_dense_kind) == (1, "window")
    assert model.layer_pattern == ("window", "full", "window", "window") and model.periods == 1
    assert model.output_gate == "element" and model.window == 2048 and model.rope_kinds == ("window",)
    assert text.count("tpu_custom_call") >= family.expected_custom_calls == 3 * 5 + 9 * 4
    lines = [l.strip() for l in text.splitlines()]
    flash, experts = (family.kernels[k] for k in ("flash", "experts"))
    # five layers' three calls each: the dense prefix's scan of one and the
    # period's four in line; full remat keeps the forward's out and lse
    assert [len([l for l in lines if flash[k].search(l)]) for k in ("fwd", "dq", "dkv")] == [5, 5, 5]
    # q of 32 heads of 128 over 16,384 positions, K and V at their own 4
    for kernel in ("fwd", "dq", "dkv"):
        call = next(l for l in lines if flash[kernel].search(l))
        assert "bf16[32,16384,128]" in call and "bf16[4,16384,128]" in call
    # no mask or score array of the context's square in the step
    assert not re.search(r"\[(?:\d+,)*16384,16384[,\]]", text)
    # an expert layer's nine grouped matmuls and, full remat's second forward, three more
    assert len([l for l in lines if experts["gmm"].search(l)]) == 9 * 4
    assert len([l for l in lines if experts["tgmm"].search(l)]) == 3 * 4
    reads = [l for l in lines if experts["gmm"].search(l)]
    assert any("bf16[131072,2048]" in l for l in reads)          # every pair's row: _by_every_pair
    # the rule's state is written in the step: the biases are float32 [periods, layers of a kind, 128]
    assert "f32[1,3,128]" in text and "f32[1,1,128]" in text
    assert FLOOR * BYTES_LIMIT < memory["total_bytes"] <= FITS * BYTES_LIMIT
    # weights and both AdamW moments (arguments) at 6 bytes a parameter (routers and biases float32 on top)
    assert family.parameters() == 705_474_304
    assert 6 * family.parameters() <= memory["argument_bytes"] < 6.2 * family.parameters()
    assert not [op for op in ("all-reduce(", "all-gather(", "all-to-all(") if f" {op}" in text]
