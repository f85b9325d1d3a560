"""Compile ``ling-flash-seq16k-fixed``'s REAL training step for a TPU v5e
that is described, not attached, as ``test_compile_v5e_olmo_hybrid.py`` does
for its cell: the compiler's verdict, its memory analysis and the kernels
in the program, at published widths, at no chip time. Nothing executes.

The sizing it decides (ISSUE 36): one leading dense layer, one period of six
expert layers and an eighth of the vocabulary with 16 of 512 experts held a
layer fit under the 92 % rule; 32 held do not. Run with ``-s`` to see the
figures.

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
CELL = "ling-flash-seq16k-fixed"
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
    from ray_tpu.ops import gated_delta_rule, grouped_matmul, short_conv

    manifest = Manifest(ROOT)
    cell = manifest.cell(CELL)
    config = dict(manifest.config(cell["config"]), **config_changes)
    traffic = manifest.traffic(cell["traffic"])
    family = importlib.import_module(f"benchmarks.families.{config['family']}").build(config, traffic)
    # described.compile_step steers the flash module off the interpreter;
    # the other kernels' modules ask the platform rule under their own names
    with contextlib.ExitStack() as compiled_for_the_chip:
        for module in (gated_delta_rule, grouped_matmul, short_conv):
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
    assert family.config["num_experts"] == 16 and "16 experts held" in cell["why"]
    assert text.count("tpu_custom_call") >= family.expected_custom_calls == 75
    lines = [l.strip() for l in text.splitlines()]
    delta, flash, experts = (family.kernels[k] for k in ("delta_rule", "flash", "experts"))
    # six linear layers: the scan's forward, the forward again for the
    # chunk-start states, its backward; the dense one sits in a scan of its own
    assert len([l for l in lines if delta["fwd"].search(l)]) == 12
    assert len([l for l in lines if delta["bwd"].search(l)]) == 6
    assert [len([l for l in lines if flash[k].search(l)]) for k in ("fwd", "dq", "dkv")] == [1, 1, 1]
    # six expert layers: gate / up / down forward, forward again in the
    # backward's recompute (full remat keeps no grouped matmul's result) and
    # their input gradients; three weight gradients
    assert len([l for l in lines if experts["gmm"].search(l)]) == 54
    assert len([l for l in lines if experts["tgmm"].search(l)]) == 18
    # the scan walks two (batch x head) rows a call with a decay per channel:
    # gamma is a [.., 1, 128] row a chunk, not a scalar
    backward = [l for l in lines if delta["bwd"].search(l)]
    assert all("f32[2,16384,128]" in l and "f32[2,256,1,128]" in l for l in backward)
    # the flash calls of the one latent layer: q / k of 192 against v of 128
    forward = [l for l in lines if flash["fwd"].search(l)]
    assert all("bf16[32,16384,192]" in l and "bf16[32,16384,128]" in l for l in forward)
    # the grouped matmuls read the period's stack of HELD experts in place: five
    # linear layers x 16 as one [80, ...] stack, the one latent layer's 16; never 512
    reads = [l for l in lines if experts["gmm"].search(l)]
    assert any("bf16[80,2560,768]" in l for l in reads) and any("bf16[16,2560,768]" in l for l in reads)
    assert not [l for l in reads if "bf16[512," in l]
    assert FLOOR * BYTES_LIMIT < memory["total_bytes"] <= FITS * BYTES_LIMIT
    # weights and both AdamW moments (arguments) at 6 bytes a parameter
    assert family.parameters() == 1_167_574_976
    assert memory["argument_bytes"] >= 6 * family.parameters()
    assert not [op for op in ("all-reduce(", "all-gather(", "all-to-all(") if f" {op}" in text]


def test_thirty_two_held_experts_would_not_fit(topo):
    """Why 16: with 32 of 512 experts held a layer (16 chips sharing a
    layer) the step needs more than the chip has, with every width kept;
    the compiler says so itself, or reads over the 92 % rule."""
    config = Manifest(ROOT).config("ling-3.0-flash-vl")
    assert (config["num_experts"], config["published"]["num_experts"]) == (16, 512)
    try:
        _f, _c, _t, memory, _text = compile_cell(topo, num_experts=32)
    except Exception as e:
        assert "hbm" in str(e).lower() and "RESOURCE_EXHAUSTED" in str(e), e
    else:
        assert memory["total_bytes"] > FITS * BYTES_LIMIT
