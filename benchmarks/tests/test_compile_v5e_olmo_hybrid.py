"""Compile ``olmo-hybrid-seq16k-fixed``'s REAL training step for a TPU v5e
that is described, not attached, as ``test_compile_v5e_moonlight.py`` does
for its cell: the compiler's verdict, its memory analysis and the kernels
in the program, at published widths, at no chip time. Nothing executes.

Why the configuration is cut to ONE period (depth 4) and an eighth of the
vocabulary: two periods, or the whole vocabulary, need more than the chip
has. Run with ``-s`` to see the figures.

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
CELL = "olmo-hybrid-seq16k-fixed"
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
    from ray_tpu.ops import gated_delta_rule

    manifest = Manifest(ROOT)
    cell = manifest.cell(CELL)
    config = dict(manifest.config(cell["config"]), **config_changes)
    traffic = manifest.traffic(cell["traffic"])
    family = importlib.import_module(f"benchmarks.families.{config['family']}").build(config, traffic)
    # described.compile_step steers the flash module off the interpreter;
    # the scan kernels' module asks the platform rule under its own name
    with mock.patch.object(gated_delta_rule, "resolve_interpret", lambda _i: False):
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
    # a linear layer: the scan's forward, the forward again for the
    # chunk-start states, its backward; the full layer: fwd, dq, dkv
    assert text.count("tpu_custom_call") == 12 == family.expected_custom_calls
    lines = [l.strip() for l in text.splitlines()]
    delta, flash = family.kernels["delta_rule"], family.kernels["flash"]
    assert len([l for l in lines if delta["fwd"].search(l)]) == 6
    assert len([l for l in lines if delta["bwd"].search(l)]) == 3
    assert [len([l for l in lines if flash[k].search(l)]) for k in ("fwd", "dq", "dkv")] == [1, 1, 1]
    # the scan walks two (batch x head) rows a call: float32 operands of [2, 16384, 96 | 192]
    backward = [l for l in lines if delta["bwd"].search(l)]
    assert all("f32[2,16384,96]" in l and "f32[2,16384,192]" in l for l in backward)
    # the flash calls of the one full layer take 30 heads of 128
    assert all("bf16[30,16384,128]" in l for l in lines if flash["fwd"].search(l))
    assert FLOOR * BYTES_LIMIT < memory["total_bytes"] <= FITS * BYTES_LIMIT
    # weights and both AdamW moments (arguments) at 6 bytes a parameter
    assert memory["argument_bytes"] >= 6 * family.parameters()
    assert not [op for op in ("all-reduce(", "all-gather(", "all-to-all(") if f" {op}" in text]


@pytest.mark.parametrize("change", [{"num_hidden_layers": 8}, {"vocab_size": 100352}])
def test_two_periods_or_the_whole_vocabulary_would_not_fit(topo, change):
    """Why the cuts: a second period (depth 8), or the published
    vocabulary at depth 4, needs more than the chip has, with every width
    kept; the compiler says so itself, or reads over the 92 % rule."""
    config = Manifest(ROOT).config("olmo-hybrid-7b")
    assert (config["num_hidden_layers"], config["vocab_size"]) == (4, 12544)
    try:
        _f, _c, _t, memory, _text = compile_cell(topo, **change)
    except Exception as e:
        assert "hbm" in str(e).lower() and "RESOURCE_EXHAUSTED" in str(e), e
    else:
        assert memory["total_bytes"] > FITS * BYTES_LIMIT
