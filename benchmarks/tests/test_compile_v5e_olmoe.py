"""Compile ``olmoe-seq4k-ingest``'s REAL training step for a TPU v5e that
is described, not attached, as ``test_compile_v5e.py`` does for the cells
it lists by hand: the compiler's verdict, its memory analysis and the
kernels in the program, at published widths, at no chip time. Nothing
executes: this says nothing about results or times.

Why the configuration is cut to depth 2: every width and all 64 experts
kept, depth 3 does not fit. Run with ``-s`` to see the figures.

The topology is described inside a fixture that skips when it cannot be;
``python -m pytest benchmarks/tests`` is one process, so this file and
``test_compile_v5e.py`` share the one load of the TPU's library.
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
CELL = "olmoe-seq4k-ingest"
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
    # described.compile_step steers the flash kernels off the interpreter;
    # the grouped matmul asks the same platform rule from its own module.
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
    return family, cell, memory, text


def test_cell_step_compiles_fits_and_has_its_kernels(topo):
    family, cell, memory, text = compile_cell(topo)
    assert cell["chips"] == 1 and cell["traffic"] == "seq4k-ingest"   # the file that exists: no remat
    # three flash kernels; gate / up / down forward, input gradient (gmm), weight gradient (tgmm)
    assert text.count("tpu_custom_call") == 12 == family.expected_custom_calls
    for name, pattern in {**family.kernels["flash"], **family.kernels["experts"]}.items():
        found = [l for l in text.splitlines() if pattern.search(l.strip())]
        assert len(found) == {"gmm": 6, "tgmm": 3}.get(name, 1), name
    assert FLOOR * BYTES_LIMIT < memory["total_bytes"] <= FITS * BYTES_LIMIT
    # weights, both AdamW moments (arguments) at 6 bytes a parameter; gradients are temporaries
    assert memory["argument_bytes"] >= 6 * family.parameters()
    assert not [op for op in ("all-reduce(", "all-gather(", "all-to-all(") if f" {op}" in text]
    # the router and the dispatch are sorts, gathers and sums, forward and
    # backward: no scatter (the kernels' group metadata has one of 191 ints)
    scatters = [l for l in text.splitlines() if " scatter(" in l]
    assert not [l for l in scatters if "/mlp/dispatch/" in l or "/mlp/router/" in l]


def test_one_more_layer_would_not_fit(topo):
    """Why the cut is 2: the largest depth whose seq4k-ingest step needs at
    most 92 % of the chip, with every width and all 64 experts kept."""
    depth = Manifest(ROOT).config("olmoe-1b-7b-0125")["num_hidden_layers"]
    assert depth == 2
    _f, _c, memory, _t = compile_cell(topo, num_hidden_layers=depth + 1)
    assert memory["total_bytes"] > FITS * BYTES_LIMIT
