"""Compile every cell's REAL training step for a TPU v5e that is
described, not attached (``on-chip-measurement`` guide, section 2): the
compiler's verdict, its memory analysis and the kernels in the program,
at published widths, at no chip time. A compile that passes is not a chip
run: nothing executes, so this says nothing about results or times.

The depth of ``mistral-7b-v0.3``, the 16384 of ``seq16k-fixed`` and the
full remat of ``seq4k-mesh`` were decided from these figures (PERF.md
section 4). Run with ``-s`` to see them.

All in ONE file and in the test's own process: only one process at a time
may load the TPU's library. The topology is described inside a fixture
that skips when it cannot be — never at import, in a ``skipif`` or in
``parametrize``.
"""

import json
import os

import jax
import pytest

from benchmarks.harness import described
from benchmarks.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BYTES_LIMIT = int(15.75 * 2**30)   # a v5e chip's memory_stats()['bytes_limit'] (my chip run, PR 21)
FITS = 0.92                        # of bytes_limit: room for the batch, the check's leftovers, fragmentation
FLOOR = 0.25                       # a cell under a quarter of the chip does not stand for a deployment


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
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; the next one would warn."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def compile_cell(topo, name, **config_changes):
    import importlib

    manifest = Manifest(ROOT)
    cell = manifest.cell(name)
    config = dict(manifest.config(cell["config"]), **config_changes)
    traffic = manifest.traffic(cell["traffic"])
    family = importlib.import_module(f"benchmarks.families.{config['family']}").build(config, traffic)
    _lowered, compiled = described.compile_step(
        family, topo.devices, config["mesh_axes"], traffic["batch_size"], traffic["seq_len"]
    )
    memory = described.step_memory(compiled)
    text = compiled.as_text()
    print(json.dumps({
        "cell": name, **config_changes, "memory_analysis": memory,
        "share_of_bytes_limit": memory["total_bytes"] / BYTES_LIMIT,
        "tpu_custom_calls": text.count("tpu_custom_call"),
    }))
    return family, traffic, memory, text


@pytest.mark.parametrize(
    "cell, kernels",
    [
        ("mistral7b-seq4k-ingest", 3),       # fwd, dq, dkv
        ("mistral7b-seq16k-fixed", 4),       # full remat runs the forward kernel again
        ("mistral-large-seq4k-mesh4", 4),    # the same, per shard under shard_map
    ],
)
def test_cell_step_compiles_and_fits(topo, cell, kernels):
    family, traffic, memory, text = compile_cell(topo, cell)
    assert text.count("tpu_custom_call") == kernels >= family.expected_custom_calls
    assert FLOOR * BYTES_LIMIT < memory["total_bytes"] <= FITS * BYTES_LIMIT
    collectives = sum(text.count(f" {op}") for op in ("all-reduce(", "all-gather(", "all-reduce-start(", "all-gather-start("))
    assert (collectives > 0) == (cell == "mistral-large-seq4k-mesh4")


def test_one_more_layer_would_not_fit(topo):
    """Why mistral-7b-v0.3 is cut to depth 2 and not 3: the rule is the
    largest depth whose seq4k-ingest step needs at most 92 % of the chip."""
    depth = Manifest(ROOT).config("mistral-7b-v0.3")["num_hidden_layers"]
    _f, _t, memory, _x = compile_cell(topo, "mistral7b-seq4k-ingest", num_hidden_layers=depth + 1)
    assert memory["total_bytes"] > FITS * BYTES_LIMIT
