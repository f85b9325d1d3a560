"""Compile ``moonlight-seq8k-ingest``'s REAL training step for a TPU v5e
that is described, not attached, as ``test_compile_v5e_olmoe.py`` does for
its cell: the compiler's verdict, its memory analysis and the kernels in
the program, at published widths, at no chip time. Nothing executes.

Why the configuration is cut to depth 2 (the dense layer and ONE expert
layer): every width, all 64 routed and both shared experts kept, depth 3
is refused by the compiler. Run with ``-s`` to see the figures.

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
CELL = "moonlight-seq8k-ingest"
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
    assert cell["chips"] == 1 and cell["traffic"] == "seq8k-ingest"
    assert (traffic["batch_size"], traffic["seq_len"], traffic["remat"]) == (1, 8192, None)
    # three flash kernels in each of the two layer scans; gate / up / down
    # forward, input gradient (gmm), weight gradient (tgmm) in the expert layer's
    assert text.count("tpu_custom_call") == 15 == family.expected_custom_calls
    for name, pattern in {**family.kernels["flash"], **family.kernels["experts"]}.items():
        found = [l for l in text.splitlines() if pattern.search(l.strip())]
        assert len(found) == {"gmm": 6, "tgmm": 3}.get(name, 2), name
    # the flash calls take q / k of 192 and v of 128
    forward = [l for l in text.splitlines() if family.kernels["flash"]["fwd"].search(l.strip())]
    assert all("bf16[16,8192,192]" in l and "bf16[16,8192,128]" in l for l in forward)
    assert FLOOR * BYTES_LIMIT < memory["total_bytes"] <= FITS * BYTES_LIMIT
    # weights and both AdamW moments (arguments) at 6 bytes a parameter
    assert memory["argument_bytes"] >= 6 * family.parameters()
    assert not [op for op in ("all-reduce(", "all-gather(", "all-to-all(") if f" {op}" in text]
    scatters = [l for l in text.splitlines() if " scatter(" in l]
    assert not [l for l in scatters if "/mlp/dispatch/" in l or "/mlp/router/" in l]


def test_one_more_expert_layer_would_not_fit(topo):
    """Why the cut is 2: a second expert layer (depth 3) needs more than
    the chip has, with every width and every expert kept; the compiler
    says so itself, or reads over the 92 % rule."""
    depth = Manifest(ROOT).config("moonlight-16b-a3b")["num_hidden_layers"]
    assert depth == 2
    try:
        _f, _c, _t, memory, _text = compile_cell(topo, num_hidden_layers=depth + 1)
    except Exception as e:
        assert "hbm" in str(e).lower() and "RESOURCE_EXHAUSTED" in str(e), e
    else:
        assert memory["total_bytes"] > FITS * BYTES_LIMIT
