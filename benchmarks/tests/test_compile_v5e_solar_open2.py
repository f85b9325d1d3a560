"""Compile ``solar-open2-seq4k-fixed``'s REAL training step for a TPU v5e
that is described, not attached, as ``test_compile_v5e_ling.py`` does for its
cell: the compiler's verdict, its memory analysis and the kernels in the
program, at published widths, at no chip time. Nothing executes.

The sizing it decides (ISSUE 48): one period of four expert layers (a
grouped-query layer, three linear ones at 64 heads of 128) with 8 of 320
experts held and an eighth of the vocabulary fits under the 92 % rule at one
sequence of 4,096 (77.9 % of 15.75 GiB), and at 8,192 too (84.0 %): memory
is not what holds the cell at 4k. Run with ``-s`` to see the figures.

A file of its own with its own time limit (ISSUE 47): the compile files are
the suite's longest under ``--dist loadfile``.
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
CELL = "solar-open2-seq4k-fixed"
BYTES_LIMIT = int(15.75 * 2**30)   # a v5e chip's memory_stats()['bytes_limit'] (my chip run, PR 21)
FITS = 0.92                        # of bytes_limit, the rule of test_compile_v5e.py
FLOOR = 0.25
PARAMETERS = 1_295_087_424
LIMIT_S = 900                      # a test of this file; the compiles take about two minutes each


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


@pytest.fixture(autouse=True)
def time_limit(request):
    """This file's own limit on each of its tests: a compile that hangs fails
    by name instead of holding its xdist worker (``benchmarks/tests`` has no
    conftest limit, and ``tests/conftest.py``'s 240 s is not a compile's)."""
    import signal

    def expired(_signum, _frame):
        pytest.fail(f"{request.node.nodeid} exceeded {LIMIT_S} s", pytrace=False)

    before = signal.signal(signal.SIGALRM, expired)
    signal.alarm(LIMIT_S)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, before)


def compile_cell(topo, **traffic_changes):
    from ray_tpu.ops import gated_delta_rule, grouped_matmul, short_conv

    manifest = Manifest(ROOT)
    cell = manifest.cell(CELL)
    config = manifest.config(cell["config"])
    traffic = dict(manifest.traffic(cell["traffic"]), **traffic_changes)
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
        "cell": CELL, **traffic_changes, "memory_analysis": memory,
        "share_of_bytes_limit": memory["total_bytes"] / BYTES_LIMIT,
        "tpu_custom_calls": text.count("tpu_custom_call"),
    }))
    return family, cell, traffic, memory, text


def test_cell_step_compiles_fits_and_has_its_kernels(topo):
    family, cell, traffic, memory, text = compile_cell(topo)
    assert cell["chips"] == 1 and cell["traffic"] == "seq4k-fixed"
    assert (traffic["batch_size"], traffic["seq_len"], traffic["remat"]) == (1, 4096, "full")
    assert family.config["n_routed_experts"] == 8 and "8 of 320 experts held" in cell["why"]
    assert text.count("tpu_custom_call") >= family.expected_custom_calls == 57
    lines = [l.strip() for l in text.splitlines()]
    delta, flash, experts = (family.kernels[k] for k in ("delta_rule", "flash", "experts"))
    # three linear layers: the scan's forward, the forward again for the
    # chunk-start states, its backward; and the channel preparation's pair
    # under the names the readers find it by: forward twice, backward once
    assert len([l for l in lines if delta["fwd"].search(l)]) == 6
    assert len([l for l in lines if delta["bwd"].search(l)]) == 3
    assert len([l for l in lines if l.startswith("%_channel_prepare_forward")]) == 6
    assert len([l for l in lines if l.startswith("%_channel_prepare_backward")]) == 3
    assert [len([l for l in lines if flash[k].search(l)]) for k in ("fwd", "dq", "dkv")] == [1, 1, 1]
    assert len([l for l in lines if experts["gmm"].search(l)]) == 36
    assert len([l for l in lines if experts["tgmm"].search(l)]) == 12
    # the rule walks eight (batch x head) rows a call with a decay per channel:
    # gamma is a [.., 1, 128] row a chunk of 64
    backward = [l for l in lines if delta["bwd"].search(l)]
    assert all("f32[8,4096,128]" in l and "f32[8,64,1,128]" in l for l in backward)
    # the one grouped-query layer's flash calls take K and V at their 8 heads
    forward = [l for l in lines if flash["fwd"].search(l)]
    assert all("bf16[64,4096,128]" in l and "bf16[8,4096,128]" in l for l in forward)
    # the grouped matmuls read the period's stack of HELD experts in place:
    # three linear layers x 8 as one [24, ...] stack, the full layer's 8; never 320
    reads = [l for l in lines if experts["gmm"].search(l)]
    assert any("bf16[24,4096,1280]" in l for l in reads) and any("bf16[8,4096,1280]" in l for l in reads)
    assert not [l for l in reads if "bf16[320," in l]
    assert FLOOR * BYTES_LIMIT < memory["total_bytes"] <= FITS * BYTES_LIMIT
    # weights and both AdamW moments (arguments) at 6 bytes a parameter
    assert family.parameters() == PARAMETERS
    assert memory["argument_bytes"] >= 6 * family.parameters()
    assert not [op for op in ("all-reduce(", "all-gather(", "all-to-all(") if f" {op}" in text]


def test_one_sequence_of_8192_fits_too_so_memory_is_not_why_4096(topo):
    """ISSUE 48 sized the cell at 4,096 from a stand-in (bounded gate, whole
    gate matrices, ungated GQA) that read 99.9 % of the chip at 8,192. The real
    file does not: the rule walks four heads a call there where it walks eight
    at 4,096 (``_heads_per_call``), and a token costs 0.24 MiB, not 0.62. The
    step at 8,192 compiles and stays under the 92 % rule; the cell is at 4,096
    because that is the traffic the issue fixed, a 4k pre-training sequence."""
    _f, _c, traffic, memory, _text = compile_cell(topo, seq_len=8192)
    assert traffic["seq_len"] == 8192
    assert 0.80 * BYTES_LIMIT < memory["total_bytes"] <= FITS * BYTES_LIMIT
