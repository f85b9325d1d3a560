"""Compile ``nemotron3-super-seq8k-fixed``'s REAL training step for a TPU v5e
that is described, not attached, as ``test_compile_v5e_solar_open2.py`` does for
its cell: the compiler's verdict, its memory analysis and the kernels in the
program, at published widths, at no chip time. Nothing executes.

The sizing it decides (ISSUE 55's rule: 16 experts held if the step is under
the 92 % rule at the cell's traffic, else 8): one period of eleven one-block
layers (five Mamba-2, five latent expert layers, one grouped-query) with 16 of
512 experts held and an eighth of the vocabulary fits at one sequence of 8,192:
11.98 GiB by the compiler's count, 76.0 % of 15.75 GiB, so 16 are held. (With
the period's layers stacked by KIND the same step read 14.41 GiB, 91.5 %: a
leaf of five layers keeps its gradient until the last of five backward passes
is through; ``models/transformer.py::_scan_periods``.) Run with ``-s`` to see
the figures.

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
CELL = "nemotron3-super-seq8k-fixed"
BYTES_LIMIT = int(15.75 * 2**30)   # a v5e chip's memory_stats()['bytes_limit'] (my chip run, PR 21)
FITS = 0.92                        # of bytes_limit, the rule of test_compile_v5e.py
FLOOR = 0.25
PARAMETERS = 1_431_132_544
LIMIT_S = 900                      # a test of this file; the compile takes about two minutes


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
    by name instead of holding its xdist worker."""
    import signal

    def expired(_signum, _frame):
        pytest.fail(f"{request.node.nodeid} exceeded {LIMIT_S} s", pytrace=False)

    before = signal.signal(signal.SIGALRM, expired)
    signal.alarm(LIMIT_S)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, before)


def compile_cell(topo, **config_changes):
    from ray_tpu.ops import grouped_matmul, short_conv

    manifest = Manifest(ROOT)
    cell = manifest.cell(CELL)
    config = dict(manifest.config(cell["config"]), **config_changes)
    traffic = manifest.traffic(cell["traffic"])
    family = importlib.import_module(f"benchmarks.families.{config['family']}").build(config, traffic)
    # described.compile_step steers the flash module off the interpreter;
    # the other kernels' modules ask the platform rule under their own names
    with contextlib.ExitStack() as compiled_for_the_chip:
        for module in (grouped_matmul, short_conv):
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
        "parameters": family.parameters(),
        "tpu_custom_calls": text.count("tpu_custom_call"),
    }))
    return family, cell, traffic, memory, text


def test_cell_step_compiles_fits_and_has_its_kernels(topo):
    family, cell, traffic, memory, text = compile_cell(topo)
    assert cell["chips"] == 1 and cell["traffic"] == "seq8k-fixed"
    assert (traffic["batch_size"], traffic["seq_len"], traffic["remat"]) == (1, 8192, "full")
    assert family.config["n_routed_experts"] == 16 and "16 of 512 held" in cell["why"] and "16384 steered rows" in cell["why"]
    assert text.count("tpu_custom_call") >= family.expected_custom_calls == 43
    lines = [l.strip() for l in text.splitlines()]
    conv, flash, experts = (family.kernels[k] for k in ("short_conv", "flash", "experts"))
    # five Mamba-2 layers: the convolution's forward, full remat's second
    # forward, its backward, at the convolved width with the bias's row
    forward = [l for l in lines if conv["fwd"].search(l)]
    assert len(forward) == 10 and len([l for l in lines if conv["bwd"].search(l)]) == 5
    assert all("bf16[1,8192,10240]" in l and "f32[8,10240]" in l for l in forward)
    # the one grouped-query layer's flash calls take K and V at their 2 heads: a group of 16
    assert [len([l for l in lines if flash[k].search(l)]) for k in ("fwd", "dq", "dkv")] == [1, 1, 1]
    assert all("bf16[32,8192,128]" in l and "bf16[2,8192,128]" in l for l in lines if flash["fwd"].search(l))
    # five expert layers of TWO matrices an expert (no gate): forward, the
    # backward's own forward, the input gradients; the weight gradients
    assert len([l for l in lines if experts["gmm"].search(l)]) == 30
    assert len([l for l in lines if experts["tgmm"].search(l)]) == 10
    # ... over held_row_bound's 45,056 rows (a quarter of 8192 x 22) IN THE LATENT,
    # reading a place's stack of 16 held experts where it lies; never 512
    reads = [l for l in lines if experts["gmm"].search(l)]
    assert all("bf16[45056,1024]" in l and "bf16[45056,2688]" in l for l in reads)
    assert any("bf16[16,1024,2688]" in l for l in reads) and any("bf16[16,2688,1024]" in l for l in reads)
    assert not [l for l in reads if "bf16[512," in l or "4096]" in l.split("custom-call")[0]]
    # no array repeats B or C to the 128 heads, and no state is kept a token
    assert "8192,128,128]" not in text and "128,64,128]{" not in text.replace("8,16,64,128]", "")
    assert FLOOR * BYTES_LIMIT < memory["total_bytes"] <= FITS * BYTES_LIMIT
    assert memory["total_bytes"] < 0.80 * BYTES_LIMIT                  # by place: 76.0 %
    # weights and both AdamW moments (arguments) at 6 bytes a parameter
    assert family.parameters() == PARAMETERS
    assert memory["argument_bytes"] >= 6 * family.parameters()
    assert not [op for op in ("all-reduce(", "all-gather(", "all-to-all(") if f" {op}" in text]
