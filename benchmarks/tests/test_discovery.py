"""Driven by data: a configuration, a traffic mix, a cell and a per-layer
metric are each added as NEW files (and entries in BENCHMARK.json) to a
temporary copy of the benchmark, and the harness finds and runs them with
no edit to a file that was there. Each case is also the CPU rehearsal of
one kind of cell, end to end through ``run.py``: cluster, JaxTrainer, gang
worker, reference check, window, result line."""

import filecmp
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY = {
    "source": "a test", "family": "dense_decoder", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "sliding_window": None, "tie_word_embeddings": False,
    "torch_dtype": "float32", "reduced": [], "assumed": ["everything"],
}
TOKENS = {"distribution": "zipf", "a": 1.1}
CASES = {
    "ingest": (
        dict(TINY, chips=1, mesh_axes={"dp": 1}),
        {"kind": "train_ingest", "seq_len": 128, "batch_size": 2, "remat": None, "rows": 16,
         "tokens": TOKENS, "report_every": 1, "loss_must_fall": False, "check_positions": None},
    ),
    "fixed": (
        dict(TINY, chips=1, mesh_axes={"dp": 1}),
        {"kind": "train_fixed", "seq_len": 256, "batch_size": 1, "remat": "full",
         "tokens": TOKENS, "report_every": 2, "loss_must_fall": True, "check_positions": 64},
    ),
    "mesh": (
        dict(TINY, chips=4, mesh_axes={"fsdp": 2, "tp": 2}),
        {"kind": "train_fixed", "seq_len": 128, "batch_size": 4, "remat": None,
         "tokens": TOKENS, "report_every": 1, "loss_must_fall": True, "check_positions": None},
    ),
}
NEW_METRIC = '''"""A metric a later PR might add: how far the loss fell in the window."""


def read(run):
    losses = run["facts"]["losses"]
    return losses[0] - losses[-1]
'''


@pytest.mark.parametrize("case", sorted(CASES))
def test_new_files_are_found_and_run(tmp_path, case):
    config, traffic = CASES[case]
    copy = tmp_path / "checkout"
    shutil.copytree(
        os.path.join(ROOT, "benchmarks"), copy / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", ".*"),
    )
    bench = copy / "benchmarks"
    (bench / "configs" / "tiny.json").write_text(json.dumps(dict(config, name="tiny")))
    (bench / "traffic" / f"tiny-{case}.json").write_text(json.dumps(dict(traffic, name=f"tiny-{case}")))
    (bench / "layer_metrics" / "loss_drop.py").write_text(NEW_METRIC)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = f"tiny.{case}"
    manifest["configs"].append(
        {"name": "tiny", "source": "a test", "file": "benchmarks/configs/tiny.json",
         "reduced": [], "why": "a test"}
    )
    manifest["workloads"].append(
        {"name": cell, "config": "tiny", "traffic": f"tiny-{case}", "chips": config["chips"], "why": "a test"}
    )
    manifest["per_layer"].append(
        {"name": "loss_drop", "unit": "nats", "better": "higher", "source": "program_counter",
         "layer": "Step", "moves": "tokens_per_s_per_chip", "workloads": [cell]}
    )
    for metric in manifest["per_layer"]:
        wanted = {"ingest": ("data_wait_ms",), "mesh": ("collective_ms", "comm_exposed_pct")}
        if metric["name"] in wanted.get(case, ()):
            metric["workloads"] = metric["workloads"] + [cell]
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))

    sys.path.insert(0, str(copy))
    try:
        from benchmarks.harness.manifest import Manifest
        problems = Manifest(str(copy)).problems()
    finally:
        sys.path.remove(str(copy))
    # a second four-chip cell among four is over the quarter, and only that
    assert [p for p in problems if "four chips" not in p] == []
    assert bool(problems) == (config["chips"] == 4)

    # nothing that was there has been edited
    def same(a, b):
        cmp = filecmp.dircmp(a, b, ignore=["__pycache__"])
        gone = [name for name in cmp.left_only if not name.startswith(".")]
        assert not cmp.diff_files and not gone, (cmp.diff_files, gone)
        for sub in cmp.common_dirs:
            same(os.path.join(a, sub), os.path.join(b, sub))
    same(os.path.join(ROOT, "benchmarks"), str(bench))

    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
        JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"),
    )
    env.pop("XLA_FLAGS", None)
    lines = {}
    for trace in (1, 0):
        done = subprocess.run(
            [sys.executable, str(bench / "run.py"), "--workload", cell, "--seed", str(3 + trace),
             "--seconds", "2", "--trace", str(trace), "--platform", "cpu"],
            cwd=str(copy), env=env, capture_output=True, text=True, timeout=300,
        )
        assert done.returncode == 0, done.stderr[-3000:]
        out = [json.loads(l) for l in done.stdout.splitlines() if l.startswith("{")]
        lines[trace] = out[-1]
        facts = {l["fact"]: l for l in out[:-1]}
        assert set(out[-1]) >= {"correct", "attempted", "failed", "metrics", "device"}
        assert out[-1]["correct"] is True and out[-1]["failed"] == 0
        assert out[-1]["attempted"] > 5
        assert out[-1]["device"]["platform"] == "cpu"
        assert out[-1]["device"]["count"] == config["chips"]
        assert facts["setup"]["backend_compiles_in_window"] == 0
        assert facts["check"]["ok"] and facts["check"]["published"]["rel_rms"] < 1e-5
        assert facts["window"]["mesh"] == config["mesh_axes"]
    # the second run of the cell, under ANOTHER seed, found every program in
    # the cache: the seed is data, not a constant of any program
    assert facts["setup"]["cache_misses"] == 0 and facts["setup"]["cache_hits"] > 0
    assert set(lines[0]["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    traced = lines[1]["metrics"]
    assert traced["loss_drop"]["unit"] == "nats" and traced["loss_drop"]["value"] > 0
    assert {"report_wait_ms", "step_jitter_pct", "hbm_step_gib"} <= set(traced)
    assert ("data_wait_ms" in traced) == (case == "ingest")
    # no chip here: what is read from a device trace is left out, not invented
    assert not {"device_step_ms", "step_mfu_pct", "flash_ms", "device_idle_pct"} & set(traced)
    assert "busy_s" not in lines[1]["device"]


def test_no_accelerator_no_result_line(tmp_path):
    """The command as the driver gives it, on a machine with no chip: a
    non-zero exit and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload",
         "mistral7b-seq4k-ingest", "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode != 0
    assert not [l for l in done.stdout.splitlines() if l.startswith("{") and "correct" in l]
    assert "chip" in done.stderr


def test_benchmark_alone_fails(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` there is no program to measure: non-zero, no result."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "mistral7b-seq4k-ingest",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True, timeout=120,
    )
    assert done.returncode != 0 and "correct" not in done.stdout
